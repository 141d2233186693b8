"""Share of the trainer's run in which the device stood idle while the
trainer thread was inside a save (``spoton.save.*``: the extract's
prestage, diff, device to host copy and queue put): idle time of the first
chip whose innermost trainer span is a save's, over the ``spoton.run``
span's length."""

from harness import spans


def read(rec):
    split = spans.idle_split(rec.trace)
    return None if split is None else split["save"]
