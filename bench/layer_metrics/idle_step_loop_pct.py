"""Share of the trainer's run in which the device stood idle while the
trainer thread was in the step loop outside a save (``spoton.step`` and its
batch, dispatch, wait and hook parts): idle time of the first chip whose
innermost trainer span is a step's, over the ``spoton.run`` span's
length."""

from harness import spans


def read(rec):
    split = spans.idle_split(rec.trace)
    return None if split is None else split["step"]
