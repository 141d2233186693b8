"""Seconds a save's device to host copy holds the trainer: the
``spoton.save.d2h`` spans (the extract's gather pass, up to the last byte
on the host) inside the run, their total over their count."""

from harness import spans


def read(rec):
    return spans.mean_s(rec.trace, "spoton.save.d2h")
