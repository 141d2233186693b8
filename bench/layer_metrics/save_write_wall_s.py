"""Seconds from a background save's start on the writer thread to its
commit: the ``spoton.save.write`` spans inside the run, their total over
their count."""

from harness import spans


def read(rec):
    return spans.mean_s(rec.trace, "spoton.save.write")
