"""Seconds the codec workers spend writing chunks to the pool: the
``spoton.save.pool_write`` spans (one chunk's file write, fsync and rename)
inside the run, summed over all workers."""

from harness import spans


def read(rec):
    return spans.total_s(rec.trace, "spoton.save.pool_write")
