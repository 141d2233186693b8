"""Seconds the codec workers spend hashing and compressing a save's chunks:
the ``spoton.save.encode`` spans (one per encode job) inside the run,
summed over all workers, less the ``spoton.save.pool_write`` spans that lie
inside them (each chunk's write runs inside its encode job)."""

from harness import spans
from harness.trace import merge


def read(rec):
    encode = spans.spans(rec.trace, "spoton.save.encode")
    if not encode:
        return None
    jobs = merge([("", lo, hi - lo) for lo, hi in encode])
    inside = 0
    for lo, hi in spans.spans(rec.trace, "spoton.save.pool_write"):
        if any(j_lo <= lo and hi <= j_hi for j_lo, j_hi in jobs):
            inside += hi - lo
    return (sum(hi - lo for lo, hi in encode) - inside) / 1e9
