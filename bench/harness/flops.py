"""Operations and bytes the work needs, counted from shapes alone.

What depends on an architecture is counted by the configuration's program
module (``spec.program_module``): its ``work(cfg)`` gives the model FLOPs
of one training step (``step_flops``) and those of one attention kernel
call (``attention_fwd_flops``, ``attention_bwd_flops``), built from the
helpers here. Attention is counted causal: only the query-key pairs the
mask keeps, and within a sliding window only the pairs inside it.
"""

from __future__ import annotations


def attention_pairs(seq_len: int, window: int | None) -> int:
    """Query-key pairs of one causal sequence, within ``window`` if set."""
    if not window or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    w = window
    return w * (w + 1) // 2 + (seq_len - w) * w


def attention_call_flops(batch: int, heads: int, seq_len: int,
                         window: int | None, d_qk: int,
                         d_v: int) -> tuple[float, float]:
    """(forward, backward) FLOPs of one causal self-attention kernel call
    over ``batch`` rows of ``heads`` query heads: QK^T (``d_qk`` a pair) and
    PV (``d_v``), 2 FLOPs per multiply-add, per kept pair. The backward
    computes dP, dV, dQ and dK, twice the forward; the scores it computes
    again are not useful work and are not counted."""
    fwd = (2.0 * batch * heads * attention_pairs(seq_len, window)
           * (d_qk + d_v))
    return fwd, 2.0 * fwd


def fingerprint_bytes(leaf_nbytes, min_bytes: int = 1 << 16) -> int:
    """Bytes a fingerprint pass over a saved state must read: every array
    leaf of at least ``min_bytes`` (smaller leaves are copied whole), each
    read once, whatever implements the pass."""
    return sum(n for n in leaf_nbytes if n >= min_bytes)
