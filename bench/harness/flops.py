"""Operations and bytes the work needs, counted from shapes.

Model FLOPs of one training step count the forward and backward passes
(backward = twice forward) and leave recomputation out: a step with full
rematerialization does more, and that surplus is not useful work.
Attention is counted causal: only the query-key pairs the mask keeps, and
within a sliding window only the pairs inside it. The embedding lookup is
not a matmul and is not counted; the output head is.
"""

from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    """Matmul weights one token passes through (dense decoder)."""
    d = cfg["hidden_size"]
    hd = d // cfg["num_attention_heads"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    f = cfg["intermediate_size"]
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * f     # gated MLP
    return cfg["num_hidden_layers"] * per_layer + d * cfg["vocab_size"]


def attention_pairs(seq_len: int, window: int | None) -> int:
    """Query-key pairs of one causal sequence, within ``window`` if set."""
    if not window or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    w = window
    return w * (w + 1) // 2 + (seq_len - w) * w


def train_step_flops(cfg: dict, batch: int, seq_len: int) -> float:
    """Model FLOPs of one forward + backward pass over ``batch`` rows."""
    tokens = batch * seq_len
    dense = 6.0 * matmul_params(cfg) * tokens
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    # QK^T and PV: 2 FLOPs per multiply-add each, per kept pair and head
    attn_fwd = (4.0 * cfg["num_attention_heads"] * hd
                * attention_pairs(seq_len, cfg.get("sliding_window"))
                * batch * cfg["num_hidden_layers"])
    return dense + 3.0 * attn_fwd


def fingerprint_bytes(leaf_nbytes, min_bytes: int = 1 << 16) -> int:
    """Bytes a fingerprint pass over a saved state must read: every array
    leaf of at least ``min_bytes`` (smaller leaves are copied whole), each
    read once, whatever implements the pass."""
    return sum(n for n in leaf_nbytes if n >= min_bytes)
