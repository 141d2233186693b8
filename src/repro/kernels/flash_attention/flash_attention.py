"""Causal, sliding-window self-attention over a whole sequence on the TPU.

What runs on the chip is JAX's splash attention
(`jax.experimental.pallas.ops.tpu.splash_attention`): a tiled online-softmax
Pallas forward that keeps the log-sum-exp, and one fused backward pass that
accumulates dK and dV per KV block and writes dQ, each over (heads, seq,
head_dim) operands in blocks of `BLOCK` query rows by `BLOCK` keys. On one
v5e at phi3-mini's (2, 2048, 32, 96, window 2047), 512 blocks with the fused
backward ran a forward plus a forward and backward within 1 % of 1024
blocks and faster than 256 blocks, split KV compute or the two-pass
backward; 512 tiles more sequence lengths. Score tiles live in VMEM and never reach HBM; the residuals are
q, k, v, o and the log-sum-exp, all O(S). The mask is the model's
(`layers._mask_bias` without a cache): key position <= query position, and
with a window, key position > query position - window. It is reduced on
the host, once per (heads, seq, window), to a map of the blocks it keeps:
blocks it empties are neither fetched nor computed, blocks it keeps whole
skip the mask, and partial blocks mask with iotas in the kernel. GQA and
MQA run in the MHA form with fewer KV heads (each KV head accumulates the
gradients of its group of query heads).

Numerics: QK^T and the whole backward take bf16 operands with float32
accumulation; the softmax statistics are float32; the forward's PV product
takes float32 P and V. Every query-key pair the mask keeps is computed.
"""

from __future__ import annotations

import functools
import math

import jax
from jax.experimental.pallas.ops.tpu import splash_attention as sa

BLOCK = 512
HEAD_DIMS = (64, 96, 128, 256)   # compiled for a v5e and checked in interpret mode


def block_for(seq: int) -> int:
    """The block that tiles `seq`: BLOCK or the largest lane multiple under
    it that divides `seq`, 0 where none does."""
    b = math.gcd(seq, BLOCK)
    return b if b % 128 == 0 else 0


def supports(q_shape, k_shape) -> bool:
    """Self-attention shapes (B, S, H, hd) / (B, S, KV, hd) the kernel was
    compiled and tested for."""
    B, S, H, hd = q_shape
    return (tuple(k_shape[:2]) == (B, S) and H % k_shape[2] == 0
            and hd in HEAD_DIMS and block_for(S) > 0)


@functools.lru_cache(maxsize=None)
def splash_kernel(heads: int, seq: int, window: int, interpret: bool):
    """The splash kernel for (heads, seq, head_dim) q and (kv_heads, seq,
    head_dim) k, v; built once per shape and window, its block map concrete
    arrays even when the first call comes inside a trace."""
    if window and window < seq:
        mask = sa.LocalMask((seq, seq), (window - 1, 0), offset=0)
    else:
        mask = sa.CausalMask((seq, seq))
    b = block_for(seq)
    blocks = sa.BlockSizes(block_q=b, block_kv=b, block_q_dkv=b,
                           block_kv_dkv=b, use_fused_bwd_kernel=True)
    with jax.ensure_compile_time_eval():
        return sa.make_splash_mha(sa.MultiHeadMask([mask] * heads),
                                  block_sizes=blocks, head_shards=1,
                                  q_seq_shards=1, interpret=interpret)
