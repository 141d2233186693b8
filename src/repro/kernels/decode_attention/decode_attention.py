"""Pallas TPU flash-decode: one query token against a deep KV cache.

The decode regime is memory-bound (every cache byte read once per token), so
the kernel is organized around streaming KV blocks through VMEM with online
softmax state in scratch — grid (B, H, NK) with the KV-block dimension
innermost ("arbitrary"). `valid_len` (the filled cache depth) arrives in SMEM
so one compiled kernel serves every decode position.

For the 500k-token cells, the KV stream per (batch, head) is S·hd·2·2 bytes;
block_k=512 keeps each resident block at 512·hd·4 B ≈ 256 KiB (hd=128) —
VMEM-safe with double buffering while maximizing DMA efficiency.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -2.0 ** 30
DEFAULT_BLOCK_K = 512


def _decode_kernel(vlen_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                   *, scale, block_k):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    valid_len = vlen_ref[0]

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(ki * block_k < valid_len)
    def _update():
        q = q_ref[0, 0, :].astype(jnp.float32) * scale        # (hd,)
        k = k_ref[0, :, 0, :].astype(jnp.float32)             # (bk, hd)
        v = v_ref[0, :, 0, :].astype(jnp.float32)
        s = jnp.sum(k * q[None, :], axis=1)[None, :]          # (1, bk)
        kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        s = jnp.where(kpos < valid_len, s, NEG_INF)
        m_prev = m_scr[...]                                   # (1,1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                                # (1, bk)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)         # (1, hd)
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.where(l_scr[...] == 0.0, 1.0, l_scr[...])
        o_ref[0, 0, :] = (acc_scr[...] / l)[0].astype(o_ref.dtype)


def decode_attention(q, k, v, valid_len, *, scale=None,
                     block_k=DEFAULT_BLOCK_K, interpret=False):
    """q: (B,H,hd); k,v: (B,S,KV,hd); valid_len: int32 scalar (tokens filled).
    Returns o: (B,H,hd). Causality is implied by valid_len (the query is the
    newest token)."""
    B, H, hd = q.shape
    _, S, KV, _ = k.shape
    G = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    block_k = min(block_k, S)
    assert S % block_k == 0, (S, block_k)
    vlen = jnp.asarray(valid_len, jnp.int32).reshape(1)

    kernel = functools.partial(_decode_kernel, scale=scale, block_k=block_k)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H, S // block_k),
        in_specs=[
            pl.BlockSpec((1, 1, hd), lambda b, h, ki, vl: (b, h, 0)),
            pl.BlockSpec((1, block_k, 1, hd), lambda b, h, ki, vl: (b, ki, h // G, 0)),
            pl.BlockSpec((1, block_k, 1, hd), lambda b, h, ki, vl: (b, ki, h // G, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, hd), lambda b, h, ki, vl: (b, h, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(vlen, q, k, v)
