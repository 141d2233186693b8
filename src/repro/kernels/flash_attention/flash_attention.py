"""Pallas TPU flash attention: causal / sliding-window GQA, fwd + bwd.

TPU-native design (not a CUDA port): the grid iterates KV blocks in the
innermost ("arbitrary") dimension while online-softmax statistics (m, l) and
the output accumulator live in VMEM scratch across those iterations; the MXU
sees (block_q × head_dim) @ (head_dim × block_k) matmuls with 128-aligned
defaults. Fully-masked KV blocks (beyond the causal frontier or outside the
sliding-window band) are skipped with `pl.when` — compute for a window layer
is O(S·window), matching the banded XLA reference.

VMEM budget per program @ defaults (bq=bk=128, hd=128, fp32 scratch):
q,k,v,o blocks ≈ 4·128·128·2B = 128 KiB; acc+m+l ≈ 66 KiB — comfortably
inside the ~16 MiB/core VMEM with double buffering.

Backward uses the standard two-pass formulation (dkv pass over KV blocks,
dq pass over Q blocks) with the fwd log-sum-exp and D = rowsum(dO·O)
precomputed. GQA backward writes per-Q-head dk/dv which the ops wrapper
group-sums to KV heads.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -2.0 ** 30


def _block_mask(qpos, kpos, *, causal: bool, window: int):
    m = jnp.ones((qpos.shape[0], kpos.shape[0]), jnp.bool_)
    if causal:
        m &= kpos[None, :] <= qpos[:, None]
    if window:
        m &= kpos[None, :] > qpos[:, None] - window
    return m


def _visible(qi, ki, *, block_q, block_k, causal, window):
    """Can block (qi, ki) contain any unmasked element? (traced scalars ok)"""
    q_lo = qi * block_q
    q_hi = q_lo + block_q - 1
    k_lo = ki * block_k
    k_hi = k_lo + block_k - 1
    vis = jnp.bool_(True)
    if causal:
        vis &= k_lo <= q_hi
    if window:
        vis &= k_hi > q_lo - window
    return vis


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
                scale, causal, window, block_q, block_k):
    ki = pl.program_id(3)
    qi = pl.program_id(2)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(_visible(qi, ki, block_q=block_q, block_k=block_k,
                      causal=causal, window=window))
    def _update():
        q = q_ref[0, :, 0, :].astype(jnp.float32) * scale  # (bq, hd)
        k = k_ref[0, :, 0, :].astype(jnp.float32)          # (bk, hd)
        v = v_ref[0, :, 0, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
        kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        mask = jnp.ones_like(s, jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.where(l_scr[...] == 0.0, 1.0, l_scr[...])
        o_ref[0, :, 0, :] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_scr[...] + jnp.log(l))[:, 0]


def flash_attention_fwd(q, k, v, *, causal=True, window=0, scale=None,
                        block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                        interpret=False):
    """q: (B,Sq,H,hd); k,v: (B,Skv,KV,hd). Returns (o (B,Sq,H,hd), lse (B,H,Sq))."""
    B, Sq, H, hd = q.shape
    _, Skv, KV, _ = k.shape
    G = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    assert Sq % block_q == 0 and Skv % block_k == 0, (Sq, block_q, Skv, block_k)
    grid = (B, H, Sq // block_q, Skv // block_k)

    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               window=window, block_q=block_q, block_k=block_k)
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, 1, hd), lambda b, h, qi, ki: (b, qi, h, 0)),
            pl.BlockSpec((1, block_k, 1, hd), lambda b, h, qi, ki: (b, ki, h // G, 0)),
            pl.BlockSpec((1, block_k, 1, hd), lambda b, h, qi, ki: (b, ki, h // G, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, 1, hd), lambda b, h, qi, ki: (b, qi, h, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, h, qi, ki: (b, h, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((B, H, Sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return o, lse


# ---------------------------------------------------------------------------
# backward: dkv pass (grid over KV blocks, inner loop over Q blocks)
# ---------------------------------------------------------------------------

def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dsum_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *,
                scale, causal, window, block_q, block_k):
    qi = pl.program_id(3)
    ki = pl.program_id(2)
    nq = pl.num_programs(3)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(_visible(qi, ki, block_q=block_q, block_k=block_k,
                      causal=causal, window=window))
    def _update():
        q = q_ref[0, :, 0, :].astype(jnp.float32) * scale  # (bq,hd)
        k = k_ref[0, :, 0, :].astype(jnp.float32)           # (bk,hd)
        v = v_ref[0, :, 0, :].astype(jnp.float32)
        do = do_ref[0, :, 0, :].astype(jnp.float32)         # (bq,hd)
        lse = lse_ref[0, 0]                                 # (bq,)
        dsum = dsum_ref[0, 0]                               # (bq,)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
        kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        mask = jnp.ones_like(s, jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window:
            mask &= kpos > qpos - window
        p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)   # (bq,bk)
        dv_scr[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                           preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dsum[:, None])                      # (bq,bk)
        dk_scr[...] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                           preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0, :, 0, :] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, :, 0, :] = dv_scr[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dsum_ref, dq_ref,
               dq_scr, *, scale, causal, window, block_q, block_k):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(_visible(qi, ki, block_q=block_q, block_k=block_k,
                      causal=causal, window=window))
    def _update():
        q = q_ref[0, :, 0, :].astype(jnp.float32) * scale
        k = k_ref[0, :, 0, :].astype(jnp.float32)
        v = v_ref[0, :, 0, :].astype(jnp.float32)
        do = do_ref[0, :, 0, :].astype(jnp.float32)
        lse = lse_ref[0, 0]
        dsum = dsum_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
        kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        mask = jnp.ones_like(s, jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window:
            mask &= kpos > qpos - window
        p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dsum[:, None])
        dq_scr[...] += jax.lax.dot(ds, k, preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0, :, 0, :] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def flash_attention_bwd(q, k, v, o, lse, do, *, causal=True, window=0,
                        scale=None, block_q=DEFAULT_BLOCK_Q,
                        block_k=DEFAULT_BLOCK_K, interpret=False):
    """Returns (dq, dk, dv). dk/dv are group-summed to KV heads."""
    B, Sq, H, hd = q.shape
    _, Skv, KV, _ = k.shape
    G = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    dsum = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    dsum = dsum.transpose(0, 2, 1)  # (B,H,Sq)

    kern = functools.partial(_dkv_kernel, scale=scale, causal=causal,
                             window=window, block_q=block_q, block_k=block_k)
    dkh, dvh = pl.pallas_call(
        kern,
        grid=(B, H, Skv // block_k, Sq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, 1, hd), lambda b, h, ki, qi: (b, qi, h, 0)),
            pl.BlockSpec((1, block_k, 1, hd), lambda b, h, ki, qi: (b, ki, h // G, 0)),
            pl.BlockSpec((1, block_k, 1, hd), lambda b, h, ki, qi: (b, ki, h // G, 0)),
            pl.BlockSpec((1, block_q, 1, hd), lambda b, h, ki, qi: (b, qi, h, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, h, ki, qi: (b, h, qi)),
            pl.BlockSpec((1, 1, block_q), lambda b, h, ki, qi: (b, h, qi)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, 1, hd), lambda b, h, ki, qi: (b, ki, h, 0)),
            pl.BlockSpec((1, block_k, 1, hd), lambda b, h, ki, qi: (b, ki, h, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Skv, H, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, Skv, H, hd), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, hd), jnp.float32),
            pltpu.VMEM((block_k, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, dsum)
    # group-sum per-Q-head contributions back to KV heads
    dk = dkh.reshape(B, Skv, KV, G, hd).sum(axis=3).astype(k.dtype)
    dv = dvh.reshape(B, Skv, KV, G, hd).sum(axis=3).astype(v.dtype)

    kern_q = functools.partial(_dq_kernel, scale=scale, causal=causal,
                               window=window, block_q=block_q, block_k=block_k)
    dq = pl.pallas_call(
        kern_q,
        grid=(B, H, Sq // block_q, Skv // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, 1, hd), lambda b, h, qi, ki: (b, qi, h, 0)),
            pl.BlockSpec((1, block_k, 1, hd), lambda b, h, qi, ki: (b, ki, h // G, 0)),
            pl.BlockSpec((1, block_k, 1, hd), lambda b, h, qi, ki: (b, ki, h // G, 0)),
            pl.BlockSpec((1, block_q, 1, hd), lambda b, h, qi, ki: (b, qi, h, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, h, qi, ki: (b, h, qi)),
            pl.BlockSpec((1, 1, block_q), lambda b, h, qi, ki: (b, h, qi)),
        ],
        out_specs=pl.BlockSpec((1, block_q, 1, hd), lambda b, h, qi, ki: (b, qi, h, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, dsum)
    return dq, dk, dv
