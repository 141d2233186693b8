"""Pallas TPU RG-LRU scan: gated diagonal linear recurrence
    h_t = a_t ⊙ h_{t-1} + b_t
with a_t, b_t precomputed (the gate matmuls are MXU work best left to XLA;
the kernel owns only the sequential part — the right compute split on TPU).

Grid (B, W/block_w, S/chunk), chunk innermost; log-depth associative scan in
chunk, (1, block_w) carry in VMEM scratch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK_W = 512
DEFAULT_CHUNK = 128


def _scan_op(l, r):
    a1, b1 = l
    a2, b2 = r
    return a1 * a2, a2 * b1 + b2


def _rglru_kernel(a_ref, b_ref, y_ref, hlast_ref, h_scr):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    a = a_ref[0].astype(jnp.float32)          # (chunk, bw)
    b = b_ref[0].astype(jnp.float32)
    acum, bcum = jax.lax.associative_scan(_scan_op, (a, b), axis=0)
    h = acum * h_scr[...] + bcum              # (chunk, bw) via (1,bw) broadcast
    y_ref[0] = h.astype(y_ref.dtype)
    h_scr[...] = h[-1:][...]

    @pl.when(ci == nc - 1)
    def _final():
        hlast_ref[0] = h_scr[0].astype(hlast_ref.dtype)


def rglru_scan(a, b, *, block_w=DEFAULT_BLOCK_W, chunk=DEFAULT_CHUNK,
               interpret=False):
    """a, b: (B,S,W) -> (h (B,S,W), h_last (B,W))."""
    Bb, S, W = a.shape
    block_w = min(block_w, W)
    chunk = min(chunk, S)
    assert W % block_w == 0 and S % chunk == 0, (W, block_w, S, chunk)
    y, hlast = pl.pallas_call(
        _rglru_kernel,
        grid=(Bb, W // block_w, S // chunk),
        in_specs=[
            pl.BlockSpec((1, chunk, block_w), lambda b_, wi, ci: (b_, ci, wi)),
            pl.BlockSpec((1, chunk, block_w), lambda b_, wi, ci: (b_, ci, wi)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, block_w), lambda b_, wi, ci: (b_, ci, wi)),
            pl.BlockSpec((1, block_w), lambda b_, wi, ci: (b_, wi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(a.shape, a.dtype),
            jax.ShapeDtypeStruct((Bb, W), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, block_w), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)
    return y, hlast
