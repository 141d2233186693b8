"""Pallas TPU selective-scan (Mamba-1 recurrence).

Layout decision (TPU adaptation, not a CUDA port): the GPU mamba kernel
assigns one CUDA block per (batch, channel-slab) and loops time sequentially
with warp shuffles for the intra-block scan. On TPU we instead
*vectorize over channels* (the VPU's 8×128 lanes want the d_inner dimension)
and run a **log-depth associative scan within a sequence chunk**, carrying the
(d_block × d_state) recurrence state across chunks in VMEM scratch. The grid
is (B, d_inner/block_d, S/chunk) with the chunk dimension innermost
("arbitrary") so the carry is legal.

h_t = exp(Δ_t A) h_{t-1} + Δ_t B_t u_t ;  y_t = C_t · h_t + D u_t
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK_D = 512
DEFAULT_CHUNK = 64


def _scan_op(l, r):
    a1, b1 = l
    a2, b2 = r
    return a1 * a2, a2 * b1 + b2


def _ssm_kernel(u_ref, dt_ref, A_ref, B_ref, C_ref, D_ref, y_ref, hlast_ref,
                h_scr, *, chunk):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    u = u_ref[0].astype(jnp.float32)          # (chunk, bd)
    dt = dt_ref[0].astype(jnp.float32)        # (chunk, bd)
    A = A_ref[...].astype(jnp.float32)        # (bd, N)
    Bm = B_ref[0].astype(jnp.float32)         # (chunk, N)
    Cm = C_ref[0].astype(jnp.float32)         # (chunk, N)
    D = D_ref[...].astype(jnp.float32)        # (bd,)

    dA = jnp.exp(dt[:, :, None] * A[None])                    # (chunk,bd,N)
    dBu = (dt * u)[:, :, None] * Bm[:, None, :]               # (chunk,bd,N)
    # log-depth scan within the chunk, then fuse the carried state:
    # h_t = (prod_{i<=t} dA_i) h_carry + scan_t
    acum, bcum = jax.lax.associative_scan(_scan_op, (dA, dBu), axis=0)
    h = acum * h_scr[...][None] + bcum                        # (chunk,bd,N)
    y = jnp.sum(h * Cm[:, None, :], axis=2) + u * D[None, :]  # (chunk,bd)
    y_ref[0] = y.astype(y_ref.dtype)
    h_scr[...] = h[-1]

    @pl.when(ci == nc - 1)
    def _final():
        hlast_ref[0] = h_scr[...].astype(hlast_ref.dtype)


def ssm_scan(u, delta, A, B, C, D, *, block_d=DEFAULT_BLOCK_D,
             chunk=DEFAULT_CHUNK, interpret=False):
    """u,delta: (B,S,DI); A: (DI,N); B,C: (B,S,N); D: (DI,).
    Returns (y (B,S,DI), h_last (B,DI,N))."""
    Bb, S, DI = u.shape
    N = A.shape[1]
    block_d = min(block_d, DI)
    chunk = min(chunk, S)
    assert DI % block_d == 0 and S % chunk == 0, (DI, block_d, S, chunk)

    kernel = functools.partial(_ssm_kernel, chunk=chunk)
    y, hlast = pl.pallas_call(
        kernel,
        grid=(Bb, DI // block_d, S // chunk),
        in_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda b, di, ci: (b, ci, di)),
            pl.BlockSpec((1, chunk, block_d), lambda b, di, ci: (b, ci, di)),
            pl.BlockSpec((block_d, N), lambda b, di, ci: (di, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, di, ci: (b, ci, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, di, ci: (b, ci, 0)),
            pl.BlockSpec((block_d,), lambda b, di, ci: (di,)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda b, di, ci: (b, ci, di)),
            pl.BlockSpec((1, block_d, N), lambda b, di, ci: (b, di, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(u.shape, u.dtype),
            jax.ShapeDtypeStruct((Bb, DI, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_d, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(u, delta, A, B, C, D)
    return y, hlast
