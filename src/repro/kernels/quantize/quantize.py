"""Pallas TPU absmax-int8 quantize/dequantize — the checkpoint hot path's
device halves.

Three small kernels over the same (rows, 128) blocking of the flattened
tensor:

  1. ``absmax`` — sequential grid over row-blocks accumulating max|x| in a
     (1, 1) SMEM scratch cell (a scalar reduction, per the TPU idiom).
  2. ``quantize`` — elementwise fused scale/round/clip/cast; the scalar
     scale rides in SMEM so every block reads it without an HBM round-trip.
  3. ``dequantize`` — the restore mirror: fused int8→float32 widen,
     multiply by the SMEM scalar scale, cast to the logical dtype. Restored
     int8 payloads cross the host→device link at 1/4 width and widen on
     device instead of paying a host ``astype`` double-copy.

The arithmetic (float32 intermediate, round-half-even, clip to ±127,
absmax/127 scale) matches ``checkpoint.serialize.quantize`` bit-for-bit —
that identity is what lets device-quantized urgent-save chunks dedup against
host-quantized periodic-save chunks in the content-addressed pool. The
dequantize matches ``serialize.finish_payload`` the same way (multiply-only
in float32 — never divide, fast-math rewrites division), so a streaming
device restore is bit-identical to the host path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


LANES = 128
DEFAULT_BLOCK_ROWS = 256


def _absmax_kernel(x_ref, out_ref, acc_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[0, 0] = 0.0

    m = jnp.max(jnp.abs(x_ref[...].astype(jnp.float32)))
    acc_ref[0, 0] = jnp.maximum(acc_ref[0, 0], m)

    @pl.when(i == pl.num_programs(0) - 1)
    def _final():
        out_ref[0, 0] = acc_ref[0, 0]


def _quantize_kernel(inv_ref, x_ref, q_ref):
    # multiply by the precomputed 1/scale — never divide: fast-math rewrites
    # division into reciprocal-multiply, and the stored bytes must be
    # bit-identical to the host quantize (see serialize.int8_scale_inv)
    inv = inv_ref[0, 0]
    q_ref[...] = jnp.clip(jnp.round(x_ref[...].astype(jnp.float32) * inv),
                          -127.0, 127.0).astype(jnp.int8)


def _dequant_kernel(scale_ref, q_ref, out_ref):
    # widen → multiply by the scalar scale → cast, all fused in one pass;
    # the float32 intermediate and final cast replicate the host
    # serialize.finish_payload sequence bit-for-bit
    s = scale_ref[0, 0]
    out_ref[...] = (q_ref[...].astype(jnp.float32) * s).astype(out_ref.dtype)


def absmax_2d(x2d, *, block_rows: int = DEFAULT_BLOCK_ROWS, interpret=False):
    """max|x| over a (rows, LANES) array -> (1, 1) float32."""
    rows, cols = x2d.shape
    block_rows = min(block_rows, rows)
    assert rows % block_rows == 0 and cols == LANES, (x2d.shape, block_rows)
    return pl.pallas_call(
        _absmax_kernel,
        grid=(rows // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, cols), lambda i: (i, 0))],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
        scratch_shapes=[pltpu.SMEM((1, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(x2d)


def quantize_2d(inv, x2d, *, block_rows: int = DEFAULT_BLOCK_ROWS,
                interpret=False):
    """Fused q = int8(clip(round(x * inv))) over (rows, LANES); ``inv`` is
    the precomputed float32 reciprocal of the absmax scale."""
    rows, cols = x2d.shape
    block_rows = min(block_rows, rows)
    assert rows % block_rows == 0 and cols == LANES, (x2d.shape, block_rows)
    return pl.pallas_call(
        _quantize_kernel,
        grid=(rows // block_rows,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, cols), jnp.int8),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(jnp.asarray(inv, jnp.float32).reshape(1, 1), x2d)


def dequantize_2d(scale, q2d, *, out_dtype, block_rows: int = DEFAULT_BLOCK_ROWS,
                  interpret=False):
    """Fused x = out_dtype(float32(q) * scale) over (rows, LANES); ``scale``
    is the absmax scale stored in the checkpoint record."""
    rows, cols = q2d.shape
    block_rows = min(block_rows, rows)
    assert rows % block_rows == 0 and cols == LANES, (q2d.shape, block_rows)
    return pl.pallas_call(
        _dequant_kernel,
        grid=(rows // block_rows,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, cols), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(jnp.asarray(scale, jnp.float32).reshape(1, 1), q2d)
