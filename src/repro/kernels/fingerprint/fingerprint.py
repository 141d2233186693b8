"""Pallas TPU per-block fingerprint kernel.

Grid = one program per block: each step sees one (rows, 128) uint32 window
of the word stream in VMEM, walks it in ``SUB_ROWS``-row tiles (so the
position/mix temporaries stay small whatever the block height), mixes every
word with its position and folds the tiles into one (1, 128) vector of
per-lane partial sums. That lane vector is the block's output row: a
(1, 1, 128) block of an (n_blocks, 1, 128) array, the lane-dense shape the
TPU lowering accepts. The cross-lane sum and the ``fmix32`` finalizer run
in XLA right after the kernel, inside the same jit.

The arithmetic is ``ref.mix_words``/``ref.fmix32`` verbatim (integer xor,
multiply, logical shift on uint32 — all wrap mod 2^32 identically on VPU,
XLA and numpy). Addition mod 2^32 is associative and commutative, so the
tile/lane summation order yields exactly the reference's digest, which is
what the interpret-mode parity tests pin down.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import fmix32, mix_words

LANES = 128
# one block's words sit in VMEM (double-buffered by the pipeline): 8192 rows
# x 128 lanes x 4 B = 4 MiB covers a 1 MiB chunk of int8, the widest word
# expansion, and two such buffers fit the default scoped-VMEM budget
MAX_BLOCK_ROWS = 8192
# rows per inner tile: bounds the iota/position/mix temporaries to
# 512 x 128 x 4 B = 256 KiB each, independent of the block height
SUB_ROWS = 512


def _fingerprint_kernel(w_ref, out_ref):
    rows, lanes = w_ref.shape
    sub = math.gcd(rows, SUB_ROWS)
    r = jax.lax.broadcasted_iota(jnp.uint32, (sub, lanes), 0)
    c = jax.lax.broadcasted_iota(jnp.uint32, (sub, lanes), 1)
    tile_pos = r * jnp.uint32(lanes) + c

    def body(j, acc):
        start = pl.multiple_of(j * sub, sub)
        w = w_ref[pl.ds(start, sub), :]
        base = (j * (sub * lanes)).astype(jnp.uint32)
        h = mix_words(w, tile_pos + base)
        # Mosaic reduces no unsigned integers; int32 two's-complement
        # addition wraps to the same bits as uint32 addition mod 2^32
        h = jax.lax.bitcast_convert_type(h, jnp.int32)
        return acc + jnp.sum(h, axis=0, keepdims=True, dtype=jnp.int32)

    acc = jax.lax.fori_loop(0, rows // sub, body,
                            jnp.zeros((1, lanes), jnp.int32))
    out_ref[0] = acc


def fingerprint_blocks_2d(w2d, *, rows_per_block: int, interpret=False):
    """(n_blocks * rows_per_block, LANES) uint32 words -> uint32[n_blocks]
    digests. Rows of one block are contiguous."""
    total_rows, cols = w2d.shape
    assert cols == LANES and total_rows % rows_per_block == 0, (
        w2d.shape, rows_per_block)
    n_blocks = total_rows // rows_per_block
    lane_sums = pl.pallas_call(
        _fingerprint_kernel,
        grid=(n_blocks,),
        in_specs=[pl.BlockSpec((rows_per_block, cols), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, 1, cols), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_blocks, 1, cols), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(w2d)
    lane_sums = jax.lax.bitcast_convert_type(lane_sums, jnp.uint32)
    return fmix32(jnp.sum(lane_sums.reshape(n_blocks, cols), axis=1,
                          dtype=jnp.uint32))
