"""The Pallas kernels of the save/restore path and of the model step
compile for a TPU v5e chip.

Interpret mode (the other kernel tests) checks results, not whether the TPU
lowering accepts a kernel: block shapes off the (8, 128) tiling and blocks
that overflow VMEM pass there and fail only on the chip. These tests compile
the kernels the checkpoint path runs — the per-block fingerprint and the
int8 absmax/quantize/dequantize trio — at real sizes (1 MiB chunks of a
3072 x 8192 weight), and the sequence attention's forward and backward at
the configs' attention widths, for a described, not attached, ``v5e:2x2``
topology.

The topology is described inside a fixture so that importing this module
never loads the TPU library, and every compile lives in this one file.
"""

import functools
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.checkpoint.chunkstore import DEFAULT_CHUNK_SIZE
from repro.kernels.flash_attention import flash_attention, supports
from repro.kernels.fingerprint.fingerprint import LANES
from repro.kernels.fingerprint.ops import _fp_pallas
from repro.kernels.fingerprint.ref import n_blocks_of, words_per_block
from repro.kernels.quantize.quantize import (DEFAULT_BLOCK_ROWS, absmax_2d,
                                             dequantize_2d, quantize_2d)

WEIGHT = (3072, 8192)          # phi3-mini's d_model x d_ff
ROWS = WEIGHT[0] * WEIGHT[1] // LANES


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()   # the Pallas kernel ran
    return compiled


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int8])
def test_fingerprint_compiles_at_1mib_chunks(one_chip, dtype):
    itemsize = np.dtype(dtype).itemsize
    x = jax.ShapeDtypeStruct(WEIGHT, dtype, sharding=one_chip)
    wpb = words_per_block(DEFAULT_CHUNK_SIZE, itemsize)
    n_blocks = n_blocks_of(WEIGHT[0] * WEIGHT[1] * itemsize,
                           DEFAULT_CHUNK_SIZE)
    fp = functools.partial(_fp_pallas, wpb=wpb, n_blocks=n_blocks,
                           interpret=False)
    _compile(fp, x)
    out = jax.eval_shape(fp, x)
    assert (out.shape, out.dtype) == ((n_blocks,), jnp.uint32)  # per chunk


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_absmax_compiles(one_chip, dtype):
    x2d = jax.ShapeDtypeStruct((ROWS, LANES), dtype, sharding=one_chip)
    _compile(functools.partial(absmax_2d, block_rows=DEFAULT_BLOCK_ROWS), x2d)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quantize_compiles(one_chip, dtype):
    inv = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    x2d = jax.ShapeDtypeStruct((ROWS, LANES), dtype, sharding=one_chip)
    _compile(functools.partial(quantize_2d, block_rows=DEFAULT_BLOCK_ROWS),
             inv, x2d)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dequantize_compiles(one_chip, dtype):
    scale = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    q2d = jax.ShapeDtypeStruct((ROWS, LANES), jnp.int8, sharding=one_chip)
    _compile(functools.partial(dequantize_2d, out_dtype=dtype,
                               block_rows=DEFAULT_BLOCK_ROWS), scale, q2d)


@pytest.mark.parametrize("B,S,H,KV,hd,window", [
    (2, 2048, 32, 32, 96, 2047),   # phi3-mini, the benchmark's cell
    (1, 2048, 24, 24, 64, 0),      # musicgen: MHA, hd 64
    (1, 2048, 32, 8, 128, 0),      # minitron: GQA 4:1, hd 128
    (1, 2048, 4, 1, 256, 512),     # gemma3 local: MQA, hd 256, window 512
])
def test_flash_attention_fwd_bwd_compiles(one_chip, B, S, H, KV, hd, window):
    """The entry point's forward and backward compile to the kernel, with
    temporaries of O(S): XLA's chunked path needs 3.91 GB at phi3's shape,
    for its float32 score buffers."""
    q = jax.ShapeDtypeStruct((B, S, H, hd), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((B, S, KV, hd), jnp.bfloat16, sharding=one_chip)
    assert supports(q.shape, kv.shape)

    def fwd_bwd(q, k, v, do):
        o, vjp = jax.vjp(
            lambda q, k, v: flash_attention(q, k, v, window=window), q, k, v)
        return o, vjp(do)

    compiled = _compile(fwd_bwd, q, kv, kv, q)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9
