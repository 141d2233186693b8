"""Dispatching wrapper: Pallas fingerprint kernel on TPU, jnp oracle elsewhere.

``fingerprint_blocks`` is what the delta save path calls: tensor in (any
layout, device-resident), uint32[n_blocks] digest array out — one digest per
``block_bytes`` window of the tensor's raw bytes, aligned with the chunk
boundaries ``chunkstore.iter_chunks`` uses, so "digest b changed" means
exactly "pool chunk b must be re-encoded". The result stays on device: the
tracker compares it against the previous save's digests with one elementwise
``!=`` and only the tiny bool vector crosses device→host.

All paths (Pallas, jitted jnp, numpy ref) produce bit-identical digests —
the tracker stores device digests across saves and the tests pin the
identity in interpret mode.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .fingerprint import LANES, MAX_BLOCK_ROWS, fingerprint_blocks_2d
from .ref import (fingerprint_blocks_ref, fmix32, mix_words, n_blocks_of,
                  word_bytes, words_per_block)

__all__ = ["fingerprint_blocks", "fingerprint_blocks_ref", "supported_dtype"]


def supported_dtype(dtype) -> bool:
    """Dtypes the word stream is defined for (everything the checkpoint
    stores except bool, whose bitcast semantics differ across backends)."""
    dt = np.dtype(dtype)
    return dt.kind != "b" and dt.itemsize in (1, 2, 4, 8)


def _words_impl(x, wpb, n_blocks):
    """Trace-time helper: ``x`` flattened to its uint32 word stream,
    zero-padded to whole blocks, shaped (n_blocks, wpb)."""
    flat = x.reshape(-1)
    it = np.dtype(x.dtype).itemsize
    if it == 4:
        w = jax.lax.bitcast_convert_type(flat, jnp.uint32)
    elif it == 2:
        w = jax.lax.bitcast_convert_type(flat, jnp.uint16).astype(jnp.uint32)
    elif it == 1:
        w = jax.lax.bitcast_convert_type(flat, jnp.uint8).astype(jnp.uint32)
    else:  # 8-byte elements split into two uint32 words (memory order)
        w = jax.lax.bitcast_convert_type(flat, jnp.uint32).reshape(-1)
    pad = n_blocks * wpb - w.size
    if pad:
        w = jnp.pad(w, (0, pad))
    return w.reshape(n_blocks, wpb)


@functools.partial(jax.jit, static_argnames=("wpb", "n_blocks"))
def _fp_jnp(x, wpb, n_blocks):
    # bitcast + mix + reduce in ONE jit: XLA fuses the word stream into the
    # mixer, so the uint32 view never materializes — the digest pass reads
    # the tensor once at memory bandwidth
    w2d = _words_impl(x, wpb, n_blocks)
    pos = jnp.arange(wpb, dtype=jnp.uint32)
    h = mix_words(w2d, pos)
    return fmix32(jnp.sum(h, axis=1, dtype=jnp.uint32))


@functools.partial(jax.jit, static_argnames=("wpb", "n_blocks"))
def _fp_diff_jnp(x, old_fp, wpb, n_blocks):
    fp = _fp_jnp(x, wpb, n_blocks)
    return fp, fp != old_fp


@functools.partial(jax.jit, static_argnames=("wpb", "n_blocks", "interpret"))
def _fp_pallas(x, wpb, n_blocks, interpret):
    # word-stream prep fused into the same jit as the pallas_call: for
    # 4-byte dtypes the bitcast is a free aliasing view inside XLA, so the
    # kernel reads the leaf's own buffer instead of a full-size uint32
    # temporary. (1/2-byte dtypes still pay the zero-extend to uint32 —
    # the kernel's word width — which is inherent until the widen moves
    # inside the kernel body.)
    rows = wpb // LANES
    w = _words_impl(x, wpb, n_blocks).reshape(n_blocks * rows, LANES)
    return fingerprint_blocks_2d(w, rows_per_block=rows, interpret=interpret)


def _single_device(x) -> bool:
    try:
        return len(x.sharding.device_set) == 1
    except AttributeError:
        return True


def fingerprint_blocks(x, *, block_bytes: int, interpret: bool = False):
    """x (device array) -> uint32[n_blocks] digests, one per ``block_bytes``
    window of its raw bytes. The digests stay on device."""
    x = jnp.asarray(x)
    if block_bytes % 4 or block_bytes < 4:
        raise ValueError(f"block_bytes must be a multiple of 4, got {block_bytes}")
    dt = np.dtype(x.dtype)
    if not supported_dtype(dt):
        raise TypeError(f"fingerprint unsupported for dtype {dt}")
    nbytes = x.size * dt.itemsize
    if nbytes == 0:
        return jnp.zeros(0, jnp.uint32)
    wpb = words_per_block(block_bytes, dt.itemsize)
    n_blocks = n_blocks_of(nbytes, block_bytes)
    rows = wpb // LANES
    if ((interpret or jax.default_backend() == "tpu") and _single_device(x)
            and wpb % LANES == 0 and 0 < rows <= MAX_BLOCK_ROWS):
        return _fp_pallas(x, wpb, n_blocks, interpret)
    return _fp_jnp(x, wpb, n_blocks)


def fingerprint_diff(x, old_fp, *, block_bytes: int, interpret: bool = False):
    """(new fingerprints, per-block changed mask) in one dispatch.

    The save path's hot call: digest + compare against the previous save's
    device-resident fingerprints without materializing anything but the two
    small output arrays. ``old_fp`` must have n_blocks entries for ``x``
    (the tracker guarantees it via its shape/dtype identity checks)."""
    x = jnp.asarray(x)
    dt = np.dtype(x.dtype)
    wpb = words_per_block(block_bytes, dt.itemsize)
    n_blocks = n_blocks_of(x.size * dt.itemsize, block_bytes)
    rows = wpb // LANES
    if ((interpret or jax.default_backend() == "tpu") and _single_device(x)
            and wpb % LANES == 0 and 0 < rows <= MAX_BLOCK_ROWS):
        fp = _fp_pallas(x, wpb, n_blocks, interpret)
        return fp, fp != old_fp
    return _fp_diff_jnp(x, old_fp, wpb, n_blocks)
