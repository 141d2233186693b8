"""Jit-ready entry point: differentiable flash attention in the model's
(B, S, H, hd) layout. The kernel runs with interpret=True on the CPU (tests)
and natively on the TPU."""

from __future__ import annotations

import math

import jax

from .flash_attention import splash_kernel


def flash_attention(q, k, v, *, window: int = 0, interpret: bool = False):
    """Causal self-attention, optionally sliding-window (keys in
    (pos - window, pos]). q: (B,S,H,hd); k, v: (B,S,KV,hd), H % KV == 0,
    S a multiple of 128. q is scaled by 1/sqrt(hd) in its own dtype before
    the kernel, as `models.layers.attention` scales it. Returns (B,S,H,hd)."""
    B, S, H, hd = q.shape
    kernel = splash_kernel(H, S, window, interpret)
    heads_major = lambda x: x.transpose(0, 2, 1, 3)
    q = heads_major(q * (1.0 / math.sqrt(hd)))
    return heads_major(jax.vmap(kernel)(q, heads_major(k), heads_major(v)))
