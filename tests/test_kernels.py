"""Pallas kernels vs pure-jnp oracles (interpret=True), shape/dtype sweeps."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention import decode_attention_ref, flash_decode
from repro.kernels.flash_attention import attention_ref, flash_attention
from repro.kernels.rglru_scan import lru_scan, rglru_scan, rglru_scan_ref
from repro.kernels.ssm_scan import selective_scan, ssm_scan, ssm_scan_ref


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


FLASH_CASES = [
    # B, S, H, KV, hd, window, dtype, tol
    (2, 256, 4, 2, 64, 0, jnp.float32, 2e-5),
    (1, 256, 4, 1, 64, 64, jnp.float32, 2e-5),
    (2, 256, 2, 2, 96, 255, jnp.float32, 2e-5),   # window S-1: one pair masked
    (1, 128, 8, 4, 128, 0, jnp.float32, 2e-5),
    (1, 256, 4, 4, 64, 0, jnp.bfloat16, 2e-2),
    (1, 384, 2, 1, 64, 128, jnp.float32, 2e-5),   # 128-row blocks
    (1, 256, 2, 1, 256, 100, jnp.float32, 2e-5),
]


@pytest.mark.parametrize("B,S,H,KV,hd,window,dtype,tol", FLASH_CASES)
def test_flash_attention_fwd(B, S, H, KV, hd, window, dtype, tol):
    ks = jax.random.split(jax.random.key(S + H), 3)
    q = _rand(ks[0], (B, S, H, hd), dtype)
    k = _rand(ks[1], (B, S, KV, hd), dtype)
    v = _rand(ks[2], (B, S, KV, hd), dtype)
    o = flash_attention(q, k, v, window=window, interpret=True)
    o_ref = attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32), atol=tol)


@pytest.mark.parametrize("B,S,H,KV,hd,window,dtype,tol", FLASH_CASES[:4])
def test_flash_attention_grads(B, S, H, KV, hd, window, dtype, tol):
    ks = jax.random.split(jax.random.key(S * H), 3)
    q = _rand(ks[0], (B, S, H, hd), dtype)
    k = _rand(ks[1], (B, S, KV, hd), dtype)
    v = _rand(ks[2], (B, S, KV, hd), dtype)

    def f(q, k, v):
        return jnp.sum(jnp.sin(flash_attention(q, k, v, window=window,
                                               interpret=True)))

    def fr(q, k, v):
        return jnp.sum(jnp.sin(attention_ref(q, k, v, causal=True, window=window)))

    g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(fr, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=10 * tol)


@pytest.mark.parametrize("B,H,KV,hd,S,vl,bk,dtype,tol", [
    (2, 4, 2, 64, 256, 200, 128, jnp.float32, 2e-5),
    (1, 8, 1, 128, 512, 512, 256, jnp.float32, 2e-5),
    (3, 4, 4, 32, 128, 1, 64, jnp.float32, 2e-5),
    (2, 8, 2, 64, 256, 77, 128, jnp.bfloat16, 2e-2),
])
def test_flash_decode(B, H, KV, hd, S, vl, bk, dtype, tol):
    ks = jax.random.split(jax.random.key(S + vl), 3)
    q = _rand(ks[0], (B, H, hd), dtype)
    k = _rand(ks[1], (B, S, KV, hd), dtype)
    v = _rand(ks[2], (B, S, KV, hd), dtype)
    o = flash_decode(q, k, v, vl, block_k=bk, interpret=True)
    r = decode_attention_ref(q, k, v, vl)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32), atol=tol)


@pytest.mark.parametrize("B,S,DI,N,bd,ch,dtype,tol", [
    (2, 128, 256, 16, 128, 32, jnp.float32, 1e-4),
    (1, 64, 512, 8, 512, 64, jnp.float32, 1e-4),
    (2, 96, 128, 16, 64, 32, jnp.float32, 1e-4),
    (1, 128, 256, 16, 256, 64, jnp.bfloat16, 5e-2),
])
def test_ssm_scan(B, S, DI, N, bd, ch, dtype, tol):
    ks = jax.random.split(jax.random.key(S * DI), 5)
    u = _rand(ks[0], (B, S, DI), dtype)
    dt = jax.nn.softplus(_rand(ks[1], (B, S, DI), jnp.float32) - 2.0)
    A = -jnp.exp(_rand(ks[2], (DI, N), jnp.float32) * 0.3)
    Bm = _rand(ks[3], (B, S, N), jnp.float32)
    Cm = _rand(ks[4], (B, S, N), jnp.float32)
    D = jnp.full((DI,), 0.5, jnp.float32)
    y, h = ssm_scan(u, dt, A, Bm, Cm, D, block_d=bd, chunk=ch, interpret=True)
    yr, hr = ssm_scan_ref(u, dt, A, Bm, Cm, D)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), atol=tol)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), atol=tol)


def test_ssm_scan_grad_via_ref():
    B, S, DI, N = 1, 32, 64, 8
    ks = jax.random.split(jax.random.key(0), 5)
    u = _rand(ks[0], (B, S, DI), jnp.float32)
    dt = jax.nn.softplus(_rand(ks[1], (B, S, DI), jnp.float32) - 2.0)
    A = -jnp.exp(_rand(ks[2], (DI, N), jnp.float32) * 0.3)
    Bm = _rand(ks[3], (B, S, N), jnp.float32)
    Cm = _rand(ks[4], (B, S, N), jnp.float32)
    D = jnp.full((DI,), 0.5, jnp.float32)
    g = jax.grad(lambda *a: jnp.sum(selective_scan(*a, 64, 16, True)),
                 argnums=(0, 1, 2))(u, dt, A, Bm, Cm, D)
    gr = jax.grad(lambda *a: jnp.sum(ssm_scan_ref(*a)[0]),
                  argnums=(0, 1, 2))(u, dt, A, Bm, Cm, D)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


@pytest.mark.parametrize("B,S,W,bw,ch,dtype,tol", [
    (2, 128, 256, 128, 32, jnp.float32, 1e-4),
    (1, 256, 512, 512, 128, jnp.float32, 1e-4),
    (3, 64, 128, 64, 64, jnp.float32, 1e-4),
    (1, 128, 256, 128, 32, jnp.bfloat16, 5e-2),
])
def test_rglru_scan(B, S, W, bw, ch, dtype, tol):
    ks = jax.random.split(jax.random.key(S * W), 2)
    a = jax.nn.sigmoid(_rand(ks[0], (B, S, W), jnp.float32)).astype(dtype)
    b = _rand(ks[1], (B, S, W), dtype)
    y, h = rglru_scan(a, b, block_w=bw, chunk=ch, interpret=True)
    yr, hr = rglru_scan_ref(a, b)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), atol=tol)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), atol=tol)


def test_rglru_grad_via_ref():
    B, S, W = 1, 64, 128
    ks = jax.random.split(jax.random.key(1), 2)
    a = jax.nn.sigmoid(_rand(ks[0], (B, S, W), jnp.float32))
    b = _rand(ks[1], (B, S, W), jnp.float32)
    g = jax.grad(lambda a, b: jnp.sum(lru_scan(a, b, 128, 32, True)),
                 argnums=(0, 1))(a, b)
    gr = jax.grad(lambda a, b: jnp.sum(rglru_scan_ref(a, b)[0]),
                  argnums=(0, 1))(a, b)
    for x, y in zip(g, gr):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-4)
