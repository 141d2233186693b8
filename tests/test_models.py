"""Per-arch smoke tests (reduced configs): one forward + one train step on
CPU asserting shapes and finiteness; decode-vs-teacher-forcing consistency for
one representative of each cache family (ring / KV / SSM / LRU / MoE);
the sequence attention's Pallas kernel path against XLA's."""

import dataclasses
import functools
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, SHAPES, cell_is_runnable, get_config, get_smoke_config
from repro.kernels.flash_attention import flash_attention
from repro.models import decode_step, forward, init_cache, init_params, prefill
from repro.models import layers as L
from repro.models import transformer as T
from repro.optim import AdamWConfig
from repro.train.train_step import (cross_entropy, init_train_state,
                                    make_train_step)
from repro.data import TokenPipeline


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_and_train_step(arch):
    cfg = get_smoke_config(arch)
    B, S = 2, 32
    pipe = TokenPipeline(vocab_size=cfg.vocab_size, batch=B, seq_len=S,
                         embed_dim=None if cfg.embed_inputs else cfg.d_model)
    batch = pipe.batch_at(0)
    state = init_train_state(cfg, AdamWConfig(total_steps=10))
    logits, aux, _ = jax.jit(lambda p, x: forward(p, cfg, x))(
        state["params"], batch["inputs"])
    assert logits.shape == (B, S, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    step = jax.jit(make_train_step(cfg, AdamWConfig(total_steps=10)))
    state2, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert int(state2["step"]) == 1
    # params actually moved
    moved = any(
        not np.array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
        for a, b in zip(jax.tree.leaves(state["params"]),
                        jax.tree.leaves(state2["params"])))
    assert moved


@pytest.mark.parametrize("arch", [
    "gemma3_1b",          # ring + full caches, 5:1 local:global
    "deepseek_moe_16b",   # MoE routed+shared, dense prelude
    "falcon_mamba_7b",    # SSM state cache
    "recurrentgemma_2b",  # RG-LRU + local attn hybrid
])
def test_decode_matches_teacher_forcing(arch):
    cfg = dataclasses.replace(get_smoke_config(arch), dtype="float32")
    params = init_params(cfg, jax.random.key(0))
    B, S = 2, 24
    if cfg.embed_inputs:
        inputs = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size)
    else:
        inputs = jax.random.normal(jax.random.key(1), (B, S, cfg.d_model))
    logits_tf, _, _ = jax.jit(lambda p, x: forward(p, cfg, x))(params, inputs)
    dec = jax.jit(lambda p, x, c, pos: decode_step(p, cfg, x, c, pos))
    caches = init_cache(cfg, B, S)
    errs = []
    for t in range(S):
        lg, caches = dec(params, inputs[:, t:t + 1], caches, t)
        errs.append(float(np.max(np.abs(np.asarray(lg) - np.asarray(logits_tf[:, t])))))
    assert max(errs) < 5e-4, max(errs)
    # prefill handoff
    half = S // 2
    last, caches_p, _ = jax.jit(
        lambda p, x: prefill(p, cfg, x, cache_len=S))(params, inputs[:, :half])
    lg, _ = dec(params, inputs[:, half:half + 1], caches_p, half)
    assert float(np.max(np.abs(np.asarray(lg) - np.asarray(logits_tf[:, half])))) < 5e-4


def test_param_count_within_spec():
    """Analytic parameter counts stay near the published sizes."""
    expected = {
        "command_r_plus_104b": (104e9, 0.10),
        "grok1_314b": (314e9, 0.10),
        "falcon_mamba_7b": (7.3e9, 0.15),
        "phi3_mini_3p8b": (3.8e9, 0.10),
        "deepseek_moe_16b": (16.4e9, 0.10),
        "minitron_8b": (8e9, 0.25),
        "gemma3_1b": (1.0e9, 0.35),
        "recurrentgemma_2b": (2.7e9, 0.25),
        "llava_next_34b": (34e9, 0.15),
        "musicgen_medium": (1.5e9, 0.35),
    }
    for arch, (target, tol) in expected.items():
        n = get_config(arch).param_count()
        assert abs(n - target) / target < tol, (arch, n, target)


def test_long_context_eligibility():
    eligible = {a for a in ARCH_IDS
                if cell_is_runnable(get_config(a), "long_500k")[0]}
    assert eligible == {"falcon_mamba_7b", "recurrentgemma_2b", "gemma3_1b"}


def test_pipeline_deterministic_and_stateless():
    pipe = TokenPipeline(vocab_size=100, batch=2, seq_len=8, seed=3)
    a = pipe.batch_at(5)
    b = pipe.batch_at(5)
    np.testing.assert_array_equal(a["inputs"], b["inputs"])
    c = pipe.batch_at(6)
    assert not np.array_equal(a["inputs"], c["inputs"])
    # labels are next-token shifted
    full = TokenPipeline(vocab_size=100, batch=2, seq_len=8, seed=3)
    d = full.batch_at(0)
    assert d["labels"].shape == d["inputs"].shape


@pytest.fixture
def kernel_path(monkeypatch):
    """Steer the model's sequence attention onto the Pallas kernel, run in
    interpret mode, as the dispatch would on a TPU."""
    monkeypatch.setattr(T, "_tpu_backend", lambda: True)
    monkeypatch.setattr(T, "flash_attention",
                        functools.partial(flash_attention, interpret=True))


def _bf16(key, shape):
    return jax.random.normal(key, shape, jnp.float32).astype(jnp.bfloat16)


@pytest.mark.parametrize("B,S,H,KV,hd,window", [
    (2, 256, 4, 4, 96, 0),       # MHA, causal only
    (1, 256, 8, 2, 64, 255),     # GQA, window S-1: one pair a head masked
    (1, 512, 4, 1, 128, 200),    # MQA, a window well inside S
])
def test_sequence_attention_kernel_matches_xla(kernel_path, B, S, H, KV, hd,
                                               window):
    ks = jax.random.split(jax.random.key(S + window), 4)
    q, k, v = (_bf16(ks[0], (B, S, H, hd)), _bf16(ks[1], (B, S, KV, hd)),
               _bf16(ks[2], (B, S, KV, hd)))
    do = _bf16(ks[3], (B, S, H, hd))
    assert T._flash_engages(q, k)

    def loss(attn):
        return lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32) * do)

    kern = lambda q, k, v: T._sequence_attention(q, k, v, window)
    xla = lambda q, k, v: L.attention(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(kern(q, k, v), np.float32),
                               np.asarray(xla(q, k, v), np.float32),
                               atol=1e-2, rtol=1e-2)
    g = jax.grad(loss(kern), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss(xla), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        np.testing.assert_allclose(a, b, atol=1e-2 * np.abs(b).max())


@pytest.mark.parametrize("tpu,S,hd,mesh,engages", [
    (False, 256, 96, None, False),   # every CPU run keeps XLA's path
    (True, 256, 96, None, True),
    (True, 256, 96, 1, True),        # sharding rules over a mesh of one
    (True, 256, 96, 4, False),       # sharded activations keep XLA's path
    (True, 200, 96, None, False),    # S not a multiple of a 128-row block
    (True, 256, 48, None, False),    # a head_dim the kernel was not tested for
])
def test_flash_dispatch(monkeypatch, tpu, S, hd, mesh, engages):
    monkeypatch.setattr(T, "_tpu_backend", lambda: tpu)
    rules = None if mesh is None else types.SimpleNamespace(
        mesh=types.SimpleNamespace(size=mesh))
    monkeypatch.setattr(T, "current_rules", lambda: rules)
    q = jax.ShapeDtypeStruct((2, S, 4, hd), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((2, S, 2, hd), jnp.bfloat16)
    assert T._flash_engages(q, k) is engages


@pytest.mark.parametrize("remat", ["none", "full"])
def test_train_step_kernel_path_matches_xla(monkeypatch, remat):
    """A 2-layer smoke model through `train_step` on the kernel path against
    XLA's path: the step's loss and gradient norm, and every leaf's
    gradient."""
    cfg = dataclasses.replace(get_smoke_config("phi3_mini_3p8b"), n_layers=2,
                              head_dim=64, block_pattern=("local",), window=100)
    opt = AdamWConfig(total_steps=10)
    batch = TokenPipeline(vocab_size=cfg.vocab_size, batch=2,
                          seq_len=256).batch_at(0)
    state = init_train_state(cfg, opt)
    step = make_train_step(cfg, opt, remat=remat)

    def grads(params):
        logits, _, _ = forward(params, cfg, batch["inputs"], remat=remat)
        return cross_entropy(logits, batch["labels"])

    def run():
        _, m = jax.jit(step)(state, batch)
        return m, jax.jit(jax.grad(grads))(state["params"])

    m_xla, g_xla = run()
    monkeypatch.setattr(T, "_tpu_backend", lambda: True)
    monkeypatch.setattr(T, "flash_attention",
                        functools.partial(flash_attention, interpret=True))
    m_kern, g_kern = run()
    assert float(m_kern["loss"]) == pytest.approx(float(m_xla["loss"]), rel=2e-3)
    assert float(m_kern["grad_norm"]) == pytest.approx(float(m_xla["grad_norm"]),
                                                       rel=2e-2)
    for a, b in zip(jax.tree.leaves(g_kern), jax.tree.leaves(g_xla)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        np.testing.assert_allclose(a, b, atol=3e-2 * np.abs(b).max())
