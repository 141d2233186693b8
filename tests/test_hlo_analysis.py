"""Trip-count-aware HLO analysis vs unrolled references — the correctness
basis of the roofline table (EXPERIMENTS.md §Roofline)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.launch.hlo_analysis import analyze


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_scan_flops_match_unrolled():
    L, B, D = 8, 4, 128
    W = jax.random.normal(jax.random.key(0), (L, D, D))
    x = jax.random.normal(jax.random.key(1), (B, D))

    def scanned(x, W):
        y, _ = jax.lax.scan(lambda x, w: (x @ w, None), x, W)
        return y

    def unrolled(x, W):
        for i in range(L):
            x = x @ W[i]
        return x

    a_s = analyze(_compile(scanned, x, W).as_text())
    a_u = analyze(_compile(unrolled, x, W).as_text())
    expect = L * 2 * B * D * D
    assert a_s["flops"] == expect
    assert a_u["flops"] == expect


def test_grad_scan_counts_bwd_loop():
    L, B, D = 8, 4, 64
    W = jax.random.normal(jax.random.key(0), (L, D, D))
    x = jax.random.normal(jax.random.key(1), (B, D))

    def scanned(x, W):
        y, _ = jax.lax.scan(lambda x, w: (x @ w, None), x, W)
        return jnp.sum(y)

    g = _compile(jax.grad(scanned, argnums=(0, 1)), x, W)
    a = analyze(g.as_text())
    # fwd + dx + dW dots = 3 x L matmuls
    assert a["flops"] == 3 * L * 2 * B * D * D


def test_bytes_not_inflated_by_loop_accumulators():
    """xs-stacking via dynamic-update-slice must count update bytes, not the
    full stacked buffer, per iteration."""
    L, D = 16, 256
    x = jax.random.normal(jax.random.key(0), (D,))

    def f(x):
        def body(c, _):
            c = c * 1.0001
            return c, c
        _, ys = jax.lax.scan(body, x, None, length=L)
        return ys

    a = analyze(_compile(f, x).as_text())
    # ys buffer is L*D floats; per-iteration update is D floats. If the full
    # buffer were counted per iteration we'd see ~L^2*D*4 bytes.
    assert a["bytes"] < L * D * 4 * 20, a["bytes"]


def test_collectives_counted_with_trips():
    import os
    import subprocess
    import sys
    import textwrap
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.hlo_analysis import analyze
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("model",))
        sh_w = NamedSharding(mesh, P(None, None, "model"))
        sh_x = NamedSharding(mesh, P(None))
        L, D = 4, 64
        W = jax.ShapeDtypeStruct((L, D, D), jnp.float32, sharding=sh_w)
        x = jax.ShapeDtypeStruct((8, D), jnp.float32, sharding=sh_x)
        def f(x, W):
            def body(x, w):
                # column-parallel then implicit gather back to replicated
                h = x @ w
                return jax.lax.with_sharding_constraint(
                    h, NamedSharding(mesh, P(None))), None
            y, _ = jax.lax.scan(body, x, W)
            return y
        with mesh:
            c = jax.jit(f).lower(x, W).compile()
        a = analyze(c.as_text())
        n = sum(a["collective_counts"].values())
        assert n >= L, (n, a["collective_counts"])   # one per layer, x trips
        print("COLL_OK", n)
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "COLL_OK" in proc.stdout


def test_model_flops_sane():
    from repro.configs import get_config
    from repro.launch.roofline import active_matmul_params, model_flops
    cfg = get_config("phi3_mini_3p8b")
    N = active_matmul_params(cfg)
    assert 3.0e9 < N < 4.5e9
    tokens = 256 * 4096
    mf = model_flops(cfg, kind="train", batch=256, seq_len=4096)
    assert mf > 6 * N * tokens                # attention adds on top
    assert mf < 6 * N * tokens * 1.6
    # MoE: active < total
    moe = get_config("grok1_314b")
    assert active_matmul_params(moe) < 0.45 * moe.param_count()


def test_roofline_uses_the_device_kinds_peaks():
    from repro.launch.roofline import roofline_terms
    t = roofline_terms(per_device_flops=197e12, per_device_bytes=819e9 / 2,
                       per_device_coll_bytes=0.0, device_kind="TPU v5 lite")
    assert t["compute_s"] == 1.0 and t["memory_s"] == 0.5
    assert t["dominant"] == "compute"
    with pytest.raises(KeyError, match="no published peaks"):
        roofline_terms(per_device_flops=1.0, per_device_bytes=1.0,
                       per_device_coll_bytes=0.0, device_kind="TPU v4")
