"""What every training cell does around the program: drive its compiled
step through the first steps and read them, run the plain reference over
the same weights and batches, and checksum states bit for bit."""

from __future__ import annotations

import gc
import time

import numpy as np

from . import common, spec


def named_leaves(tree) -> list[str]:
    import jax
    return [jax.tree_util.keystr(p)
            for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def _norm_fns():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def norms(a):
        return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                for x in jax.tree.leaves(a)]

    @jax.jit
    def diff_norms(a, b):
        return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)
                                            - y.astype(jnp.float32))))
                for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))]
    return norms, diff_norms


def params_fn(template, cfg: dict, seed: int):
    """The initial parameters alone, as the whole state has them."""
    return common.make_state_fn(template, cfg["initializer_range"], seed,
                                part="params")


def first_steps(step, make_state, batch_at, n: int, *, template, cfg: dict,
                seed: int):
    """Drive the program's compiled ``step`` through ``n`` steps from the
    state ``make_state()`` returns on ``batch_at(i)``. Returns (state after
    them, readings as ``compare.gaps`` takes them, host seconds of the
    fastest step after the first).
    The state is made here, so that no caller holds the initial one while
    the steps run: the step does not donate, and a third state would not
    fit beside its input and output."""
    norms, diff_norms = _norm_fns()
    b1 = cfg["train"]["optimizer"]["b1"]
    state = make_state()
    names = named_leaves(state["params"])
    losses, times, grad = [], [], None
    for i in range(n):
        batch = batch_at(i)
        t0 = time.perf_counter()
        state, metrics = step(state, batch)
        losses.append(float(np.asarray(metrics["loss"])))
        times.append(time.perf_counter() - t0)
        if i == 0:
            # the first moment after one step is (1 - b1) times the
            # gradient as the optimizer took it
            grad = [float(x) / (1 - b1) for x in norms(state["opt"]["mu"])]
    # the initial parameters are made again from the seed rather than kept
    # through the steps, which would hold a third copy on the device
    p0 = params_fn(template, cfg, seed)()
    change = [float(x) for x in diff_norms(state["params"], p0)]
    del p0
    prog = {"loss": losses, "grad": dict(zip(names, grad)),
            "change": dict(zip(names, change))}
    # the fastest step: a step the host delayed says nothing about the chip
    return state, prog, min(times[1:] or times)


def reference(cell: spec.Cell, seed: int, template, *, quant=None,
              rows=None) -> dict:
    """The plain reference's readings of the set-up steps, on the weights
    the benchmark makes and the batches it generates from ``seed``."""
    cfg, tr = cell.config, cell.traffic
    train = cfg["train"]
    ref = spec.reference_module(cfg)
    params = params_fn(template, cfg, seed)()
    batches = [common.token_batch(cfg["vocab_size"], train["batch"],
                                  train["seq_len"], seed, i)
               for i in range(tr["setup_steps"])]
    try:
        return ref.train_readings(cfg, params, batches,
                                  total_steps=tr["lr_horizon_steps"],
                                  quant=quant, rows=rows)
    finally:
        del params
        gc.collect()


def checksum_fn():
    """A jitted per-leaf checksum: every element's bits mixed with its
    position and summed modulo 2**32, so a changed, moved or missing
    element changes it. Works on sharded arrays as on whole ones."""
    import jax
    import jax.numpy as jnp

    def words(x):
        x = x.reshape(-1)
        size = np.dtype(x.dtype).itemsize
        if x.dtype == jnp.bool_:
            return x.astype(jnp.uint32)
        if size == 4:
            return jax.lax.bitcast_convert_type(x, jnp.uint32)
        if size == 2:
            return jax.lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.uint32)
        if size == 1:
            return jax.lax.bitcast_convert_type(x, jnp.uint8).astype(jnp.uint32)
        return jax.lax.bitcast_convert_type(x, jnp.uint32).reshape(-1)

    def mix(w):
        pos = jnp.arange(w.size, dtype=jnp.uint32)
        h = w ^ (pos * jnp.uint32(0x9E3779B9))
        h = h ^ (h >> 16)
        h = h * jnp.uint32(0x85EBCA6B)
        h = h ^ (h >> 13)
        return jnp.sum(h, dtype=jnp.uint32)

    return jax.jit(lambda tree: [mix(words(x)) for x in jax.tree.leaves(tree)])
