"""The training helpers shared by the drivers."""

import gc

import jax
import jax.numpy as jnp
import numpy as np

from harness import training


def test_first_steps_hold_one_state_while_the_step_runs():
    """The step does not donate: its input and output are two states, and
    nothing else may hold a third (at the cells' sizes it does not fit)."""
    def make():
        return {"params": {"w": jnp.ones((256, 256), jnp.bfloat16)},
                "opt": {"mu": {"w": jnp.zeros((256, 256), jnp.float32)}}}
    template = jax.eval_shape(make)
    state_bytes = 256 * 256 * 6
    gc.collect()
    base = sum(a.nbytes for a in jax.live_arrays())
    seen = []

    def step(state, batch):
        gc.collect()
        seen.append(sum(a.nbytes for a in jax.live_arrays()) - base)
        return (jax.tree.map(lambda a: a + 1, state),
                {"loss": jnp.float32(2.0)})

    cfg = {"initializer_range": 0.02, "train": {"optimizer": {"b1": 0.9}}}
    state, prog, _ = training.first_steps(
        step, make, lambda i: None, 3, template=template, cfg=cfg, seed=7)
    assert prog["loss"] == [2.0, 2.0, 2.0]
    assert max(seen) <= state_bytes + 1024, seen
    assert float(state["params"]["w"][0, 0]) == 4.0


def test_checksum_sees_one_changed_bit_and_a_swap():
    cs = training.checksum_fn()
    a = jnp.arange(1024, dtype=jnp.float32).reshape(32, 32)
    b = np.asarray(a).copy()
    b.view(np.uint32)[3, 4] ^= 1
    swapped = np.asarray(a)[::-1].copy()
    base = [int(x) for x in cs({"x": a})]
    assert [int(x) for x in cs({"x": jnp.asarray(a)})] == base
    assert [int(x) for x in cs({"x": jnp.asarray(b)})] != base
    assert [int(x) for x in cs({"x": jnp.asarray(swapped)})] != base
    bf = jnp.ones((8,), jnp.bfloat16)
    assert len(cs({"x": bf, "k": jnp.zeros((2,), jnp.uint32)})) == 2
