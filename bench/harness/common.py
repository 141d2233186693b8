"""Seeds, the benchmark's weights and batches, host and compile readings,
and the result line."""

from __future__ import annotations

import collections
import ctypes
import json
import resource
import sys

import numpy as np


def seed_key(seed: int):
    """A PRNG key from any non-negative seed: the low 32 bits make the key
    and the rest is folded in, so seeds past 2**32 stay distinct."""
    import jax

    key = jax.random.key(seed % (1 << 32))
    if seed >> 32:
        key = jax.random.fold_in(key, (seed >> 32) % (1 << 32))
    return key


def make_state_fn(template, init_range: float, seed: int,
                  part: str | None = None):
    """One jitted call that makes a training state shaped like ``template``
    (the program's own layout, as ``jax.eval_shape`` gives it) on the
    device: every parameter matrix drawn from N(0, ``init_range``) in its
    stored dtype, as the source initializes them; every vector parameter
    (norm scales, stored as ``1 + w``), optimizer moment, counter and key
    zero. Layers stacked along a leading axis count by their own shape.
    With ``part``, only that top-level subtree is made, with the same
    values."""
    import jax
    import jax.numpy as jnp

    flat, treedef = jax.tree_util.tree_flatten_with_path(template)

    # the key is an argument, not a constant, so every seed runs the one
    # compiled program
    @jax.jit
    def make(key):
        out = []
        for i, (path, leaf) in enumerate(flat):
            keys = [getattr(k, "key", None) for k in path]
            # layers are stacked along a leading axis under "segments"
            ndim = len(leaf.shape) - ("segments" in keys)
            if (keys[0] == "params" and ndim >= 2
                    and jnp.issubdtype(leaf.dtype, jnp.floating)):
                x = jax.random.normal(jax.random.fold_in(key, i), leaf.shape,
                                      jnp.float32) * init_range
                out.append(x.astype(leaf.dtype))
            else:
                out.append(jnp.zeros(leaf.shape, leaf.dtype))
        tree = jax.tree_util.tree_unflatten(treedef, out)
        return tree if part is None else tree[part]

    return lambda: make(seed_key(seed))


def token_batch(vocab_size: int, batch: int, seq_len: int, seed: int,
                index: int) -> dict:
    """Batch ``index`` of the token stream the trainer feeds: a Zipf(1.3)
    stream folded into the vocabulary, seeded by (seed, index); labels are
    the inputs shifted by one. A copy of the program's generator
    (``repro.data.pipeline.TokenPipeline.batch_at``), kept here so the
    reference is fed by the benchmark."""
    rng = np.random.Generator(np.random.PCG64(
        np.random.SeedSequence([seed, index])))
    z = rng.zipf(1.3, size=(batch, seq_len + 1))
    tokens = (z % vocab_size).astype(np.int32)
    return {"inputs": tokens[:, :-1], "labels": tokens[:, 1:]}


def host_peak_rss_bytes() -> int:
    """This process's peak resident set so far (Linux reports KiB)."""
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def host_rss_bytes() -> int:
    """This process's resident set now."""
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmRSS:"):
                return int(line.split()[1]) * 1024
    return 0


def release_free_heap() -> None:
    """Hand the C heap's free pages back to the system (glibc's
    ``malloc_trim``). A set-up that compiled leaves over a gigabyte of
    freed compiler heap resident, which one that loaded every program from
    the cache does not; trimmed, both start the window nearer to the memory
    they hold live."""
    ctypes.CDLL("libc.so.6").malloc_trim(0)


class CompileLog:
    """Counts JAX's compile-cache events and sums backend compile time."""

    HITS = "/jax/compilation_cache/cache_hits"
    MISSES = "/jax/compilation_cache/cache_misses"
    BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.events: collections.Counter = collections.Counter()
        self.secs: collections.Counter = collections.Counter()
        self.n: collections.Counter = collections.Counter()
        jax.monitoring.register_event_listener(
            lambda ev, **kw: self.events.update([ev]))

        def on_duration(ev, d, **kw):
            self.secs[ev] += d
            self.n[ev] += 1
        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def snapshot(self) -> dict:
        return {"backend_compiles": self.n[self.BACKEND],
                "backend_compile_s": self.secs[self.BACKEND],
                "cache_hits": self.events[self.HITS],
                "cache_misses": self.events[self.MISSES]}

    def since(self, snap: dict) -> dict:
        now = self.snapshot()
        return {k: now[k] - snap[k] for k in now}


def device_info(devices, chips: int) -> dict:
    used = devices[:chips]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in used]
    return {"platform": used[0].platform, "kind": used[0].device_kind,
            "count": len(used), "memory_peak_bytes": int(max(peaks))}


def emit(result: dict, checks: dict) -> None:
    """Print the compared numbers (name -> (value, limit)) beside their
    limits as the last lines of standard error, and the result as the last
    line of standard output with the same numbers under ``checks``, its
    last key."""
    for name, (value, limit) in checks.items():
        print(f"check {name}: {value!r} limit {limit!r}", file=sys.stderr)
    line = dict(result)
    line["checks"] = {name: {"value": v, "limit": lim}
                      for name, (v, lim) in checks.items()}
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
