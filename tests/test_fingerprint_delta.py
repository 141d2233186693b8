"""Device-resident delta detection: fingerprint kernel parity, dirty-block
save bit-identity, collision/shape guards, urgent-save bypass."""

import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import ml_dtypes

from repro.checkpoint import (AsyncCheckpointer, CheckpointStore,
                              DeviceDeltaTracker, extract_snapshot, prestage)
from repro.checkpoint.device_delta import DeltaBlocks
from repro.kernels.fingerprint import (fingerprint_blocks,
                                       fingerprint_blocks_ref,
                                       fingerprint_diff, n_blocks_of)

CHUNK = 64 * 1024


# ---------------------------------------------------------------------------
# kernel parity
# ---------------------------------------------------------------------------

FP_CASES = [
    # dtype, n elements (odd sizes exercise the zero-padded partial block)
    (np.float32, 3 * CHUNK // 4 + 17),
    (ml_dtypes.bfloat16, 2 * CHUNK + 1),
    (np.int8, 5 * CHUNK + 333),
    (np.float32, 7),                     # single partial block
]


def _payload(dtype, n):
    rng = np.random.default_rng(n)
    if np.dtype(dtype) == np.dtype(np.int8):
        return rng.integers(-100, 100, n).astype(dtype)
    return (rng.standard_normal(n) * 3).astype(dtype)


@pytest.mark.parametrize("dtype,n", FP_CASES)
def test_fingerprint_ref_vs_jnp(dtype, n):
    a = _payload(dtype, n)
    ref = fingerprint_blocks_ref(a, CHUNK)
    got = np.asarray(fingerprint_blocks(jnp.asarray(a), block_bytes=CHUNK))
    assert ref.dtype == np.uint32 and got.shape == ref.shape
    np.testing.assert_array_equal(ref, got)


@pytest.mark.parametrize("dtype,n", FP_CASES)
def test_fingerprint_pallas_interpret_parity(dtype, n):
    a = _payload(dtype, n)
    ref = fingerprint_blocks_ref(a, CHUNK)
    got = np.asarray(fingerprint_blocks(jnp.asarray(a), block_bytes=CHUNK,
                                        interpret=True))
    np.testing.assert_array_equal(ref, got)


@pytest.mark.parametrize("dtype", [np.float32, ml_dtypes.bfloat16, np.int8])
def test_fingerprint_pallas_interpret_parity_1mib_chunks(dtype):
    # the default 1 MiB chunk: 2048 to 8192 rows per block, so the kernel
    # walks each block in several row tiles
    block = 1 << 20
    a = _payload(dtype, 2 * block // np.dtype(dtype).itemsize + 999)
    got = np.asarray(fingerprint_blocks(jnp.asarray(a), block_bytes=block,
                                        interpret=True))
    np.testing.assert_array_equal(fingerprint_blocks_ref(a, block), got)


class _NoAsyncCopy:
    def __init__(self, message):
        self.message = message

    def copy_to_host_async(self):
        raise jax.errors.JaxRuntimeError(self.message)


def test_copy_to_host_async_tolerates_only_unimplemented():
    from repro.checkpoint.device_delta import copy_to_host_async
    copy_to_host_async(_NoAsyncCopy("UNIMPLEMENTED: async host copy"))
    with pytest.raises(jax.errors.JaxRuntimeError):
        copy_to_host_async(_NoAsyncCopy("INTERNAL: device lost"))
    donated = jnp.arange(4.0)
    donated.delete()
    with pytest.raises(RuntimeError):              # a deleted buffer is a bug
        copy_to_host_async(donated)


def test_fingerprint_diff_matches_separate_compare():
    a = _payload(np.float32, 4 * CHUNK // 4)
    b = a.copy()
    b[CHUNK // 4 + 5] += 1.0            # dirty exactly block 1
    old = fingerprint_blocks(jnp.asarray(a), block_bytes=CHUNK)
    fp, diff = fingerprint_diff(jnp.asarray(b), old, block_bytes=CHUNK)
    np.testing.assert_array_equal(np.asarray(fp),
                                  fingerprint_blocks_ref(b, CHUNK))
    assert np.asarray(diff).tolist() == [False, True, False, False]


def test_fingerprint_block_sensitivity_and_position():
    a = _payload(np.float32, CHUNK)     # 4 blocks of 64 KiB
    base = fingerprint_blocks_ref(a, CHUNK)
    flipped = a.copy()
    flipped[0], flipped[1] = a[1], a[0]     # swap two words in block 0
    swapped = fingerprint_blocks_ref(flipped, CHUNK)
    assert swapped[0] != base[0]            # position is part of the digest
    np.testing.assert_array_equal(swapped[1:], base[1:])


# ---------------------------------------------------------------------------
# dirty-block saves
# ---------------------------------------------------------------------------

def _state(step, churn_rows=8, n=4, rows=64, cols=1024):
    """~1 MiB of f32 per tensor; `churn_rows` leading rows move per step."""
    rng = np.random.default_rng(42)
    out = {}
    for i in range(n):
        base = jnp.asarray(rng.standard_normal((rows, cols)).astype(np.float32))
        out[f"w{i}"] = base.at[:churn_rows].add(float(step * (i + 1)))
    out["step"] = step
    return out


def _template(state):
    return {k: (np.zeros_like(np.asarray(v)) if hasattr(v, "shape") else 0)
            for k, v in state.items()}


def _tracker_for(store, **kw):
    return DeviceDeltaTracker(store.pool, chunk_size=store.chunk_size,
                              compress=store.compress,
                              quantize_moments=store.quantize_moments, **kw)


def test_dirty_block_save_bit_identical_to_full_v1_and_v2(tmp_path):
    """Restores from fingerprint-delta saves must match, byte for byte,
    restores from v1 (full shard files) and v2-dense (no tracker) saves of
    the same states."""
    stores = {
        "v1": CheckpointStore(str(tmp_path / "v1"), mode="full"),
        "v2": CheckpointStore(str(tmp_path / "v2"), mode="delta",
                              chunk_size=CHUNK),
        "fp": CheckpointStore(str(tmp_path / "fp"), mode="delta",
                              chunk_size=CHUNK),
    }
    tracker = _tracker_for(stores["fp"])
    infos = []
    for step in range(3):
        state = _state(step)
        stores["v1"].save(step, state)
        i_dense = stores["v2"].save(step, state)
        i_fp = stores["fp"].save(step, state, tracker=tracker)
        infos.append((i_dense, i_fp))
        tpl = _template(state)
        restored = {k: s.restore(tpl, step=step)[0] for k, s in stores.items()}
        for k in tpl:
            a = np.asarray(restored["fp"][k])
            np.testing.assert_array_equal(a, np.asarray(restored["v1"][k]))
            np.testing.assert_array_equal(a, np.asarray(restored["v2"][k]))
            np.testing.assert_array_equal(
                a, np.asarray(state[k]) if hasattr(state[k], "shape")
                else state[k])
    # warm fingerprint saves write the same dirty chunks as the dense delta
    for i_dense, i_fp in infos[1:]:
        assert i_fp.new_bytes == i_dense.new_bytes
        # ... while moving far fewer bytes device→host
        assert i_fp.d2h_bytes < i_dense.d2h_bytes / 2
        assert i_fp.d2h_bytes_skipped > 0


def test_unchanged_state_skips_everything(tmp_path):
    store = CheckpointStore(str(tmp_path), mode="delta", chunk_size=CHUNK)
    tracker = _tracker_for(store)
    state = _state(0)
    store.save(0, state, tracker=tracker)
    info = store.save(1, {**state, "step": 1}, tracker=tracker)
    assert info.new_bytes <= 64                     # only the step scalar...
    # ...and (almost) nothing crossed the link: the step scalar plus the
    # per-leaf diff vectors
    assert info.d2h_bytes < 4096
    assert info.d2h_bytes_skipped == sum(
        np.asarray(v).nbytes for k, v in state.items() if k != "step")
    got, _ = store.restore(_template(state), step=1)
    for k, v in state.items():
        if hasattr(v, "shape"):
            np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(v))


def test_forced_collision_shape_dtype_mismatch_never_skips(tmp_path):
    """A fingerprint match may only suppress transfers when shape, dtype,
    chunk size and codec also match. Forge a matching fingerprint under a
    changed shape/dtype: the save must take the dense path, not trust it."""
    store = CheckpointStore(str(tmp_path), mode="delta", chunk_size=CHUNK)
    tracker = _tracker_for(store)
    state = _state(0)
    store.save(0, state, tracker=tracker)

    # same total bytes, different shape; and a dtype change at equal shape
    w0 = np.asarray(state["w0"])
    reshaped = {**state, "w0": jnp.asarray(w0.reshape(128, 512)),
                "step": 1}
    with tracker._lock:
        ent = tracker._entries[("w0", 0)]
        # forge: make the stored fingerprints exactly what the reshaped
        # leaf will digest to (bytes unchanged -> digests identical anyway)
        assert ent.shape == (64, 1024)
    info = store.save(1, reshaped, tracker=tracker)
    # shape mismatch -> dense path: the full leaf crossed the link
    assert info.d2h_bytes >= w0.nbytes
    got, _ = store.restore({**_template(state),
                            "w0": np.zeros((128, 512), np.float32)}, step=1)
    np.testing.assert_array_equal(np.asarray(got["w0"]),
                                  w0.reshape(128, 512))
    assert tracker.stats["fallbacks"] >= 1

    recast = {**state, "w0": jnp.asarray(w0.view(np.int32)), "step": 2}
    info2 = store.save(2, recast, tracker=tracker)
    assert info2.d2h_bytes >= w0.nbytes             # dtype mismatch -> dense
    got2, _ = store.restore({**_template(state),
                             "w0": np.zeros((64, 1024), np.int32)}, step=2)
    np.testing.assert_array_equal(np.asarray(got2["w0"]), w0.view(np.int32))


def test_missing_pool_chunk_turns_block_dirty(tmp_path):
    """A clean-by-fingerprint block whose pool chunk vanished (swept by
    another writer) must be re-transferred, not dangled."""
    store = CheckpointStore(str(tmp_path), mode="delta", chunk_size=CHUNK)
    tracker = _tracker_for(store, touch_interval_s=0.0)  # verify every save
    state = _state(0)
    store.save(0, state, tracker=tracker)
    with tracker._lock:
        ent = tracker._entries[("w1", 0)]
        victim = ent.refs[2]
    os.remove(store.pool.path(victim.hash))
    info = store.save(1, {**state, "step": 1}, tracker=tracker)
    assert info.new_bytes >= victim.nbytes          # block re-written
    got, _ = store.restore(_template(state), step=1)
    np.testing.assert_array_equal(np.asarray(got["w1"]),
                                  np.asarray(state["w1"]))


def test_urgent_save_bypasses_fingerprints(tmp_path):
    """Termination saves take the full prestage path: fingerprints never
    gate them, and the tracker stays consistent for later periodic saves."""
    store = CheckpointStore(str(tmp_path), mode="delta", chunk_size=CHUNK)
    tracker = _tracker_for(store)
    ckpt = AsyncCheckpointer(store)
    try:
        state = _state(0)
        snap0 = ckpt.save_async(0, state, tracker=tracker)
        ckpt.wait_until_finished()
        nbytes = snap0.nbytes
        urgent_state = _state(1)
        info = ckpt.save_urgent(1, urgent_state, timeout_s=120.0)
        # bypass: the full state crossed the link, nothing was skipped
        assert info.d2h_bytes >= nbytes
        assert info.d2h_bytes_skipped == 0
        got, _ = store.restore(_template(urgent_state), step=1)
        for k, v in urgent_state.items():
            if hasattr(v, "shape"):
                np.testing.assert_array_equal(np.asarray(got[k]),
                                              np.asarray(v))
        # periodic save after the urgent one still restores bit-exactly
        state2 = _state(2)
        snap2 = ckpt.save_async(2, state2, tracker=tracker)
        ckpt.wait_until_finished()
        assert snap2.d2h_bytes < snap2.nbytes       # delta path engaged
        got2, _ = store.restore(_template(state2), step=2)
        for k, v in state2.items():
            if hasattr(v, "shape"):
                np.testing.assert_array_equal(np.asarray(got2[k]),
                                              np.asarray(v))
    finally:
        ckpt.close()


def test_high_churn_falls_back_dense(tmp_path):
    """When most blocks are dirty the gather cannot pay; the leaf takes the
    dense path while fingerprints still refresh for the next save."""
    store = CheckpointStore(str(tmp_path), mode="delta", chunk_size=CHUNK)
    tracker = _tracker_for(store)
    state = _state(0, churn_rows=64)                # 100% churn
    store.save(0, state, tracker=tracker)
    info = store.save(1, _state(1, churn_rows=64), tracker=tracker)
    assert info.d2h_bytes >= info.nbytes            # dense fallback
    # fingerprints still refreshed through the fallback: the next save
    # restores bit-exactly off refs recorded by the dense path
    store.save(2, _state(2, churn_rows=64), tracker=tracker)
    got, _ = store.restore(_template(state), step=2)
    for k, v in _state(2, churn_rows=64).items():
        if hasattr(v, "shape"):
            np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(v))


@pytest.mark.parametrize("churn_rows,dense", [(64, 4), (8, 0)])
def test_dense_fallbacks_are_counted(tmp_path, churn_rows, dense):
    """Each diffed leaf that resolve sends down the dense path counts once in
    ``dense_fallbacks``; a low-churn save gathers blocks and counts none."""
    store = CheckpointStore(str(tmp_path), mode="delta", chunk_size=CHUNK)
    tracker = _tracker_for(store)
    store.save(0, _state(0, churn_rows=churn_rows), tracker=tracker)
    assert tracker.stats["dense_fallbacks"] == 0     # nothing to diff yet
    store.save(1, _state(1, churn_rows=churn_rows), tracker=tracker)
    assert tracker.stats["tracked_saves"] == 1
    assert tracker.stats["dense_fallbacks"] == dense
    assert (tracker.stats["blocks_transferred"] == 0) == (dense == 4)


def test_prestage_with_tracker_feeds_extract(tmp_path):
    """The trainer supplier path: prestage dispatches fingerprint+diff, the
    subsequent extract consumes the pending work and produces DeltaBlocks."""
    store = CheckpointStore(str(tmp_path), mode="delta", chunk_size=CHUNK)
    tracker = _tracker_for(store)
    state = _state(0)
    store.save(0, state, tracker=tracker)
    state1 = _state(1)
    prestage(state1, tracker=tracker)
    assert tracker._pending                          # work is in flight
    snap = extract_snapshot(state1, step=1, tracker=tracker)
    assert not tracker._pending                      # consumed, not leaked
    assert any(isinstance(p, DeltaBlocks)
               for lp in snap.leaves.values() for _i, p in lp.pieces)
    info = store.save_snapshot(snap)
    got, _ = store.restore(_template(state1), step=1)
    np.testing.assert_array_equal(np.asarray(got["w2"]),
                                  np.asarray(state1["w2"]))


def test_prestaged_diff_discarded_when_entry_swaps(tmp_path):
    """Async-writer race: a diff prestaged against save N-2's fingerprints
    must be discarded when save N-1 commits in between — pairing the old
    diff with the new refs would reuse a stale chunk for any block that
    reverted to its N-2 value."""
    store = CheckpointStore(str(tmp_path), mode="delta", chunk_size=CHUNK)
    t1 = _tracker_for(store)
    state_a = _state(0, churn_rows=0)               # block content X
    store.save(0, state_a, tracker=t1)

    # save B (content Y for the leading rows) through a second tracker on
    # the same pool — its entries stand in for the async writer's commit
    t2 = _tracker_for(store)
    state_b = _state(5)                             # rows 0..7 differ
    store.save(1, state_b, tracker=t2)

    # state C reverts to A's bytes; prestage diffs it against t1's entry
    # (vs A: everything clean), then the "async commit" swaps the entries
    state_c = {**{k: v for k, v in state_a.items()}, "step": 2}
    prestage(state_c, tracker=t1)
    with t1._lock, t2._lock:
        for key, ent in t2._entries.items():
            t1._entries[key] = ent
    info = store.save(2, state_c, tracker=t1)
    got, _ = store.restore(_template(state_c), step=2)
    for k, v in state_c.items():
        if hasattr(v, "shape"):
            np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(v))
    # the reverted blocks had to cross again (they differ from B)
    assert info.d2h_bytes > 0


def test_coordinator_accounts_d2h(tmp_path):
    """Periodic saves through the coordinator surface d2h/skip/stall in
    CoordinatorStats and the stalls in the TimeLedger's observations."""
    import dataclasses

    from repro.core import CheckpointPolicy, SpotOnCoordinator, WallClock

    store = CheckpointStore(str(tmp_path), mode="delta", chunk_size=CHUNK)
    policy = dataclasses.replace(CheckpointPolicy.transparent(1e9),
                                 async_writes=False)
    coord = SpotOnCoordinator(store, policy, WallClock())
    assert coord.delta_tracker is not None
    state = _state(0)
    assert coord.save_periodic_now(0, state)
    assert coord.save_periodic_now(1, _state(1))
    st = coord.stats
    assert st.d2h_bytes > 0
    assert st.d2h_bytes_skipped > 0                 # second save skipped blocks
    assert st.save_stall_s > 0
    assert len(coord.ledger.observed.get("save_stall", [])) == 2


def test_urgent_stall_observed_apart_from_periodic(tmp_path):
    """The ledger keeps an urgent save's stall under ``urgent_save_stall``:
    ``save_stall`` holds the periodic saves' alone, while
    ``CoordinatorStats.save_stall_s`` still totals both."""
    import dataclasses

    from repro.core import CheckpointPolicy, SpotOnCoordinator, WallClock

    store = CheckpointStore(str(tmp_path), mode="delta", chunk_size=CHUNK)
    policy = dataclasses.replace(CheckpointPolicy.transparent(1e9),
                                 async_writes=False)
    coord = SpotOnCoordinator(store, policy, WallClock())
    assert coord.save_periodic_now(0, _state(0))
    assert coord._save_termination(1, _state(1),
                                   deadline=coord.clock.now() + 3600.0)
    observed = coord.ledger.observed
    assert len(observed["save_stall"]) == 1
    assert len(observed["urgent_save_stall"]) == 1
    assert len(observed["urgent_save_wall"]) == 1
    assert coord.stats.save_stall_s == pytest.approx(
        observed["save_stall"][0] + observed["urgent_save_stall"][0])


# ---------------------------------------------------------------------------
# compile-cache gc + post-commit hooks
# ---------------------------------------------------------------------------

def test_sweep_compilation_cache_age_and_size(tmp_path):
    from repro.launch.train import sweep_compilation_cache

    cache = tmp_path / "xla_cache"
    cache.mkdir()
    now = time.time()
    old = cache / "jit_old"
    old.write_bytes(b"x" * 1000)
    os.utime(old, (now - 30 * 86400, now - 30 * 86400))   # past the age gate
    entries = []
    for i in range(4):
        p = cache / f"jit_{i}"
        p.write_bytes(b"y" * 1000)
        os.utime(p, (now - i * 60, now - i * 60))
        entries.append(p)
    removed = sweep_compilation_cache(str(cache), max_bytes=2500,
                                      max_age_s=14 * 86400, min_interval_s=0)
    assert not old.exists()                         # age-gated
    live = sorted(p.name for p in cache.iterdir())
    assert len(live) == 2                           # size budget: keep newest 2
    assert "jit_0" in live and "jit_1" in live
    assert removed == 3000

    # rate limit: immediate rerun is a no-op even with garbage present
    junk = cache / "jit_junk"
    junk.write_bytes(b"z" * 5000)
    os.utime(junk, (now - 30 * 86400, now - 30 * 86400))
    assert sweep_compilation_cache(str(cache), max_bytes=2500,
                                   max_age_s=14 * 86400,
                                   min_interval_s=3600) == 0
    assert junk.exists()


CACHE_PROBE = """
import sys
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_smoke_config
from repro.core import NoEviction, VirtualClock
from repro.launch.train import build_run, setup_compilation_cache
owned = setup_compilation_cache(sys.argv[1])
print(owned, jax.config.jax_compilation_cache_dir)
jax.jit(lambda x: x * 3 + 1)(jnp.arange(8.0)).block_until_ready()
trainer, _ = build_run(get_smoke_config("phi3-mini-3.8b"), clock=VirtualClock(),
                       schedule=NoEviction(), ckpt_dir=sys.argv[2], steps=1,
                       compile_cache_dir=owned)
trainer.coord.store.save(0, {"w": np.ones(8, np.float32)})
"""


@pytest.mark.parametrize("env_set", [True, False])
def test_compilation_cache_lands_where_the_rule_says(tmp_path, env_set):
    """JAX_COMPILATION_CACHE_DIR wins, nothing is set in code and no commit
    sweeps that directory; without it the cache goes to the directory the
    caller names, which the program owns and its commits sweep."""
    import subprocess
    import sys
    env_dir, flag_dir = tmp_path / "env", tmp_path / "flag"
    used, unused = (env_dir, flag_dir) if env_set else (flag_dir, env_dir)
    used.mkdir()
    stale = used / "jit_stale-cache"                # idle past the age gate
    stale.write_bytes(b"x" * 100)
    old = time.time() - 30 * 86400
    os.utime(stale, (old, old))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_set:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    r = subprocess.run([sys.executable, "-c", CACHE_PROBE, str(flag_dir),
                        str(tmp_path / "ckpt")],
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    owned = "None" if env_set else str(flag_dir)
    assert r.stdout.split() == [owned, str(used)]
    assert any(p != stale for p in used.iterdir())  # an executable was cached
    assert not unused.exists()
    assert stale.exists() == env_set                # swept only where owned


def test_store_post_commit_hook_runs_and_never_fails_save(tmp_path):
    store = CheckpointStore(str(tmp_path), mode="delta", chunk_size=CHUNK)
    calls = []
    store.post_commit.append(lambda: calls.append(1))
    def boom():
        raise RuntimeError("janitor exploded")
    store.post_commit.append(boom)
    info = store.save(0, _state(0))
    assert calls == [1]
    assert info.step == 0                           # save survived the hook
