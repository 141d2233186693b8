#!/usr/bin/env python3
"""Checkpointed training on a TPU, end to end: the quickest proof that the
Spot-on training path still starts on the chip.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # sharded save and restores, four chips

One chip. phi3-mini-3.8b at its published widths (d_model 3072, 32x96
heads, d_ff 8192, vocab 32064), cut to 3 layers, trains at batch 2 x 2048
tokens with full rematerialization, wired exactly as ``repro.launch.train``
wires a run (``build_run``: delta-mode ``CheckpointStore``, then
``SpotOnCoordinator``, then ``SpotTrainer``). A ``VirtualClock`` with a
modeled step time and a ``PeriodicEviction`` drive the schedule, so device
steps, saves and restores are real while the provider's notice window costs
no wall time. Each checkpointed job takes a cold start, two periodic saves
(the second diffs the device-resident fingerprints), an eviction notice, an
urgent save, a replacement, a streaming resume and training to the last
step. Three jobs run in one process:

  * ``uninterrupted``: the same job with no eviction and no saves, the
    reference trajectory;
  * ``resumed``: lossless optimizer moments; its final loss must equal the
    reference's bit for bit;
  * ``resumed_int8``: ``quantize_moments=True``, so the urgent save quantizes
    the Adam moments on device and the restore dequantizes them on device.
    Its checks are those of ``resumed`` less the bit-identity. At this size
    the int8 resume does not keep training (ROADMAP Design 10): its final
    loss is printed as a known failure, outside the checks, beside the
    reference's.

After each checkpointed job the newest checkpoint is restored once more and
its optimizer moments are read: of the elements with a nonzero first
moment, the fraction whose second moment restores as exactly 0 (there
Adam's next update is m / eps), and the largest |m| / (sqrt(nu) + eps).

Four chips. The same configuration takes a few steps sharded over a
(data=2, model=2) mesh with the ``distributed.sharding`` rules, then a delta
save. Per-shard streaming restores put it back onto the same layout and onto
the 2-chip (data=1, model=2) layout ``core.elastic.plan_mesh_for`` plans
after losing half the chips. Every restored leaf must equal the saved state
bit for bit with its shards on distinct devices, and one step on the 2-chip
layout must match the 4-chip layout's loss for the same batch.

The compile cache follows ``launch.train.setup_compilation_cache``:
``JAX_COMPILATION_CACHE_DIR`` where set (left as JAX finds it), otherwise
``.jax_cache`` in the checkout, which the run's checkpoint commits sweep. Checkpoints go to ``.chip_smoke/`` in the checkout and are removed
at the end. The script exits non-zero without a result line when JAX finds
no TPU or any check fails; otherwise the last line of standard output is one
JSON object naming the device.
"""

from __future__ import annotations

import argparse
import collections
import gc
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

SEED = 0
N_LAYERS = 3
BATCH, SEQ_LEN, REMAT = 2, 2048, "full"

# Virtual schedule of a checkpointed job (seconds on the VirtualClock).
# Azure's notice is 30 s and the urgent save starts one step after the
# notice, so STEP_S leaves it 28 s of wall time to commit. Periodic saves
# land at steps 40 and 80, the notice at step 100 and the urgent save at
# step 101; the replacement resumes there and trains to TOTAL_STEPS. The
# next eviction (400 s) lies far beyond the resumed session's end.
STEP_S = 2.0
SAVE_EVERY_S = 80.0
EVICT_EVERY_S = 200.0
PROVISION_S = 1.0
TOTAL_STEPS = 104

# The 2-chip step against the 4-chip step on the same batch: the per-token
# math is the same, but splitting the batch over the data axis changes
# where the float32 partial sums of the bf16 matmuls and of the mean
# cross-entropy meet, which moves the loss by float32 rounding only. A
# restore that put any shard in the wrong place moves it by far more.
LOSS_RTOL = 1e-4


def _listen():
    """Count JAX's compile-cache events and sum its compile durations."""
    import jax

    events: collections.Counter = collections.Counter()
    secs: collections.Counter = collections.Counter()
    jax.monitoring.register_event_listener(
        lambda ev, **kw: events.update([ev]))

    def on_duration(ev, d, **kw):
        secs[ev] += d
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    return events, secs


def _compile_line(events, secs) -> str:
    return (f"backend_compile_s={secs['/jax/core/compile/backend_compile_duration']:.2f} "
            f"cache_hits={events['/jax/compilation_cache/cache_hits']} "
            f"cache_misses={events['/jax/compilation_cache/cache_misses']}")


def _peak_bytes(device) -> int | None:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _host_peak_rss_bytes() -> int:
    """This process's peak resident set so far (Linux reports KiB)."""
    import resource
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def _dir_bytes(path: str) -> int:
    total = 0
    for dirpath, _dirs, files in os.walk(path):
        for f in files:
            try:
                total += os.path.getsize(os.path.join(dirpath, f))
            except OSError:
                pass
    return total


def _bytes(a):
    """A host copy of ``a``'s raw bytes (bit-identity, not float equality)."""
    import numpy as np
    return np.asarray(a).reshape(-1).view(np.uint8)


def phi3_cut():
    from repro.configs import get_config
    return get_config("phi3-mini-3.8b").scaled(n_layers=N_LAYERS)


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def run_job(cfg, *, ckpt_dir: str, evict: bool, quantize_moments: bool,
            batch: int, seq_len: int, remat: str, cache_dir: str | None):
    """One training job through the production wiring; returns
    (trainer, report, wall seconds)."""
    from repro.core import NoEviction, PeriodicEviction, VirtualClock
    from repro.launch.train import build_run

    shutil.rmtree(ckpt_dir, ignore_errors=True)
    trainer, _ = build_run(
        cfg, clock=VirtualClock(),
        schedule=PeriodicEviction(EVICT_EVERY_S) if evict else NoEviction(),
        ckpt_dir=ckpt_dir, steps=TOTAL_STEPS,
        mode="transparent" if evict else "off", interval=SAVE_EVERY_S,
        batch=batch, seq_len=seq_len, seed=SEED, remat=remat,
        provision_delay=PROVISION_S, quantize_moments=quantize_moments,
        step_time_s=STEP_S, compile_cache_dir=cache_dir)
    t0 = time.perf_counter()
    report = trainer.run()
    trainer.coord.close()
    return trainer, report, time.perf_counter() - t0


def one_chip(cfg, *, batch: int = BATCH, seq_len: int = SEQ_LEN,
             remat: str = REMAT, ckpt_root: str,
             cache_dir: str | None = None) -> list[str]:
    """The one-chip phase; returns the names of the checks that failed."""
    import jax
    import numpy as np

    device = jax.devices()[0]
    events, secs = _listen()
    failed: list[str] = []

    def check(name: str, ok: bool) -> None:
        print(f"  check {'PASS' if ok else 'FAIL'}: {name}")
        if not ok:
            failed.append(name)

    ref_loss = None
    known: list[str] = []
    for name, evict, qm in (("uninterrupted", False, False),
                            ("resumed", True, False),
                            ("resumed_int8", True, True)):
        ckpt_dir = os.path.join(ckpt_root, name)
        hits0 = events["/jax/compilation_cache/cache_hits"]
        compile0 = secs["/jax/core/compile/backend_compile_duration"]
        trainer, report, wall = run_job(
            cfg, ckpt_dir=ckpt_dir, evict=evict, quantize_moments=qm,
            batch=batch, seq_len=seq_len, remat=remat, cache_dir=cache_dir)
        c = report.coordinator
        ledger = trainer.coord.ledger
        compile_s = secs["/jax/core/compile/backend_compile_duration"] - compile0
        print(f"job {name}: wall_s={wall:.2f} steps_executed="
              f"{report.steps_executed} final_loss={report.final_loss!r} "
              f"compile_s={compile_s:.2f} cache_hits="
              f"{events['/jax/compilation_cache/cache_hits'] - hits0}")
        print(f"  peak_bytes_in_use={_peak_bytes(device)} "
              f"host_peak_rss_bytes={_host_peak_rss_bytes()} "
              f"ckpt_dir_bytes={_dir_bytes(ckpt_dir)}")
        check(f"{name}: run completed", report.completed)
        check(f"{name}: final loss finite", bool(np.isfinite(report.final_loss)))
        if not evict:
            ref_loss = report.final_loss
            # no saves and no restore: the wall time is compile plus steps
            print(f"  steps_per_s={report.steps_executed / max(wall - compile_s, 1e-9):.3f} "
                  "(host clock, compile excluded)")
        else:
            stalls = ledger.observed.get("save_stall", [])
            print(f"  periodic_ckpts={c['periodic_ckpts']} "
                  f"termination_ckpts={c['termination_ckpts']} "
                  f"restores={report.restores} lost_steps={report.lost_steps}")
            print(f"  save_stall_s={[round(s, 3) for s in stalls]} "
                  "urgent_save_stall_s="
                  f"{ledger.observed.get('urgent_save_stall')} "
                  f"urgent_save_wall_s={ledger.observed.get('urgent_save_wall')} "
                  f"restore_wall_s={ledger.total('restore_wall'):.3f}")
            print(f"  d2h_bytes={c['d2h_bytes']} "
                  f"d2h_bytes_skipped={c['d2h_bytes_skipped']} "
                  f"fingerprint={trainer.coord.delta_tracker.stats}")
            check(f"{name}: periodic_ckpts >= 2", c["periodic_ckpts"] >= 2)
            check(f"{name}: termination_ckpts == 1", c["termination_ckpts"] == 1)
            check(f"{name}: restores >= 1", report.restores >= 1)
            check(f"{name}: periodic_failures == 0", c["periodic_failures"] == 0)
            check(f"{name}: termination_failures == 0",
                  c["termination_failures"] == 0)
            check(f"{name}: AOT precompiled step used",
                  trainer._compiled_step is not None)
            check(f"{name}: a periodic save diffed device fingerprints",
                  trainer.coord.delta_tracker.stats["tracked_saves"] >= 1)
            if qm:
                known.append(f"{name}: final loss {report.final_loss!r}, "
                             f"{report.final_loss - ref_loss!r} from the "
                             "reference (ROADMAP Design 10)")
                print(f"  known failure, not a check: {known[-1]}")
            else:
                check(f"{name}: final loss bit-identical to uninterrupted",
                      np.float32(report.final_loss).tobytes()
                      == np.float32(ref_loss).tobytes())
        template = jax.eval_shape(trainer._fresh_state)
        eps = trainer.job.opt.eps
        del trainer, report
        gc.collect()
        if evict:
            t0 = time.perf_counter()
            flushed, max_ratio = moment_hazard(ckpt_dir, template, eps)
            print(f"  restored moments: nu_flushed_fraction={flushed!r} "
                  f"max_m_over_sqrt_nu={max_ratio!r} "
                  f"({time.perf_counter() - t0:.2f} s)")
            gc.collect()
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    print("compile totals: " + _compile_line(events, secs))
    print(f"{len(known)} known failure(s) outside the checks: {known}")
    return failed


def moment_hazard(ckpt_dir: str, template, eps: float) -> tuple[float, float]:
    """Restore ``ckpt_dir``'s newest checkpoint onto the device as a resume
    does and read its Adam moments. Returns (flushed, max_ratio): of the
    elements with a nonzero first moment, the fraction whose second moment
    is exactly 0, and the largest |m| / (sqrt(nu) + eps)."""
    import jax
    import jax.numpy as jnp

    from repro.checkpoint import CheckpointStore
    from repro.train.train_step import state_template_on_device

    @jax.jit
    def leaf(m, v):
        live = m != 0
        return (jnp.sum(live), jnp.sum(live & (v == 0)),
                jnp.max(jnp.abs(m) / (jnp.sqrt(v) + eps)))

    state, _man = CheckpointStore(ckpt_dir).restore(
        state_template_on_device(template), streaming=True)
    live = flushed = 0
    max_ratio = 0.0
    for m, v in zip(jax.tree.leaves(state["opt"]["mu"]),
                    jax.tree.leaves(state["opt"]["nu"])):
        n_live, n_flushed, ratio = leaf(m, v)
        live += int(n_live)
        flushed += int(n_flushed)
        max_ratio = max(max_ratio, float(ratio))
    return flushed / max(live, 1), max_ratio


def fingerprint_kernel_lowers(cfg) -> bool:
    """True when the save path's fingerprint of the largest parameter leaf
    lowers to the Pallas TPU kernel (a ``tpu_custom_call``)."""
    import jax
    import jax.numpy as jnp

    from repro.checkpoint.chunkstore import DEFAULT_CHUNK_SIZE
    from repro.kernels.fingerprint.ops import _fp_pallas
    from repro.kernels.fingerprint.ref import n_blocks_of, words_per_block

    shape = (cfg.n_layers, cfg.d_model, cfg.d_ff)        # stacked MLP weight
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    wpb = words_per_block(DEFAULT_CHUNK_SIZE, 2)
    n_blocks = n_blocks_of(2 * cfg.n_layers * cfg.d_model * cfg.d_ff,
                           DEFAULT_CHUNK_SIZE)
    return "tpu_custom_call" in _fp_pallas.lower(x, wpb, n_blocks,
                                                 False).as_text()


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def four_chips(cfg, *, batch: int = BATCH, seq_len: int = SEQ_LEN,
               remat: str = REMAT, ckpt_root: str, steps: int = 3,
               devices=None) -> list[str]:
    """The multi-chip phase; returns the names of the checks that failed."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding

    from repro.checkpoint import CheckpointStore
    from repro.core.elastic import plan_mesh_for
    from repro.data import TokenPipeline
    from repro.distributed.sharding import (batch_spec, elastic_rules,
                                            tree_shardings, use_sharding_rules)
    from repro.launch.mesh import make_mesh, mesh_info
    from repro.optim import AdamWConfig
    from repro.train.train_step import init_train_state, make_train_step

    devices = list(devices if devices is not None else jax.devices()[:4])
    failed: list[str] = []

    def check(name: str, ok: bool) -> None:
        print(f"  check {'PASS' if ok else 'FAIL'}: {name}")
        if not ok:
            failed.append(name)

    opt = AdamWConfig(total_steps=steps + 1)
    pipe = TokenPipeline(vocab_size=cfg.vocab_size, batch=batch,
                         seq_len=seq_len, seed=SEED)
    shapes = jax.eval_shape(lambda: init_train_state(cfg, opt, SEED))
    batch_sds = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                             pipe.batch_at(0))

    def layout(mesh):
        """(state shardings, batch shardings, compiled step) on ``mesh``."""
        rules = elastic_rules(mesh)
        state_sh = tree_shardings(shapes, rules)
        batch_sh = jax.tree.map(
            lambda _: NamedSharding(mesh, batch_spec(rules, batch)), batch_sds)
        t0 = time.perf_counter()
        with use_sharding_rules(rules), mesh:
            step = jax.jit(make_train_step(cfg, opt, remat=remat),
                           in_shardings=(state_sh, batch_sh),
                           out_shardings=(state_sh, None)
                           ).lower(shapes, batch_sds).compile()
        print(f"  step compile on {dict(mesh.shape)}: "
              f"{time.perf_counter() - t0:.2f} s")
        return state_sh, batch_sh, step

    def place(i, batch_sh):
        return jax.device_put(pipe.batch_at(i), batch_sh)

    mesh4 = make_mesh((2, 2), ("data", "model"), devices=devices)
    sh4, bsh4, step4 = layout(mesh4)
    state = jax.jit(lambda: init_train_state(cfg, opt, SEED),
                    out_shardings=sh4)()
    for i in range(steps):
        state, metrics = step4(state, place(i, bsh4))
    print(f"4-chip: {steps} steps, loss={float(metrics['loss'])!r}")
    _, metrics = step4(state, place(steps, bsh4))
    loss4 = float(metrics["loss"])

    store = CheckpointStore(os.path.join(ckpt_root, "sharded"))
    t0 = time.perf_counter()
    info = store.save(steps, state, mesh_info=mesh_info(mesh4))
    # nbytes and new_bytes count encoded (compressed) chunk bytes;
    # d2h_bytes counts the raw bytes of the one copy of each shard saved
    print(f"  delta save: {time.perf_counter() - t0:.2f} s, "
          f"nbytes={info.nbytes} new_bytes={info.new_bytes} "
          f"d2h_bytes={info.d2h_bytes} state_bytes="
          f"{sum(a.nbytes for a in jax.tree.leaves(state))}")
    saved = jax.device_get(state)
    del state
    flat_saved = jax.tree_util.tree_flatten_with_path(saved)[0]

    def restore_onto(mesh, state_sh, label):
        template = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            shapes, state_sh)
        t0 = time.perf_counter()
        restored, _man = store.restore(template, streaming=True)
        jax.block_until_ready(restored)
        print(f"  streaming restore onto {label}: "
              f"{time.perf_counter() - t0:.2f} s")
        want = set(mesh.devices.flat)
        flat = jax.tree_util.tree_flatten_with_path(restored)[0]
        mismatched = [jax.tree_util.keystr(p) for (p, a), (_, b)
                      in zip(flat, flat_saved)
                      if not np.array_equal(_bytes(a), _bytes(b))]
        misplaced = [jax.tree_util.keystr(p) for p, a in flat
                     if {s.device for s in a.addressable_shards} != want]
        check(f"{label}: every leaf bit-identical to the saved state "
              f"(mismatched: {mismatched[:5]})", not mismatched)
        check(f"{label}: shards on the layout's {len(want)} devices "
              f"(misplaced: {misplaced[:5]})", not misplaced)
        return restored

    restore_onto(mesh4, sh4, "4 chips (data=2, model=2)")
    mesh2 = plan_mesh_for(2, model_parallel=2).build(devices[:2])
    sh2, bsh2, step2 = layout(mesh2)
    restored2 = restore_onto(mesh2, sh2, "2 chips (data=1, model=2)")
    _, metrics = step2(restored2, place(steps, bsh2))
    loss2 = float(metrics["loss"])
    rel = abs(loss2 - loss4) / abs(loss4)
    print(f"  next-step loss: 4 chips {loss4!r}, 2 chips {loss2!r}, "
          f"relative difference {rel!r} (tolerance {LOSS_RTOL})")
    check("2-chip step loss matches the 4-chip step", rel <= LOSS_RTOL)
    shutil.rmtree(os.path.join(ckpt_root, "sharded"), ignore_errors=True)
    return failed


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: checkpointed training on one chip; 4: only the "
                         "sharded save/restore path across four chips")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}")
    if dev.platform != "tpu":
        print("no TPU found: this check runs on the chip only",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 1

    from repro.launch.train import setup_compilation_cache

    cache_dir = setup_compilation_cache(os.path.join(ROOT, ".jax_cache"))
    print("compile cache: " + (cache_dir or "JAX_COMPILATION_CACHE_DIR="
                               + os.environ["JAX_COMPILATION_CACHE_DIR"]))
    ckpt_root = os.path.join(ROOT, ".chip_smoke")
    os.makedirs(ckpt_root, exist_ok=True)
    print(f"checkpoint volume free bytes: {shutil.disk_usage(ckpt_root).free}")
    cfg = phi3_cut()
    print(f"config: {cfg.name} n_layers={cfg.n_layers} d_model={cfg.d_model} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} params={cfg.param_count()} "
          f"batch={BATCH} seq_len={SEQ_LEN} remat={REMAT}")
    try:
        if args.chips == 4:
            failed = four_chips(cfg, ckpt_root=ckpt_root)
        else:
            failed = one_chip(cfg, ckpt_root=ckpt_root, cache_dir=cache_dir)
            lowers = fingerprint_kernel_lowers(cfg)
            print(f"  check {'PASS' if lowers else 'FAIL'}: fingerprint "
                  "lowers to the Pallas TPU kernel (tpu_custom_call)")
            if not lowers:
                failed.append("fingerprint kernel")
    finally:
        shutil.rmtree(ckpt_root, ignore_errors=True)
    if failed:
        print(f"{len(failed)} check(s) failed: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
