"""Production training entrypoint: Spot-on-protected training of any assigned
architecture.

    PYTHONPATH=src python -m repro.launch.train \
        --arch gemma3-1b --smoke --steps 200 --ckpt-dir /nfs/ckpts \
        --mode transparent --interval 300 --simulate-eviction-every 3600

On a real cluster this runs under the pod scheduler with a real metadata
backend; in this container `--smoke` selects the reduced config and the
simulated cloud so the full eviction→termination-checkpoint→restore loop is
exercised end-to-end on CPU. All Spot-on machinery (coordinator, atomic
sharded store, async writer, scale-set replacement, cost accounting) is the
production code path either way.
"""

from __future__ import annotations

import argparse
import json
import os
import time

# compile-cache retention knobs: same shape as the checkpoint store's sweep
# (age gate first, then a size budget), tuned for a shared volume that many
# fleet members write executables into
CACHE_GC_MAX_BYTES = 2 << 30          # 2 GiB of cached executables
CACHE_GC_MAX_AGE_S = 14 * 86400       # entries idle two weeks are dead weight
CACHE_GC_MIN_INTERVAL_S = 300.0       # walk the dir at most once per 5 min

_last_cache_gc = 0.0


def sweep_compilation_cache(cache_dir: str, *,
                            max_bytes: int = CACHE_GC_MAX_BYTES,
                            max_age_s: float = CACHE_GC_MAX_AGE_S,
                            min_interval_s: float = CACHE_GC_MIN_INTERVAL_S,
                            ) -> int:
    """Size/age-gated gc of the persistent XLA compilation cache.

    The cache dir on the shared checkpoint volume grows without bound (every
    new model config / jax version adds executables; nothing ever removes
    them). Retention mirrors the checkpoint store's pool sweep: entries past
    the age gate go first (mtime refreshes on cache hits, so "old" means
    *unused*), then the oldest entries beyond the size budget. Runs
    opportunistically after checkpoint commits (``CheckpointStore.post_commit``)
    and rate-limits itself so the directory walk never becomes a per-save
    cost. Best-effort throughout — a janitor must never fail a save. Returns
    bytes removed.
    """
    import stat as stat_mod

    global _last_cache_gc
    now = time.time()
    if min_interval_s > 0 and now - _last_cache_gc < min_interval_s:
        return 0
    _last_cache_gc = now
    entries = []       # (mtime, size, path)
    try:
        for name in os.listdir(cache_dir):
            path = os.path.join(cache_dir, name)
            try:
                st = os.stat(path)
            except OSError:
                continue
            if stat_mod.S_ISREG(st.st_mode):   # one stat per entry, no TOCTOU
                entries.append((st.st_mtime, st.st_size, path))
    except OSError:
        return 0
    removed = 0

    def _rm(size: int, path: str) -> int:
        try:
            os.remove(path)
            return size
        except OSError:
            return 0

    entries.sort()                      # oldest first
    kept = []
    for mtime, size, path in entries:
        if now - mtime > max_age_s:
            removed += _rm(size, path)
        else:
            kept.append((mtime, size, path))
    total = sum(size for _, size, _ in kept)
    for mtime, size, path in kept:      # oldest-first until under budget
        if total <= max_bytes:
            break
        removed += _rm(size, path)
        total -= size
    return removed


def setup_compilation_cache(cache_dir: str) -> str | None:
    """Turn on XLA's persistent compilation cache.

    This is the compile leg of the fast-resume pipeline: a replacement
    instance deserializes the step executable from the cache instead of
    re-running XLA passes, so `SpotTrainer.resume`'s overlapped precompile
    degenerates to a disk read. Where ``JAX_COMPILATION_CACHE_DIR`` is set,
    JAX already reads it: no directory is set here and None is returned,
    because that directory belongs to whoever set the variable. Otherwise
    the cache goes to ``cache_dir`` (made absolute, never a temporary name),
    which is returned: the program owns it, so ``build_run`` may sweep it.
    Thresholds are zeroed because on a spot fleet *every* recompile sits
    inside the MTTR window.
    """
    import jax

    owned = None
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        owned = os.path.abspath(cache_dir)
        os.makedirs(owned, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", owned)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return owned


def build_run(cfg, *, clock, schedule, ckpt_dir: str, steps: int,
              mode: str = "transparent", interval: float = 60.0,
              stages: int = 5, batch: int = 8, seq_len: int = 64,
              seed: int = 0, remat: str = "none", microbatches: int = 1,
              provision_delay: float = 5.0, quantize_moments: bool = False,
              step_time_s: float | None = None,
              compile_cache_dir: str | None = None):
    """Wire one Spot-on-protected training run: a delta-mode
    ``CheckpointStore``, then the ``SpotOnCoordinator``, then the
    ``SpotTrainer`` on a scale set driven by ``schedule``. Returns
    (trainer, accountant); ``trainer.run()`` executes the job and
    ``trainer.coord.close()`` drains its writer. ``compile_cache_dir``
    names a compile cache the program owns (``setup_compilation_cache``'s
    return value); each checkpoint commit then sweeps it."""
    from ..checkpoint import CheckpointStore
    from ..core import (AZURE_D8S_V3, CheckpointPolicy, CostAccountant,
                        ScaleSet, SpotOnCoordinator, StragglerDetector)
    from ..optim import AdamWConfig
    from ..train import SpotTrainer, TrainJob

    accountant = CostAccountant(AZURE_D8S_V3)
    pool = ScaleSet(clock=clock, schedule=schedule, accountant=accountant,
                    provisioning_delay_s=provision_delay)
    store = CheckpointStore(ckpt_dir, quantize_moments=quantize_moments)
    if compile_cache_dir:
        # cache hygiene rides the checkpoint cadence: after each commit the
        # (rate-limited) sweep keeps the cache dir the program owns (see
        # setup_compilation_cache) inside its size/age budget — off the
        # save's critical path, never fatal
        store.post_commit.append(
            lambda d=compile_cache_dir: sweep_compilation_cache(d))
    policy = {
        "off": CheckpointPolicy.off(),
        "application": CheckpointPolicy.application(),
        "transparent": CheckpointPolicy.transparent(interval),
    }[mode]
    coord = SpotOnCoordinator(store, policy, clock,
                              straggler=StragglerDetector())
    job = TrainJob(cfg=cfg, opt=AdamWConfig(total_steps=steps),
                   total_steps=steps, n_stages=stages, batch=batch,
                   seq_len=seq_len, seed=seed, remat=remat,
                   microbatches=microbatches)
    trainer = SpotTrainer(job, coord, pool, clock, step_time_s=step_time_s)
    return trainer, accountant


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--stages", type=int, default=5)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--ckpt-dir", default=".spoton_ckpts",
                    help="checkpoint directory (default: .spoton_ckpts in "
                         "the working directory)")
    ap.add_argument("--mode", choices=["off", "application", "transparent"],
                    default="transparent")
    ap.add_argument("--interval", type=float, default=60.0,
                    help="periodic transparent-checkpoint interval (s)")
    ap.add_argument("--simulate-eviction-every", type=float, default=0.0,
                    help="inject an eviction every N seconds (0 = none)")
    ap.add_argument("--provision-delay", type=float, default=5.0)
    ap.add_argument("--quantize-moments", type=int, default=0)
    ap.add_argument("--compile-cache-dir", default=".jax_cache",
                    help="persistent XLA compilation cache directory, used "
                         "and swept when JAX_COMPILATION_CACHE_DIR is unset "
                         "(default: .jax_cache in the working directory)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--remat", default="none")
    ap.add_argument("--microbatches", type=int, default=1)
    args = ap.parse_args(argv)

    cache_dir = setup_compilation_cache(args.compile_cache_dir)

    from ..configs import get_config, get_smoke_config
    from ..core import NoEviction, PeriodicEviction, WallClock

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    clock = WallClock()
    schedule = PeriodicEviction(args.simulate_eviction_every) \
        if args.simulate_eviction_every else NoEviction()
    trainer, accountant = build_run(
        cfg, clock=clock, schedule=schedule, ckpt_dir=args.ckpt_dir,
        steps=args.steps, mode=args.mode, interval=args.interval,
        stages=args.stages, batch=args.batch, seq_len=args.seq_len,
        seed=args.seed, remat=args.remat, microbatches=args.microbatches,
        provision_delay=args.provision_delay,
        quantize_moments=bool(args.quantize_moments),
        compile_cache_dir=cache_dir)
    report = trainer.run()
    trainer.coord.close()
    summary = {
        "arch": cfg.name, "completed": report.completed,
        "total_time_s": round(report.total_time_s, 2),
        "final_loss": report.final_loss,
        "steps_executed": report.steps_executed,
        "lost_steps": report.lost_steps,
        "restores": report.restores,
        "instances_used": report.instances_used,
        "evictions": report.evictions_seen,
        "coordinator": report.coordinator,
        "cost": accountant.summary(clock.now()),
    }
    print(json.dumps(summary, indent=1))
    return 0 if report.completed else 1


if __name__ == "__main__":
    raise SystemExit(main())
