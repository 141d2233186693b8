"""Driver of the ``periodic`` traffic: full-churn AdamW pretraining through
``SpotTrainer.run()``, wired by ``repro.launch.train.build_run`` (delta-mode
store, transparent policy, async writer, device-delta tracker), with one
steady-state periodic save inside the measured window and no eviction.

Set-up builds the trainer, makes the initial state on the device from the
seed in one jitted call, compiles the step through the trainer's own
warm start (``SpotTrainer.resume`` with no checkpoint yet), and drives that
compiled step through the first steps on the trainer's own feed. Those
steps are what the reference follows, and their times size the window: N
steps, about ``--seconds`` long, with the save at step ``save_at * N``, so
its background write ends well before the window does. Set-up then commits
one periodic save through the coordinator, so that the window's save is not
the process's first: it diffs every leaf's fingerprints against that
committed save's, finds the blocks dirty and falls back to the dense copy,
as every save after the first does in full-churn training. The state the
set-up steps left is handed to the same trainer's ``run()``, which is the
window; every save it started is durable when it returns. The window calls
the compiled step through a tap that checksums, on the device, the state
the save writes. Just before the window, the C heap that set-up freed is
handed back to the system, so that the window's host memory depends less on
whether set-up compiled or loaded its programs from the cache.

After the window the save is restored: its checksums must equal the saved
state's, and the compiled step replays the window's remaining steps from it
to the window's last loss, bit for bit. Then the plain reference trains the
same initial weights on the same batches, and the gaps between the two are
compared with the cell's limits.
"""

from __future__ import annotations

import dataclasses
import gc
import os
import shutil
import time
from dataclasses import dataclass

import numpy as np

from . import common, compare, flops, record, spec, training


@dataclass
class Setup:
    trainer: object
    template: object          # the train state's shapes
    state: object             # after the set-up steps
    prog: dict                # the program's readings of those steps
    step_s: float             # measured host seconds per step
    ckpt_dir: str


class _Handoff:
    """Stands in for the trainer's cold-start state maker: ``run()`` asks
    once for the state's shapes (under ``jax.eval_shape``) and once for the
    state, and gets the state the set-up steps left; the second call lets go
    of it, so the window holds no extra copy."""

    def __init__(self, state):
        self._state = state
        self._calls = 0

    def __call__(self):
        import jax
        import jax.numpy as jnp

        self._calls += 1
        state = self._state
        if self._calls == 1:
            # traced stand-ins: a concrete state returned under tracing
            # would be kept by the trace as a constant
            return jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), state)
        self._state = None
        return state


class _Tap:
    """The compiled step as the window calls it, which also checksums, on
    the device and without waiting, the state that the ``at``-th call
    returns: the state the save at that step writes."""

    def __init__(self, step, checksum, at: int):
        self.step, self.checksum, self.at = step, checksum, at
        self.calls = 0
        self.sums = None

    def __call__(self, state, batch):
        out = self.step(state, batch)
        self.calls += 1
        if self.calls == self.at:
            self.sums = self.checksum(out[0])
        return out


def setup(cell: spec.Cell, seed: int, *, root: str, cache_dir) -> Setup:
    import jax

    from repro.core import NoEviction, VirtualClock
    from repro.launch.train import build_run
    from repro.train.train_step import init_train_state

    cfg, tr = cell.config, cell.traffic
    train = cfg["train"]
    mcfg = spec.program_module(cfg).model_config(cfg)
    ckpt_dir = os.path.join(root, ".spoton_ckpts", cell.name)
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    trainer, _ = build_run(
        mcfg, clock=VirtualClock(), schedule=NoEviction(), ckpt_dir=ckpt_dir,
        steps=tr["lr_horizon_steps"], mode="transparent",
        interval=float("inf"), batch=train["batch"],
        seq_len=train["seq_len"], seed=seed, remat=train["remat"],
        provision_delay=tr["provision_delay_s"],
        step_time_s=tr["virtual_step_s"], compile_cache_dir=cache_dir)
    template = jax.eval_shape(
        lambda: init_train_state(mcfg, trainer.job.opt, seed=0))
    # the trainer's warm start compiles its step from shapes; with no
    # checkpoint in the store it restores nothing
    if trainer.resume(template) is not None:
        raise RuntimeError(f"{ckpt_dir} held a checkpoint at set-up")
    state, prog, step_s = training.first_steps(
        trainer._compiled_step,
        common.make_state_fn(template, cfg["initializer_range"], seed),
        trainer.pipeline.batch_at,
        tr["setup_steps"], template=template, cfg=cfg, seed=seed)
    return Setup(trainer=trainer, template=template, state=state, prog=prog,
                 step_s=step_s, ckpt_dir=ckpt_dir)


def program_readings(cell: spec.Cell, seed: int, *, root: str, cache_dir):
    """(template, readings of the set-up steps)."""
    s = setup(cell, seed, root=root, cache_dir=cache_dir)
    s.trainer.coord.close()
    shutil.rmtree(s.ckpt_dir, ignore_errors=True)
    return s.template, s.prog


def _commit_prior_save(trainer, template) -> None:
    """Commit one periodic save through the coordinator's own path and wait
    until it is durable, so that the tracker holds every leaf's
    fingerprints and chunk refs from a committed save, as it does before
    every save but a process's first.

    The state saved is zeros in the real state's layout. Against the state
    the window saves, its fingerprints differ in the blocks that a save a
    few dozen steps earlier would differ in: in full-churn training every
    block that is not all zero has changed since then, and a block that is
    all zero now (the moments of a token not yet seen) was all zero then.
    Zeros encode and deduplicate to a few chunks, so a run writes one full
    save to disk, not two."""
    import jax
    import jax.numpy as jnp

    coord = trainer.coord
    zeros = jax.jit(lambda: jax.tree.map(
        lambda a: jnp.zeros(a.shape, a.dtype), template))()
    if not coord.save_periodic_now(0, zeros):
        raise RuntimeError("the set-up's periodic save failed")
    del zeros
    coord.flush()
    if coord.store.committed_steps() != [0]:
        raise RuntimeError("the set-up's periodic save did not commit: "
                           f"{coord.store.committed_steps()}")


def _warm_save_path(trainer, state) -> None:
    """Compile the programs a steady-state periodic save dispatches (the
    fingerprint and diff of every tracked leaf against the committed save's)
    through the tracker's own prestage, then take the pending work back so
    that the tracker is as it was."""
    from repro.checkpoint.serialize import flatten_state

    tracker = trainer.coord.delta_tracker
    if tracker is None:
        return
    stats = dict(tracker.stats)
    named = flatten_state(state)
    for name, leaf in named.items():
        tracker.prestage_leaf(name, leaf)
    tracker.begin(named)
    tracker.stats.update(stats)


def _check_save(s: Setup, tap: _Tap, report, n_steps: int,
                save_step: int) -> int:
    """Restore the window's save: its checksums must equal those of the
    state the window saved, and replaying the window's remaining steps from
    it on the same compiled step and feed must end on the window's last
    loss, bit for bit. Returns the number of leaves that differ, plus one
    if the replay's loss does."""
    import jax
    from repro.train.train_step import state_template_on_device

    trainer = s.trainer
    state, _man = trainer.coord.store.restore(
        state_template_on_device(s.template), step=save_step, streaming=True)
    saved = [int(x) for x in tap.sums] if tap.sums is not None else []
    restored = [int(x) for x in tap.checksum(state)]
    bad = (sum(a != b for a, b in zip(saved, restored))
           if len(saved) == len(restored) else len(restored))
    loss = None
    for i in range(save_step, n_steps):
        state, metrics = tap.step(state, trainer.pipeline.batch_at(i))
        loss = np.float32(np.asarray(metrics["loss"]))
    jax.block_until_ready(state)
    del state
    same = loss is not None and loss.tobytes() == np.float32(
        report.final_loss).tobytes()
    return bad + (0 if same else 1)


def run(cell: spec.Cell, *, seed: int, seconds: float, trace: bool,
        root: str, cache_dir, devices, t_start: float,
        compile_log: common.CompileLog):
    """One run of the cell: returns (result line, compared numbers)."""
    import jax
    from repro.core.policy import CheckpointPolicy

    cfg, tr = cell.config, cell.traffic
    train = cfg["train"]
    s = setup(cell, seed, root=root, cache_dir=cache_dir)
    trainer = s.trainer
    n_steps = max(int(round(seconds / s.step_s)),
                  int(np.ceil(tr["min_steps_after_save"]
                              / (1 - tr["save_at"]))))
    save_step = int(round(tr["save_at"] * n_steps))
    if not 0 < save_step < n_steps < 2 * save_step:
        raise ValueError(f"save at step {save_step} of {n_steps}: the "
                         "window must hold exactly one save")
    trainer.job.total_steps = n_steps
    trainer.coord.policy = CheckpointPolicy.transparent(
        save_step * tr["virtual_step_s"])
    _commit_prior_save(trainer, s.template)
    _warm_save_path(trainer, s.state)
    checksum = training.checksum_fn()
    jax.block_until_ready(checksum(s.state))
    tap = _Tap(trainer._compiled_step, checksum, save_step)
    trainer._compiled_step = tap
    # the window continues the set-up's session with the state in hand: it
    # restores nothing, though the store holds the set-up's save
    trainer.resume = lambda template: None
    trainer._fresh_state = _Handoff(s.state)
    s.state = None
    coord = trainer.coord
    before = {"observed": {k: len(v) for k, v in
                           trainer.ledger.observed.items()},
              "tracker": dict(coord.delta_tracker.stats),
              "stats": dataclasses.asdict(coord.stats)}
    leaf_bytes = [int(np.prod(l.shape)) * np.dtype(l.dtype).itemsize
                  for l in jax.tree.leaves(s.template)]
    gc.collect()
    rss_setup = common.host_rss_bytes()
    common.release_free_heap()
    print(f"setup: step_s={s.step_s!r} window_steps={n_steps} "
          f"save_step={save_step} compile={compile_log.snapshot()} "
          f"rss_gb={rss_setup / 1e9!r} "
          f"rss_trimmed_gb={common.host_rss_bytes() / 1e9!r} "
          f"peak_rss_gb={common.host_peak_rss_bytes() / 1e9!r}", flush=True)

    tdir = record.trace_dir(root, cell)
    try:
        if trace:
            record.start_trace(tdir)
        c0 = compile_log.snapshot()
        t0 = time.perf_counter()
        setup_s = t0 - t_start
        report = trainer.run()
        trainer.coord.close()
        t1 = time.perf_counter()
        if trace:
            jax.profiler.stop_trace()
        window_s = t1 - t0
        in_window = compile_log.since(c0)
        rss = common.host_peak_rss_bytes()
        device = common.device_info(devices, cell.chips)
        # the window's own share of the trainer's running totals
        observed = {k: list(v)[before["observed"].get(k, 0):]
                    for k, v in trainer.ledger.observed.items()}
        stats = {k: v - before["stats"][k]
                 for k, v in dataclasses.asdict(coord.stats).items()
                 if isinstance(v, (int, float))}
        fp = {k: v - before["tracker"][k]
              for k, v in coord.delta_tracker.stats.items()}
        committed = coord.store.committed_steps()
        saved = (stats["periodic_ckpts"] == 1 and bool(committed)
                 and committed[-1] == save_step)
        print(f"window: seconds={window_s!r} steps={report.steps_executed} "
              f"saves={stats['periodic_ckpts']} committed={committed} "
              f"diffed_saves={fp['tracked_saves']} "
              f"save_stall_s={observed.get('save_stall')} "
              f"d2h_bytes={stats['d2h_bytes']} "
              f"d2h_bytes_skipped={stats['d2h_bytes_skipped']} "
              f"fingerprint={fp} compiles_in_window={in_window}", flush=True)
        mismatch = (_check_save(s, tap, report, n_steps, save_step)
                    if saved else 1)
    finally:
        shutil.rmtree(s.ckpt_dir, ignore_errors=True)
    trainer._fresh_state = None
    gc.collect()
    ref = training.reference(cell, seed, s.template)
    g = compare.gaps(s.prog, ref)

    lim = cell.limits
    failures = coord.stats.periodic_failures
    checks = {"loss_gap": (g["loss_gap"], lim["loss_gap"]),
              "grad_gap": (g["grad_gap"], lim["grad_gap"]),
              "change_gap": (g["change_gap"], lim["change_gap"]),
              "save_mismatch": (mismatch, 0),
              "failed_saves": (failures, 0)}
    correct = all(v <= l for v, l in checks.values()) and report.completed
    tokens = report.steps_executed * train["batch"] * train["seq_len"]
    result = {"correct": bool(correct),
              "attempted": n_steps + 1,
              "failed": (n_steps - report.steps_executed + failures
                         + (0 if saved else 1))}
    if not trace:
        result["metrics"] = {
            "goodput_tokens_per_s": {"value": tokens / window_s,
                                     "unit": "tokens/s"},
            "host_peak_rss_gb": {"value": rss / 1e9, "unit": "GB"},
            "setup_s": {"value": setup_s, "unit": "s"}}
        result["device"] = device
        return result, checks

    return record.traced_result(
        result, device, tdir, cell=cell, window_s=window_s,
        work=spec.program_module(cfg).work(cfg), observed=observed,
        fingerprint_bytes=flops.fingerprint_bytes(leaf_bytes)), checks
