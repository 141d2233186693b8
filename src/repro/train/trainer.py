"""SpotTrainer — the paper's Fig. 1 workflow as a training-cluster loop.

One run = the life of a long-running workload on a spot Scale Set:

    provision instance → restore most-recent-valid checkpoint (or cold-start)
    → step loop [periodic ckpts | stage ckpts | eviction notice → termination
    ckpt] → instance dies → replacement provisions → restore → ... → complete.

The *workload* is a staged training job — `n_stages` plays metaSPAdes'
k-mer-stage role: the application-specific policy may checkpoint only at stage
boundaries, the transparent policy at any step. Stage completion times are
reported exactly as Table I reports per-K times (on the surviving lineage:
a crossing rolled back by an eviction doesn't count).

Two time modes:
  * wall mode (clock=WallClock, step_time_s=None): every train step really
    executes (jit) and durations are physical — integration tests, small runs.
  * virtual mode (clock=VirtualClock, step_time_s=x): steps still execute (the
    state evolution and checkpoint bytes are real) but the clock advances by a
    modeled per-step cost, and checkpoint/restore costs come from the
    coordinator's TimeModel — replaying the paper's multi-hour schedules in
    seconds, deterministically.
"""

from __future__ import annotations

import math
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import numpy as np

from ..checkpoint import sharded
from ..core.clock import Clock
from ..core.coordinator import Signal, SpotOnCoordinator
from ..core.ledger import span
from ..core.spot_sim import InstancePool
from ..data import PipelineState, TokenPipeline
from ..models.config import ModelConfig
from ..optim import AdamWConfig
from .train_step import (init_train_state, make_train_step,
                         state_template_on_device)


@dataclass
class TrainJob:
    cfg: ModelConfig
    opt: AdamWConfig
    total_steps: int
    n_stages: int = 5                      # metaSPAdes used 5 k-mer stages
    batch: int = 8
    seq_len: int = 64
    seed: int = 0
    remat: str = "none"
    microbatches: int = 1

    def stage_boundaries(self) -> list[int]:
        return [math.ceil(self.total_steps * (i + 1) / self.n_stages)
                for i in range(self.n_stages)]


@dataclass
class RunReport:
    completed: bool
    total_time_s: float
    stage_times_s: list[float]             # per-stage durations (Table I rows)
    steps_executed: int                    # including rolled-back work
    lost_steps: int
    restores: int
    cold_starts: int
    instances_used: int
    evictions_seen: int
    final_loss: float
    coordinator: dict
    extra: dict = field(default_factory=dict)


class SpotTrainer:
    def __init__(self, job: TrainJob, coordinator: SpotOnCoordinator,
                 pool: InstancePool, clock: Clock, *,
                 step_time_s: float | None = None,
                 max_sessions: int = 200):
        self.job = job
        self.coord = coordinator
        self.pool = pool
        self.clock = clock
        self.ledger = coordinator.ledger   # shared virtual-time accounting
        self.step_time_s = step_time_s
        self.max_sessions = max_sessions
        cfg = job.cfg
        self.pipeline = TokenPipeline(
            vocab_size=cfg.vocab_size, batch=job.batch, seq_len=job.seq_len,
            seed=job.seed,
            embed_dim=None if cfg.embed_inputs else cfg.d_model,
            embed_dtype=np.dtype("float32") if cfg.dtype == "float32"
            else np.dtype("float32"))
        self._step_fn = jax.jit(make_train_step(
            cfg, job.opt, remat=job.remat, microbatches=job.microbatches))
        self._compiled_step = None    # AOT-compiled step (resume warm start)

    # -----------------------------------------------------------------------

    def _fresh_state(self):
        return init_train_state(self.job.cfg, self.job.opt, seed=self.job.seed)

    # -- fast resume --------------------------------------------------------

    def _compile_step(self, template):
        """AOT-compile the train step from abstract shapes — no state needed,
        so it can run while the checkpoint restore is still on disk. With a
        persistent XLA compilation cache this is a disk hit on every
        instance after the first."""
        state_sds = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype)
            if hasattr(x, "shape") else x, template)
        batch_sds = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            self.pipeline.batch_at(0))
        return self._step_fn.lower(state_sds, batch_sds).compile()

    def resume(self, template):
        """Eviction→first-step-back warm start.

        The MTTR window decomposes into restore + H2D + recompile + data
        seek; this overlaps them: step compilation runs on a side thread
        (abstract shapes only) while the streaming restore decodes the
        latest checkpoint straight onto the device, and the data pipeline
        fast-forwards to the restored cursor in O(1). Returns
        (state, manifest, step, pipeline_state) or None when no checkpoint
        exists (cold start — the compile still warms the session).
        """
        # an executable surviving from the previous session (same process)
        # is already warm; only the replacement-instance case pays a
        # compile, and it overlaps the restore below
        compile_ex = cfut = None
        if self._compiled_step is None:
            compile_ex = ThreadPoolExecutor(1,
                                            thread_name_prefix="spoton-compile")
            cfut = compile_ex.submit(self._compile_step, template)
        try:
            restored = self.coord.restore_latest(
                state_template_on_device(template))
            if cfut is not None:
                # a failed precompile is the failure jit would hit at the
                # first dispatch (device OOM, compiler error): surface it
                self._compiled_step = cfut.result()
        finally:
            if compile_ex is not None:
                compile_ex.shutdown(wait=False)
        if restored is None:
            return None
        state, man = restored
        step = int(np.asarray(state["step"]))
        pstate = self.pipeline.fast_forward(
            int(np.asarray(state["data"]["next_batch_index"])))
        return state, man, step, pstate

    def run(self) -> RunReport:
        """Run the job to completion across sessions. The step loop runs
        under program spans (``ledger.span``): ``spoton.run`` around the
        call; per iteration ``spoton.step`` with its parts ``step.batch``,
        ``step.dispatch``, ``step.wait`` and ``step.hook`` in turn; and
        ``spoton.flush`` around the wait for the last write."""
        with span("run"):
            return self._run()

    def _run(self) -> RunReport:
        job = self.job
        clock = self.clock
        t_start = clock.now()
        boundaries = job.stage_boundaries()
        stage_cross_time: dict[int, float] = {}   # stage idx -> crossing time
        steps_executed = 0
        lost_steps = 0
        cold_starts = 0
        sessions = 0
        last_session_max_step = 0
        final_loss = float("nan")
        # shapes and dtypes only: a zero-filled host copy of the state
        # would hold as many host bytes as the state for the whole run
        template = jax.eval_shape(self._fresh_state)
        self.pool.start()
        completed = False

        while not completed and sessions < self.max_sessions:
            sessions += 1
            inst = self.pool.wait_for_instance()
            self.coord.attach_instance(inst.metadata, inst.name)
            # the evicted session's state died with its instance; holding it
            # (or the resume tuple below) through the next session would
            # keep a whole extra state on the device beside the step's two
            state = None
            resumed = self.resume(template)
            if resumed is not None:
                state, _man, step, pstate = resumed
                del resumed
            else:
                state = self._fresh_state()
                step = 0
                cold_starts += 1
                pstate = self.pipeline.fast_forward(0)
            # work executed beyond this restore point is lost
            if last_session_max_step > step:
                lost_steps += last_session_max_step - step
            # crossings beyond the restore point are invalidated (rolled back)
            for si in [s for s, _ in list(stage_cross_time.items())
                       if boundaries[s] > step]:
                stage_cross_time.pop(si, None)

            preempted = False
            while step < job.total_steps:
                with span("step"):
                    if self.pool.tick() is None:   # platform killed the VM
                        break
                    # the host-side cursor mirrors
                    # state["data"]["next_batch_index"] (both advance by 1
                    # per step; resume() re-syncs from the restored state) —
                    # reading it here instead of the device cursor saves a
                    # device→host sync per step
                    with span("step.batch"):
                        batch = self.pipeline.batch_at(
                            pstate.next_batch_index)
                    t0 = clock.now()
                    step_fn = (self._compiled_step
                               if self._compiled_step is not None
                               else self._step_fn)
                    with span("step.dispatch"):
                        state, metrics = step_fn(state, batch)
                    with span("step.wait"):
                        jax.block_until_ready(metrics["loss"])
                        final_loss = float(np.asarray(metrics["loss"]))
                    pstate = PipelineState(pstate.next_batch_index + 1)
                    self.ledger.charge_step(self.step_time_s)
                    dur = clock.now() - t0
                    step += 1
                    steps_executed += 1
                    with span("step.hook"):
                        # stage boundary bookkeeping + app-specific
                        # checkpoint hook
                        for si, b in enumerate(boundaries):
                            if step == b:
                                stage_cross_time[si] = clock.now()
                                self.coord.on_stage_end(si, step, state)
                        # staging handoff: the supplier is invoked lazily,
                        # only when the coordinator decides to checkpoint.
                        # The coordinator owns the prestage call (it knows
                        # the save kind): periodic saves prestage through
                        # the device-delta tracker — fingerprint + diff
                        # compute instead of full-state DMAs — while urgent
                        # saves prestage the plain way, never paying digest
                        # kernels inside the eviction-notice window. The
                        # tracker's gathered blocks are fresh device
                        # buffers, so the next step may freely donate
                        # `state`.
                        sig = self.coord.on_step_end(
                            step, lambda s=state: s, step_duration_s=dur)
                if sig is Signal.PREEMPTING:
                    preempted = True
                    break
                if sig is Signal.STRAGGLER:
                    inst.terminate()
                    break
            last_session_max_step = step
            if step >= job.total_steps:
                completed = True
                break
            if preempted:       # ride the notice out until the platform kills us
                while self.pool.tick() is not None:
                    clock.sleep(1.0)
            self.coord.detach()

        with span("flush"):
            self.coord.flush()
        self.pool.shutdown()
        total = clock.now() - t_start
        # per-stage durations on the surviving lineage
        stage_times = []
        prev = t_start
        for si in range(job.n_stages):
            t = stage_cross_time.get(si)
            if t is None:
                stage_times.append(float("nan"))
            else:
                stage_times.append(t - prev)
                prev = t
        st = self.coord.stats
        return RunReport(
            completed=completed,
            total_time_s=total,
            stage_times_s=stage_times,
            steps_executed=steps_executed,
            lost_steps=lost_steps,
            restores=st.restores,
            cold_starts=cold_starts,
            instances_used=self.pool.instances_created,
            evictions_seen=self.pool.evictions_announced,
            final_loss=final_loss,
            coordinator={
                "periodic_ckpts": st.periodic_ckpts,
                "periodic_failures": st.periodic_failures,
                "termination_ckpts": st.termination_ckpts,
                "termination_failures": st.termination_failures,
                "rebalance_ckpts": st.rebalance_ckpts,
                "stage_ckpts": st.stage_ckpts,
                "ckpt_bytes_written": st.ckpt_bytes_written,
                "ckpt_time_s": st.ckpt_time_s,
                "d2h_bytes": st.d2h_bytes,
                "d2h_bytes_skipped": st.d2h_bytes_skipped,
                "save_stall_s": st.save_stall_s,
                "restore_queue_wait_s": st.restore_queue_wait_s,
                "restore_decode_s": st.restore_decode_s,
                "save_yields": st.save_yields,
                "io_retries": st.io_retries,
                "faults_injected": st.faults_injected,
                "saves_degraded": st.saves_degraded,
                "backend_retries": st.backend_retries,
                "backend_outages": st.backend_outages,
                "spooled_bytes": st.spooled_bytes,
                "poll_failures": st.poll_failures,
                "mttr_mean_s": st.mttr_mean_s,
                "mttr_samples": list(st.mttr_samples),
            },
            extra={"provider": self.coord.provider.name},
        )
