"""SpotOnCoordinator policy semantics — the paper's §III-A contract."""

import errno

import jax
import numpy as np
import pytest

from repro.checkpoint import CheckpointStore
from repro.core import (CheckpointPolicy, Mode, Signal, SimulatedMetadataService,
                        SpotOnCoordinator, TimeModel, VirtualClock)


def state(step):
    return {"w": np.full((16,), float(step), np.float32), "step": step}


def make(tmp_path, policy, clock=None, tm=TimeModel()):
    clock = clock or VirtualClock()
    store = CheckpointStore(str(tmp_path), time_fn=clock.now)
    coord = SpotOnCoordinator(store, policy, clock, time_model=tm)
    md = SimulatedMetadataService(clock, "vm-0")
    coord.attach_instance(md, "vm-0")
    return coord, md, clock, store


class TestTransparent:
    def test_periodic_cadence(self, tmp_path):
        coord, md, clock, store = make(tmp_path, CheckpointPolicy.transparent(100.0))
        for step in range(1, 31):
            clock.advance(10.0)
            coord.on_step_end(step, lambda s=step: state(s))
        coord.flush()
        assert coord.stats.periodic_ckpts == pytest.approx(3, abs=1)

    def test_termination_checkpoint_on_preempt(self, tmp_path):
        coord, md, clock, store = make(tmp_path, CheckpointPolicy.transparent(1e9))
        md.simulate_eviction()
        clock.advance(2.0)
        sig = coord.on_step_end(7, lambda: state(7))
        assert sig is Signal.PREEMPTING
        assert coord.stats.termination_ckpts == 1
        got, man = store.restore(state(0))
        assert man.kind == "termination" and got["step"] == 7

    def test_termination_missing_window_fails_gracefully(self, tmp_path):
        # write cost exceeds the notice -> opportunistic failure, not crash
        tm = TimeModel(write_bw=1.0, latency_s=1000.0)   # absurdly slow NFS
        coord, md, clock, store = make(tmp_path, CheckpointPolicy.transparent(1e9),
                                       tm=tm)
        md.simulate_eviction()
        clock.advance(1.0)
        sig = coord.on_step_end(3, lambda: state(3))
        assert sig is Signal.PREEMPTING
        assert coord.stats.termination_failures == 1

    def test_same_event_handled_once(self, tmp_path):
        coord, md, clock, store = make(tmp_path, CheckpointPolicy.transparent(1e9))
        md.simulate_eviction()
        clock.advance(2.0)
        assert coord.on_step_end(1, lambda: state(1)) is Signal.PREEMPTING
        clock.advance(2.0)
        assert coord.on_step_end(2, lambda: state(2)) is Signal.CONTINUE


class TestDeadlineEdges:
    """Termination-checkpoint deadline edges: zero/negative budget, virtual
    cost exceeding the notice window, duplicate-event suppression."""

    def test_zero_budget_fails_without_write(self, tmp_path):
        coord, md, clock, store = make(tmp_path, CheckpointPolicy.transparent(1e9))
        ev = md.schedule_preempt(notice_s=30.0)
        clock.advance(ev.not_before - clock.now())     # poll lands AT NotBefore
        sig = coord.on_step_end(5, lambda: state(5))
        assert sig is Signal.PREEMPTING
        assert coord.stats.termination_failures == 1
        assert coord.stats.termination_ckpts == 0
        assert store.committed_steps() == []

    def test_negative_budget_fails_without_write(self, tmp_path):
        coord, md, clock, store = make(tmp_path, CheckpointPolicy.transparent(1e9))
        md.schedule_preempt(notice_s=30.0)
        clock.advance(90.0)                            # way past the deadline
        sig = coord.on_step_end(5, lambda: state(5))
        assert sig is Signal.PREEMPTING
        assert coord.stats.termination_failures == 1
        assert store.committed_steps() == []

    def test_virtual_cost_exceeding_window_charges_only_budget(self, tmp_path):
        # write cost exceeds the remaining notice: the failure must consume
        # exactly the budget (the VM was writing until the platform killed it)
        tm = TimeModel(write_bw=1.0, latency_s=500.0)  # cost >> 30 s window
        coord, md, clock, store = make(tmp_path, CheckpointPolicy.transparent(1e9),
                                       tm=tm)
        ev = md.simulate_eviction()
        clock.advance(2.0)
        t_before = clock.now()
        budget = ev.not_before - t_before
        sig = coord.on_step_end(3, lambda: state(3))
        assert sig is Signal.PREEMPTING
        assert coord.stats.termination_failures == 1
        assert clock.now() - t_before == pytest.approx(budget)

    def test_duplicate_event_id_suppressed(self, tmp_path):
        coord, md, clock, store = make(tmp_path, CheckpointPolicy.transparent(1e9))
        md.simulate_eviction()
        clock.advance(2.0)
        assert coord.on_step_end(1, lambda: state(1)) is Signal.PREEMPTING
        assert coord.stats.termination_ckpts == 1
        # same event still in the document: must not be handled twice
        for step in (2, 3, 4):
            clock.advance(2.0)
            assert coord.on_step_end(step, lambda s=step: state(s)) is Signal.CONTINUE
        assert coord.stats.termination_ckpts == 1

    def test_distinct_event_handled_separately(self, tmp_path):
        coord, md, clock, store = make(tmp_path, CheckpointPolicy.transparent(1e9))
        md.simulate_eviction()
        clock.advance(2.0)
        assert coord.on_step_end(1, lambda: state(1)) is Signal.PREEMPTING
        md.clear()
        md.simulate_eviction()                         # a NEW event id
        clock.advance(2.0)
        assert coord.on_step_end(2, lambda: state(2)) is Signal.PREEMPTING
        assert coord.stats.termination_ckpts == 2


class TestApplication:
    def test_cannot_checkpoint_on_demand(self, tmp_path):
        """Paper: 'application-specific checkpointing cannot be taken on
        demand' — a preempt produces NO termination checkpoint."""
        coord, md, clock, store = make(tmp_path, CheckpointPolicy.application())
        md.simulate_eviction()
        clock.advance(2.0)
        sig = coord.on_step_end(9, lambda: state(9))
        assert sig is Signal.PREEMPTING
        assert coord.stats.termination_ckpts == 0
        assert store.committed_steps() == []

    def test_stage_boundary_checkpoints(self, tmp_path):
        coord, md, clock, store = make(tmp_path, CheckpointPolicy.application())
        coord.on_stage_end(0, 100, state(100))
        assert coord.stats.stage_ckpts == 1
        got, man = store.restore(state(0))
        assert man.kind == "application" and man.extra["stage"] == 0

    def test_no_periodic(self, tmp_path):
        coord, md, clock, store = make(tmp_path, CheckpointPolicy.application())
        for step in range(1, 50):
            clock.advance(60.0)
            coord.on_step_end(step, lambda s=step: state(s))
        coord.flush()
        assert coord.stats.periodic_ckpts == 0


class TestOff:
    def test_nothing_saved(self, tmp_path):
        coord, md, clock, store = make(tmp_path, CheckpointPolicy.off())
        md.simulate_eviction()
        clock.advance(2.0)
        assert coord.on_step_end(1, lambda: state(1)) is Signal.PREEMPTING
        coord.on_stage_end(0, 1, state(1))
        coord.flush()
        assert store.committed_steps() == []


class TestRestore:
    def test_restore_latest_valid(self, tmp_path):
        coord, md, clock, store = make(tmp_path, CheckpointPolicy.transparent(1.0))
        store.save(4, state(4))
        store.save(8, state(8))
        got, man = coord.restore_latest(state(0))
        assert got["step"] == 8 and coord.stats.restores == 1

    def test_restore_none_when_empty(self, tmp_path):
        coord, md, clock, store = make(tmp_path, CheckpointPolicy.transparent(1.0))
        assert coord.restore_latest(state(0)) is None


class TestDeviceErrors:
    """A device runtime error inside a save (OOM, failed compile, lost chip)
    fails the run; storage faults keep their skip-and-alert degradation."""

    @staticmethod
    def _device_oom():
        return jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED: out of memory")

    @pytest.mark.parametrize("wrapped", [False, True])
    def test_periodic_save_device_error_propagates(self, tmp_path, monkeypatch,
                                                   wrapped):
        coord, md, clock, store = make(tmp_path, CheckpointPolicy.transparent(100.0))
        err = self._device_oom()

        def save_async(*a, **kw):
            if wrapped:       # how an async writer's failure arrives
                raise RuntimeError("async checkpoint write failed") from err
            raise err
        monkeypatch.setattr(coord._async, "save_async", save_async)
        clock.advance(100.0)
        with pytest.raises(RuntimeError) as ei:
            coord.on_step_end(1, lambda: state(1))
        assert ei.value is err or ei.value.__cause__ is err
        assert coord.stats.periodic_failures == 0
        assert coord.stats.saves_degraded == 0

    def test_urgent_save_device_error_propagates(self, tmp_path, monkeypatch):
        coord, md, clock, store = make(tmp_path, CheckpointPolicy.transparent(1e9))
        err = self._device_oom()

        def save_urgent(*a, **kw):
            raise err
        monkeypatch.setattr(coord._async, "save_urgent", save_urgent)
        md.simulate_eviction()
        clock.advance(2.0)
        with pytest.raises(jax.errors.JaxRuntimeError):
            coord.on_step_end(7, lambda: state(7))
        assert coord.stats.termination_failures == 0

    def test_periodic_save_enospc_degrades(self, tmp_path, monkeypatch):
        coord, md, clock, store = make(tmp_path, CheckpointPolicy.transparent(100.0))

        def save_async(*a, **kw):
            raise OSError(errno.ENOSPC, "No space left on device")
        monkeypatch.setattr(coord._async, "save_async", save_async)
        clock.advance(100.0)
        assert coord.on_step_end(1, lambda: state(1)) is Signal.CONTINUE
        assert coord.stats.periodic_failures == 1
        assert coord.stats.saves_degraded == 1
        # the next cadence lands inside the window: skipped, not retried
        clock.advance(100.0)
        coord.on_step_end(2, lambda: state(2))
        assert coord.stats.periodic_failures == 1
        assert coord.stats.saves_degraded == 2
