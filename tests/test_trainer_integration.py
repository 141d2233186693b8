"""End-to-end trainer × coordinator × simulator integration — the paper's
workflow, including the headline property: transparent checkpointing makes an
evicted run finish with BIT-EXACT final state and less wall time than
application-stage checkpointing."""

import weakref

import numpy as np
import pytest

import jax

from repro.checkpoint import CheckpointStore
from repro.configs import get_smoke_config
from repro.core import (CheckpointPolicy, CostAccountant, AZURE_D8S_V3,
                        NoEviction, PeriodicEviction, ScaleSet,
                        SpotOnCoordinator, TimeModel, VirtualClock)
from repro.optim import AdamWConfig
from repro.train import SpotTrainer, TrainJob


def run_job(tmp_path, mode, evict_s, *, total=60, step_time=10.0,
            periodic_s=200.0, tag=""):
    clock = VirtualClock()
    acct = CostAccountant(AZURE_D8S_V3)
    sched = PeriodicEviction(evict_s) if evict_s else NoEviction()
    pool = ScaleSet(clock=clock, schedule=sched, accountant=acct,
                    provisioning_delay_s=60.0, notice_s=30.0)
    store = CheckpointStore(str(tmp_path / f"ckpt{tag}"), time_fn=clock.now)
    policy = {"off": CheckpointPolicy.off(),
              "application": CheckpointPolicy.application(),
              "transparent": CheckpointPolicy.transparent(periodic_s)}[mode]
    coord = SpotOnCoordinator(store, policy, clock, time_model=TimeModel())
    cfg = get_smoke_config("phi3_mini_3p8b")
    job = TrainJob(cfg=cfg, opt=AdamWConfig(total_steps=total),
                   total_steps=total, n_stages=3, batch=2, seq_len=16)
    tr = SpotTrainer(job, coord, pool, clock, step_time_s=step_time,
                     max_sessions=40)
    rep = tr.run()
    coord.close()
    return rep, acct.summary(clock.now())


class TestNoEviction:
    def test_off_and_transparent_equal_time(self, tmp_path):
        off, _ = run_job(tmp_path, "off", None, tag="a")
        tr, _ = run_job(tmp_path, "transparent", None, tag="b")
        assert off.completed and tr.completed
        # Table I rows 1-2: negligible overhead without evictions
        assert tr.total_time_s <= off.total_time_s * 1.05


class TestEvicted:
    def test_transparent_bit_exact_resume(self, tmp_path):
        base, _ = run_job(tmp_path, "off", None, tag="base")
        ev, _ = run_job(tmp_path, "transparent", 250.0, periodic_s=100.0,
                        tag="ev")
        assert ev.completed
        assert ev.evictions_seen >= 1 and ev.restores >= 1
        # identical data order + full state capture => identical final loss
        assert ev.final_loss == pytest.approx(base.final_loss, abs=1e-6)
        assert ev.lost_steps == 0  # termination ckpt caught the frontier

    def test_restored_state_released_by_the_first_step(self, tmp_path,
                                                       monkeypatch):
        """A session holds one state: once the first step after a restore
        has replaced it, nothing keeps the restored tree alive, so the
        device holds the step's input and output and no third copy."""
        restored, alive_at_step_end = [], []
        restore_latest = SpotOnCoordinator.restore_latest
        on_step_end = SpotOnCoordinator.on_step_end

        def track_restore(self, template, **kw):
            out = restore_latest(self, template, **kw)
            if out is not None:
                big = max(jax.tree.leaves(out[0]), key=lambda a: a.size)
                restored.append(weakref.ref(big))
            return out

        def track_step_end(self, step, state_provider, **kw):
            if restored:
                alive_at_step_end.append(restored[-1]() is not None)
            return on_step_end(self, step, state_provider, **kw)

        monkeypatch.setattr(SpotOnCoordinator, "restore_latest", track_restore)
        monkeypatch.setattr(SpotOnCoordinator, "on_step_end", track_step_end)
        ev, _ = run_job(tmp_path, "transparent", 250.0, periodic_s=100.0,
                        tag="gc")
        assert ev.completed and ev.restores >= 1
        assert alive_at_step_end and not any(alive_at_step_end)

    def test_application_rolls_back_to_stage(self, tmp_path):
        ev, _ = run_job(tmp_path, "application", 420.0, tag="app")
        assert ev.completed
        assert ev.lost_steps > 0          # work since last stage lost
        assert ev.coordinator["termination_ckpts"] == 0

    def test_transparent_faster_and_cheaper_than_application(self, tmp_path):
        app, capp = run_job(tmp_path, "application", 420.0, tag="x")
        tr, ctr = run_job(tmp_path, "transparent", 420.0, periodic_s=100.0,
                          tag="y")
        assert app.completed and tr.completed
        assert tr.total_time_s < app.total_time_s      # paper Fig. 3
        assert ctr["total_usd"] < capp["total_usd"]    # paper Fig. 2

    def test_off_mode_restarts_from_scratch(self, tmp_path):
        rep, _ = run_job(tmp_path, "off", 350.0, tag="z")
        # either limps to completion with full restarts or hits the session cap
        assert rep.cold_starts >= 2 or not rep.completed


class TestStageTimes:
    def test_stage_times_cover_total(self, tmp_path):
        rep, _ = run_job(tmp_path, "transparent", None, tag="st")
        assert rep.completed
        assert not any(np.isnan(rep.stage_times_s))
        assert sum(rep.stage_times_s) == pytest.approx(rep.total_time_s, rel=0.05)
