"""The periodic driver end to end on the CPU at a small size: a sound run
is correct, and a run whose timed path is broken underneath is not."""

import time

import jax
import pytest

from harness import common, periodic, spec

import smoke

CELL = "phi3-mini-3l.periodic"


def _run(tmp_path):
    cell = smoke.smoke_cell(CELL)
    return periodic.run(cell, seed=2 ** 31 + 11, seconds=0.5, trace=False,
                        root=str(tmp_path), cache_dir=None,
                        devices=jax.devices(), t_start=time.perf_counter(),
                        compile_log=common.CompileLog())


def test_sound_run_is_correct(tmp_path, capsys):
    result, checks = _run(tmp_path)
    assert result["correct"], checks
    # the window's save is a steady-state one: it diffed against the save
    # the set-up committed
    window = next(line for line in capsys.readouterr().out.splitlines()
                  if line.startswith("window:"))
    assert " saves=1 " in window and " diffed_saves=1 " in window, window
    assert checks["save_mismatch"] == (0, 0)
    assert result["failed"] == 0
    m = result["metrics"]
    assert set(m) == {e["name"] for e in spec.load_cell(
        CELL).end_to_end}
    assert all(v["value"] > 0 for v in m.values())
    # checkpoints are removed by the run
    assert not any((tmp_path / ".spoton_ckpts").rglob("MANIFEST*"))


@pytest.mark.parametrize("fault,caught_by", [
    ("unchanged", "change_gap"),
    ("half_batch", "change_gap"),
    ("altered_save", "save_mismatch"),
])
def test_broken_timed_path_is_not_correct(tmp_path, monkeypatch, fault,
                                          caught_by):
    smoke.plant(monkeypatch, fault)
    result, checks = _run(tmp_path)
    assert not result["correct"]
    value, limit = checks[caught_by]
    assert value > limit


def test_traced_run_reports_per_layer_metrics(tmp_path, monkeypatch):
    """The traced path end to end. The CPU has no device plane in its
    trace, so only the program-span metric finds something to read; the
    device readers are checked on a recorded chip trace (test_trace)."""
    from harness import peaks
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    cell = smoke.smoke_cell(CELL)
    result, _checks = periodic.run(
        cell, seed=5, seconds=0.5, trace=True, root=str(tmp_path),
        cache_dir=None, devices=jax.devices(), t_start=time.perf_counter(),
        compile_log=common.CompileLog())
    assert result["correct"]
    assert set(result["metrics"]) == {"save_stall_s"}
    assert result["device"]["window_s"] > 0
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    assert not (tmp_path / ".bench_trace" / cell.name).exists()
