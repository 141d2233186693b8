"""The trace reduction on a small trace recorded on one v5e chip: three
executions each of a bf16 matmul program and of the fingerprint kernel's
program over 8 MiB (``jit__fp_pallas``)."""

import os

import pytest

from harness import trace

DATA = os.path.join(os.path.dirname(__file__), "data", "small_trace.xplane.pb")


@pytest.fixture(scope="module")
def small():
    return trace.reduce_file(DATA)


def test_one_device_with_ops_and_programs(small):
    assert sorted(small.devices) == ["/device:TPU:0"]
    dev = small.devices["/device:TPU:0"]
    assert len(dev.modules) == 6
    assert len(dev.ops) == 21


def test_busy_is_the_union_of_op_intervals(small):
    dev = small.devices["/device:TPU:0"]
    assert small.busy_s() == pytest.approx(120921e-9)
    # nested and overlapping ops count once: the union is at most the sum
    assert small.busy_s() <= sum(d for _n, _s, d in dev.ops) / 1e9


def test_program_time_by_name(small):
    secs, n = small.module_time_s(lambda name: "_fp_pallas" in name)
    assert n == 3
    assert secs == pytest.approx(66428e-9)
    secs, n = small.module_time_s(lambda name: "nothing" in name)
    assert (secs, n) == (0.0, 0)


def test_top_ops_use_short_names_and_self_time(small):
    top = small.top_ops(3)
    assert [name for name, _ in top] == [
        "fusion (fusion)", "convert_bitcast_fusion (fusion)",
        "_fp_pallas.1 (custom-call)"]
    assert top[0][1] == pytest.approx(45045e-9)


def test_idle_gaps_are_named_by_the_host(small):
    gaps = small.idle_gaps(3)
    assert len(gaps) == 3
    assert gaps[0][1] >= gaps[1][1] >= gaps[2][1] > 0
    assert gaps[1][0].startswith("after jit__lambda")
    assert "PjitFunction(_fp_pallas)" in gaps[1][0]


def test_self_times_subtract_direct_children():
    events = [("loop", 0, 10), ("body", 1, 3), ("inner", 1, 1),
              ("next", 5, 2), ("after", 12, 1)]
    assert trace.self_times(events) == [
        ("loop", 5), ("body", 2), ("inner", 1), ("next", 2), ("after", 1)]


def test_merge_and_short_names():
    assert trace.merge([("a", 0, 5), ("b", 3, 4), ("c", 9, 1),
                        ("z", 20, 0)]) == [(0, 7), (9, 10)]
    assert trace.short_op_name(
        "%while.3 = (s32[]{:T(128)}, bf16[2]{0}) while((s32[], bf16[2]) "
        "%t), body=%b") == "while.3 (while)"
    assert trace.short_op_name("no hlo here") == "no hlo here"


def test_no_trace_file():
    assert trace.find_xplane(os.path.dirname(DATA) + "/missing") is None


def test_fingerprint_roofline_counts_the_diff_and_no_other_program():
    from harness import peaks, record, spec

    dev = trace.Device(modules=[
        ("jit__fp_pallas(1)", 0, 4_000_000),
        ("jit_not_equal(2)", 5_000_000, 1_000_000),
        ("jit_train_step(3)", 7_000_000, 200_000_000),
        ("jit__lambda(4)", 300_000_000, 1_000_000)])
    rec = record.RunRecord(
        cell=spec.load_cell("phi3-mini-3l.periodic"), window_s=1.0,
        peaks=peaks.peaks_for("TPU v5 lite"),
        trace=trace.Trace(devices={"/device:TPU:0": dev}, host=[]),
        fingerprint_bytes=819_000_000)
    read = spec.layer_reader("fingerprint_roofline")
    # 819 MB at 819 GB/s is 1 ms, over the 5 ms of the two programs
    assert read(rec) == pytest.approx(20.0)
    rec.trace = trace.Trace(devices={"/device:TPU:0": trace.Device(
        modules=dev.modules[2:])}, host=[])
    assert read(rec) is None
