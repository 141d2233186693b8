"""The trace reduction on a small trace recorded on one v5e chip: three
executions each of a bf16 matmul program and of the fingerprint kernel's
program over 8 MiB (``jit__fp_pallas``)."""

import os

import pytest

from harness import peaks, record, spec, trace

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


def _splash_record(ops, work):
    return record.RunRecord(
        cell=spec.load_cell("phi3-mini-3l.periodic"), window_s=1.0,
        peaks=peaks.peaks_for("TPU v5 lite"),
        trace=trace.Trace(devices={"/device:TPU:0": trace.Device(ops=ops)},
                          host=[]),
        work=work)


# the forward, its recomputation with residuals, a fused backward holding
# a copy that is not the kernel's time, a two-pass backward's dq call, and
# an op of another kind
SPLASH_OPS = [
    ("%splash_mha_fwd_no_residuals.3 = bf16[2]{0} custom-call(%a)",
     0, 1_000_000),
    ("%splash_mha_fwd_residuals.4 = bf16[2]{0} custom-call(%a)",
     2_000_000, 1_000_000),
    ("%splash_mha_dkv_no_residuals.10 = bf16[2]{0} custom-call(%a)",
     4_000_000, 3_000_000),
    ("%copy.1 = bf16[2]{0} copy(%a)", 6_000_000, 1_000_000),
    ("%splash_mha_dq_no_residuals.11 = bf16[2]{0} custom-call(%a)",
     8_000_000, 1_000_000),
    ("%fusion.7 = bf16[2]{0} fusion(%a)", 10_000_000, 5_000_000),
]


def test_op_time_sums_self_time_by_short_name():
    dev = trace.Device(ops=SPLASH_OPS)
    t = trace.Trace(devices={"/device:TPU:0": dev, "/device:TPU:1": dev},
                    host=[])
    # the dkv call's 3 ms less the 1 ms copy inside it
    assert t.op_time_s(lambda n: "dkv" in n) == (pytest.approx(2e-3), 1)
    assert t.op_time_s(lambda n: n.startswith("splash_mha")) == (
        pytest.approx(5e-3), 4)
    assert t.op_time_s(lambda n: n == "fusion.7 (fusion)") == (
        pytest.approx(5e-3), 1)
    assert t.op_time_s(lambda n: False) == (0.0, 0)


def test_attention_roofline_counts_forward_and_fused_backward_flops():
    read = spec.layer_reader("attention_roofline")
    # 2 forwards of 197 MFLOP and a backward of 394 over the 5 ms of the
    # four calls' self time: 788 MFLOP in 5 ms at 197 TFLOP/s is 0.08 %
    rec = _splash_record(SPLASH_OPS, {"attention_fwd_flops": 197e6,
                                      "attention_bwd_flops": 394e6})
    assert read(rec) == pytest.approx(100.0 * 788e6 / 5e-3 / 197e12)
    rec.work = {"attention_fwd_flops": 197e9, "attention_bwd_flops": 394e9}
    assert read(rec) == pytest.approx(80.0)


def test_attention_roofline_reads_nothing_without_its_kernel_or_count():
    read = spec.layer_reader("attention_roofline")
    work = {"attention_fwd_flops": 1e9, "attention_bwd_flops": 2e9}
    assert read(_splash_record(SPLASH_OPS[3:4] + SPLASH_OPS[5:], work)) is None
    assert read(_splash_record(SPLASH_OPS, {"step_flops": 1e12})) is None


def test_step_mfu_reads_the_step_flops_of_work():
    read = spec.layer_reader("step_mfu")
    dev = trace.Device(modules=[("jit_train_step(1)", 0, 100_000_000),
                                ("jit_train_step(1)", 200_000_000,
                                 100_000_000),
                                ("jit__fp_pallas(2)", 300_000_000, 10)])
    rec = _splash_record([], {"step_flops": 9.85e12})
    rec.trace = trace.Trace(devices={"/device:TPU:0": dev}, host=[])
    # two steps of 9.85 TFLOP in 0.2 s at 197 TFLOP/s
    assert read(rec) == pytest.approx(50.0)
    rec.work = {}
    assert read(rec) is None
