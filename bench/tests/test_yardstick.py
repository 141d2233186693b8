"""FLOP and byte counts, the peak table, and the comparisons."""

import math

import pytest

from harness import compare, flops, peaks, spec

# phi3-mini-3l's model FLOPs of one step, as harness.flops counted them
# before the count moved to the configuration's program module
STEP_FLOPS = 11_234_250_104_832.0


def test_phi3_mini_3l_step_flops():
    cfg = spec.load_cell("phi3-mini-3l.periodic").config
    program = spec.program_module(cfg)
    # 3 layers of 4 x 3072^2 attention and 3 x 3072 x 8192 MLP weights,
    # plus the 3072 x 32064 head
    assert program.matmul_params(cfg) == 3 * (4 * 3072 ** 2
                                              + 3 * 3072 * 8192) + 3072 * 32064
    pairs = 2047 * 2048 // 2 + 1 * 2047          # window 2047 of 2048
    assert flops.attention_pairs(2048, 2047) == pairs
    assert flops.attention_pairs(2048, None) == 2048 * 2049 // 2
    want = (6.0 * program.matmul_params(cfg) * 4096
            + 3 * 4.0 * 32 * 96 * pairs * 2 * 3)
    work = program.work(cfg)
    assert work["step_flops"] == want
    # the number step_mfu has divided by since it was first measured
    assert work["step_flops"] == STEP_FLOPS
    assert 11.1e12 < want < 11.3e12
    # one kernel call: one layer, both rows, head size 96 for q, k and v
    assert work["attention_fwd_flops"] == 2.0 * 2 * 32 * pairs * 192
    assert work["attention_bwd_flops"] == 2 * work["attention_fwd_flops"]


@pytest.mark.parametrize("d_qk,d_v,gflop", [
    (96, 96, 51.5647488),       # phi3-mini: one head size for q, k and v
    (192, 128, 85.941248),      # latent attention: q.k over 192, v 128
])
def test_attention_call_flops(d_qk, d_v, gflop):
    """One kernel call at phi3-mini's (2, 2048, 32 heads, window 2047):
    QK^T and PV over the 2,098,175 kept pairs; the backward twice that."""
    fwd, bwd = flops.attention_call_flops(2, 32, 2048, 2047, d_qk, d_v)
    assert fwd == 2.0 * 2 * 32 * 2_098_175 * (d_qk + d_v)
    assert fwd / 1e9 == pytest.approx(gflop)
    assert bwd == 2 * fwd


def test_fingerprint_bytes_skip_small_leaves():
    assert flops.fingerprint_bytes([1 << 16, 100, 1 << 20]) == (1 << 16) + (1 << 20)


def test_peaks_refuse_an_unknown_device():
    assert peaks.peaks_for("TPU v5 lite")["hbm_bw"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")


def _readings(loss, grad, change, raw=None):
    return {"loss": loss, "grad": grad, "change": change,
            "grad_raw": raw or dict(grad)}


def test_gaps_take_the_worst_leaf_against_the_median():
    ref = _readings([10.0, 9.0], {"a": 1.0, "b": 2.0, "c": 1e-6},
                    {"a": 1.0, "b": 1.0, "c": 1.0},
                    raw={"a": 1.0, "b": 2.0, "c": 1e-9})
    prog = _readings([10.01, 9.0], {"a": 1.1, "b": 2.0, "c": 2e-6},
                     {"a": 1.0, "b": 1.5, "c": 9.0})
    g = compare.gaps(prog, ref)
    assert g["loss_gap"] == pytest.approx(1e-3)
    # c's tiny gradient is measured against the median leaf's (1.0)
    assert g["grad_gap"] == pytest.approx(0.1)
    # c moves by round-off alone (raw gradient under 1e-3 of the median)
    assert g["change_gap"] == pytest.approx(0.5)


def test_a_nan_reads_as_the_widest_gap():
    ref = _readings([1.0], {"a": 1.0}, {"a": 1.0})
    prog = _readings([math.nan], {"a": 1.0}, {"a": math.nan})
    g = compare.gaps(prog, ref)
    assert g["loss_gap"] == math.inf and g["change_gap"] == math.inf


def test_an_unmoved_state_reads_one():
    ref = _readings([1.0], {"a": 1.0, "b": 3.0}, {"a": 1.0, "b": 2.0})
    prog = _readings([1.0], {"a": 0.0, "b": 0.0}, {"a": 0.0, "b": 0.0})
    g = compare.gaps(prog, ref)
    assert g["grad_gap"] == 1.0 and g["change_gap"] == 1.0
