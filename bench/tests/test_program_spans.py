"""The readers of the program's spans (``harness.spans`` and the seven
``bench/layer_metrics`` files that use it) on traces built here: known
answers, the idle split that sums to the run's idle share, and nothing
read from a trace without ``spoton.run``."""

import random

import pytest

from harness import record, spec, trace

MS = 1_000_000                     # ns
READERS = ("idle_save_extract_pct", "idle_step_loop_pct",
           "idle_outside_steps_pct", "save_d2h_s", "save_encode_s",
           "save_pool_write_s", "save_write_wall_s")
IDLE = READERS[:3]


def _trace(host, busy):
    """A trace of one chip whose operations cover ``busy`` ((lo, hi) ms)
    and whose host planes hold ``host`` ((name, lo, hi) ms)."""
    ops = [(f"fusion.{i}", lo * MS, (hi - lo) * MS)
           for i, (lo, hi) in enumerate(busy)]
    return trace.Trace(
        devices={"/device:TPU:0": trace.Device(ops=ops)},
        host=[(n, lo * MS, (hi - lo) * MS) for n, lo, hi in host])


# one step with one save in its hook, the flush, and a background write
# whose encode jobs and chunk writes overlap the trainer's idle time
HOST = [
    ("spoton.run", 0, 1000),
    ("spoton.step", 100, 500),
    ("spoton.step.batch", 100, 120),
    ("spoton.step.dispatch", 120, 150),
    ("PjitFunction(train_step)", 120, 150),    # a runtime span: not read
    ("spoton.step.wait", 150, 300),
    ("spoton.step.hook", 300, 500),
    ("spoton.save.extract", 310, 390),
    ("spoton.save.diff_wait", 315, 325),
    ("spoton.save.d2h", 330, 380),
    ("spoton.save.write", 400, 900),
    ("spoton.save.encode", 410, 600),
    ("spoton.save.encode", 420, 650),
    ("spoton.save.pool_write", 430, 450),
    ("spoton.save.pool_write", 610, 640),
    ("spoton.save.manifest", 880, 890),
    ("spoton.flush", 800, 1000),
    ("spoton.save.write", 920, 980),
    ("spoton.save.pool_write", 1100, 1110),    # after the run: not read
]
BUSY = [(0, 50), (120, 290), (600, 700), (1050, 1200)]


def _read(name, tr, window_ms=1000):
    rec = record.RunRecord(cell=None, window_s=window_ms * MS / 1e9,
                           trace=tr)
    return spec.layer_reader(name)(rec)


def test_known_answers():
    tr = _trace(HOST, BUSY)
    # idle in the run: 50-120, 290-600, 700-1000 ms. Under a save's span:
    # 310-330 and 380-390 (extract, diff_wait) and 330-380 (d2h); under a
    # step's: 100-120, 290-310, 390-500; the rest under run or flush alone
    assert _read("idle_save_extract_pct", tr) == pytest.approx(8.0)
    assert _read("idle_step_loop_pct", tr) == pytest.approx(15.0)
    assert _read("idle_outside_steps_pct", tr) == pytest.approx(45.0)
    assert _read("save_d2h_s", tr) == pytest.approx(0.050)
    # two writes, 500 and 60 ms: their mean
    assert _read("save_write_wall_s", tr) == pytest.approx(0.280)
    # 190 + 230 ms of encode jobs less the 20 + 30 ms of chunk writes
    assert _read("save_encode_s", tr) == pytest.approx(0.370)
    assert _read("save_pool_write_s", tr) == pytest.approx(0.050)


def test_idle_split_sums_to_the_device_idle_share_of_the_run():
    tr = _trace(HOST, [b for b in BUSY if b[1] <= 1000])
    split = sum(_read(n, tr) for n in IDLE)
    assert split == pytest.approx(68.0)
    assert split == pytest.approx(_read("device_idle_pct", tr))


def _random_trace(rng):
    """A run of nested trainer spans over random operations."""
    host = [("spoton.run", 0, 10_000)]
    t = rng.randrange(0, 300)
    while t < 8_000:
        end = t + rng.randrange(50, 600)
        host.append(("spoton.step", t, end))
        cut = sorted(rng.sample(range(t + 1, end), 3))
        for name, lo, hi in zip(("batch", "dispatch", "wait", "hook"),
                                [t] + cut, cut + [end]):
            host.append((f"spoton.step.{name}", lo, hi))
        if rng.random() < 0.3 and end - cut[2] > 4:
            lo, hi = sorted(rng.sample(range(cut[2], end), 2))
            host.append(("spoton.save.extract", lo, hi))
            if hi - lo > 2:
                host.append(("spoton.save.d2h", lo + 1, hi - 1))
            host.append(("spoton.save.write", hi, hi + 900))
        t = end + rng.randrange(0, 40)
    host.append(("spoton.flush", t, 10_000))
    busy, t = [], 0
    while t < 10_000:
        lo = t + rng.randrange(0, 30)
        hi = lo + rng.randrange(1, 200)
        busy.append((lo, min(hi, 10_000)))
        t = hi
    return _trace(host, busy)


@pytest.mark.parametrize("seed", [1, 2, 3, 2147483905])
def test_idle_split_sums_exactly_on_random_traces(seed):
    tr = _random_trace(random.Random(seed))
    parts = [_read(n, tr, window_ms=10_000) for n in IDLE]
    assert all(p >= 0 for p in parts)
    assert sum(parts) == pytest.approx(_read("device_idle_pct", tr,
                                             window_ms=10_000), abs=1e-9)


def test_nothing_without_a_run_span():
    tr = _trace([h for h in HOST if h[0] != "spoton.run"], BUSY)
    assert {n: _read(n, tr) for n in READERS} == dict.fromkeys(READERS)
    assert {n: _read(n, None) for n in READERS} == dict.fromkeys(READERS)


def test_nothing_from_a_trace_without_a_chip():
    """A CPU run's trace has host planes alone: its spans are not read."""
    tr = trace.Trace(devices={}, host=_trace(HOST, BUSY).host)
    assert {n: _read(n, tr) for n in READERS} == dict.fromkeys(READERS)


def test_a_program_without_saves_leaves_the_save_readers_silent():
    tr = _trace([h for h in HOST if not h[0].startswith("spoton.save.")],
                BUSY)
    for name in ("save_d2h_s", "save_encode_s", "save_pool_write_s",
                 "save_write_wall_s"):
        assert _read(name, tr) is None
    assert _read("idle_save_extract_pct", tr) == 0.0


def test_the_cell_reports_every_span_metric():
    cell = spec.load_cell("phi3-mini-3l.periodic")
    entries = {m["name"]: m for m in cell.per_layer}
    for name in READERS:
        assert entries[name]["source"] == "program_span"
        assert entries[name]["moves"] == "goodput_tokens_per_s"
    rec = record.RunRecord(cell=cell, window_s=1.0, trace=_trace(HOST, BUSY))
    assert set(READERS) <= set(record.per_layer(rec))
