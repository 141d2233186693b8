"""Program spans on the profiler's clock (``repro.core.ledger.span``): a
smoke-size trainer run with two async periodic saves, profiled on the CPU
and read back from its ``.xplane.pb``, against the same run with no
profiler."""

import dataclasses
import glob
import os

import pytest

import jax
from jax.profiler import ProfileData

from repro.checkpoint import CheckpointStore
from repro.configs import get_smoke_config
from repro.core import (AZURE_D8S_V3, CheckpointPolicy, CostAccountant,
                        NoEviction, ScaleSet, SpotOnCoordinator,
                        VirtualClock)
from repro.core.ledger import span
from repro.optim import AdamWConfig
from repro.train import SpotTrainer, TrainJob

STEP_PARTS = ("spoton.step.batch", "spoton.step.dispatch",
              "spoton.step.wait", "spoton.step.hook")
EXTRACT_PARTS = ("spoton.save.prestage", "spoton.save.diff_wait",
                 "spoton.save.d2h", "spoton.save.enqueue")
TRAINER = (("spoton.run", "spoton.step", "spoton.flush",
            "spoton.save.extract") + STEP_PARTS + EXTRACT_PARTS)
WRITER = ("spoton.save.write", "spoton.save.manifest", "spoton.save.commit")
WORKERS = ("spoton.save.encode", "spoton.save.pool_write")
STEPS = 25


def _run(ckpt_dir):
    """25 steps of 10 virtual seconds, a periodic save every 100: saves at
    steps 10 and 20, written by the async writer."""
    clock = VirtualClock()
    pool = ScaleSet(clock=clock, schedule=NoEviction(),
                    accountant=CostAccountant(AZURE_D8S_V3),
                    provisioning_delay_s=60.0)
    store = CheckpointStore(str(ckpt_dir), time_fn=clock.now)
    coord = SpotOnCoordinator(store, CheckpointPolicy.transparent(100.0),
                              clock)
    cfg = get_smoke_config("phi3_mini_3p8b")
    job = TrainJob(cfg=cfg, opt=AdamWConfig(total_steps=STEPS),
                   total_steps=STEPS, n_stages=1, batch=2, seq_len=16)
    trainer = SpotTrainer(job, coord, pool, clock, step_time_s=10.0)
    report = trainer.run()
    coord.close()
    return report, coord


@dataclasses.dataclass
class Span:
    name: str
    line: int           # one line of the host plane per thread
    start: int
    end: int
    args: dict

    def inside(self, other: "Span") -> bool:
        return other.start <= self.start and self.end <= other.end


def _read_spans(trace_dir) -> list[Span]:
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    with open(path, "rb") as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("spoton."):
                    out.append(Span(e.name, i, int(e.start_ns),
                                    int(e.end_ns), dict(e.stats)))
    return out


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    root = tmp_path_factory.mktemp("spans")
    plain = _run(root / "plain")
    trace_dir = str(root / "trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        traced = _run(root / "traced")
    finally:
        jax.profiler.stop_trace()
    return plain, traced, _read_spans(trace_dir)


def _named(spans, name):
    return [s for s in spans if s.name == name]


def test_every_span_appears(runs):
    *_, spans = runs
    names = {s.name for s in spans}
    assert set(TRAINER + WRITER + WORKERS) <= names
    assert len(_named(spans, "spoton.run")) == 1
    assert len(_named(spans, "spoton.step")) == STEPS
    extracts = _named(spans, "spoton.save.extract")
    writes = _named(spans, "spoton.save.write")
    assert [s.args for s in extracts] == [
        {"step": 10, "kind": "periodic"}, {"step": 20, "kind": "periodic"}]
    assert [s.args for s in writes] == [s.args for s in extracts]


def test_trainer_spans_nest_on_one_thread(runs):
    *_, spans = runs
    run, = _named(spans, "spoton.run")
    trainer = [s for s in spans if s.name in TRAINER]
    assert {s.line for s in trainer} == {run.line}
    steps = _named(spans, "spoton.step")
    hooks = _named(spans, "spoton.step.hook")
    extracts = _named(spans, "spoton.save.extract")
    for s in steps + _named(spans, "spoton.flush"):
        assert s.inside(run)
    flush, = _named(spans, "spoton.flush")
    assert flush.start >= steps[-1].end
    for name in STEP_PARTS:
        assert all(any(s.inside(st) for st in steps)
                   for s in _named(spans, name))
    for e in extracts:
        assert any(e.inside(h) for h in hooks)
    for name in EXTRACT_PARTS:
        found = _named(spans, name)
        assert found and all(any(s.inside(e) for e in extracts)
                             for s in found)


def test_step_parts_tile_each_iteration(runs):
    *_, spans = runs
    steps = _named(spans, "spoton.step")
    parts = sorted((s for s in spans if s.name in STEP_PARTS),
                   key=lambda s: s.start)
    uncovered = 0
    for st in steps:
        mine = [p for p in parts if p.inside(st)]
        assert [p.name for p in mine] == list(STEP_PARTS)
        for a, b in zip(mine, mine[1:]):
            assert a.end <= b.start
        uncovered += (st.end - st.start) - sum(p.end - p.start
                                               for p in mine)
    # what lies between the parts is bookkeeping: the pool's tick, the
    # step's clock charge
    total = sum(st.end - st.start for st in steps)
    assert uncovered < 0.1 * total


def test_writer_and_codec_spans_run_off_the_trainer_thread(runs):
    *_, spans = runs
    run, = _named(spans, "spoton.run")
    for name in WRITER + WORKERS:
        assert all(s.line != run.line for s in _named(spans, name)), name
    writes = _named(spans, "spoton.save.write")
    for name in ("spoton.save.manifest", "spoton.save.commit"):
        found = _named(spans, name)
        assert len(found) == 2
        assert all(any(s.inside(w) and s.line == w.line for w in writes)
                   for s in found)


def test_each_chunk_write_lies_inside_an_encode_job(runs):
    *_, spans = runs
    encodes = _named(spans, "spoton.save.encode")
    writes = _named(spans, "spoton.save.pool_write")
    assert writes
    for w in writes:
        assert any(w.inside(e) and w.line == e.line for e in encodes)


def test_spans_change_nothing_the_run_computes(runs):
    (plain, plain_coord), (traced, traced_coord), _ = runs
    assert plain.completed and traced.completed
    assert plain.final_loss == traced.final_loss
    a = dataclasses.asdict(plain_coord.stats)
    b = dataclasses.asdict(traced_coord.stats)
    # the extract's stall is a wall time; every count and byte is equal
    a.pop("save_stall_s")
    b.pop("save_stall_s")
    assert a == b
    assert a["periodic_ckpts"] == 2 and a["periodic_failures"] == 0
    assert (plain_coord.store.committed_steps()
            == traced_coord.store.committed_steps() == [10, 20])


def test_span_is_a_plain_annotation_that_starts_no_thread():
    import threading

    threads = threading.active_count()
    s = span("test.outside_any_trace", step=1, kind="periodic")
    assert isinstance(s, jax.profiler.TraceAnnotation)
    with s:
        pass
    assert threading.active_count() == threads
