"""The program's own spans in a reduced trace, and the device's idle time
split by what the trainer was doing.

The program opens named spans on the profiler's clock
(``repro.core.ledger.span``): ``spoton.run`` around the trainer's run,
``spoton.step`` and its parts per step, the save's extract on the trainer
thread, and the save's write, encode and chunk writes on the writer thread
and the codec workers. They land on the host planes, which
``trace.reduce_file`` keeps in ``Trace.host`` as ``(name, start_ns,
dur_ns)``, without the thread. The trainer thread's spans are told apart by
name. Only spans inside the ``spoton.run`` interval of a chip's trace are
read: without one, or without a chip's plane (a CPU run), every function
here returns None, as on a program that opens no spans.
"""

from __future__ import annotations

from .trace import merge

RUN = "spoton.run"
# the spans the trainer thread opens, outermost first; a save's writer and
# codec spans (spoton.save.write, .manifest, .commit, .encode, .pool_write)
# run on other threads and never name the trainer's idle time
TRAINER = ("spoton.run", "spoton.flush", "spoton.step", "spoton.step.batch",
           "spoton.step.dispatch", "spoton.step.wait", "spoton.step.hook",
           "spoton.save.extract", "spoton.save.prestage",
           "spoton.save.diff_wait", "spoton.save.d2h", "spoton.save.enqueue")


def run_interval(trace) -> tuple[int, int] | None:
    """(start, end) ns of the longest ``spoton.run`` span of a trace with a
    chip's plane, or None."""
    if trace is None or not trace.devices:
        return None
    runs = [(dur, start) for name, start, dur in trace.host if name == RUN]
    if not runs:
        return None
    dur, start = max(runs)
    return start, start + dur


def spans(trace, name: str) -> list[tuple[int, int]] | None:
    """(start, end) ns of the spans called ``name`` inside the run, or None
    when the trace has no ``spoton.run``."""
    run = run_interval(trace)
    if run is None:
        return None
    lo, hi = run
    return [(s, s + d) for n, s, d in trace.host
            if n == name and s >= lo and s + d <= hi]


def total_s(trace, name: str) -> float | None:
    """Summed seconds of the spans called ``name`` inside the run, or None
    when there are none."""
    found = spans(trace, name)
    if not found:
        return None
    return sum(hi - lo for lo, hi in found) / 1e9


def mean_s(trace, name: str) -> float | None:
    """Total seconds of the spans called ``name`` inside the run over their
    count, or None when there are none."""
    found = spans(trace, name)
    if not found:
        return None
    return sum(hi - lo for lo, hi in found) / len(found) / 1e9


def _bucket(name: str) -> str:
    if name.startswith("spoton.save."):
        return "save"
    if name.startswith("spoton.step"):
        return "step"
    return "outside"                  # spoton.run, spoton.flush


def idle_split(trace) -> dict | None:
    """Percent of the ``spoton.run`` interval in which the first chip ran
    no operation, split by the innermost trainer span open at that moment:
    ``{"save": ..., "step": ..., "outside": ...}``. Their sum is the idle
    share of the run. None without a run span or a device."""
    run = run_interval(trace)
    if run is None:
        return None
    lo, hi = run
    dev = trace.devices[sorted(trace.devices)[0]]
    idle = []
    t = lo
    for b_lo, b_hi in merge(dev.ops):
        if b_hi <= t:
            continue
        if b_lo >= hi:
            break
        if b_lo > t:
            idle.append((t, b_lo))
        t = max(t, b_hi)
    if t < hi:
        idle.append((t, hi))
    # the trainer's spans cut the run into segments, each with one
    # innermost span: the open span that started last (the shorter on a tie)
    trainer = [(s, s + d, n) for n, s, d in trace.host
               if n in TRAINER and s >= lo and s + d <= hi]
    cuts = sorted({lo, hi} | {x for s, e, _n in trainer for x in (s, e)})
    trainer.sort()
    segments = []                     # (start, end, bucket)
    open_: list = []
    k = 0
    for a, b in zip(cuts, cuts[1:]):
        while k < len(trainer) and trainer[k][0] <= a:
            open_.append(trainer[k])
            k += 1
        open_ = [sp for sp in open_ if sp[1] > a]
        inner = max(open_, key=lambda sp: (sp[0], -sp[1]))
        segments.append((a, b, _bucket(inner[2])))
    out = {"save": 0, "step": 0, "outside": 0}
    j = 0
    for i_lo, i_hi in idle:
        while segments[j][1] <= i_lo:
            j += 1
        m = j
        while m < len(segments) and segments[m][0] < i_hi:
            s_lo, s_hi, bucket = segments[m]
            out[bucket] += min(i_hi, s_hi) - max(i_lo, s_lo)
            m += 1
    length = hi - lo
    return {k: 100.0 * v / length for k, v in out.items()}
