"""What a traced run hands the per-layer metric readers, and the loop that
asks each reader of the cell for its number."""

from __future__ import annotations

import os
import shutil
from dataclasses import dataclass, field

from . import spec


@dataclass
class RunRecord:
    cell: spec.Cell
    window_s: float            # host seconds of the traced window
    work: dict = field(default_factory=dict)       # program module's work()
    peaks: dict = field(default_factory=dict)
    trace: object = None       # harness.trace.Trace, or None
    observed: dict = field(default_factory=dict)   # program spans, by name
    fingerprint_bytes: int = 0
    spans: dict = field(default_factory=dict)      # the harness's own spans


def per_layer(rec: RunRecord) -> dict:
    """{name: {"value", "unit"}} of every per-layer metric of the cell whose
    reader found something to read."""
    out = {}
    for m in rec.cell.per_layer:
        value = spec.layer_reader(m["name"])(rec)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def trace_dir(root: str, cell: spec.Cell) -> str:
    return os.path.join(root, ".bench_trace", cell.name)


def start_trace(path: str) -> None:
    """Profile the window: device operations and the runtime's host spans,
    without Python's, which would slow the host loop the window times."""
    import jax

    shutil.rmtree(path, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(path, profiler_options=opts)


def traced_result(result: dict, device: dict, path: str, *, cell: spec.Cell,
                  window_s: float, **record) -> dict:
    """Fill a traced run's result: its per-layer metrics, the device's busy
    and window seconds, and the breakdown; the trace is then removed."""
    from .peaks import peaks_for
    from .trace import find_xplane, reduce_file

    xplane = find_xplane(path)
    trace = reduce_file(xplane) if xplane else None
    shutil.rmtree(path, ignore_errors=True)
    if trace:
        print(f"trace: devices={sorted(trace.devices)} "
              f"programs={trace.top_modules(40)}", flush=True)
    rec = RunRecord(cell=cell, window_s=window_s,
                    peaks=peaks_for(device["kind"]), trace=trace, **record)
    result["metrics"] = per_layer(rec)
    device["busy_s"] = trace.busy_s() if trace else 0.0
    device["window_s"] = window_s
    result["device"] = device
    if trace:
        result["breakdown"] = {"device_ops": trace.top_ops(10),
                               "idle_gaps": trace.idle_gaps(10)}
    return result
