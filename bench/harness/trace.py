"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read: per-device operation and program intervals, the union of the
device's busy time, the operations that took most time, and the longest
idle gaps named by what the host was doing in them.

A TPU trace holds one plane per chip (``/device:TPU:<n>``). Its line
``XLA Ops`` has one event per HLO operation and ``XLA Modules`` one per
program execution, named after the jitted function (``jit_train_step``).
Host planes (``/host:...``) hold the runtime's and the benchmark's spans.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass
class Device:
    ops: list = field(default_factory=list)       # (name, start_ns, dur_ns)
    modules: list = field(default_factory=list)   # (name, start_ns, dur_ns)

    def busy_ns(self) -> int:
        return sum(hi - lo for lo, hi in merge(self.ops))


@dataclass
class Trace:
    devices: dict                                  # plane name -> Device
    host: list                                     # (name, start_ns, dur_ns)

    def busy_s(self) -> float:
        """Busy seconds averaged over the chips in the trace."""
        if not self.devices:
            return 0.0
        return (sum(d.busy_ns() for d in self.devices.values())
                / len(self.devices) / 1e9)

    def module_time_s(self, match) -> tuple[float, int]:
        """Seconds and executions of the programs whose name ``match``
        accepts, averaged over chips."""
        secs = n = 0
        for d in self.devices.values():
            for name, _s, dur in d.modules:
                if match(name):
                    secs += dur
                    n += 1
        k = max(len(self.devices), 1)
        return secs / k / 1e9, n // k

    def op_time_s(self, match) -> tuple[float, int]:
        """Self seconds and executions of the operations whose short name
        (``short_op_name``) ``match`` accepts, as ``top_ops`` sums them,
        averaged over chips. A kernel inside a program is an operation of
        it (a custom call), seen here and not by ``module_time_s``."""
        secs = n = 0
        for d in self.devices.values():
            for name, self_ns in self_times(d.ops):
                if match(short_op_name(name)):
                    secs += self_ns
                    n += 1
        k = max(len(self.devices), 1)
        return secs / k / 1e9, n // k

    def top_modules(self, k: int = 10) -> list:
        """[[name, seconds, executions]] of the programs with most device
        time, averaged over chips."""
        tot: dict[str, list] = {}
        for d in self.devices.values():
            for name, _s, dur in d.modules:
                t = tot.setdefault(name, [0, 0])
                t[0] += dur
                t[1] += 1
        n = max(len(self.devices), 1)
        top = sorted(tot.items(), key=lambda kv: -kv[1][0])[:k]
        return [[name, t / n / 1e9, c // n] for name, (t, c) in top]

    def top_ops(self, k: int = 10) -> list:
        """[[name, seconds]] of the operations with most device self time
        (an operation that holds others, such as a loop, less the time of
        the operations inside it), summed over their executions and
        averaged over chips. Names are the HLO name and opcode."""
        tot: dict[str, int] = {}
        for d in self.devices.values():
            for name, self_ns in self_times(d.ops):
                short = short_op_name(name)
                tot[short] = tot.get(short, 0) + self_ns
        n = max(len(self.devices), 1)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[name, dur / n / 1e9] for name, dur in top]

    def idle_gaps(self, k: int = 10) -> list:
        """[[name, seconds]] of the longest gaps between device operations
        on the first chip, each named by the host span that overlaps it
        most (the shorter one on a tie) and the programs either side."""
        if not self.devices:
            return []
        dev = self.devices[sorted(self.devices)[0]]
        spans = merge(dev.modules) or merge(dev.ops)
        gaps = []
        for (lo0, hi0), (lo1, _hi1) in zip(spans, spans[1:]):
            gaps.append((lo1 - hi0, hi0, lo1))
        gaps.sort(reverse=True)
        out = []
        for length, lo, hi in gaps[:k]:
            best = None
            for name, start, dur in self.host:
                ov = min(hi, start + dur) - max(lo, start)
                if ov <= 0:
                    continue
                key = (ov, -dur)
                if best is None or key > best[0]:
                    best = (key, name)
            host = best[1] if best else "no host span"
            prev = _module_ending_at(dev.modules, lo)
            out.append([f"after {prev}: host {host}"[:160], length / 1e9])
        return out


_HLO = re.compile(r"%?([\w.\-]+) = .*?\s([a-z][\w\-]*)\(")


def short_op_name(hlo: str) -> str:
    """``"%fusion.3 = bf16[8]{0} fusion(...)"`` -> ``"fusion.3 (fusion)"``."""
    m = _HLO.match(hlo)
    return f"{m.group(1)} ({m.group(2)})" if m else hlo[:80]


def self_times(events) -> list:
    """(name, self ns) of nested (name, start, dur) events: each event's
    duration less that of the events directly inside it."""
    order = sorted(events, key=lambda e: (e[1], -e[2]))
    out = []
    stack: list = []          # [end, index into out]
    for name, start, dur in order:
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= min(dur, stack[-1][0] - start)
        out.append([name, dur])
        stack.append([start + dur, len(out) - 1])
    return [(n, max(t, 0)) for n, t in out]


def _module_ending_at(modules, t) -> str:
    best = None
    for name, start, dur in modules:
        end = start + dur
        if end <= t and (best is None or end > best[0]):
            best = (end, name)
    return best[1] if best else "start"


def merge(events) -> list:
    """Union of (name, start, dur) intervals as sorted (lo, hi) pairs."""
    spans = sorted((s, s + d) for _n, s, d in events if d > 0)
    out: list = []
    for lo, hi in spans:
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def find_xplane(log_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def reduce_file(path: str) -> Trace:
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    devices: dict[str, Device] = {}
    host: list = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = devices.setdefault(plane.name, Device())
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev.ops.extend((e.name, int(e.start_ns), int(e.duration_ns))
                                   for e in line.events)
                elif line.name == MODULES_LINE:
                    dev.modules.extend((e.name, int(e.start_ns),
                                        int(e.duration_ns))
                                       for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, int(e.start_ns), int(e.duration_ns))
                            for e in line.events)
    devices = {k: v for k, v in devices.items() if v.ops or v.modules}
    return Trace(devices=devices, host=host)
