"""Reduction of a profiler trace (``.xplane.pb``) to device time.

The benchmark brackets what it traces with host spans of its own
(``jax.profiler.TraceAnnotation``): one ``bench.window`` around the traced
window, and ``bench.<part>`` spans around each call into the program. From
the device planes it takes every operation (the ``XLA Ops`` line) and every
program execution (the ``XLA Modules`` line), clipped to the window:

- busy time: the union of the operation intervals, per device;
- time per program and per operation, by the names the trace gives;
- idle gaps: the holes in the busy union, each named after the innermost
  ``bench.*`` host span that covers its midpoint.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
# ops that only contain others: their time is their children's
CONTAINER = re.compile(r"\s(while|conditional|call)\(")
OPCODE = re.compile(r"\s([a-z][a-z0-9_-]*)\(")


@dataclasses.dataclass
class DeviceTrace:
    name: str
    ops: List[Tuple[str, float, float]]       # (name, start_s, end_s)
    modules: List[Tuple[str, float, float]]


@dataclasses.dataclass
class Reduced:
    window: Tuple[float, float]               # (start_s, end_s), host clock
    devices: List[DeviceTrace]
    spans: List[Tuple[str, float, float]]     # bench.* host spans

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy_s(self, dev: DeviceTrace) -> float:
        return sum(e - s for s, e in _union(dev.ops))

    def mean_busy_s(self) -> float:
        return sum(self.busy_s(d) for d in self.devices) / len(self.devices)

    def idle_pct(self) -> float:
        """1 - busy / window, in percent, the mean over devices."""
        return 100.0 * (1.0 - self.mean_busy_s() / self.window_s)

    def module_s(self, pattern: str) -> float:
        """Device seconds of the programs whose name matches ``pattern``,
        the mean over devices."""
        rx = re.compile(pattern)
        return sum(e - s for d in self.devices for n, s, e in d.modules
                   if rx.search(n)) / len(self.devices)

    def module_count(self, pattern: str) -> int:
        rx = re.compile(pattern)
        return max(sum(1 for n, _, _ in d.modules if rx.search(n))
                   for d in self.devices)

    def top_ops(self, k: int = 10) -> List[List]:
        """The ``k`` operations with the most device time (mean over
        devices), as [name, seconds], loops and calls left out (their
        time is that of the operations inside them)."""
        per_name: Dict[str, float] = {}
        for d in self.devices:
            for n, s, e in d.ops:
                per_name[n] = per_name.get(n, 0.0) + (e - s) / len(self.devices)
        total: Dict[str, float] = {}
        for n, t in per_name.items():
            if not CONTAINER.search(n):
                total[short_name(n)] = total.get(short_name(n), 0.0) + t
        return [[n, t] for n, t in sorted(total.items(), key=lambda x: -x[1])[:k]]

    def top_modules(self, k: int = 10) -> List[List]:
        total: Dict[str, float] = {}
        for d in self.devices:
            for n, s, e in d.modules:
                total[n] = total.get(n, 0.0) + (e - s) / len(self.devices)
        return [[n, t] for n, t in sorted(total.items(), key=lambda x: -x[1])[:k]]

    def idle_gaps(self, k: int = 10) -> List[List]:
        """The ``k`` longest gaps on the first device in which no operation
        ran, as [host span covering the gap, seconds]."""
        busy = _union(self.devices[0].ops)
        edges = [self.window[0]] + [x for iv in busy for x in iv] + [self.window[1]]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[self.span_at((s + e) / 2), e - s] for s, e in gaps[:k]]

    def span_at(self, t: float) -> str:
        best = None
        for n, s, e in self.spans:
            if s <= t <= e and n != WINDOW_SPAN and (best is None or e - s < best[1]):
                best = (n, e - s)
        return best[0] if best else WINDOW_SPAN


def short_name(hlo: str) -> str:
    """``%fusion.166 = s32[...] fusion(...), kind=kCustom`` -> ``%fusion.166
    fusion``: the instruction's name and opcode, as the trace gives them."""
    name, _, rest = hlo.partition(" = ")
    m = OPCODE.search(" " + rest)
    return f"{name} {m.group(1)}" if m else name


def _union(intervals) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for _, s, e in sorted(intervals, key=lambda x: x[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, "
                                f"found {len(files)}")
    return files[0]


def reduce_xplane(path: str) -> Reduced:
    """Read ``path`` and clip every device event to the ``bench.window``
    span of the host."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            ops, modules = [], []
            for line in plane.lines:
                into = {"XLA Ops": ops, "XLA Modules": modules}.get(line.name)
                if into is None:
                    continue
                for ev in line.events:
                    into.append((ev.name, ev.start_ns * 1e-9,
                                 (ev.start_ns + ev.duration_ns) * 1e-9))
            if ops or modules:
                devices.append(DeviceTrace(plane.name, ops, modules))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, ev.start_ns * 1e-9,
                                      (ev.start_ns + ev.duration_ns) * 1e-9))
    windows = [s for s in spans if s[0] == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"trace holds {len(windows)} {WINDOW_SPAN} spans, not 1")
    if not devices:
        raise ValueError("trace holds no device operations")
    w0, w1 = windows[0][1], windows[0][2]

    def clip(evs):
        return [(n, max(s, w0), min(e, w1)) for n, s, e in evs if e > w0 and s < w1]

    devices = [DeviceTrace(d.name, clip(d.ops), clip(d.modules))
               for d in sorted(devices, key=lambda d: d.name)]
    return Reduced((w0, w1), devices, spans)
