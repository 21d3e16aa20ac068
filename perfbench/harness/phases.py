"""Device time by phase of the entropy stage, and device idle time by host
span, from the program's own marks on the profiler's clock.

The program (``core/api.py``) marks each batch with host spans named
``repro.*`` (``jax.profiler.TraceAnnotation``), each carrying the batch's
id as ``batch`` and its counters as arguments: ``repro.from_bytes`` over
parse, plan, pad and upload; ``repro.decode`` over the entropy stage's
dispatch (``s_max`` among its arguments), the host's wait for its rounds
(``rounds``) and the pixel stage's dispatch. Inside the entropy program two
named scopes, ``repro.entropy.sync`` and ``repro.entropy.write``, reach
the compiled HLO's metadata; ``DecodeProgram.device_phases()`` maps its
entry-level instructions to them. An entry-level instruction's device
time covers every operation nested in it, and names are unique within a
module, so a phase's time is the sum of its instructions' events inside
each ``jit__coeffs`` execution, with nothing counted twice.

The trace reduction (``trace.py``) keeps only the benchmark's own spans, so
the program's spans are read here from the same ``.xplane.pb``: the one in
a ``bench-trace-*`` directory of the run's temporary directory whose
``bench.window`` is the reduction's window to the nanosecond. Where a piece
does not match (no such file, no program spans, a program with no phase
map, maps that disagree, an execution without both phases, executions and
batches of different counts) the reading is None: nothing is guessed.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import tempfile
from typing import Dict, List, Optional, Tuple

from .trace import WINDOW_SPAN, Reduced, _union

PREFIX = "repro."
SYNC, WRITE = "repro.entropy.sync", "repro.entropy.write"
ENTROPY_MODULE = "jit__coeffs"
TRACE_DIRS = "bench-trace-*"          # cell.py's tempfile.mkdtemp prefix

Span = Tuple[str, float, float, dict]  # (name, start_s, end_s, arguments)


@dataclasses.dataclass
class ProgramTrace:
    spans: List[Span]                 # the program's repro.* host spans
    phases: Optional[Dict[str, str]]  # entry-level HLO name -> phase


def program_trace(ctx) -> Optional[ProgramTrace]:
    """The program's spans and phase map for the run whose reduction is
    ``ctx["trace"]``; read once and kept in ``ctx``, which every reader of
    a run shares."""
    if "program" not in ctx:
        spans = find_spans(ctx["trace"].window)
        ctx["program"] = ProgramTrace(spans, program_phases()) if spans else None
    return ctx["program"]


def read_spans(path: str) -> Tuple[Optional[Tuple[float, float]], List[Span]]:
    """(the ``bench.window`` span's interval or None, the ``repro.*`` host
    spans) of one ``.xplane.pb``, on the clock ``trace.reduce_xplane``
    uses."""
    from jax.profiler import ProfileData
    window, spans = None, []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if name.startswith(PREFIX):
                    spans.append((name, ev.start_ns * 1e-9,
                                  (ev.start_ns + ev.duration_ns) * 1e-9,
                                  dict(ev.stats)))
                elif name == WINDOW_SPAN:
                    window = (ev.start_ns * 1e-9,
                              (ev.start_ns + ev.duration_ns) * 1e-9)
    return window, spans


def find_spans(window: Tuple[float, float]) -> List[Span]:
    """The program's spans from the trace file whose ``bench.window`` is
    ``window``; [] where no file matches."""
    pattern = os.path.join(tempfile.gettempdir(), TRACE_DIRS, "**", "*.xplane.pb")
    for path in glob.glob(pattern, recursive=True):
        found, spans = read_spans(path)
        if found == tuple(window):
            return spans
    return []


def program_phases() -> Optional[Dict[str, str]]:
    """The phase map of every entropy program this process ran, merged;
    None where there is none or two disagree on a name."""
    from repro.core import api
    merged: Dict[str, str] = {}
    for prog in api.decode_programs():
        phases = getattr(prog, "device_phases", None)
        for name, phase in (phases() if phases else {}).items():
            if merged.setdefault(name, phase) != phase:
                return None
    return merged or None


def phase_seconds(red: Reduced, phases: Dict[str, str]):
    """({phase: device seconds, the mean over devices}, executions of the
    entropy program per device), or None where an execution lacks either
    phase or the devices ran different counts."""
    totals: Dict[str, float] = {SYNC: 0.0, WRITE: 0.0}
    counts = set()
    for dev in red.devices:
        runs = sorted((s, e) for n, s, e in dev.modules
                      if n.split("(")[0] == ENTROPY_MODULE)
        starts = [s for s, _ in runs]
        per_run = [dict.fromkeys(totals, 0.0) for _ in runs]
        for n, s, e in dev.ops:
            phase = phases.get(n.partition(" = ")[0].lstrip("%"))
            i = bisect.bisect_right(starts, s) - 1
            if phase and i >= 0 and s <= runs[i][1]:
                per_run[i][phase] += e - s
        if any(not all(r.values()) for r in per_run):
            return None
        for r in per_run:
            for phase, t in r.items():
                totals[phase] += t / len(red.devices)
        counts.add(len(runs))
    if len(counts) != 1:
        return None
    return totals, counts.pop()


def batches(red: Reduced, spans: List[Span]) -> Dict[int, dict]:
    """Per batch id whose entropy dispatch and wait for rounds both lie in
    the window: the arguments of the two spans, merged."""
    w0, w1 = red.window
    out: Dict[int, Dict[str, dict]] = {}
    for name, s, e, args in spans:
        if name in ("repro.dispatch.entropy", "repro.rounds") and w0 <= s and e <= w1:
            out.setdefault(args["batch"], {})[name] = args
    return {b: {**d["repro.dispatch.entropy"], **d["repro.rounds"]}
            for b, d in out.items() if len(d) == 2}


def entropy(ctx):
    """(phase seconds, the window's batches) where the executions of the
    entropy program and the batches match one to one; else None."""
    program = program_trace(ctx)
    if program is None or not program.phases:
        return None
    found = phase_seconds(ctx["trace"], program.phases)
    if found is None:
        return None
    seconds, runs = found
    per_batch = list(batches(ctx["trace"], program.spans).values())
    if runs == 0 or runs != len(per_batch):
        return None
    return seconds, per_batch


def idle_under_pct(ctx, span: str):
    """Percent of the window the devices were idle while the host was
    inside a ``span`` (and so in it or a span nested in it), the mean over
    devices; None where the trace holds no such span."""
    program = program_trace(ctx)
    if program is None:
        return None
    red = ctx["trace"]
    host = _union([(n, s, e) for n, s, e, _ in program.spans if n == span])
    if not host:
        return None
    w0, w1 = red.window
    idle_s = 0.0
    for dev in red.devices:
        busy = _union(dev.ops)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        idle_s += _overlap(idle, host) / len(red.devices)
    return 100.0 * idle_s / red.window_s


def _overlap(a, b) -> float:
    """Total length of the intersection of two sorted disjoint interval
    lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0.0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total
