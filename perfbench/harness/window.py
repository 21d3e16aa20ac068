"""What every traffic driver shares: the answers it keeps, the window it
hands back, the compile log, the tag each input carries, and the
profiler's spans.

A driver is ``drivers/<name>.py``, named by its mix's ``driver`` key
(``traffic/<mix>.json``), with a function

    run(cell, blobs, seed, seconds, trace_dir, compiles, t_setup0) -> Window

that warms every shape its window will use (``setup_s`` ends there), then
measures for ``seconds``, under the profiler when ``trace_dir`` is set.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional


@dataclasses.dataclass
class Sample:
    """One answer of the window, kept for the comparison."""
    blob: bytes
    coeffs: object          # program's coefficients (device or host array) or None
    rgb: object             # program's RGB, (H, W, 3), or (H, W) for grayscale
    missing: bool = False   # the answer never came
    # (top, left, height, width, out_height, out_width): the answer is that
    # box of the frame resized to (out_height, out_width, 3); None: the frame
    crop: Optional[tuple] = None


@dataclasses.dataclass
class Window:
    seconds: float                  # elapsed, first timed call to the last answer
    attempted: int
    failed: int
    samples: List[Sample]
    counters: dict                  # per-layer readings taken by the driver
    lines: List[str]                # what ran, for the earlier lines
    e2e: dict = dataclasses.field(default_factory=dict)


class CompileLog:
    """XLA compiles and persistent-cache loads, from JAX's monitoring
    events (a load from the cache counts as a compile here)."""

    def __init__(self):
        import jax
        self.count = 0
        self.seconds = 0.0
        self.hits = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += secs


def annotate(name):
    """A host span in the profiler's trace (``bench.*``)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def start_trace(trace_dir):
    """Start the profiler when ``trace_dir`` is set; returns a stop()."""
    import jax
    if trace_dir is None:
        return lambda: None
    jax.profiler.start_trace(trace_dir)
    return jax.profiler.stop_trace


def tag(seed: int, n: int) -> str:
    """The comment every input of a run carries, unique to its pass or
    request, of one length in every run."""
    return f"bench run {seed:012d} input {n:08d}"
