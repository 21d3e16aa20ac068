"""One run of one cell: set up, measure, check, print.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (``setup_s``, from process start to the first timed call): the
configuration's fixed corpus is encoded in a pool of worker processes (or
read from ``.bench_datasets/``), JAX starts on the chip, and every program the
window will run is compiled or loaded from the persistent cache in
``.jax_cache/``. Then the mix's driver (``drivers/<name>.py``) measures
for ``--seconds``; with ``--trace 1`` under the profiler, whose trace gives the per-layer metrics.
Last, the answers kept from the window are compared with the plain
reference in the worker pool.

The earlier lines of standard output say what ran. The last line of
standard output is one JSON object; the last lines of standard error are
the compared numbers with their limits.
"""
from __future__ import annotations

import argparse
import json
import multiprocessing
import os
import shutil
import sys
import tempfile
import time

from . import check, corpus, spec, trace
from .peaks import peaks
from .window import CompileLog


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def parse_args(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def start_jax(chips: int, require_tpu: bool = True):
    """The devices the cell runs on. On the chip, programs are kept in a
    persistent cache at a fixed path in the checkout, with no size bound,
    so that no entry is ever evicted and only a checkout's first run
    compiles."""
    import jax
    if require_tpu:
        # set before the first compile; JAX makes the directory on first use
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(spec.ROOT, ".jax_cache"))
        jax.config.update("jax_compilation_cache_max_size", -1)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"no TPU found (JAX's platform is {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devices)}")
    return devices[:chips]


def pool_size() -> int:
    return max(1, min(12, (os.cpu_count() or 2) - 1))


def make_pool(size: int = 0):
    """The worker pool: spawned, so that no worker shares the parent's JAX,
    with one BLAS thread per worker (the workers inherit the environment
    and read it when numpy loads), so that a pool as wide as the host
    neither oversubscribes its cores nor holds a thread's buffers per core."""
    for var in ("OPENBLAS_NUM_THREADS", "OMP_NUM_THREADS", "MKL_NUM_THREADS"):
        os.environ[var] = "1"
    return multiprocessing.get_context("spawn").Pool(size or pool_size())


def run(cell: spec.Cell, seed: int, seconds: float, traced: bool, t_start: float,
        pool, require_tpu: bool = True, control=None,
        out=sys.stdout):
    """One run of ``cell``: (the result line's object, the compared numbers
    with their limits, the numbers in full, the answers compared)."""
    def say(msg):
        print(msg, file=out, flush=True)

    resize = check.answers_spec(cell.config)
    pending = corpus.start(cell.config, pool)
    devices = start_jax(cell.chips, require_tpu)
    kind = devices[0].device_kind
    if require_tpu:
        peaks(kind)                  # an unknown chip fails before any work
    log = CompileLog()
    t_jax = time.perf_counter() - t_start
    blobs = pending.get()
    say(f"device: {len(devices)} x {kind} ({devices[0].platform}), ready at "
        f"{t_jax:.1f} s; run seed {seed}; corpus {cell.config['name']}: {len(blobs)} "
        f"images, {sum(map(len, blobs))} bytes, {pending.how}, ready at "
        f"{time.perf_counter() - t_start:.1f} s")
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    try:
        if traced:
            seconds = min(seconds, cell.traffic["trace_seconds"])
        win = spec.load_driver(cell.traffic["driver"])(
            cell, blobs, seed, seconds, trace_dir, log, t_start)
        say(f"compiles before the window: {log.count - win.counters['compiles']} "
            f"({log.seconds:.1f} s compiling or loading), persistent-cache hits "
            f"{log.hits}")
        for line in win.lines:
            say(line)
        stats = [d.memory_stats() or {} for d in devices]
        peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
        say(f"memory: peak_bytes_in_use {peak} on the fullest chip "
            f"({[s.get('peak_bytes_in_use') for s in stats]})")
        device = {"platform": devices[0].platform, "kind": kind,
                  "count": len(devices), "memory_peak_bytes": int(peak)}
        metrics, breakdown = {}, None
        if traced:
            red = trace.reduce_xplane(trace.find_xplane(trace_dir))
            ctx = {"trace": red, "counters": win.counters, "cell": cell,
                   "device_kind": kind, "window": win}
            for m in cell.per_layer:
                value = spec.load_reader(m["name"])(ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            device["busy_s"] = red.mean_busy_s()
            device["window_s"] = red.window_s
            breakdown = {"device_ops": red.top_ops(10),
                         "idle_gaps": red.idle_gaps(10)}
            say(f"trace: window {red.window_s:.6f} s, busy {device['busy_s']:.6f} s "
                f"(mean of {len(red.devices)} device(s)); programs "
                f"{red.top_modules(5)}")
        else:
            values = dict(win.e2e, setup_s=win.counters["setup_s"])
            for m in cell.end_to_end:
                metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    say(f"setup_s {win.counters['setup_s']:.3f}; window {win.seconds:.3f} s")
    samples, attempted, failed = win.samples, win.attempted, win.failed
    del win
    t0 = time.perf_counter()
    numbers = check.compare(samples, pool, control=control, resize=resize)
    correct, checks = check.verdict(numbers, cell.config["limits"])
    say(f"check: {numbers['images']} answers against the reference in "
        f"{time.perf_counter() - t0:.1f} s; largest RGB difference "
        f"{numbers['rgb_max']}{f' (control {control})' if control else ''}")
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown:
        result["breakdown"] = breakdown
    return result, checks, numbers, samples


def main(argv, t_start: float) -> int:
    args = parse_args(argv)
    try:
        cell = spec.resolve(spec.load_benchmark(), args.workload)
    except (OSError, KeyError, ValueError) as e:
        print(f"perfbench: no such cell or its files are missing: {e!r}",
              file=sys.stderr)
        return 2
    try:
        import repro  # noqa: F401  (the system under test, from src/)
    except ImportError as e:
        print(f"perfbench: cannot import the decoder: {e}", file=sys.stderr)
        return 2
    with make_pool() as pool:
        try:
            result, checks, _, _ = run(cell, args.seed, args.seconds,
                                    bool(args.trace), t_start, pool)
        except NoChip as e:
            print(f"perfbench: {e}", file=sys.stderr)
            return 1
        pool.close()
        pool.join()
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0
