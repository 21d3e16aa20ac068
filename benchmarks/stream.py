"""Beyond-paper: steady-state streaming decode throughput (plan buckets).

The deployment the ROADMAP's north star describes is a *stream*: every
training/serving step decodes a fresh, content-distinct batch. Before the
PlanShape/PlanData split, each fresh batch baked its words into a new
jitted closure — one XLA compilation per step, thousands of times the
decode cost. This suite measures the streaming behavior directly:

* ``stream/bucketed`` — decode ``N_BATCHES`` distinct batches through one
  ``JpegVisionPipeline`` with capacity bucketing on (the default).
  ``us_per_call`` is the *median warm step* (decode + patch embed, post
  compile); derived fields report the cold (compiling) step, the number of
  compiles per 100 batches (the compile-once target is <= the number of
  capacity buckets the stream spans, independent of N), and the buckets.
* ``stream/unbucketed`` — the same stream with ``bucket=False`` (exact-fit
  shapes, the pre-split behavior) over fewer batches: every distinct batch
  shape compiles, so compiles-per-100 sits near 100 and the "warm" step is
  dominated by retracing.

* ``stream/multihost/hN`` (``--hosts N`` CLI mode only) — the same stream
  fed per host: N localhost ``jax.distributed`` processes each stream
  their contiguous slice of the batches through their own pipeline, and
  the parent reports every host's warm-step ms and compile count
  separately (summing would hide a host stuck recompiling — see
  ``JpegVisionPipeline.decode_stats``).

Rows fold into the BENCH_JSON artifact in CI; the corpus is a fixed
CI-sized synthetic stream (streaming behavior is a cache property, not a
perf scale, so BENCH_SCALE does not apply; rows carry ``corpus=fixed``).
The decode honors BENCH_BACKEND.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

import numpy as np

from .common import BENCH_BACKEND, emit

from repro.data.jpeg_pipeline import JpegVisionPipeline
from repro.jpeg import codec_ref as cr
from repro.jpeg.encoder import synth_frame

N_BATCHES = 24       # distinct batches in the bucketed stream
N_UNBUCKETED = 6     # the exact-fit baseline compiles per batch: keep short
BATCH = 4
CHUNK_BITS = 256


def stream_blobs(n_batches: int, batch: int = BATCH):
    """Distinct same-geometry batches (a fixed-resolution training feed)."""
    rng = np.random.default_rng(0)
    out = []
    for b in range(n_batches):
        out.append([
            cr.encode_baseline(
                synth_frame(rng, 32, 32, t=0.13 * (b * batch + i)),
                quality=80).jpeg_bytes
            for i in range(batch)
        ])
    return out


def _run_stream(batches, bucket: bool):
    pipe = JpegVisionPipeline(patch=8, embed_dim=64, chunk_bits=CHUNK_BITS,
                              backend=BENCH_BACKEND, bucket=bucket,
                              decoder_cache_size=0, sync_stats=True)
    for blobs in batches:
        pipe.patches_for(blobs)
    return pipe.decode_stats()


def run_rows():
    rows = []
    for name, bucket, n in (("bucketed", True, N_BATCHES),
                            ("unbucketed", False, N_UNBUCKETED)):
        st = _run_stream(stream_blobs(n), bucket)
        per100 = 100.0 * st["compile_count"] / max(st["batches"], 1)
        # an unbucketed "warm" step only exists when two batches collide on
        # an exact shape; report the cold step as the steady state then
        warm = st["warm_step_ms"] or st["cold_step_ms"]
        rows.append({
            "name": f"stream/{name}",
            "us_per_call": warm * 1e3,
            "derived": (
                f"cold_ms={st['cold_step_ms']:.1f}"
                f";compiles_per_100={per100:.1f}"
                f";batches={st['batches']};buckets={len(st['buckets'])}"
                f";sync_rounds={st['sync_rounds']}"
                f";transfer_saving={st['transfer_saving']:.1f}x"
                f";corpus=fixed"
            ),
        })
    return rows


def _host_worker(pid: int, n_hosts: int, port: int) -> None:
    """One process of the ``--hosts N`` mode: stream my slice, report."""
    from repro.launch.multihost import init_distributed
    init_distributed(coordinator=f"127.0.0.1:{port}",
                     num_processes=n_hosts, process_id=pid)
    batches = stream_blobs(N_BATCHES)
    lo = pid * len(batches) // n_hosts
    hi = (pid + 1) * len(batches) // n_hosts
    st = _run_stream(batches[lo:hi], bucket=True)
    print("RESULT " + json.dumps(st), flush=True)


def run_multihost_rows(n_hosts: int):
    """Spawn ``n_hosts`` localhost jax.distributed workers, one row each.

    Per-host warm-step ms is the multi-host steady-state claim: every
    process keeps its own compile-once bucket cache, so each row's
    ``compiles`` should equal its bucket count, N times over.
    """
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    procs = [subprocess.Popen(
        [sys.executable, "-m", "benchmarks.stream", "--host-worker",
         str(pid), str(n_hosts), str(port)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        for pid in range(n_hosts)]
    # one shared wall clock + kill-all on any failure: a dead coordinator
    # must not leave the other workers orphaned in their connect loops
    import time
    deadline = time.monotonic() + 900
    outs = []
    try:
        for pid, p in enumerate(procs):
            out, _ = p.communicate(timeout=max(1, deadline - time.monotonic()))
            if p.returncode != 0:
                raise RuntimeError(f"host {pid} failed:\n{out[-3000:]}")
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    rows = []
    for pid, out in enumerate(outs):
        line = [ln for ln in out.splitlines()
                if ln.startswith("RESULT ")][-1]
        st = json.loads(line[len("RESULT "):])
        warm = st["warm_step_ms"] or st["cold_step_ms"]
        rows.append({
            "name": f"stream/multihost/h{pid}",
            "us_per_call": warm * 1e3,
            "derived": (
                f"host={st['process_id']}/{st['process_count']}"
                f";compiles={st['compile_count']}"
                f";batches={st['batches']};buckets={len(st['buckets'])}"
                f";cold_ms={st['cold_step_ms']:.1f};corpus=fixed"
            ),
        })
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--hosts", type=int, default=0, metavar="N",
                    help="also run the stream split over N localhost "
                         "jax.distributed processes and report per-host "
                         "warm-step ms")
    ap.add_argument("--hosts-only", action="store_true",
                    help="skip the single-process rows (CI runs them in "
                         "the main bench job already)")
    ap.add_argument("--host-worker", nargs=3, metavar=("PID", "N", "PORT"),
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.host_worker:
        pid, n, port = (int(x) for x in args.host_worker)
        _host_worker(pid, n, port)
        return
    if args.hosts_only and not args.hosts:
        ap.error("--hosts-only requires --hosts N")
    if args.hosts and os.environ.get("JAX_PLATFORMS") != "cpu":
        # this process has already imported JAX, and a chip belongs to one
        # process: the N workers can only share the CPU backend
        ap.error("--hosts N starts N JAX processes; run it with "
                 "JAX_PLATFORMS=cpu")
    rows = [] if args.hosts_only else run_rows()
    if args.hosts:
        rows += run_multihost_rows(args.hosts)
    emit(rows)


if __name__ == "__main__":
    main()
