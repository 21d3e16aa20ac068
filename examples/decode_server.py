"""Batched JPEG decode "server": the paper's decoder serving continuous
request batches, with the three baselines the paper compares against.

    PYTHONPATH=src python examples/decode_server.py --images 32 --rounds 3

Modes (DESIGN.md §9):
  jacobi     : ours (bulk-synchronous self-sync, beyond-paper schedule)
  faithful   : the paper's two-level overflow pattern (Algorithm 3)
  sequential : per-image parallelism only (nvJPEG-hybrid stand-in)

With ``--serve``, requests go through the real continuous-batching async
service (``repro.serve.DecodeService``) instead of pre-formed batches:
open-loop Poisson arrivals, a deadline-aware batch former, and
host/device pipelining — see docs/SERVING.md §Serving front-end.

    PYTHONPATH=src python examples/decode_server.py --serve \
        --images 64 --rate 200 --slo 250
"""
import argparse
import sys
import time

sys.path.insert(0, "src")

import numpy as np

from repro.core import ParallelDecoder
from repro.jpeg.encoder import DatasetSpec, build_dataset
from repro.launch.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--images", type=int, default=32)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--width", type=int, default=320)
    ap.add_argument("--height", type=int, default=192)
    ap.add_argument("--quality", type=int, default=85)
    ap.add_argument("--chunk-bits", type=int, default=1024)
    ap.add_argument("--backend", choices=("jnp", "pallas"), default="jnp",
                    help="decode backend (pallas = kernels, interpret "
                         "mode on CPU; refused on TPU, see docs/KERNELS.md)")
    ap.add_argument("--serve", action="store_true",
                    help="run the continuous-batching async service "
                         "instead of the pre-formed batch modes")
    ap.add_argument("--batch", type=int, default=8,
                    help="--serve: micro-batch size the former packs to")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="--serve: Poisson arrival rate in images/sec "
                         "(0 = submit the whole backlog at once)")
    ap.add_argument("--slo", type=float, default=250.0,
                    help="--serve: per-request deadline in ms")
    args = ap.parse_args()

    ds = build_dataset(DatasetSpec("serve", args.images, args.width,
                                   args.height, args.quality))
    print(f"dataset: {args.images} x {args.width}x{args.height} "
          f"q{args.quality} = {ds.compressed_mb:.2f} MB compressed")

    if args.serve:
        serve(ds, args)
        return

    for mode in ("jacobi", "faithful", "sequential"):
        dec = ParallelDecoder.from_bytes(ds.jpeg_bytes,
                                         chunk_bits=args.chunk_bits,
                                         sync=mode, backend=args.backend)
        # warmup/compile
        out = dec.decode(emit="rgb")
        out.rgb.block_until_ready()
        t0 = time.perf_counter()
        for _ in range(args.rounds):
            out = dec.decode(emit="rgb")
            out.rgb.block_until_ready()
        dt = (time.perf_counter() - t0) / args.rounds
        print(f"{mode:10s}: {dt*1e3:7.1f} ms/batch "
              f"{ds.compressed_mb/dt:8.1f} MB/s "
              f"{args.images/dt:7.1f} img/s (rounds={out.sync_rounds})")


def serve(ds, args):
    from repro.serve import DecodeService, ServiceConfig, run_open_loop

    with DecodeService(ServiceConfig(
            batch_size=args.batch, chunk_bits=args.chunk_bits,
            backend=args.backend, slo_ms=args.slo)) as svc:
        svc.prewarm(ds.jpeg_bytes[:args.batch])
        svc.reset_stats()
        load = run_open_loop(
            svc, ds.jpeg_bytes, n_requests=args.images,
            rate_ips=args.rate,
            deadline_ms=args.slo if args.rate > 0 else 600_000.0)
        stats = svc.serve_stats()
    print(f"serve     : {load['completed']}/{load['n_requests']} done "
          f"{load['ips']:7.1f} img/s  p50 {load['p50_ms']:6.2f} ms  "
          f"p99 {load['p99_ms']:6.2f} ms  "
          f"misses {load['deadline_misses']}")
    print(f"            occupancy {stats['occupancy_mean']:.2f}/"
          f"{args.batch}  batches {stats['batches']}  admitted buckets "
          f"{len(stats['buckets'])}/{stats['max_buckets']}")


if __name__ == "__main__":
    main()
