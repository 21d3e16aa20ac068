"""Shared benchmark utilities: scaled paper datasets + timing."""
from __future__ import annotations

import os
import sys
import time
from typing import Callable, Dict, List, Tuple

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.core import ParallelDecoder
from repro.jpeg.encoder import PAPER_DATASETS, Dataset, build_dataset, \
    scaled_spec

# CPU-container scale factor for the paper's corpora (images x resolution).
# The *structure* (relative sizes, qualities, subsequence sizes) is kept.
BENCH_SCALE = float(os.environ.get("BENCH_SCALE", "0.02"))
CACHE_DIR = os.environ.get("BENCH_CACHE") or os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".bench_datasets")
# Decode backend for every suite: "jnp" (reference) or "pallas" (kernels,
# interpret mode on CPU — see repro.kernels.backend for overrides).
BENCH_BACKEND = os.environ.get("BENCH_BACKEND", "jnp")


def load_dataset(name: str, scale: float = None) -> Dataset:
    spec = scaled_spec(PAPER_DATASETS[name], scale or BENCH_SCALE)
    return build_dataset(spec, cache_dir=CACHE_DIR)


def time_call(fn: Callable, *args, warmup: int = 1, rounds: int = 3) -> float:
    """Median wall seconds per call (post-warmup)."""
    for _ in range(warmup):
        fn(*args)
    ts = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        fn(*args)
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[len(ts) // 2]


def decode_time(ds: Dataset, sync: str, chunk_bits: int = None,
                rounds: int = 3, backend: str = None, fuse: str = None
                ) -> Tuple[float, ParallelDecoder]:
    dec = ParallelDecoder.from_bytes(
        ds.jpeg_bytes, chunk_bits=chunk_bits or ds.spec.subsequence_bits,
        sync=sync, backend=backend or BENCH_BACKEND, fuse=fuse)

    def run():
        out = dec.decode(emit="rgb")
        out.rgb.block_until_ready()

    return time_call(run, rounds=rounds), dec


def emit(rows: List[Dict]) -> None:
    """Print the harness CSV: name,us_per_call,derived."""
    for r in rows:
        print(f"{r['name']},{r['us_per_call']:.1f},{r.get('derived','')}")
