"""Ingest: a closed loop of batches of ``batch`` images, each through
``ParallelDecoder.from_bytes`` and ``.decode(emit="rgb")`` with a block on
the RGB (the two halves of ``decode_batch``).

The corpus is cut into batches once; the window runs whole passes over
them, every batch once per pass in an order the seed draws, until
``seconds`` have passed. So every seed times the same work in another
order, and a window of any number of passes the same mix. Every input
carries a comment segment unique to its pass (``corpus.tagged``), so that
a repeated image is new bytes to the program.

Mix keys: ``batch``; ``sample_batches``, the window's batches kept for the
comparison (drawn by the seed); ``trace_seconds``, the window of a traced
run.
"""
from __future__ import annotations

import time
from typing import List

import numpy as np

from harness import corpus
from harness.window import Sample, Window, annotate, start_trace, tag


def run(cell, blobs, seed, seconds, trace_dir, compiles, t_setup0) -> Window:
    from repro.core.api import ParallelDecoder
    from repro.core.bitstream import build_batch_plan, plan_shape
    from repro.jpeg.format import parse_jpeg

    tr, dec_cfg = cell.traffic, cell.config["decoder"]
    size = tr["batch"]
    batches = [blobs[i:i + size] for i in range(0, len(blobs) - size + 1, size)]

    def decode(batch):
        with annotate("bench.plan"):
            t0 = time.perf_counter()
            dec = ParallelDecoder.from_bytes(
                batch, chunk_bits=dec_cfg["chunk_bits"], sync=dec_cfg["sync"])
            t1 = time.perf_counter()
        with annotate("bench.decode"):
            out = dec.decode(emit="rgb")
            out.rgb.block_until_ready()
        return out, t1 - t0

    # warm every capacity bucket the window's batches fall in, by the
    # program's own planner, on inputs of the window's lengths
    shapes = {}
    for b in batches:
        b = [corpus.tagged(x, tag(seed, 0)) for x in b]
        plan = build_batch_plan(b, chunk_bits=dec_cfg["chunk_bits"],
                                parsed=[parse_jpeg(x) for x in b])
        shapes.setdefault(plan_shape(plan), b)
    for b in shapes.values():
        decode(b)
    setup_s = time.perf_counter() - t_setup0

    rng = np.random.default_rng([seed, 1])
    keep = tr["sample_batches"]
    kept: List = []                  # reservoir of (window batch, outputs)
    plan_s, rounds, decoded = [], [], []
    n_before = compiles.count
    stop = start_trace(trace_dir)
    with annotate("bench.window"):
        t0 = time.perf_counter()
        passes = 0
        while True:
            # one pass: every batch of the corpus once, in the seed's order,
            # under this pass's tag
            for b in rng.permutation(len(batches)):
                batch = [corpus.tagged(x, tag(seed, passes)) for x in batches[b]]
                out, host = decode(batch)
                plan_s.append(host)
                rounds.append(out.sync_rounds)
                item = (len(decoded), out.coeffs, out.rgb)
                decoded.append(batch)
                if len(kept) < keep:
                    kept.append(item)
                else:
                    j = int(rng.integers(0, len(decoded)))
                    if j < keep:
                        kept[j] = item
                del out
            passes += 1
            if time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
    stop()
    in_window = compiles.count - n_before
    n_images = len(decoded) * size
    samples = []
    for b, coeffs, rgb in sorted(kept, key=lambda x: x[0]):
        coeffs, rgb = np.asarray(coeffs), np.asarray(rgb)
        units = coeffs.shape[0] // size
        for k, blob in enumerate(decoded[b]):
            samples.append(Sample(blob, coeffs[k * units:(k + 1) * units], rgb[k]))
    lines = [
        f"window: {passes} pass(es) over the corpus's {len(batches)} batches of "
        f"{size}, {len(decoded)} batches ({n_images} images) in {elapsed:.3f} s; "
        f"{len(shapes)} capacity bucket(s) warmed",
        f"compiles in window: {in_window}",
        f"sync rounds per batch: {rounds}",
        f"host plan per batch (ms): {[round(1e3 * x, 3) for x in plan_s]}",
    ]
    return Window(seconds=elapsed, attempted=n_images, failed=0,
                  samples=samples, lines=lines,
                  counters={"host_plan_ms": 1e3 * float(np.mean(plan_s)),
                            "sync_rounds": float(np.mean(rounds)),
                            "setup_s": setup_s, "compiles": in_window,
                            "batch_blobs": decoded},
                  e2e={"images_per_s": n_images / elapsed})
