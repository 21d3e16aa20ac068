"""Ingest over the chips of one host: a closed loop of one global batch,
the corpus's frames ``copies`` times, each copy under a tag of its own,
through ``ParallelDecoder.from_bytes(balance="lpt", lanes=<chips>)`` and
``.decode_on(mesh, emit="rgb")`` with a block on the RGB. The lanes of
the entropy stage and the units of the pixel stage are sharded over a 1-D
mesh of the cell's chips, so chain entries cross chips every sync round.

Every pass decodes the one batch; the window runs whole passes until
``seconds`` have passed, every input of a pass under a tag unique to its
pass and copy.

Mix keys: ``copies``; ``sample_batches``, the window's batches kept for the
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
    import jax
    from repro.core.api import ParallelDecoder

    tr, dec_cfg = cell.traffic, cell.config["decoder"]
    copies = tr["copies"]
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:cell.chips]), ("data",))

    def batch_of(p):
        return [corpus.tagged(x, tag(seed, copies * p + c))
                for c in range(copies) for x in blobs]

    def decode(batch):
        with annotate("bench.plan"):
            t0 = time.perf_counter()
            dec = ParallelDecoder.from_bytes(
                batch, chunk_bits=dec_cfg["chunk_bits"], sync=dec_cfg["sync"],
                balance="lpt", lanes=cell.chips)
            t1 = time.perf_counter()
        with annotate("bench.decode"):
            out = dec.decode_on(mesh, emit="rgb")
            out.rgb.block_until_ready()
        return out, t1 - t0

    # warm the one capacity bucket on inputs of the window's lengths
    decode(batch_of(0))
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
            batch = batch_of(passes)
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
    size = len(decoded[0])
    n_images = len(decoded) * size
    samples = []
    for b, coeffs, rgb in sorted(kept, key=lambda x: x[0]):
        coeffs, rgb = np.asarray(coeffs), np.asarray(rgb)
        units = coeffs.shape[0] // size
        for k, blob in enumerate(decoded[b]):
            samples.append(Sample(blob, coeffs[k * units:(k + 1) * units], rgb[k]))
    lines = [
        f"window: {passes} pass(es), each one batch of {size} ({copies} copies "
        f"of the corpus's {len(blobs)}) over {cell.chips} chips, {n_images} "
        f"images in {elapsed:.3f} s",
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
