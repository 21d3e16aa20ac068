"""Training ingest with crops: a closed loop of batches of ``batch``
images, each through ``ParallelDecoder.from_bytes`` and
``.decode(emit="crop")`` with a block on the (batch, 224, 224, 3) crops.

A copy of ``ingest.py``'s loop: the corpus is cut into batches once, and
the window runs whole passes over them, every batch once per pass in an
order the seed draws, each input under a comment segment unique to its
pass. Every image of every pass gets its own box, drawn from
``default_rng([seed, 2, pass])`` by the random-resized-crop recipe of
Szegedy et al. (CVPR 2015) as torchvision's ``RandomResizedCrop`` draws it
(the configuration's ``crop``): a share of the frame's area from
U(scale) and an aspect ratio log-uniform in ``ratio``, up to ``attempts``
draws, then a centre crop. Every answer is a ``Sample`` with its crop;
``batch_crops`` gives them to ``work.py``.

Mix keys: ``batch``; ``sample_batches``, the window's batches kept for the
comparison (drawn by the seed); ``trace_seconds``, the window of a traced
run.
"""
from __future__ import annotations

import inspect
import math
import time
from typing import List

import numpy as np

from harness import corpus, reference
from harness.window import Sample, Window, annotate, start_trace, tag


def rrc_box(rng, width: int, height: int, spec: dict):
    """(top, left, height, width) of one random-resized crop of a
    ``width`` x ``height`` frame."""
    area = width * height
    lo, hi = (math.log(r) for r in spec["ratio"])
    for _ in range(spec["attempts"]):
        target = area * rng.uniform(*spec["scale"])
        aspect = math.exp(rng.uniform(lo, hi))
        w = int(round(math.sqrt(target * aspect)))
        h = int(round(math.sqrt(target / aspect)))
        if 0 < w <= width and 0 < h <= height:
            top = int(rng.integers(0, height - h + 1))
            left = int(rng.integers(0, width - w + 1))
            return top, left, h, w
    ratio = width / height
    if ratio < min(spec["ratio"]):
        w, h = width, int(round(width / min(spec["ratio"])))
    elif ratio > max(spec["ratio"]):
        w, h = int(round(height * max(spec["ratio"]))), height
    else:
        w, h = width, height
    return (height - h) // 2, (width - w) // 2, h, w


def run(cell, blobs, seed, seconds, trace_dir, compiles, t_setup0) -> Window:
    from repro.core.api import ParallelDecoder
    from repro.core.bitstream import build_batch_plan, plan_shape
    from repro.jpeg.format import parse_jpeg

    if "crops" not in inspect.signature(ParallelDecoder.decode).parameters:
        raise NotImplementedError("this decoder has no crop output "
                                  "(ParallelDecoder.decode(emit='crop'))")
    tr, cfg = cell.traffic, cell.config
    dec_cfg, spec = cfg["decoder"], cfg["crop"]
    out_h, out_w = spec["out_size"]
    size = tr["batch"]
    batches = [blobs[i:i + size] for i in range(0, len(blobs), size)
               if i + size <= len(blobs)]
    dims = [(f.width, f.height) for f in map(reference.parse, blobs)]

    def boxes_for(p):
        rng = np.random.default_rng([seed, 2, p])
        return [rrc_box(rng, w, h, spec) for w, h in dims]

    def decode(batch, boxes):
        with annotate("bench.plan"):
            t0 = time.perf_counter()
            dec = ParallelDecoder.from_bytes(
                batch, chunk_bits=dec_cfg["chunk_bits"], sync=dec_cfg["sync"])
            t1 = time.perf_counter()
        with annotate("bench.decode"):
            out = dec.decode(emit="crop", crops=np.asarray(boxes, np.int32),
                             out_size=(out_h, out_w))
            out.crop.block_until_ready()
        pad = 100.0 * (dec.shape.n_units - dec.plan.total_units) / dec.shape.n_units
        return out, t1 - t0, pad

    # warm every capacity bucket the window's batches fall in, by the
    # program's own planner, on inputs of the window's lengths
    shapes = {}
    first = boxes_for(0)
    for n, b in enumerate(batches):
        b = [corpus.tagged(x, tag(seed, 0)) for x in b]
        plan = build_batch_plan(b, chunk_bits=dec_cfg["chunk_bits"],
                                parsed=[parse_jpeg(x) for x in b])
        shapes.setdefault(plan_shape(plan), (b, first[n * size:(n + 1) * size]))
    for b, boxes in shapes.values():
        decode(b, boxes)
    setup_s = time.perf_counter() - t_setup0

    rng = np.random.default_rng([seed, 1])
    keep = tr["sample_batches"]
    kept: List = []                  # reservoir of (window batch, outputs)
    plan_s, rounds, pads, decoded, crops = [], [], [], [], []
    n_before = compiles.count
    stop = start_trace(trace_dir)
    with annotate("bench.window"):
        t0 = time.perf_counter()
        passes = 0
        while True:
            # one pass: every batch of the corpus once, in the seed's order,
            # under this pass's tag, every image under this pass's box
            boxes = boxes_for(passes)
            for b in rng.permutation(len(batches)):
                batch = [corpus.tagged(x, tag(seed, passes)) for x in batches[b]]
                bx = boxes[b * size:(b + 1) * size]
                out, host, pad = decode(batch, bx)
                plan_s.append(host)
                rounds.append(out.sync_rounds)
                pads.append(pad)
                item = (len(decoded), out.coeffs, out.crop)
                decoded.append(batch)
                crops.append([tuple(x) + (out_h, out_w) for x in bx])
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
    for b, coeffs, crop in sorted(kept, key=lambda x: x[0]):
        coeffs, crop = np.asarray(coeffs), np.asarray(crop)
        # the batch's images in order: their units, counted from each JPEG
        sizes = [reference.parse(x).n_units for x in decoded[b]]
        bases = np.concatenate([[0], np.cumsum(sizes)])
        for k, blob in enumerate(decoded[b]):
            samples.append(Sample(blob, coeffs[bases[k]:bases[k + 1]], crop[k],
                                  crop=crops[b][k]))
    lines = [
        f"window: {passes} pass(es) over the corpus's {len(batches)} batches of "
        f"{size}, {len(decoded)} batches ({n_images} images) in {elapsed:.3f} s; "
        f"{len(shapes)} capacity bucket(s) warmed; crops {out_h}x{out_w}",
        f"compiles in window: {in_window}",
        f"sync rounds per batch: {rounds}",
        f"host plan per batch (ms): {[round(1e3 * x, 3) for x in plan_s]}",
        f"padded units per batch (%): {[round(x, 3) for x in pads]}",
    ]
    return Window(seconds=elapsed, attempted=n_images, failed=0,
                  samples=samples, lines=lines,
                  counters={"host_plan_ms": 1e3 * float(np.mean(plan_s)),
                            "sync_rounds": float(np.mean(rounds)),
                            "pad_units_pct": float(np.mean(pads)),
                            "setup_s": setup_s, "compiles": in_window,
                            "batch_blobs": decoded, "batch_crops": crops},
                  e2e={"images_per_s": n_images / elapsed})
