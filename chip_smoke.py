"""Smoke run of the decoder on a TPU, through its public entry points.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the mesh-sharded decode, four chips

One chip: eight full-size frames of the paper's ``newyork`` corpus
(1920x1080, quality 95, 1024-bit subsequences; ``jpeg/encoder.py``),
generated from a fixed seed, go through

1. ``decode_batch(blobs, sync="jacobi", emit="rgb")``; image 0's
   coefficients must equal the sequential oracle (``jpeg/codec_ref.py``)
   bit for bit;
2. a ``DecodeService`` pre-warmed on the same geometry (``batch_size``
   4), which answers one request per frame; every future must resolve
   with ``STATUS_OK`` and RGB within 1 per channel of ``decode_batch``'s.

``--chips 4`` runs only ``decode_batch(mesh=<1-D 4-device mesh>,
balance="lpt")`` on the same frames, which must be bit-identical to the
one-chip decode and span four devices.

Earlier lines report what ran; times are smoke wall times, not metrics.
The last line is ``{"ok": true, "device": {...}}`` and is printed only
when every check passed on a TPU; otherwise the script exits non-zero
with the reason.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

N_IMAGES = 8
SERVICE_BATCH = 4
SEED = 0


class SmokeFailure(Exception):
    """A check of the smoke run failed; the message says which."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class CompileLog:
    """XLA compiles and persistent-cache traffic, from JAX's monitoring
    events (``compile_s`` includes loading an entry from the cache)."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_writes = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_writes += 1

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def line(self) -> str:
        return (f"compile: {self.compiles} XLA compiles, {self.compile_s:.1f} "
                f"s compiling or loading (smoke wall time), persistent "
                f"cache hits {self.cache_hits}, entries written "
                f"{self.cache_writes}, cache "
                f"{'hit' if self.cache_hits else 'not hit'}")


def make_corpus(n_images: int, width: int, height: int):
    """``n_images`` frames of the ``newyork`` corpus at the given size."""
    from repro.jpeg.encoder import PAPER_DATASETS, build_dataset
    spec = dataclasses.replace(PAPER_DATASETS["newyork"], n_images=n_images,
                               width=width, height=height)
    return spec, build_dataset(spec, seed=SEED).jpeg_bytes


def run_one_chip(blobs, chunk_bits: int) -> None:
    """decode_batch + oracle check + DecodeService, checked; raises
    :class:`SmokeFailure` on any failed check."""
    import numpy as np
    from repro.core import decode_batch
    from repro.core.api import decode_program_stats
    from repro.core.bitstream import STATUS_OK
    from repro.jpeg import codec_ref as cr
    from repro.serve.decode_service import DecodeService, RequestRejected

    t0 = time.perf_counter()
    out = decode_batch(blobs, chunk_bits=chunk_bits, sync="jacobi",
                       emit="rgb")
    rgb = np.asarray(out.rgb)
    coeffs = np.asarray(out.coeffs)
    print(f"decode_batch: {len(blobs)} images, {sum(map(len, blobs))} bytes, "
          f"rgb {rgb.shape} {rgb.dtype}, sync rounds {out.sync_rounds}, "
          f"converged {out.converged}, cold call "
          f"{time.perf_counter() - t0:.1f} s (smoke wall time)", flush=True)
    check(out.converged, "decode_batch did not converge")
    check(rgb.shape[0] == len(blobs) and rgb.dtype == np.uint8,
          f"decode_batch rgb has shape {rgb.shape} {rgb.dtype}")

    t0 = time.perf_counter()
    parsed = cr.parse_jpeg(blobs[0])
    ref = cr.undiff_dc(parsed, cr.decode_coefficients(parsed))
    exact = np.array_equal(coeffs[:len(ref)], ref)
    print(f"oracle: image 0 coefficients ({len(ref)} units) bit-exact vs "
          f"codec_ref: {exact} (oracle {time.perf_counter() - t0:.1f} s, "
          f"smoke wall time)", flush=True)
    check(exact, "image 0 coefficients differ from codec_ref")

    with DecodeService(batch_size=SERVICE_BATCH, chunk_bits=chunk_bits,
                       sync="jacobi", slo_ms=600_000.0) as svc:
        svc.prewarm(blobs[:SERVICE_BATCH])
        svc.reset_stats()
        futures = svc.submit_many(blobs)
        try:
            results = [f.result(timeout=600) for f in futures]
        except RequestRejected as e:
            # the device thread turns a failed decode into a typed
            # rejection of the batch's futures: a failure here
            raise SmokeFailure(f"a service request failed: {e}") from e
        stats = svc.serve_stats()
    ok = [r.status == STATUS_OK for r in results]
    worst = max(int(np.abs(r.rgb.astype(np.int16)
                           - rgb[i].astype(np.int16)).max())
                for i, r in enumerate(results))
    print(f"service: {len(results)} requests, {sum(ok)} STATUS_OK, "
          f"{stats['batches']} batches of {SERVICE_BATCH}, max |rgb - "
          f"decode_batch rgb| = {worst}", flush=True)
    check(all(ok), "a service request did not resolve with STATUS_OK")
    check(worst <= 1, f"service rgb differs from decode_batch by {worst}")
    progs = decode_program_stats()
    print(f"buckets: {progs['programs']} compiled decode programs, "
          f"{progs['compiles']} traces", flush=True)


def run_mesh(blobs, chunk_bits: int, n_devices: int) -> None:
    """The sharded decode over ``n_devices`` against the one-chip decode
    of the same batch; raises :class:`SmokeFailure` on any failed check."""
    import numpy as np
    from repro.core import decode_batch
    from repro.dist.plan import plan_lane_loads
    from repro.launch.mesh import make_mesh

    one = decode_batch(blobs, chunk_bits=chunk_bits, emit="rgb")
    mesh = make_mesh((n_devices,), ("data",))
    t0 = time.perf_counter()
    out = decode_batch(blobs, chunk_bits=chunk_bits, emit="rgb", mesh=mesh,
                       balance="lpt")
    out.rgb.block_until_ready()
    loads = plan_lane_loads(out.plan, n_devices)
    spans = len(out.coeffs.sharding.device_set)
    same = (np.array_equal(np.asarray(out.coeffs), np.asarray(one.coeffs))
            and np.array_equal(np.asarray(out.rgb), np.asarray(one.rgb)))
    print(f"mesh: decode_batch over {n_devices} devices (balance=lpt), "
          f"cold call {time.perf_counter() - t0:.1f} s (smoke wall time); "
          f"per-device lane loads {loads.tolist()}; coeffs span {spans} "
          f"devices; bit-identical to the one-chip decode: {same}",
          flush=True)
    check(same, "sharded decode differs from the one-chip decode")
    check(spans == n_devices,
          f"sharded coefficients span {spans} devices, not {n_devices}")
    check(int((loads > 0).sum()) == n_devices,
          f"lanes not spread over every device: {loads.tolist()}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip phases; 4: only the sharded "
                         "decode, compared with the one-chip decode")
    args = ap.parse_args(argv)
    try:
        import jax
        import repro  # noqa: F401  (the checkout's src/ must be present)
    except ImportError as e:
        print(f"chip_smoke: cannot import the decoder: {e}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's platform is {platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1
    print(f"device: {len(devices)} x {devices[0].device_kind}; compile "
          f"cache {enable_compile_cache()}", flush=True)
    log = CompileLog()
    t0 = time.perf_counter()
    spec, blobs = make_corpus(N_IMAGES, 1920, 1080)
    print(f"corpus: {len(blobs)} x {spec.width}x{spec.height} q{spec.quality} "
          f"newyork frames, seed {SEED}, {sum(map(len, blobs))} bytes, "
          f"encoded in {time.perf_counter() - t0:.1f} s (smoke wall time)",
          flush=True)
    try:
        if args.chips == 1:
            run_one_chip(blobs, spec.subsequence_bits)
        else:
            run_mesh(blobs, spec.subsequence_bits, args.chips)
    except SmokeFailure as e:
        print(log.line(), flush=True)
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(log.line(), flush=True)
    mem = devices[0].memory_stats() or {}
    print(f"memory: peak_bytes_in_use {mem.get('peak_bytes_in_use')} on "
          f"device 0", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
