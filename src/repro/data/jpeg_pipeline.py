"""The paper's decoder as a first-class input-pipeline stage.

This is the deployment the paper motivates: a VLM training job where only
*compressed* JPEG bytes cross the host->device link; entropy decoding, IDCT,
and patching all run on the accelerators, then feed the model's vision
frontend directly.

Pipeline: jpeg bytes --(host: parse+frame)--> device plan
          --(device: parallel decode)--> RGB planes, or 224x224 crops
          --(device: patchify + linear embed stub)--> (B, n_patches, 1024)

A batch of one geometry is patched whole; a batch of mixed geometries,
or any batch given crop boxes, is decoded to fixed-size crops on the
device (``ParallelDecoder.decode(emit="crop")``), so every image yields
the same number of tokens.

The host work is exactly the paper's host share (header parse + subsequence
framing); pixels never exist host-side.

Streaming compilation: compiled decode programs live in the module-level
per-bucket cache (:func:`repro.core.api.decode_program`, keyed on the
batch's capacity-bucketed ``PlanShape``), NOT in this pipeline — a stream
of fresh batches compiles once per bucket and then only moves data. The
pipeline's own ``_decoders`` LRU caches per-*batch* handles (parsed plan +
uploaded metadata arrays), which only matters when the same byte-identical
batch repeats; ``decoder_cache_size=0`` disables that handle cache entirely
without losing the shared compiled programs. :meth:`decode_stats` surfaces
the streaming counters (compiles, warm-step ms, active bucket, ...) for
``launch/report.py`` and ``benchmarks/stream.py``.
"""
from __future__ import annotations

import collections
import dataclasses
import hashlib
import threading
import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core import (ParallelDecoder, STATUS_OK, STATUS_RECOVERED,
                    STATUS_REJECTED)
from ..core.api import CROP_SIZE
from ..jpeg.encoder import Dataset


@dataclasses.dataclass
class JpegPipelineStats:
    compressed_mb: float
    decoded_mb: float
    n_images: int
    sync_rounds: int
    # streaming decode stats (compile-once observability)
    decode_ms: float = 0.0        # wall ms of this batch's decode+embed
    compiled: bool = False        # this batch traced a decode program
    bucket: str = ""              # PlanShape label of the batch's bucket
    # resilience (validate=True pipelines): per-image STATUS_* array and
    # the batch's damaged-image counts
    status: Optional[np.ndarray] = None   # (B,) int32 or None
    images_recovered: int = 0
    images_rejected: int = 0

    @property
    def transfer_saving(self) -> float:
        return self.decoded_mb / max(self.compressed_mb, 1e-9)


def _centre_boxes(dec: ParallelDecoder) -> np.ndarray:
    """The centre square of each frame's shorter side, as crop boxes (a
    quarantined image's box is never read)."""
    geoms = dec.plan.image_geometry or ()
    boxes = np.zeros((len(geoms), 4), np.int64)
    for i, g in enumerate(geoms):
        if g is not None:
            side = min(g.width, g.height)
            boxes[i] = ((g.height - side) // 2, (g.width - side) // 2, side, side)
    return boxes


class JpegVisionPipeline:
    """Decode a batch of JPEGs on-device and emit ViT-style patch tokens."""

    def __init__(self, patch: int = 16, embed_dim: int = 1024,
                 chunk_bits: int = 1024, sync: str = "jacobi",
                 use_kernels: bool = False, backend: Optional[str] = None,
                 seed: int = 0, mesh=None, balance: str = "none",
                 decoder_cache_size: int = 16, bucket: bool = True,
                 sync_stats: bool = False, validate: bool = False,
                 fuse: Optional[str] = None):
        self.patch = patch
        self.embed_dim = embed_dim
        self.chunk_bits = chunk_bits
        self.sync = sync
        self.use_kernels = use_kernels
        self.backend = backend
        # fuse ("none"|"post"|"full", Pallas only) selects the fused decode
        # megakernel path; None resolves per backend (repro.kernels.backend)
        self.fuse = fuse
        # validate=True makes the stage resilient: damaged blobs are
        # classified (never raised), rejected images decode as inert gray
        # lanes, and per-batch stats carry a per-image status array plus
        # running recovered/rejected counters (see docs/ROBUSTNESS.md)
        self.validate = validate
        # with a mesh, decode work (chunk lanes / output units) is sharded
        # over the data axis — the input pipeline scales with the job;
        # balance ("roundrobin"/"lpt") redistributes skewed batches' chunk
        # lanes over the mesh's devices at plan time (bit-identical)
        self.mesh = mesh
        self.balance = balance
        # bucket=False pins exact-fit plan shapes (one compile per distinct
        # batch geometry — the pre-streaming behavior, kept for A/B runs)
        self.bucket = bucket
        # sync_stats=True blocks on each batch's tokens so decode_ms is the
        # true device wall time (benchmarks/dry-runs); the default keeps
        # dispatch asynchronous — the host overlaps the next batch's
        # parse/plan with device decode — and decode_ms then measures only
        # the host-side dispatch cost
        self.sync_stats = sync_stats
        rng = np.random.default_rng(seed)
        # stub patch-embedding projection (fixed; a real run would train it)
        self.w_embed = jnp.asarray(
            rng.normal(0, 0.02, (patch * patch * 3, embed_dim)),
            dtype=jnp.bfloat16)
        # LRU of per-batch decoder *handles* (host plan + device metadata).
        # Compiled programs live in the shared per-bucket cache in
        # repro.core.api, so eviction here never discards a compilation —
        # it only drops one batch's pinned device arrays. Size 0 turns the
        # handle cache off: every call builds (and returns) a fresh,
        # fully usable handle and pins nothing afterwards.
        if decoder_cache_size < 0:
            raise ValueError(
                f"decoder_cache_size must be >= 0 (0 disables caching), "
                f"got {decoder_cache_size}")
        self._decoder_cache_size = decoder_cache_size
        self._decoders: Dict = collections.OrderedDict()
        # One pipeline may be fed from several stage/worker threads (the
        # decode service, or a threaded data loader): every running counter
        # below and the handle LRU mutate under this lock — the bare
        # ``self._batches += 1`` increments are NOT atomic and lost updates
        # corrupted the compile-once accounting under concurrency (pinned
        # by tests/test_serve.py). Device work never runs under the lock.
        self._lock = threading.Lock()
        # streaming counters for decode_stats()
        self._batches = 0
        self._compiles = 0
        self._cold_ms: List[float] = []
        self._warm_ms: List[float] = []
        self._buckets: Dict[str, int] = {}
        self._last: Optional[JpegPipelineStats] = None
        # launch accounting of the most recent decoder's program, cached
        # per (program, fuse) — launch_stats() retraces abstractly
        self._last_dec: Optional[ParallelDecoder] = None
        self._launch_key = None
        self._launch: Dict = {}
        # resilience counters (advance only under validate=True)
        self._images_ok = 0
        self._images_recovered = 0
        self._images_rejected = 0

    @staticmethod
    def _batch_key(blobs: Sequence[bytes]) -> bytes:
        """Content digest of a batch. A decoder handle pins the batch's
        device metadata and words, so the cache key must identify the
        *bytes*, not just the shape — keying on (count, total_bytes) made
        two different same-size batches silently reuse the first batch's
        bitstream and decode the wrong images."""
        h = hashlib.blake2b(digest_size=16)
        for b in blobs:
            h.update(len(b).to_bytes(8, "little"))
            h.update(b)
        return h.digest()

    def _decoder(self, blobs: Sequence[bytes]) -> ParallelDecoder:
        key = self._batch_key(blobs)
        with self._lock:
            dec = self._decoders.get(key)
            if dec is not None:
                self._decoders.move_to_end(key)
                return dec
        # plan build + device upload happen outside the lock; two threads
        # missing the same key both build (benign — handles are content
        # addressed and the compiled program is shared), last insert wins
        dec = ParallelDecoder.from_bytes(
            list(blobs), chunk_bits=self.chunk_bits, sync=self.sync,
            use_kernels=self.use_kernels, backend=self.backend,
            balance=self.balance,
            lanes=(self.mesh.devices.size
                   if self.mesh is not None else None),
            bucket=self.bucket, validate=self.validate, fuse=self.fuse)
        if self._decoder_cache_size > 0:
            with self._lock:
                self._decoders[key] = dec
                while len(self._decoders) > self._decoder_cache_size:
                    self._decoders.popitem(last=False)
        return dec

    def patches_for(self, blobs: Sequence[bytes], crops=None):
        """(B, n_patches, embed_dim) patch tokens + stats.

        A batch of one geometry without ``crops`` is patched whole. Any
        other batch is decoded to ``CROP_SIZE`` crops on the device: each
        image's ``crops`` box (top, left, height, width), or by default the
        centre square of its shorter side. A quarantined image's crop is
        zero, so its tokens are too, and ``status`` says why."""
        t0 = time.perf_counter()
        dec = self._decoder(blobs)
        self._last_dec = dec
        compiles_before = dec.program.compiles
        if crops is None and dec.plan.uniform:
            kw = dict(emit="rgb")
        else:
            kw = dict(emit="crop", out_size=CROP_SIZE,
                      crops=crops if crops is not None else _centre_boxes(dec))
        if self.mesh is not None:
            out = dec.decode_on(self.mesh, **kw)
        else:
            out = dec.decode(**kw)
        rgb = out.rgb if kw["emit"] == "rgb" else out.crop  # (B, H, W, 3)
        p = self.patch
        b, h, w, _ = rgb.shape
        hc, wc = h // p, w // p
        x = rgb[:, : hc * p, : wc * p].astype(jnp.bfloat16) / 255.0
        x = x.reshape(b, hc, p, wc, p, 3).transpose(0, 1, 3, 2, 4, 5)
        x = x.reshape(b, hc * wc, p * p * 3)
        tokens = x @ self.w_embed
        if self.sync_stats:
            jax.block_until_ready(tokens)
        dt_ms = (time.perf_counter() - t0) * 1e3
        compiled = dec.program.compiles > compiles_before
        status = out.status
        stats = JpegPipelineStats(
            compressed_mb=sum(len(bb) for bb in blobs) / 1e6,
            decoded_mb=b * h * w * 3 / 1e6,
            n_images=b,
            sync_rounds=out.sync_rounds,
            decode_ms=dt_ms,
            compiled=compiled,
            bucket=dec.shape.label(),
            status=status,
            images_recovered=(int((status == STATUS_RECOVERED).sum())
                              if status is not None else 0),
            images_rejected=(int((status == STATUS_REJECTED).sum())
                             if status is not None else 0),
        )
        self._record(stats)
        return tokens, stats

    def _record(self, stats: JpegPipelineStats) -> None:
        with self._lock:
            self._batches += 1
            self._compiles += int(stats.compiled)
            log = self._cold_ms if stats.compiled else self._warm_ms
            log.append(stats.decode_ms)
            del log[:-100]  # bounded history for the medians
            self._buckets[stats.bucket] = \
                self._buckets.get(stats.bucket, 0) + 1
            if stats.status is not None:
                self._images_ok += int((stats.status == STATUS_OK).sum())
                self._images_recovered += stats.images_recovered
                self._images_rejected += stats.images_rejected
            self._last = stats

    def decode_stats(self) -> Dict:
        """Streaming decode counters for dry-run reports.

        ``compile_count`` counts batches that traced a decode program (the
        compile-once target is: one per (bucket, sync, backend) over the
        whole stream); ``warm_step_ms`` is the median decode+embed wall
        time of non-compiling steps — the steady-state cost. Step times
        include device execution only under ``sync_stats=True`` (the
        default keeps dispatch asynchronous and measures host cost).

        Counters are *per process*: in a multi-host launch every host
        compiles (and feeds) independently, so the dict carries
        ``process_id`` / ``process_count`` and must never be summed
        across hosts — N hosts in one bucket report one compile *each*
        (aggregate with :func:`repro.launch.multihost.gather_decode_stats`,
        which keeps the per-host dicts separate).
        """
        med = (lambda xs: float(np.median(xs)) if xs else 0.0)
        from ..launch.multihost import process_info  # lazy: launch uses us
        info = process_info()
        # snapshot every counter under the lock so a concurrent _record
        # cannot be observed half-applied; the launch accounting retrace
        # (abstract but not free) runs outside it
        with self._lock:
            last = self._last
            dec = self._last_dec
            batches, compiles = self._batches, self._compiles
            cold_ms, warm_ms = list(self._cold_ms), list(self._warm_ms)
            buckets = dict(self._buckets)
            images_ok = self._images_ok
            images_recovered = self._images_recovered
            images_rejected = self._images_rejected
        if dec is not None:
            key = (id(dec.program), dec.fuse)
            if self._launch_key != key:
                launch = dec.launch_stats()
                with self._lock:
                    self._launch, self._launch_key = launch, key
        launch = self._launch
        return {
            "batches": batches,
            "compile_count": compiles,
            "cold_step_ms": med(cold_ms),
            "warm_step_ms": med(warm_ms),
            "buckets": buckets,
            "active_bucket": last.bucket if last else "",
            "sync_rounds": last.sync_rounds if last else 0,
            "transfer_saving": last.transfer_saving if last else 0.0,
            # resilience rollups (all zero unless validate=True); per
            # process like everything else here — gather_decode_stats keeps
            # them per-host, never summed
            "images_ok": images_ok,
            "images_recovered": images_recovered,
            "images_rejected": images_rejected,
            # fusion + kernel-launch accounting of the active program
            # (ParallelDecoder.launch_stats; empty-dict defaults before
            # the first batch): launch-site counts per decode step and
            # the analytic inter-stage HBM bytes the fuse mode removes
            "fuse": launch.get("fuse", dec.fuse if dec else "none"),
            "kernel_launches": launch.get("pallas_calls", 0),
            "jaxpr_eqns": launch.get("jaxpr_eqns", 0),
            "inter_stage_hbm_bytes": launch.get("inter_stage_bytes", 0),
            "process_id": info.process_id,
            "process_count": info.num_processes,
        }

    def batches(self, dataset: Dataset, batch_size: int,
                drop_remainder: bool = False):
        """Yield (tokens, stats) per batch of ``batch_size`` images.

        When the dataset size does not divide, the tail is yielded as a
        short final batch — silently dropping the last
        ``len(blobs) % batch_size`` images (the old behavior) loses data in
        eval/export pipelines. Pass ``drop_remainder=True`` for fixed-shape
        training streams.

        This is the steady-stream deployment the plan-bucket split targets:
        every batch here is content-distinct, so only the shared per-bucket
        program cache (never the content-keyed handle LRU) keeps the stream
        from recompiling — after the first batch of a bucket, steps are
        pure data movement (see docs/SERVING.md).
        """
        blobs = dataset.jpeg_bytes
        for i in range(0, len(blobs), batch_size):
            batch = blobs[i : i + batch_size]
            if drop_remainder and len(batch) < batch_size:
                return
            yield self.patches_for(batch)
