"""Public API: batched, fully device-resident JPEG decoding.

Usage:
    dec = ParallelDecoder.from_bytes(list_of_jpeg_blobs, chunk_bits=1024)
    out = dec.decode(emit="rgb")          # DecodeOutput

The decoder is a function from a batch of encoded bitstreams to arrays of
pixels (per color channel), exactly as framed in the paper §IV. Only the
compressed words + small metadata tables are transferred to the device.

Sync schedules:   "jacobi" (default, beyond-paper), "faithful" (paper
Algorithm 3), "sequential" (one chunk per segment — the per-image-parallel
baseline that stands in for nvJPEG's hybrid mode; with a single image this
is the libjpeg-style fully sequential baseline).

Decode backends:  "jnp" (default; the pure-JAX reference hot loop) and
"pallas" (the kernels under repro.kernels — Huffman subsequence decode,
coefficient write pass, and fused IDCT). Every sync schedule runs on either
backend and the two are bit-identical; on a mesh the Pallas path runs under
shard_map over the chunk-lane axis. ``use_kernels=True`` is the deprecated
legacy spelling of ``backend="pallas"``.

Fusion (``fuse="none"|"post"|"full"``, Pallas only; default "post" via
``kernels.backend.resolve_fuse``): "post" collapses the post-entropy
pixel chain (dequant + de-zigzag + IDCT + upsample + color convert) into
one launch per MCU tile (``kernels/fused``); "full" additionally moves
the write pass's stream+scatter into an in-kernel coefficient store
wherever the verifier's scatter-race proof holds (off-mesh, VMEM-sized
buffers), falling back to the stream form elsewhere. All fuse modes are
bit-identical; lane/MCU tile sizes come from ``kernels/autotune`` and are
part of the program cache key, so tuning never retraces a warm bucket.

Compile-once streaming:  the compiled decoder is keyed on the batch's
static :class:`~repro.core.bitstream.PlanShape` (capacities bucketed up a
geometric ladder), NOT on its contents — a module-level program cache
(:func:`decode_program`) hands every ``ParallelDecoder`` whose batch lands
in the same (shape, sync, backend) bucket the same jitted function, and the
batch's :class:`~repro.core.bitstream.PlanData` streams through as plain
jit operands (the per-batch ``words`` buffer is donated). A training or
serving stream of fresh batches therefore compiles once per bucket and
performs zero retraces at steady state (see docs/SERVING.md).
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import itertools
import threading
import warnings
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import decode as D
from ..dist import sharding as S
from ..kernels.autotune import TileConfig, autotune_enabled, autotune_tiles
from ..kernels.backend import (check_backend, check_fuse,
                               check_pallas_compiles, resolve_backend,
                               resolve_fuse)
from ..jpeg.format import parse_jpeg, segment_byte_bounds, unstuff_scan
from .bitstream import (BatchPlan, BatchValidation, LADDER_STEP, PlanShape,
                        STATUS_OK, bucket_capacity, build_batch_plan,
                        build_plan_data, consensus_plan, crop_layout,
                        plan_shape, validate_batch)
from .state import DecodeState
from .sync import SyncResult, faithful_sync, jacobi_sync, specmap_sync

Array = jnp.ndarray

# Chunk-lane-indexed device arrays: one element per subsequence chunk.
# Constraining these under active logical rules shards every lane-parallel
# decode_span/sync loop over the data axis (GSPMD propagates the spec
# through the while loops); off-mesh the constraint is a no-op.
# chunk_prev/chunk_next/lane_perm/chunk_order are the explicit lane graph a
# lane-balanced plan (dist/plan.balance_lanes) permutes; they hold global
# lane/chunk indices (the gathers through them are cross-device), but they
# are lane-length arrays, so they shard like the rest of the lane axis.
_LANE_KEYS = ("chunk_start", "chunk_limit", "chunk_seg", "chunk_seq",
              "chunk_first", "chunk_seq_first", "chunk_prev", "chunk_next",
              "lane_perm", "chunk_order")


# Spans and phases, on the profiler's clock. Host spans are
# ``jax.profiler.TraceAnnotation``s named ``repro.*`` that carry the
# batch's process-wide id (``batch=``) and its counters; they nest on the
# calling thread. The entropy program's two device phases, and the crop's
# tap gather and resize inside the pixel program, are named scopes, kept
# in the compiled HLO's ``op_name`` metadata (:func:`hlo_phases`).
# With the profiler off a span costs about a microsecond on the host.
SYNC_PHASE = "repro.entropy.sync"
WRITE_PHASE = "repro.entropy.write"
CROP_PHASE = "repro.pixels.crop"
# the crop a training step takes by default (ResNet/ViT input size)
CROP_SIZE = (224, 224)
_BATCH_IDS = itertools.count()


def _span(name: str, batch: int, **counters):
    return jax.profiler.TraceAnnotation(name, batch=batch, **counters)


def hlo_phases(hlo_text: str,
               phases: Sequence[str] = (SYNC_PHASE, WRITE_PHASE)
               ) -> Dict[str, str]:
    """``{entry-level instruction name: phase}`` of a compiled program's HLO
    text, for the instructions whose ``op_name`` lies under one of
    ``phases`` (the entropy program's by default). Names are unique within
    a module and an entry-level instruction's device time covers everything
    nested in it, so summing the mapped instructions counts no time twice."""
    entry = hlo_text[hlo_text.index("\nENTRY ") + 1:].splitlines()[1:]
    out = {}
    for line in itertools.takewhile(lambda x: x != "}", entry):
        name = line.strip().removeprefix("ROOT ").split(" = ", 1)[0]
        scopes = line.partition('op_name="')[2].partition('"')[0].split("/")
        for phase in phases:
            if phase in scopes:
                out[name.lstrip("%")] = phase
    return out


def _abstract(tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, weak_type=getattr(a, "weak_type", False)),
        tree)


def _trace_context(trace_token):
    """Re-enter the (mesh, rules) context a trace token snapshots."""
    stack = contextlib.ExitStack()
    if trace_token is not None:
        mesh, rules = trace_token
        stack.enter_context(mesh)
        stack.enter_context(S.logical_rules(dict(rules)))
    return stack


def _shard_lanes(dev: Dict[str, Array]) -> Dict[str, Array]:
    out = dict(dev)
    for k in _LANE_KEYS:
        if k in out:
            out[k] = S.shard(out[k], "chunks")
    return out


def _decode_rules(mesh) -> Dict:
    """Logical rules for the decoder hot path on a given mesh."""
    axis = "data" if "data" in mesh.axis_names else mesh.axis_names[0]
    return {"chunks": (axis,), "units": (axis,), "batch": (axis,)}


def _lane_mesh_axis(trace_token):
    """(mesh, axis) the chunk lanes are sharded over, from a trace token.

    The token is :func:`repro.dist.sharding.trace_token`'s snapshot of the
    ambient (mesh, rules) context — the same static jit key the compiled
    programs are cached on, so the shard_map mesh always matches the trace
    context.
    """
    if trace_token is None:
        return None, None
    mesh, rules = trace_token
    for axis in dict(rules).get("chunks", ()):
        if axis in mesh.shape and mesh.shape[axis] > 1:
            return mesh, axis
    return None, None


@dataclasses.dataclass
class DecodeOutput:
    coeffs: Array                       # (U_total, 64) zig-zag, absolute DC
    planes: Optional[List[Array]]       # per component (B, Hc, Wc) float32
    rgb: Optional[Array]                # (B, H, W, 3) or (B, H, W) uint8
    sync_rounds: int
    converged: bool
    plan: BatchPlan
    # per-image STATUS_OK/RECOVERED/REJECTED (validated decodes only; the
    # per-segment / per-unit validity masks ride on plan.seg_valid /
    # plan.unit_valid)
    status: Optional[object] = None     # (B,) int32 np.ndarray or None
    validation: Optional[BatchValidation] = None
    crop: Optional[Array] = None        # (B, out_h, out_w, 3) uint8, emit="crop"


def _sequential_chunk_bits(unstuffed, bucket: bool = True) -> int:
    """Chunk size that makes every entropy *segment* a single chunk.

    Sized from the unstuffed scans' longest segment (restart intervals
    split a scan into many short segments), not from whole-file bytes — the
    old file-sized bound inflated ``s_max`` (the per-chunk decode loop
    bound, ``chunk_bits // min_code_bits + 2``) for every segment in the
    batch. ``unstuffed`` is a list of ``unstuff_scan`` results, shared with
    the plan builder so each scan is unstuffed once.

    With ``bucket`` (the default) the size is rounded up the capacity
    ladder before word alignment, so a stream of batches with drifting
    longest-segment sizes keeps hitting the same chunk_bits — and with it
    the same compiled-decoder bucket — instead of retracing per batch.
    """
    worst = 32
    for clean, rst_bits in unstuffed:
        bounds = segment_byte_bounds(clean, rst_bits)
        longest = max(b - a for a, b in zip(bounds, bounds[1:]))
        worst = max(worst, longest * 8)
    if bucket:
        worst = bucket_capacity(worst)
    return -(-worst // 32) * 32


# ---------------------------------------------------------------------------
# Compiled program cache: one jitted decoder per (PlanShape, sync, backend)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class DecodeProgram:
    """A compiled decoder for one capacity bucket.

    ``coeffs_fn(words, dev, trace_token)`` is the entropy stage: it takes a
    batch's padded :class:`PlanData` operands (``words`` donated — it is
    the one buffer that is fresh every batch) and returns capacity-sized
    coefficients plus sync diagnostics. ``pixels_fn`` (uniform shapes only)
    is the IDCT/color stage. ``crop_fn`` is the crop stage of any batch,
    uniform or mixed, whose per-image layout and boxes are operands and
    whose output size is static. All are shared by every decoder whose batch
    lands in this bucket; ``coeffs_traces``/``pixels_traces`` count actual
    jax traces (incremented from inside the traced python body; a crop
    program counts as a pixel program), which is
    how the compile-once guarantee is asserted in tests and surfaced in
    pipeline/benchmark stats.
    """

    shape: PlanShape
    sync: str
    backend: str
    interpret: Optional[bool]
    fuse: str = "none"
    tiles: Optional[TileConfig] = None
    coeffs_fn: object = None
    pixels_fn: object = None
    coeffs_traces: int = 0
    pixels_traces: int = 0
    # effective fusion, recorded at trace time: fuse="full" only engages
    # its in-kernel store off-mesh within the VMEM budget, and the fused
    # pixel kernel only engages off-mesh for 3-component uniform batches
    # (the gates in kernels/fused/ops.py); elsewhere each falls back to
    # the stream/unfused form, bit-identically
    store_fused: bool = False
    pixels_fused: bool = False
    # the jnp symbol step's form, fixed by the shape when the program is
    # built: "staged" reads each lane's words and LUT rows, staged once per
    # batch, by a one-hot select (chunks of up to D.STAGE_MAX_WORDS words);
    # "gather" gathers them every step (longer chunks, the Pallas backend)
    step_staged: bool = False

    # First-call serialization (thread safety). jax.jit does not promise a
    # single trace under concurrent first calls from multiple threads, and
    # the self-counting trace counters above are the compile-once contract
    # surface — a double trace would both waste a compile and corrupt the
    # counters the tests (and serve_stats) assert on. ``call_coeffs`` /
    # ``call_pixels`` funnel the first call per (stage, trace_token)
    # through a per-program lock; warm calls take the lock-free fast path.
    # Both fields are identity state, excluded from the dataclass compare.
    trace_lock: threading.Lock = dataclasses.field(
        default_factory=threading.Lock, repr=False, compare=False)
    traced_keys: set = dataclasses.field(
        default_factory=set, repr=False, compare=False)
    # abstract (words, dev, trace_token) of the first coeffs call, from
    # which device_phases() lowers the program again
    coeffs_args: Optional[tuple] = dataclasses.field(
        default=None, repr=False, compare=False)
    # the IDCT the pixel and crop stages run, the crop stage, and the
    # abstract arguments of its first call per (output size, trace_token),
    # from which pixel_phases() lowers it again
    idct_impl: object = dataclasses.field(default=None, repr=False,
                                          compare=False)
    crop_fn: object = dataclasses.field(default=None, repr=False,
                                        compare=False)
    crop_args: dict = dataclasses.field(default_factory=dict, repr=False,
                                        compare=False)

    @property
    def compiles(self) -> int:
        return self.coeffs_traces + self.pixels_traces

    @property
    def step(self) -> str:
        return "staged" if self.step_staged else "gather"

    def _call_once_locked(self, key, fn, *args):
        if key in self.traced_keys:
            return fn(*args)
        with self.trace_lock:
            out = fn(*args)
            # recorded only after the traced call returns: a concurrent
            # waiter then hits the warmed jit cache, never a second trace
            self.traced_keys.add(key)
        return out

    def call_coeffs(self, words, dev, trace_token):
        """``coeffs_fn`` with the first call per trace_token serialized
        (the operand shapes are fixed by the PlanShape, so the token is
        the only varying component of the jit key)."""
        if self.coeffs_args is None:
            self.coeffs_args = (_abstract(words), _abstract(dev), trace_token)
        return self._call_once_locked(("coeffs", trace_token),
                                      self.coeffs_fn, words, dev, trace_token)

    def device_phases(self) -> Dict[str, str]:
        """:func:`hlo_phases` of the compiled entropy program, lowered again
        with the abstract arguments and in the (mesh, rules) context of its
        first call, so from jit's cached trace (and a compile-cache hit
        where the cache holds the program); ``{}`` before the first call.
        For reading a profile after the fact, never for the hot path."""
        if self.coeffs_args is None:
            return {}
        words, dev, token = self.coeffs_args
        with _trace_context(token):
            compiled = self.coeffs_fn.lower(words, dev, token).compile()
        return hlo_phases(compiled.as_text())

    def call_pixels(self, pixdev, pix_layout, coeffs, trace_token):
        return self._call_once_locked(("pixels", trace_token),
                                      self.pixels_fn, pixdev, pix_layout,
                                      coeffs, trace_token)

    def call_crop(self, out_size, cropdev, coeffs, boxes, trace_token):
        """``crop_fn`` with the first call per (out_size, trace_token)
        serialized, as :meth:`call_coeffs`."""
        key = (out_size, trace_token)
        if key not in self.crop_args:
            self.crop_args[key] = (_abstract(cropdev), _abstract(coeffs),
                                   _abstract(boxes))
        return self._call_once_locked(("crop",) + key, self.crop_fn, cropdev,
                                      coeffs, boxes, out_size, trace_token)

    def pixel_phases(self) -> Dict[str, str]:
        """``{entry-level instruction: CROP_PHASE}`` of every crop program
        this bucket ran, lowered again as :meth:`device_phases` does; ``{}``
        before the first crop call."""
        out: Dict[str, str] = {}
        for (size, token), (cropdev, coeffs, boxes) in list(
                self.crop_args.items()):
            with _trace_context(token):
                compiled = self.crop_fn.lower(
                    cropdev, coeffs, boxes, size, token).compile()
            out.update(hlo_phases(compiled.as_text(), (CROP_PHASE,)))
        return out


_PROGRAMS: Dict[Tuple, DecodeProgram] = {}
# Guards _PROGRAMS lookup/insert (and snapshots of it): two stage threads
# first-touching the same bucket without it would each build their own
# DecodeProgram — one wins the dict insert but both get traced, and the
# loser's trace counters are silently lost (the "double-trace" race the
# decode service surfaced; regression test in tests/test_serve.py).
# _build_program only constructs closures (jax.jit is lazy — no trace
# happens under the lock), so holding it across the build is cheap.
_PROGRAMS_LOCK = threading.Lock()
_cpu_donation_warning_filtered = False


def _filter_cpu_donation_warning() -> None:
    """On CPU backends the donated per-batch words buffer can never be
    consumed and jax warns once per compile — pure noise there, so filter
    it (lazily, once, and only for CPU: on GPU/TPU donation is expected to
    succeed and the warning must stay visible as a regression signal)."""
    global _cpu_donation_warning_filtered
    if not _cpu_donation_warning_filtered:
        _cpu_donation_warning_filtered = True
        if jax.default_backend() == "cpu":
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")


def decode_program(shape: PlanShape, sync: str = "jacobi",
                   backend: str = "jnp",
                   interpret: Optional[bool] = None,
                   idct_impl=None, fuse: str = "none",
                   tiles: Optional[TileConfig] = None) -> DecodeProgram:
    """The shared compiled decoder for a (shape, sync, backend, fuse,
    tiles) bucket.

    Programs are cached at module level: a stream of distinct batches that
    bucket to the same shape reuses one jitted function and compiles only
    on the first batch (plus once more per distinct mesh/rules context,
    which is part of the jit key via ``trace_token``). The autotuned
    :class:`TileConfig` is part of the key, so a tuned bucket and an
    untuned bucket never share (or invalidate) a program, and re-resolving
    the same tiles for a warm bucket is a pure cache hit — zero retraces.
    A custom ``idct_impl`` only affects the pixel stage, so its
    (uncacheable — identity cannot key it) program still *shares* the
    cached entropy stage: streaming with a custom IDCT keeps the
    compile-once coeffs path, and only the pixel jit is per-decoder
    (custom IDCTs pin the unfused pixel chain).
    """
    assert sync in ("jacobi", "faithful", "sequential", "specmap")
    check_backend(backend)
    check_fuse(fuse, backend)
    _filter_cpu_donation_warning()
    key = (shape, sync, backend, interpret, fuse, tiles)
    with _PROGRAMS_LOCK:
        prog = _PROGRAMS.get(key)
        if prog is None:
            prog = _build_program(shape, sync, backend, interpret, None, fuse,
                                  tiles)
            _PROGRAMS[key] = prog
    if idct_impl is None:
        return prog
    custom = DecodeProgram(shape=shape, sync=sync, backend=backend,
                           interpret=interpret, fuse=fuse, tiles=tiles,
                           coeffs_fn=prog.coeffs_fn,
                           step_staged=prog.step_staged, idct_impl=idct_impl)
    custom.crop_fn = _build_crop_fn(custom)
    if shape.uniform:
        custom.pixels_fn = _build_pixels_fn(shape, idct_impl, custom)
    return custom


def clear_decode_programs() -> None:
    """Drop every cached compiled decoder (tests / memory pressure)."""
    with _PROGRAMS_LOCK:
        _PROGRAMS.clear()


def decode_programs() -> List[DecodeProgram]:
    with _PROGRAMS_LOCK:
        return list(_PROGRAMS.values())


def decode_program_stats() -> Dict:
    """Aggregate compile counters for the decode-stats surfaces
    (``launch/report.py``, ``benchmarks/stream.py``)."""
    progs = decode_programs()
    return {
        "programs": len(progs),
        "compiles": sum(p.compiles for p in progs),
        "coeffs_compiles": sum(p.coeffs_traces for p in progs),
        "pixels_compiles": sum(p.pixels_traces for p in progs),
        "buckets": [
            {"bucket": p.shape.label(), "sync": p.sync, "backend": p.backend,
             "fuse": p.fuse, "step": p.step, "compiles": p.compiles}
            for p in progs
        ],
    }


@functools.partial(jax.jit, static_argnums=(1, 2))
def _slice_units(coeffs: Array, n_units: int, trace_token) -> Array:
    """Slice capacity-padded coefficients down to the real unit count,
    keeping the unit axis sharded over the mesh (an eager out-of-jit slice
    would gather the rows to a replicated array). ``trace_token`` keys the
    jit cache on the ambient (mesh, rules) context exactly like the main
    programs; ``n_units`` is constant per bucket for uniform streams, so
    this compiles with the bucket, not with the batch."""
    del trace_token
    return S.shard(coeffs[:n_units], "units", None)


def _build_program(shape: PlanShape, sync: str, backend: str,
                   interpret: Optional[bool], idct_impl,
                   fuse: str = "none",
                   tiles: Optional[TileConfig] = None) -> DecodeProgram:
    stage_w = D.stage_words(shape.chunk_bits) if backend == "jnp" else None
    prog = DecodeProgram(shape=shape, sync=sync, backend=backend,
                         interpret=interpret, fuse=fuse, tiles=tiles,
                         step_staged=stage_w is not None)
    exits_tile = tiles.exits_tile if tiles is not None else None
    write_tile = tiles.write_tile if tiles is not None else None
    if idct_impl is None and backend == "pallas":
        from ..kernels.idct.ops import idct_units
        idct_impl = functools.partial(
            idct_units, tile=tiles.unit_tile if tiles is not None else None,
            interpret=interpret)
    idct_impl = idct_impl or D.idct_units_folded
    prog.idct_impl = idct_impl
    sh = shape
    # static at trace time: identity plans (the default) keep the old
    # shift/direct-scan lowerings; permuted plans use the chunk_prev /
    # chunk_order gather forms (see core/sync.chain_entries)
    permuted = sh.permuted

    @functools.partial(jax.jit, static_argnums=(2,), donate_argnums=(0,))
    def _coeffs(words: Array, dev: Dict[str, Array], trace_token):
        # python side effect => runs once per jax trace, never per call
        prog.coeffs_traces += 1
        # trace_token keys the jit cache on the ambient (mesh, rules)
        # context that S.shard (and the Pallas shard_map path) reads at
        # trace time
        mesh, lane_axis = _lane_mesh_axis(trace_token)
        dev = dict(dev, words=words)
        dev = _shard_lanes(dev)
        if backend == "pallas":
            from ..kernels.huffman import ops as HK
            decode_exits = HK.make_decode_exits(
                s_max=sh.s_max, min_code_bits=sh.min_code_bits,
                chunk_bits=sh.chunk_bits, tile=exits_tile,
                interpret=interpret, mesh=mesh, lane_axis=lane_axis,
            )
        else:
            decode_exits = D.make_decode_exits(
                s_max=sh.s_max, min_code_bits=sh.min_code_bits,
            )
        # the schedule, with jacobi's initial speculative pass (round 1)
        with jax.named_scope(SYNC_PHASE):
            if stage_w is not None:
                # once per batch, for every symbol step of the sync
                # schedule and of the write pass
                lane_words, lane_rows = D.stage_lanes(dev, stage_w)
                dev = dict(dev,
                           lane_words=S.shard(lane_words, None, "chunks"),
                           lane_rows=S.shard(lane_rows, None, "chunks"))
            # loop bounds are *capacities*: inert padding lanes decode nothing
            # and are stable from round zero, so convergence is driven by the
            # real lanes exactly as in the exact-fit program
            if sync == "specmap":
                from .bitstream import MAX_UPM
                # specmap's round counter starts at max_upm (the hypothesis
                # decodes count as rounds), so the verify budget must add it
                # on top of the worst-case truth-propagation chain —
                # n_chunks + 2 alone starved verification by max_upm rounds
                # and could return an unconverged (wrong) parse on long
                # single-segment batches
                res = specmap_sync(
                    dev, s_max=sh.s_max, min_code_bits=sh.min_code_bits,
                    max_upm=MAX_UPM, max_verify=sh.n_chunks + MAX_UPM + 2,
                    decode_exits=decode_exits, permuted=permuted,
                )
            elif sync == "jacobi":
                res = jacobi_sync(
                    dev, s_max=sh.s_max, min_code_bits=sh.min_code_bits,
                    max_rounds=sh.n_chunks + 2, decode_exits=decode_exits,
                    permuted=permuted,
                )
            elif sync == "faithful":
                res = faithful_sync(
                    dev, s_max=sh.s_max, min_code_bits=sh.min_code_bits,
                    seq_chunks=sh.seq_chunks, max_outer=sh.n_sequences + 2,
                    decode_exits=decode_exits, permuted=permuted,
                )
            else:  # sequential: one chunk per segment -> cold start is exact
                exits = decode_exits(dev, DecodeState.cold(dev["chunk_start"]))
                res = SyncResult(exits, jnp.asarray(1), jnp.asarray(True))

        # Output placement (Alg. 1 lines 7-8) + write pass (lines 9-15).
        # The final segment's write clamp comes from the *traced* scalar
        # units_end (the real batch's coefficient count) — pad segments
        # carry the same value in seg_coeff_base, so real lanes see
        # identical clamps whether or not the segment axis is padded.
        with jax.named_scope(WRITE_PHASE):
            bases = D.chunk_write_bases(dev, res.exits.n, permuted=permuted)
            seg_end = jnp.concatenate([
                dev["seg_coeff_base"][1:],
                dev["units_end"][None],
            ])
            write_max = seg_end[dev["chunk_seg"]] - 1
            entries = _entries_from(dev, res.exits, permuted)
            out = jnp.zeros((sh.n_units * 64,), jnp.int32)
            if backend == "pallas":
                from ..kernels.fused import ops as FK
                if fuse == "full" and FK.store_fusible(sh.n_units, mesh):
                    # fuse="full": the stream+scatter collapses into the
                    # in-kernel store; the gate re-evaluates per trace
                    # context (the mesh is part of the jit key), so sharded
                    # traces of the same program fall back to the stream form
                    prog.store_fused = True
                    _, out = FK.decode_coeffs_full(
                        dev, entries, out=out, write_base=bases,
                        write_max=write_max, s_max=sh.s_max,
                        min_code_bits=sh.min_code_bits,
                        chunk_bits=sh.chunk_bits, tile=write_tile,
                        interpret=interpret,
                    )
                else:
                    _, out = HK.decode_coeffs(
                        dev, entries, out=out, write_base=bases,
                        write_max=write_max, s_max=sh.s_max,
                        min_code_bits=sh.min_code_bits,
                        chunk_bits=sh.chunk_bits, tile=write_tile,
                        interpret=interpret, mesh=mesh, lane_axis=lane_axis,
                    )
            else:
                meta = D.chunk_meta(dev)
                _, out = D.decode_span(
                    dev, entries, meta["word_base"], meta["limit"],
                    meta["ts"], meta["upm"], s_max=sh.s_max,
                    min_code_bits=sh.min_code_bits, write=True, out=out,
                    write_base=bases, write_max=write_max,
                    stage=meta["stage"],
                )
            coeffs = out.reshape(sh.n_units, 64)
            coeffs = S.shard(D.undiff_dc(dev, coeffs), "units", None)
        return coeffs, res.rounds, res.converged

    prog.coeffs_fn = _coeffs
    prog.crop_fn = _build_crop_fn(prog)

    if sh.uniform:
        prog.pixels_fn = _build_pixels_fn(sh, idct_impl, prog, fuse=fuse,
                                          tiles=tiles, backend=backend,
                                          interpret=interpret)
    return prog


def _build_pixels_fn(sh: PlanShape, idct_impl, prog: DecodeProgram,
                     fuse: str = "none",
                     tiles: Optional[TileConfig] = None,
                     backend: str = "jnp",
                     interpret: Optional[bool] = None):
    """The jitted IDCT/color stage for one shape (``prog`` receives the
    trace counts — the shared program normally, a per-decoder wrapper when
    a custom ``idct_impl`` bypasses the cache).

    With ``fuse != "none"`` on the Pallas backend the whole stage is the
    single fused pixel kernel (``kernels/fused``) and the per-component
    planes are never materialized (the fn returns ``(None, rgb)``) —
    that is the HBM saving. The fused kernel engages off-mesh for
    3-component uniform batches; on a mesh (the unit axis is sharded and
    MCU tiles straddle shard boundaries) and for grayscale it falls back
    to the unfused chain, bit-identically.
    """
    g = sh.geometry
    u_real = sh.n_images * g.n_units
    comp_grid = tuple((g.mcus_y * g.comp_v[ci], g.mcus_x * g.comp_h[ci])
                      for ci in range(g.n_components))
    if backend == "pallas" and fuse != "none":
        from ..kernels.fused import ops as FK
    else:
        FK = None

    def _pixels_unfused(pixdev, pix_layout, coeffs):
        pixels = idct_impl(coeffs, pixdev["m_matrices"],
                           pixdev["unit_mrow"][:u_real])
        planes = D.assemble_planes(
            pixels, sh.n_images, pix_layout["comp_unit_idx"],
            pix_layout["comp_block_idx"], comp_grid,
        )
        rgb = D.upsample_color(
            planes, g.comp_h, g.comp_v, g.h_max, g.v_max,
            g.height, g.width,
        )
        return planes, rgb

    @functools.partial(jax.jit, static_argnums=(3,))
    def _pixels(pixdev: Dict[str, Array], pix_layout, coeffs: Array,
                trace_token):
        prog.pixels_traces += 1
        mesh, _ = _lane_mesh_axis(trace_token)
        coeffs = S.shard(coeffs, "units", None)
        if FK is not None and mesh is None and FK.pixels_fusible(g):
            prog.pixels_fused = True
            rgb = FK.decode_pixels_fused(
                coeffs, pixdev["m_matrices"], pixdev["unit_mrow"][:u_real],
                geometry=g, n_images=sh.n_images,
                tile=tiles.mcu_tile if tiles is not None else None,
                interpret=interpret,
            )
            return None, rgb
        return _pixels_unfused(pixdev, pix_layout, coeffs)

    return _pixels


def _build_crop_fn(prog: DecodeProgram):
    """The jitted crop stage of ``prog``'s bucket: the IDCT of every unit
    (capacity-sized, so that one program serves every batch of the
    bucket), then :func:`repro.core.decode.crop_resize_units`'s tap gather
    and resize to the static ``out_size`` under :data:`CROP_PHASE`, in the
    one ``jit__pixels`` execution per batch the uniform stage has too. ``cropdev`` holds ``m_matrices``, ``unit_mrow`` and the per-image
    ``layout`` (:func:`repro.core.bitstream.crop_layout`)."""
    @functools.partial(jax.jit, static_argnums=(3, 4))
    def _pixels(cropdev: Dict[str, Array], coeffs: Array, boxes: Array,
                out_size: Tuple[int, int], trace_token):
        prog.pixels_traces += 1
        out_h, out_w = out_size
        coeffs = S.shard(coeffs, "units", None)
        pixels = prog.idct_impl(coeffs, cropdev["m_matrices"],
                                cropdev["unit_mrow"])
        with jax.named_scope(CROP_PHASE):
            return D.crop_resize_units(pixels, cropdev["layout"], boxes,
                                       out_h, out_w)

    return _pixels


def _check_out_size(out_size) -> Tuple[int, int]:
    size = tuple(int(v) for v in out_size)
    if len(size) != 2 or min(size) < 1:
        raise ValueError(f"out_size must be (height, width) >= 1, got "
                         f"{out_size!r}")
    return size


def _shape_covers(shape: PlanShape, plan: BatchPlan) -> bool:
    """Whether ``plan`` can stream through a program compiled for ``shape``
    bit-exactly: every trace constant matches (or relaxes soundly, the
    ``consensus_plan`` argument), and every actual count fits the capacity."""
    if (shape.chunk_bits != plan.chunk_bits
            or shape.seq_chunks != plan.seq_chunks
            or shape.n_lanes != plan.n_lanes
            or shape.permuted != (plan.balance != "none")
            or shape.n_images != plan.n_images
            or shape.uniform != plan.uniform
            or shape.geometry != plan.geometry):
        return False
    if shape.s_max < plan.s_max or shape.min_code_bits > plan.min_code_bits:
        return False
    counts = dict(n_words=len(plan.words), n_luts=plan.luts.shape[0],
                  n_tablesets=plan.ts_upm.shape[0],
                  n_matrices=plan.m_matrices.shape[0],
                  n_segments=plan.n_segments, n_chunks=plan.n_chunks,
                  n_sequences=plan.n_sequences, n_units=plan.total_units)
    return all(v <= getattr(shape, k) for k, v in counts.items())


def _quarantine_shape(plan: BatchPlan, own: PlanShape, sync: str,
                      backend: str, interpret,
                      fuse: str = "none") -> PlanShape:
    """Shape selection for a batch with quarantined images.

    Quarantine removes the damaged images' compressed bits, so the batch's
    own ladder rung can drop *below* the bucket its clean siblings stream
    through — minting a fresh compile key for what is semantically the
    same traffic. Instead, prefer an already-compiled shape (same sync/
    backend key) that covers this plan; the program cache then stays
    exactly as the clean stream left it. Falls back to ``own`` when
    nothing compiled covers the plan.
    """
    best = None
    with _PROGRAMS_LOCK:
        keys = list(_PROGRAMS.keys())
    # tiles are not part of the match: they derive from the shape via the
    # memoized autotuner, so a covering shape resolves to its own tiles
    for (shape, s, b, i, f, _t) in keys:
        if (s, b, i, f) != (sync, backend, interpret, fuse):
            continue
        if not _shape_covers(shape, plan):
            continue
        if best is None or shape.n_words < best.n_words:
            best = shape
    return best if best is not None else own


class ParallelDecoder:
    """A decoder handle for one batch: shared compiled program + this
    batch's padded plan data.

    Construction is cheap after the first batch of a bucket — the jitted
    functions come from the module-level :func:`decode_program` cache keyed
    on the batch's (bucketed) :class:`PlanShape`, so a stream of distinct
    batches compiles once per (bucket, sync, backend) and then only moves
    data. ``bucket=False`` pins the exact-fit shape (no padding), which is
    the pre-bucketing behavior and the oracle the padding tests compare
    against.
    """

    def __init__(self, plan: BatchPlan, sync: str = "jacobi",
                 idct_impl=None, backend: str = "jnp",
                 interpret: Optional[bool] = None,
                 bucket: bool = True, ladder_step: float = LADDER_STEP,
                 shape: Optional[PlanShape] = None,
                 validation: Optional[BatchValidation] = None,
                 fuse: Optional[str] = None,
                 tiles: Optional[TileConfig] = None,
                 batch_id: Optional[int] = None):
        assert sync in ("jacobi", "faithful", "sequential", "specmap")
        check_backend(backend)
        if backend == "pallas" and jax.default_backend() == "tpu":
            check_pallas_compiles(jax.devices()[0])
        # the batch=... every host span of this batch carries
        self.batch_id = next(_BATCH_IDS) if batch_id is None else batch_id
        self.sync = sync
        self.backend = backend
        self.interpret = interpret
        self.validation = validation
        self.fuse = resolve_fuse(fuse, backend)
        # an explicit shape pins the compile bucket from outside — the
        # multi-host consensus path (repro.launch.multihost) hands every
        # process the merged shape so all hosts trace the same program;
        # build_plan_data validates the plan actually fits it
        with _span("repro.pad", self.batch_id):
            if shape is None:
                shape = plan_shape(plan, bucket=bucket, step=ladder_step)
                if (bucket and plan.image_status is not None
                        and (plan.image_status != STATUS_OK).any()):
                    # quarantined batches borrow an existing compiled
                    # bucket that covers them, so quarantine never mints
                    # compile keys
                    shape = _quarantine_shape(plan, shape, sync, backend,
                                              interpret, self.fuse)
            # tile selection is per compile bucket; an explicit `tiles`
            # pins it. autotune_tiles is memoized per bucket, so a
            # quarantine-borrowed shape resolves to the same tiles its
            # clean siblings compiled with
            self.tiles = tiles if tiles is not None else (
                autotune_tiles(shape, backend, self.fuse)
                if backend == "pallas" else None)
            if (shape.s_max, shape.min_code_bits, shape.n_images) != \
                    (plan.s_max, plan.min_code_bits, plan.n_images):
                plan = consensus_plan(plan, shape)
            self.plan = plan
            self.shape = shape
            self.data = build_plan_data(plan, self.shape)
        self.program = decode_program(self.shape, sync=sync, backend=backend,
                                      interpret=interpret,
                                      idct_impl=idct_impl,
                                      fuse=self.fuse, tiles=self.tiles)
        # metadata operands live on device for the handle's lifetime; the
        # words buffer intentionally does NOT (each decode call uploads a
        # fresh copy and donates it to the compiled program)
        with _span("repro.upload", self.batch_id):
            self._dev_rest = {k: jnp.asarray(v)
                              for k, v in self.data.arrays.items()}
            self._pixdev = {"m_matrices": self._dev_rest["m_matrices"],
                            "unit_mrow": self._dev_rest["unit_mrow"]}
            # the crop stage's operands: _pixdev and the per-image layout,
            # uploaded on the first crop decode
            self._cropdev: Optional[Dict[str, Array]] = None
            if plan.uniform:
                self._pix_layout = {
                    "comp_unit_idx": [jnp.asarray(a)
                                      for a in plan.comp_unit_idx],
                    "comp_block_idx": [jnp.asarray(a)
                                       for a in plan.comp_block_idx],
                }

    @property
    def dev(self) -> Dict[str, Array]:
        """The full device pytree (capacity-padded), words included —
        introspection/benchmark surface, not the hot path."""
        return dict(self._dev_rest, words=jnp.asarray(self.data.words))

    def launch_stats(self) -> Dict[str, object]:
        """Kernel-launch and HBM-traffic accounting for this decoder's
        compiled program (benchmark/introspection surface).

        ``pallas_calls`` counts ``pallas_call`` equation sites in the
        abstract jaxpr of the coefficient pass plus (when uniform) the
        pixel pass — the per-trace launch-site count, i.e. how many
        distinct kernels one decode step issues. ``jaxpr_eqns`` is the
        total equation count over the same jaxprs (pallas bodies count
        as one) — the proxy for how many XLA kernel launches the
        unfused stages add between Pallas calls. ``inter_stage_bytes``
        is the analytic HBM round-trip estimate of
        :func:`repro.kernels.fused.ops.fuse_traffic` for intermediates
        the fuse mode eliminates. Tracing is abstract (ShapeDtypeStruct
        operands, no compile/execute); the program's python-side trace
        counters are snapshotted and restored around it.
        """
        from ..kernels.fused import ops as FK

        def _subjaxprs(v):
            if hasattr(v, "eqns"):                   # Jaxpr
                yield v
            elif hasattr(v, "jaxpr"):                # ClosedJaxpr
                yield v.jaxpr
            elif isinstance(v, (tuple, list)):       # e.g. cond branches
                for item in v:
                    yield from _subjaxprs(item)

        def _count(jaxpr):
            calls, eqns = 0, 0
            for eqn in jaxpr.eqns:
                eqns += 1
                if eqn.primitive.name == "pallas_call":
                    calls += 1
                    continue  # kernel bodies are one launch, not N ops
                for v in eqn.params.values():
                    for sub in _subjaxprs(v):
                        c, e = _count(sub)
                        calls, eqns = calls + c, eqns + e
            return calls, eqns

        def _sds(tree):
            return jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)

        prog = self.program
        snap = (prog.coeffs_traces, prog.pixels_traces)
        try:
            token = S.trace_token()
            words_sds = jax.ShapeDtypeStruct(self.data.words.shape,
                                             self.data.words.dtype)
            jx = jax.make_jaxpr(prog.coeffs_fn, static_argnums=(2,))(
                words_sds, _sds(self._dev_rest), token)
            calls, eqns = _count(jx.jaxpr)
            if self.plan.uniform and prog.pixels_fn is not None:
                coeffs_sds = jax.ShapeDtypeStruct(
                    (self.plan.total_units, 64), jnp.int32)
                jp = jax.make_jaxpr(prog.pixels_fn, static_argnums=(3,))(
                    _sds(self._pixdev), _sds(self._pix_layout), coeffs_sds,
                    token)
                c, e = _count(jp.jaxpr)
                calls, eqns = calls + c, eqns + e
        finally:
            prog.coeffs_traces, prog.pixels_traces = snap
        traffic = FK.fuse_traffic(self.shape,
                                  store_fused=prog.store_fused,
                                  pixels_fused=prog.pixels_fused)
        return {"pallas_calls": calls, "jaxpr_eqns": eqns,
                "fuse": self.fuse,
                "store_fused": prog.store_fused,
                "pixels_fused": prog.pixels_fused, **traffic}

    # -- constructors -------------------------------------------------------
    @classmethod
    def from_bytes(cls, blobs: Sequence[bytes], chunk_bits: int = 1024,
                   seq_chunks: int = 32, sync: str = "jacobi",
                   idct_impl=None, use_kernels: bool = False,
                   backend: Optional[str] = None,
                   interpret: Optional[bool] = None,
                   balance: str = "none",
                   lanes: Optional[int] = None,
                   bucket: bool = True,
                   validate: bool = False,
                   fuse: Optional[str] = None,
                   tiles: Optional[TileConfig] = None) -> "ParallelDecoder":
        """Parse, plan, and compile a decoder for one batch.

        ``fuse`` selects the Pallas fusion mode ("none" | "post" | "full",
        module docstring); ``tiles`` pins an explicit
        :class:`repro.kernels.autotune.TileConfig` instead of the
        autotuned/default one. Both are bit-identity-preserving knobs.

        ``balance`` selects the plan-time lane partitioner
        (:func:`repro.dist.plan.balance_lanes`): ``"roundrobin"`` or
        ``"lpt"`` redistributes whole sequences of chunks over ``lanes``
        mesh lanes (default: ``jax.device_count()``) so a skewed batch does
        not concentrate one image's work on one device. Bit-identical to
        ``"none"`` on every schedule and backend.

        ``bucket`` (default) rounds the plan's capacities up the geometric
        ladder so a stream of distinct batches shares compiled programs;
        ``bucket=False`` compiles for the exact batch extents.

        ``validate`` turns on resilient decode: damaged blobs never raise.
        Each blob is classified (:func:`repro.core.bitstream.validate_batch`)
        and rejected images are replaced by inert quarantine lanes while
        recovered ones decode their surviving restart segments — the rest
        of the batch decodes bit-identically to a clean batch. The
        resulting :class:`DecodeOutput` carries the per-image ``status``.
        """
        from ..dist import plan as DP
        DP.check_balance(balance)
        backend = resolve_backend(backend, use_kernels)
        batch = next(_BATCH_IDS)
        with _span("repro.from_bytes", batch):
            validation = None
            with _span("repro.parse", batch):
                if validate:
                    validation = validate_batch(blobs)
                    unstuffed = [(r.clean, r.rst_bits)
                                 for r in validation.reports
                                 if r.clean is not None]
                else:
                    images = [parse_jpeg(b) for b in blobs]
                    unstuffed = [unstuff_scan(img.scan_data)
                                 for img in images]
            with _span("repro.plan", batch):
                if sync == "sequential" and unstuffed:
                    chunk_bits = _sequential_chunk_bits(unstuffed,
                                                        bucket=bucket)
                if validate:
                    plan = build_batch_plan(blobs, chunk_bits=chunk_bits,
                                            seq_chunks=seq_chunks,
                                            validation=validation)
                else:
                    plan = build_batch_plan(blobs, chunk_bits=chunk_bits,
                                            seq_chunks=seq_chunks,
                                            parsed=images,
                                            unstuffed=unstuffed)
                if balance != "none":
                    n_lanes = (int(lanes) if lanes is not None
                               else jax.device_count())
                    plan = DP.balance_lanes(plan, n_lanes, balance)
            return cls(plan, sync=sync, idct_impl=idct_impl,
                       backend=backend, interpret=interpret, bucket=bucket,
                       validation=validation, fuse=fuse, tiles=tiles,
                       batch_id=batch)

    def _crop_boxes(self, crops=None) -> np.ndarray:
        """(n_images of the bucket, 4) int32 (top, left, height, width):
        ``crops`` (one box per image of the batch, in frame pixels) checked
        against each frame, or each whole frame where ``crops`` is None.
        Quarantined images, and rows past the batch, get a 1x1 box."""
        geoms = self.plan.image_geometry or ()
        boxes = np.tile(np.array([0, 0, 1, 1], np.int64),
                        (self.shape.n_images, 1))
        given = None
        if crops is not None:
            given = np.asarray(crops)
            if (given.shape != (len(geoms), 4)
                    or not np.issubdtype(given.dtype, np.integer)):
                raise ValueError(
                    f"crops: one (top, left, height, width) box of integers "
                    f"per image, shape ({len(geoms)}, 4); got "
                    f"{given.dtype} {given.shape}")
        for i, g in enumerate(geoms):
            if g is None:
                continue
            if given is None:
                boxes[i] = (0, 0, g.height, g.width)
                continue
            top, left, h, w = (int(v) for v in given[i])
            if (min(top, left) < 0 or min(h, w) < 1 or top + h > g.height
                    or left + w > g.width):
                raise ValueError(f"crop {i}: box {(top, left, h, w)} does "
                                 f"not fit its {g.width}x{g.height} frame")
            boxes[i] = (top, left, h, w)
        return boxes.astype(np.int32)

    # -- execution ------------------------------------------------------------
    def coefficients(self) -> DecodeOutput:
        with _span("repro.decode", self.batch_id):
            return self._coefficients()[0]

    def _coefficients(self) -> Tuple[DecodeOutput, Array]:
        """(the output with the real coefficients, the capacity-sized
        coefficients the crop stage reads)."""
        # numpy in => jit transfers a fresh device buffer it may donate;
        # the capacity-sized output is sliced to the real unit count
        # host-side (a python int, so no retrace)
        batch, plan, shape = self.batch_id, self.plan, self.shape
        with _span("repro.dispatch.entropy", batch, s_max=shape.s_max,
                   lanes=shape.n_chunks, lanes_live=plan.n_chunks,
                   units=plan.total_units, units_cap=shape.n_units,
                   step=self.program.step):
            coeffs_cap, rounds, conv = self.program.call_coeffs(
                self.data.words, self._dev_rest, S.trace_token())
        coeffs = coeffs_cap
        if coeffs.shape[0] != plan.total_units:
            with _span("repro.slice", batch):
                coeffs = _slice_units(coeffs, plan.total_units,
                                      S.trace_token())
        # the host waits here for the entropy stage to finish
        with _span("repro.rounds", batch) as span:
            rounds, conv = int(rounds), bool(conv)
            span.set_metadata(rounds=rounds)
        return DecodeOutput(coeffs, None, None, rounds, conv, plan,
                            status=plan.image_status,
                            validation=self.validation), coeffs_cap

    def decode(self, emit: str = "rgb", crops=None,
               out_size: Tuple[int, int] = CROP_SIZE) -> DecodeOutput:
        """Decode the batch. ``emit``: "coeffs"; "rgb" (uniform batches)
        or "planes"; or "crop", for any batch: ``DecodeOutput.crop`` is
        (B, out_h, out_w, 3) uint8, each image's ``crops`` box (top,
        left, height, width in frame pixels, checked against its frame;
        None: the whole frame) resized to ``out_size``
        by bilinear interpolation with half-pixel centres, the taps
        clamped to the box, no antialiasing, rounded once, from the RGB
        the uniform path computes (a gray frame's plane in all three
        channels, a quarantined image's crop zero)."""
        if emit == "crop":
            out_size = _check_out_size(out_size)
            boxes = self._crop_boxes(crops)
        with _span("repro.decode", self.batch_id):
            out, coeffs_cap = self._coefficients()
            if emit == "coeffs":
                return out
            if emit == "crop":
                return self._crop(out, coeffs_cap, boxes, out_size)
            if not self.plan.uniform:
                if self.plan.image_status is not None:
                    # validated decode: a batch can lose pixel-stage
                    # uniformity to quarantine (e.g. every image rejected)
                    # — degrade to coefficients instead of throwing, the
                    # status array tells the caller why
                    return out
                raise NotImplementedError(
                    "pixel stage requires a geometry-uniform batch; decode "
                    "images with mixed geometry via bucketing in "
                    "repro.data.jpeg_pipeline"
                )
            with _span("repro.dispatch.pixels", self.batch_id,
                       layout="uniform", crops=0,
                       units=self.plan.total_units,
                       units_cap=self.shape.n_units):
                planes, rgb = self.program.call_pixels(
                    self._pixdev, self._pix_layout, out.coeffs,
                    S.trace_token())
        return dataclasses.replace(
            out, planes=planes, rgb=rgb if emit == "rgb" else None
        )

    def _crop(self, out: DecodeOutput, coeffs_cap: Array, boxes: np.ndarray,
              out_size: Tuple[int, int]) -> DecodeOutput:
        plan = self.plan
        with _span("repro.dispatch.pixels", self.batch_id,
                   layout="uniform" if plan.uniform else "mixed",
                   crops=plan.n_images, units=plan.total_units,
                   units_cap=self.shape.n_units):
            if self._cropdev is None:
                self._cropdev = dict(self._pixdev, layout=jnp.asarray(
                    crop_layout(plan, self.shape.n_images)))
            crop = self.program.call_crop(out_size, self._cropdev,
                                          coeffs_cap, jnp.asarray(boxes),
                                          S.trace_token())
        return dataclasses.replace(out, crop=crop)

    def decode_on(self, mesh, emit: str = "rgb",
                  rules: Optional[Dict] = None, crops=None,
                  out_size: Tuple[int, int] = CROP_SIZE) -> DecodeOutput:
        """Decode with chunk lanes and output units sharded over the mesh's
        data axis — the multi-device batch-decode path. Bit-identical to
        :meth:`decode`; only the work placement changes.

        The decoder is purely data-parallel (no model dimension), so by
        default a multi-axis mesh is flattened to a 1-D lane mesh over
        the same devices: every chip becomes a lane worker, and the
        partial replication a 2-D mesh would induce — which the CPU SPMD
        partitioner has been observed to mis-compile for this scatter-
        heavy program — never arises. Any mesh, whatever its axis types,
        is accepted. Caller-supplied ``rules`` name the
        axes of ``mesh`` itself and therefore require a 1-D mesh: any
        multi-axis mesh would reintroduce that partial replication, so
        the combination is rejected rather than silently re-mapped.
        """
        if rules is not None and len(mesh.axis_names) > 1:
            raise ValueError(
                "decode_on(rules=...) requires a 1-D mesh; flatten the mesh "
                "(e.g. Mesh(mesh.devices.reshape(-1), ('data',))) or omit "
                "rules to let the decoder flatten it"
            )
        # the lane mesh is rebuilt with an Auto axis: jax.make_mesh now
        # defaults to Explicit axes, which the GSPMD sharding constraints
        # of the decode program do not accept
        axis = mesh.axis_names[0] if rules is not None else "data"
        mesh = jax.sharding.Mesh(mesh.devices.reshape(-1), (axis,),
                                 axis_types=(jax.sharding.AxisType.Auto,))
        if rules is None:
            rules = _decode_rules(mesh)
        with mesh, S.logical_rules(rules):
            return self.decode(emit=emit, crops=crops, out_size=out_size)


def _entries_from(dev, exits: DecodeState, permuted: bool = True) -> DecodeState:
    from .sync import chain_entries

    return chain_entries(dev, exits, permuted)


def decode_batch(
    blobs: Sequence[bytes],
    chunk_bits: int = 1024,
    seq_chunks: int = 32,
    sync: str = "jacobi",
    emit: str = "rgb",
    mesh=None,
    backend: Optional[str] = None,
    use_kernels: bool = False,
    interpret: Optional[bool] = None,
    balance: str = "none",
    bucket: bool = True,
    validate: bool = False,
    fuse: Optional[str] = None,
    crops=None,
    out_size: Tuple[int, int] = CROP_SIZE,
) -> DecodeOutput:
    """One-shot convenience wrapper (builds the plan + compiles + decodes).

    ``emit="crop"`` gives each image's ``crops`` box resized to
    ``out_size`` for any batch, mixed geometries included
    (:meth:`ParallelDecoder.decode`).

    With ``mesh``, the decode runs under ``dist.sharding.logical_rules``
    with the chunk lanes sharded over the data axis: one compiled program,
    work divided across every device in the mesh.

    ``backend`` selects the decode implementation ("jnp" or "pallas" — see
    the module docstring); the output is bit-identical either way.

    ``balance`` ("none" | "roundrobin" | "lpt") applies the plan-time lane
    partitioner over the mesh's device count, so a skewed batch (one big
    JPEG + many small ones) spreads its sequences across every device
    instead of concentrating them in bitstream order. Also bit-identical.

    ``bucket`` pads the plan to ladder capacities so repeated calls with
    similar-sized batches reuse the module-level compiled-program cache.

    ``fuse`` ("none" | "post" | "full", Pallas backend only) selects how
    much of the post-entropy pipeline runs as a single fused kernel; the
    default resolves per backend (see ``repro.kernels.backend``). Fused
    decodes skip materializing the per-component planes
    (``DecodeOutput.planes is None``) — that is the saved HBM traffic.
    """
    dec = ParallelDecoder.from_bytes(
        blobs, chunk_bits=chunk_bits, seq_chunks=seq_chunks, sync=sync,
        backend=backend, use_kernels=use_kernels, interpret=interpret,
        balance=balance,
        lanes=(mesh.devices.size if mesh is not None else None),
        bucket=bucket, validate=validate, fuse=fuse,
    )
    if mesh is None:
        return dec.decode(emit=emit, crops=crops, out_size=out_size)
    return dec.decode_on(mesh, emit=emit, crops=crops, out_size=out_size)
