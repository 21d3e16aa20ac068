"""Decode-pipeline invariants declared as data.

This module is the single home for the numeric and lowering contracts
that the rest of the repo previously enforced with scattered one-off
asserts:

* **Checked int32 arithmetic** — :func:`checked_int32` /
  :func:`checked_coeff_capacity` generalize PR 3's ad-hoc
  ``total_units * 64 >= 2**31`` guard in ``build_batch_plan``. The same
  helpers back the *runtime* guards in ``core.bitstream`` (plan build,
  shape bucketing, multi-host shape merge) and the *static* lattice the
  jaxpr contract checker evaluates over whole shape grids.

* **An int32 interval lattice** — :class:`IntRange` plus
  :func:`plan_index_ranges`, which bounds every index expression the
  compiled decoder computes in int32 (write offsets, bit positions,
  word fetches) as a function of a ``PlanShape``'s capacities.

* **Lane-graph liveness** — :data:`IDENTITY_LIVE_OK`, the per-sync
  table of which lane-graph operands (``chunk_prev`` / ``chunk_next`` /
  ``lane_perm`` / ``chunk_order``) an *identity* (``permuted=False``)
  program may consume. The jaxpr checker taints these inputs and walks
  the trace; a gather/scatter indexed by a non-allowed lane-graph value
  in an identity program is the PR 3 "gather creep" regression.

Import policy: **stdlib only**. ``core.bitstream`` imports this module
for its runtime guards, so it must not import jax, numpy, or anything
under ``repro`` — shape arguments are duck-typed on attribute names.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, Mapping

INT32_MIN = -(2 ** 31)
INT32_MAX = 2 ** 31 - 1


class ContractViolation(ValueError):
    """A decode-pipeline contract does not hold.

    Subclasses ``ValueError`` so pre-existing callers of the runtime
    guards (which raised plain ``ValueError``) keep working.
    """


def checked_int32(value: int, what: str, hint: str = "") -> int:
    """Return ``value`` if it fits a signed 32-bit int, else raise.

    ``what`` names the quantity in the error ("write index bound", ...);
    ``hint`` optionally tells the caller how to get back under the limit
    ("split the batch below N units").
    """
    if not INT32_MIN <= value <= INT32_MAX:
        msg = (f"{what} = {value} overflows int32 "
               f"[{INT32_MIN}, {INT32_MAX}]")
        if hint:
            msg += f". {hint}"
        raise ContractViolation(msg)
    return value


# Write-pass headroom: one chunk's speculative decode can overshoot its
# segment's true coefficient range by at most s_max symbols x 64
# coefficients, plus a final zero-run of up to 63 positions. The write
# index `write_base + st.n + o.run` must stay in int32 through that
# overshoot *before* the `idx < write_max` clamp compares it.
def write_overshoot(s_max: int) -> int:
    return 64 * s_max + 63


def checked_coeff_capacity(total_units: int, s_max: int = 0) -> int:
    """The generalized PR 3 guard: dense coefficient indexing fits int32.

    ``total_units * 64`` is the dense coefficient extent
    (``seg_coeff_base`` entries, the ``units_end`` write clamp, and the
    write-buffer sentinel all reach it). With ``s_max`` given, the bound
    also covers the speculative single-chunk overshoot past the final
    segment end (see :func:`write_overshoot`) — the largest int32 the
    compiled write pass can actually compute.
    """
    units_end = total_units * 64
    hint = (f"Split the batch below {INT32_MAX // 64} units.")
    checked_int32(units_end, f"batch of {total_units} data units -> "
                  f"{units_end} dense coefficients", hint)
    if s_max:
        checked_int32(units_end + write_overshoot(s_max),
                      f"write-index bound units_end + 64*s_max + 63 "
                      f"({units_end} + {write_overshoot(s_max)})", hint)
    return total_units


def check_shape_capacities(shape) -> None:
    """Runtime guard over a PlanShape's *capacities* (not actual counts).

    ``build_batch_plan`` checks the actual unit count, but bucketing
    rounds capacities UP a geometric ladder — a batch whose true count
    passes the runtime guard can still land in a bucket whose padded
    capacity products overflow. Called from ``plan_shape`` and
    ``merge_plan_shapes`` so no compiled program ever exists for an
    overflowing shape. Duck-typed: ``shape`` needs ``n_units``,
    ``s_max``, ``n_words``, ``n_chunks``.
    """
    hint = "Use a smaller batch or a finer bucket ladder."
    # dense coefficient extent + speculative write overshoot
    checked_int32(shape.n_units * 64 + write_overshoot(shape.s_max),
                  f"bucketed write-index bound n_units*64 + 64*s_max + 63 "
                  f"({shape.n_units}*64 + {write_overshoot(shape.s_max)})",
                  hint)
    # bit positions: p ranges over [0, 32*n_words] and one extra symbol
    # advance (<= 31 code+magnitude bits) past the limit check
    checked_int32(shape.n_words * 32 + 63,
                  f"bit-position bound n_words*32 + 63 ({shape.n_words}*32)",
                  hint)
    # lane axis: chunk ids and the chain permutations are int32
    checked_int32(shape.n_chunks, f"lane capacity n_chunks", hint)


@dataclasses.dataclass(frozen=True)
class IntRange:
    """A closed integer interval [lo, hi] — the abstract value of the
    overflow lattice. Plan index expressions only need +, *, and constant
    lifting; the kernel verifier (analysis/kernel_check.py) additionally
    uses the sub/mod/clamp/shift/mask transfer functions and the
    join/meet lattice operations to abstract-interpret kernel jaxprs."""

    lo: int
    hi: int

    def __post_init__(self):
        if self.lo > self.hi:
            raise ValueError(f"empty IntRange [{self.lo}, {self.hi}]")

    @staticmethod
    def const(n: int) -> "IntRange":
        return IntRange(n, n)

    @property
    def is_const(self) -> bool:
        return self.lo == self.hi

    def __add__(self, other: "IntRange") -> "IntRange":
        return IntRange(self.lo + other.lo, self.hi + other.hi)

    def __sub__(self, other: "IntRange") -> "IntRange":
        return IntRange(self.lo - other.hi, self.hi - other.lo)

    def __mul__(self, other: "IntRange") -> "IntRange":
        ps = (self.lo * other.lo, self.lo * other.hi,
              self.hi * other.lo, self.hi * other.hi)
        return IntRange(min(ps), max(ps))

    def join(self, other: "IntRange") -> "IntRange":
        """Least upper bound (interval hull)."""
        return IntRange(min(self.lo, other.lo), max(self.hi, other.hi))

    def meet(self, other: "IntRange") -> "IntRange":
        """Intersection; raises ValueError when the intervals are disjoint
        (an unreachable abstract state — callers decide what that means)."""
        return IntRange(max(self.lo, other.lo), min(self.hi, other.hi))

    def contains(self, other: "IntRange") -> bool:
        return self.lo <= other.lo and other.hi <= self.hi

    def mod(self, other: "IntRange") -> "IntRange":
        """Transfer function for C-style truncated remainder (lax.rem):
        the result has the dividend's sign and |r| < |divisor|."""
        m = max(abs(other.lo), abs(other.hi))
        if m == 0:
            raise ValueError("IntRange.mod by an interval containing only 0")
        if self.is_const and other.is_const and other.lo != 0:
            r = abs(self.lo) % abs(other.lo)
            r = -r if self.lo < 0 else r
            return IntRange.const(r)
        lo = 0 if self.lo >= 0 else -(m - 1)
        hi = 0 if self.hi <= 0 else (m - 1)
        # the remainder also never exceeds the dividend itself
        return IntRange(max(lo, self.lo) if self.lo < 0 else lo,
                        min(hi, self.hi) if self.hi > 0 else hi)

    def clamp_min(self, other: "IntRange") -> "IntRange":
        """Transfer for max(self, other) — the 'clamp from below' of
        jnp.maximum / the lower half of jnp.clip."""
        return IntRange(max(self.lo, other.lo), max(self.hi, other.hi))

    def clamp_max(self, other: "IntRange") -> "IntRange":
        """Transfer for min(self, other) — the 'clamp from above' of
        jnp.minimum / the index clamps in the lane-window pre-gather."""
        return IntRange(min(self.lo, other.lo), min(self.hi, other.hi))

    def clamp(self, lo: int, hi: int) -> "IntRange":
        """min(max(self, lo), hi) — full jnp.clip transfer."""
        return self.clamp_min(IntRange.const(lo)).clamp_max(IntRange.const(hi))

    def shift_right(self, bits: "IntRange") -> "IntRange":
        """Arithmetic >> with a non-negative shift interval (monotone)."""
        if bits.lo < 0:
            raise ValueError(f"negative shift interval {bits}")
        return IntRange(min(self.lo >> bits.lo, self.lo >> bits.hi),
                        max(self.hi >> bits.lo, self.hi >> bits.hi))

    def bit_and_mask(self, mask: int) -> "IntRange":
        """Transfer for ``x & mask`` with a constant mask >= 0: the result
        lands in [0, mask] regardless of x's sign (two's complement)."""
        if mask < 0:
            raise ValueError(f"negative mask {mask}")
        if self.lo >= 0:
            return IntRange(0, min(self.hi, mask))
        return IntRange(0, mask)

    def scale(self, k: int) -> "IntRange":
        """Multiply by a non-negative constant — the BlockSpec tile-origin
        map ``index_map(i) * tile`` evaluated over a grid interval."""
        if k < 0:
            raise ValueError(f"negative tile scale {k}")
        return IntRange(self.lo * k, self.hi * k)

    @property
    def fits_int32(self) -> bool:
        return INT32_MIN <= self.lo and self.hi <= INT32_MAX

    def check(self, what: str) -> "IntRange":
        checked_int32(self.lo, f"{what} (lower bound)")
        checked_int32(self.hi, f"{what} (upper bound)")
        return self


def tile_origin_range(block_index: IntRange, tile: int) -> IntRange:
    """BlockSpec tile origins over a grid interval.

    A Pallas ``BlockSpec(block_shape, index_map)`` materializes, for grid
    step ``i``, the element range ``[index_map(i) * tile,
    index_map(i) * tile + tile)`` along each dimension. Given the interval
    of ``index_map(i)`` over the whole grid (``i`` in ``[0, grid-1]``),
    this returns the interval of tile *origins*; the last touched element
    is ``origin.hi + tile - 1``.
    """
    return block_index.scale(tile)


def check_block_cover(dim: int, tile: int, block_index: IntRange,
                      what: str) -> None:
    """The tiling contract for one (operand dimension, BlockSpec) pair.

    Three sub-claims, each a silent-corruption class on its own:

    * **in-bounds** — the highest tile ends at or before the dimension end
      (a tile past the end reads/writes Pallas' padding, not the operand);
    * **cover** — every element is reached by some tile (a grid that stops
      short silently truncates the remainder: output rows stay zero);
    * **divisibility** — ``dim % tile == 0``; with blocked indexing a
      non-dividing tile can only pad or truncate, never fit.
    """
    origins = tile_origin_range(block_index, tile)
    if origins.lo != 0:
        raise ContractViolation(
            f"{what}: lowest tile origin {origins.lo} != 0 "
            f"(block index {block_index.lo}..{block_index.hi} x tile {tile})")
    if origins.hi + tile > dim:
        raise ContractViolation(
            f"{what}: highest tile [{origins.hi}, {origins.hi + tile}) "
            f"overruns dimension {dim} "
            f"(block index {block_index.lo}..{block_index.hi} x tile {tile})")
    if origins.hi + tile < dim:
        raise ContractViolation(
            f"{what}: tiles cover only [0, {origins.hi + tile}) of "
            f"dimension {dim} — silent remainder truncation "
            f"(block index {block_index.lo}..{block_index.hi} x tile {tile})")
    if dim % tile:
        raise ContractViolation(
            f"{what}: tile {tile} does not divide dimension {dim}")


def plan_index_ranges(shape, model: str = "valid") -> Dict[str, IntRange]:
    """Bound every int32 index expression of the compiled decoder.

    Returns ``{expression name: IntRange}`` as a function of the shape's
    capacities, under one of two bitstream models:

    ``model="valid"``
        Well-formed (or validated/masked) bitstreams: every chunk's
        converged exit count equals the true symbol count, so a write
        base never exceeds its segment's coefficient range and only the
        *active* chunk overshoots speculatively (by
        :func:`write_overshoot`).

    ``model="adversarial"``
        No convergence assumption: a damaged segment's chunks can each
        exit with up to ``64 * s_max`` phantom coefficient positions, so
        the cumulative write base of a segment spanning ``k`` chunks
        grows as ``k * 64 * s_max``. :func:`max_damaged_segment_chunks`
        gives the largest ``k`` that stays safe; ``validate_batch``'s
        segment masking keeps real damaged inputs inside the valid
        model, so this bound is the residual exposure for *unvalidated*
        adversarial feeds (documented in docs/ANALYSIS.md).
    """
    if model not in ("valid", "adversarial"):
        raise ValueError(f"unknown lattice model {model!r}")
    units_end = IntRange(0, shape.n_units * 64)
    over = IntRange(0, write_overshoot(shape.s_max))
    if model == "valid":
        write_base = units_end
    else:
        phantom = IntRange(0, shape.n_chunks * 64 * shape.s_max)
        write_base = units_end + phantom
    ranges = {
        "units_end": units_end,
        "seg_coeff_base": units_end,
        "write_base": write_base,
        # idx = write_base + st.n (<= 64*s_max) + o.run (<= 63)
        "write_index": write_base + over,
        # bit position: within [0, 32*n_words] plus one symbol advance
        "bit_position": IntRange(0, shape.n_words * 32 + 63),
        # word fetch: word_base + (p >> 5) + 1
        "word_fetch": IntRange(0, shape.n_words + (63 >> 5) + 1),
        "lane_index": IntRange(0, shape.n_chunks - 1),
        "sentinel": IntRange(0, shape.n_units * 64),
    }
    return ranges


def check_index_lattice(shape, model: str = "valid") -> None:
    """Assert every lattice range of ``shape`` fits int32."""
    for name, rng in plan_index_ranges(shape, model=model).items():
        rng.check(f"{model}-model {name} at capacities of {_label(shape)}")


def max_damaged_segment_chunks(shape) -> int:
    """Largest chunk count of one unvalidated damaged segment for which
    the adversarial write base still cannot wrap int32."""
    per_chunk = 64 * shape.s_max
    head = INT32_MAX - shape.n_units * 64 - write_overshoot(shape.s_max)
    return max(0, head // per_chunk)


def _label(shape) -> str:
    lab = getattr(shape, "label", None)
    return lab() if callable(lab) else repr(shape)


# ---------------------------------------------------------------------------
# Lane-graph liveness (the PR 3 "gather creep" contract)
# ---------------------------------------------------------------------------

#: The plan operands that encode the lane permutation / chain adjacency.
#: On identity plans (``permuted=False``) the lowerings must use the
#: shift/direct-scan forms instead of gathering through these arrays —
#: gathers here become all-gathers under SPMD partitioning and kill the
#: identity fast path.
LANE_GRAPH_ARRAYS = ("chunk_prev", "chunk_next", "lane_perm", "chunk_order")

#: Per sync schedule: the lane-graph operands an *identity* program may
#: legitimately consume. ``faithful`` walks the chain through
#: ``chunk_next`` by construction (its inter-round scatter is the
#: algorithm, not creep); the other three schedules must not touch the
#: graph at all when ``permuted=False``.
IDENTITY_LIVE_OK: Mapping[str, frozenset] = {
    "jacobi": frozenset(),
    "faithful": frozenset({"chunk_next"}),
    "sequential": frozenset(),
    "specmap": frozenset(),
}

#: Primitives whose index operand being lane-graph-tainted constitutes a
#: violation on identity plans (operand 0 is data, operand 1 indices).
INDEXED_ACCESS_PRIMS = ("gather", "scatter", "scatter-add")

#: Primitive-name fragments that mean "leaves the device mid-trace".
#: None of these may appear anywhere in a decode program's jaxpr.
HOST_CALLBACK_PRIMS = ("callback", "infeed", "outfeed", "host_local_array",
                       "debug_print")


#: The jaxpr-level contracts, as data: name -> human description.
#: ``jaxpr_check`` iterates this to report coverage; docs/ANALYSIS.md
#: renders it as the contract catalog.
JAXPR_CONTRACTS: Dict[str, str] = {
    "identity-lane-graph": (
        "identity (permuted=False) programs never gather/scatter through "
        "lane-graph operands outside IDENTITY_LIVE_OK[sync]; permuted "
        "programs must (flip check)"),
    "no-f64": "no float64 value anywhere in the traced decode program",
    "no-host-callback": (
        "no host callback / infeed / outfeed primitive in the hot path"),
    "words-donated": (
        "the words buffer is declared donated (donate_argnums), never "
        "aliased straight to an output, and the donation survives SPMD "
        "lowering (mesh StableHLO marks words jax.buffer_donor; "
        "single-device lowerings legitimately drop it — words matches no "
        "output shape, so only the partitioned path can consume it)"),
    "collective-accounting": (
        "collective instruction counts in compiled SPMD HLO agree with "
        "dist.collectives byte accounting (same kinds, bytes > 0 wherever "
        "count > 0)"),
    "int32-lattice": (
        "plan index arithmetic cannot overflow int32 at the shape's "
        "(bucketed) capacities under the valid-bitstream model, and the "
        "adversarial headroom bound is reported"),
}


# ---------------------------------------------------------------------------
# Kernel memory-safety contracts (analysis/kernel_check.py)
# ---------------------------------------------------------------------------

#: JPEG Huffman codewords are at most 16 bits (ITU T.81 B.1.1.5); the
#: 5-bit `clen` LUT field can encode up to 31, so this documented bound
#: is strictly tighter than the field width — it is what proves the
#: per-symbol bit advance (clen + size <= 31) stays inside the lane's
#: `chunk_words + 2` word window. kernel_check cross-checks the packing
#: offsets below against repro.jpeg.tables at verification time.
MAX_CODE_BITS = 16
MAX_MAG_BITS = 15
#: Largest bit advance of one decoded symbol: codeword + magnitude bits.
MAX_SYMBOL_ADVANCE = MAX_CODE_BITS + MAX_MAG_BITS


@dataclasses.dataclass(frozen=True)
class FieldRange:
    """Documented interval of a bit-packed table-entry field: after
    ``(entry >> shift) & mask`` the value lies in [lo, hi]. ``shift`` and
    ``mask`` identify the field in the kernel's arithmetic; [lo, hi] is
    the *semantic* bound the table builder guarantees (possibly tighter
    than the field width, e.g. clen <= 16 in a 5-bit field)."""

    shift: int
    mask: int
    lo: int
    hi: int
    why: str = ""


#: The decode-LUT entry layout (repro.jpeg.tables.pack_lut_entry).
LUT_FIELD_RANGES = (
    FieldRange(0, 0x1F, 0, MAX_CODE_BITS,
               "codeword length; 0 marks an invalid window"),
    FieldRange(5, 0xF, 0, MAX_MAG_BITS, "magnitude size (bits)"),
    FieldRange(10, 0xF, 0, 15, "zero run length"),
)


@dataclasses.dataclass(frozen=True)
class OperandContract:
    """Documented value intervals for one kernel operand's *contents*.

    ``ranges`` maps a trailing-dimension column index to a callable
    ``params -> (lo, hi)`` (the key ``None`` bounds every element);
    ``fields`` declares bit-packed sub-fields (see :class:`FieldRange`).
    Operands without either entry carry no content contract — their
    values may be anything their dtype allows, and any index derived
    from them must be clamped before use.
    """

    role: str
    ranges: Mapping = dataclasses.field(default_factory=dict)
    fields: tuple = ()


@dataclasses.dataclass(frozen=True)
class KernelContract:
    """The verifier's per-kernel input contract, declared as data.

    ``operands`` follow the pallas_call operand order. ``params`` used by
    the range callables are supplied by kernel_check from the traced
    cell's statics: chunk_bits, s_max, max_upm, n_luts, tile, ...
    """

    entry: str          # dotted path of the traced wrapper (docs/reports)
    description: str
    operands: tuple


_HUFFMAN_OPERANDS = (
    # (TILE, W) uint32 word windows: arbitrary bitstream content
    OperandContract("words"),
    # flattened (L*65536,) decode LUTs: bit-packed entries
    OperandContract("luts", fields=LUT_FIELD_RANGES),
    # (TILE, 2*MAX_UPM) LUT row schedule: row ids into the LUT table
    OperandContract("rows", ranges={None: lambda p: (0, p["n_luts"] - 1)}),
    # (TILE, 4) [p_entry, u, z, limit], all chunk-local:
    #   p_entry — a lane's entry is its own chunk start (cold/speculative
    #     states) or its predecessor's exit, which stops within one symbol
    #     advance of its limit == this chunk's start;
    #   limit   — chunk limits are clamped to the chunk's bit capacity.
    OperandContract("meta", ranges={
        0: lambda p: (0, p["chunk_bits"] + MAX_SYMBOL_ADVANCE - 1),
        1: lambda p: (0, p["max_upm"] - 1),
        2: lambda p: (0, 63),
        3: lambda p: (0, p["chunk_bits"]),
    }),
    # (TILE, 1) units-per-MCU, floored to 1 for inert lanes
    OperandContract("upm", ranges={None: lambda p: (1, p["max_upm"])}),
)

KERNEL_CONTRACTS: Dict[str, KernelContract] = {
    "huffman-exits": KernelContract(
        entry="repro.kernels.huffman.huffman.decode_exits_pallas",
        description=(
            "sync-phase subsequence decode: LUT gathers, word-window "
            "fetches and the (p,u,z,n) state loop stay inside the "
            "(TILE, chunk_words+2) window and the L*65536 LUT"),
        operands=_HUFFMAN_OPERANDS,
    ),
    "huffman-write": KernelContract(
        entry="repro.kernels.huffman.huffman.decode_coeffs_pallas",
        description=(
            "write pass: the exits contract plus the per-symbol "
            "(pos, val) stream stores at pl.ds(i, 1) staying inside "
            "(TILE, s_max)"),
        operands=_HUFFMAN_OPERANDS,
    ),
    "idct": KernelContract(
        entry="repro.kernels.idct.idct.fused_idct",
        description=(
            "fused dequant+IDCT matmul: no data-dependent indexing; "
            "the contract is pure tiling (TILE_U x 64 tiles exactly "
            "cover the padded unit axis)"),
        operands=(OperandContract("coeffs"), OperandContract("rows"),
                  OperandContract("m2")),
    ),
    "color": KernelContract(
        entry="repro.kernels.color.color.upsample_color",
        description=(
            "chroma upsample + YCbCr->RGB: no data-dependent indexing; "
            "the contract is tiling, incl. the chroma tiles "
            "(TILE_H/fv, TILE_W/fh) whose sampling factors must divide "
            "the luma tile"),
        operands=(OperandContract("y"), OperandContract("cb"),
                  OperandContract("cr")),
    ),
    "huffman-write-store": KernelContract(
        entry="repro.kernels.fused.store.decode_coeffs_store_pallas",
        description=(
            "fuse='full' write pass: the exits contract plus an "
            "in-kernel clamped coefficient store into the whole-buffer "
            "(n_coef,) output ref; race-freedom reduces to the stream "
            "write kernel's monotonicity proof (same _symbol_step "
            "recurrence) plus the sequential grid/fori_loop order"),
        operands=_HUFFMAN_OPERANDS + (
            # (TILE, 1) absolute dense-coefficient base per lane
            OperandContract("write_base",
                            ranges={None: lambda p: (0, p["n_coef"] - 1)}),
            # (TILE, 1) inclusive clamp; -1 on pad lanes (never write)
            OperandContract("write_max",
                            ranges={None: lambda p: (-1, p["n_coef"] - 1)}),
        ),
    ),
    "fused-pixels": KernelContract(
        entry="repro.kernels.fused.pixels.fused_pixels_pallas",
        description=(
            "fused dequant+IDCT+assemble+upsample+color megakernel: no "
            "data-dependent indexing (the per-component unit slices are "
            "static in the MCU-blocked unit order); the contract is "
            "pure tiling over the padded MCU axis"),
        operands=(OperandContract("coeffs"), OperandContract("rows"),
                  OperandContract("m2")),
    ),
}


#: Modules whose `.at[...].set(...)` scatters the kernel verifier proves
#: duplicate-free (the `kernel-scatter-race` family). The
#: `unsafe-scatter-set` lint rule exempts exactly these files; everywhere
#: else a traced overwrite-scatter needs `.add`, an inline
#: `# repro: allow[unsafe-scatter-set]`, or a baseline entry.
VERIFIED_SCATTER_MODULES = ("repro/kernels/huffman/ops.py",)


#: The kernel-verifier contract families, as data (docs/ANALYSIS.md
#: renders this; `python -m repro.analysis kernels` reports coverage).
KERNEL_CHECK_FAMILIES: Dict[str, str] = {
    "kernel-bounds": (
        "every in-kernel ref access (get/swap, incl. pl.ds "
        "dynamic slices) and every unclamped gather index is proven "
        "in-bounds by the IntRange lattice under the documented operand "
        "intervals of KERNEL_CONTRACTS — incl. the fused cells "
        "(write-store, fused-pixels) at EVERY autotune tile candidate, "
        "not just the tuner's winner"),
    "kernel-scatter-race": (
        "the write-pass bulk `.at[tgt].set(mode='drop')` has provably "
        "duplicate-free in-bounds targets (per-lane positions strictly "
        "increase; seg_coeff_base ranges are disjoint; the shared "
        "sentinel is past-the-end so it never writes) and declares "
        "unique_indices=True; any other overwrite-scatter on traced "
        "values is flagged. The fuse='full' in-kernel store is accepted "
        "by reduction: it replays the same _symbol_step recurrence with "
        "sequential writes, so its cells only pass while the stream "
        "kernel's monotone-pos proof passes in the same run"),
    "kernel-tiling": (
        "BlockSpec shapes x grid exactly cover every operand (no "
        "remainder truncation, no tile past the end, tile divides the "
        "dimension), evaluated from each index_map jaxpr over the whole "
        "grid range; bucket-ladder capacities stay tile-aligned for "
        "every autotune lane-tile candidate and the shard_map pad-skip "
        "fast path agrees with the ladder rungs"),
}


def identity_live_ok(sync: str) -> frozenset:
    try:
        return IDENTITY_LIVE_OK[sync]
    except KeyError:
        raise ContractViolation(
            f"no lane-graph liveness entry for sync schedule {sync!r}; "
            f"add it to contracts.IDENTITY_LIVE_OK") from None


def iter_contracts() -> Iterable:
    return JAXPR_CONTRACTS.items()
