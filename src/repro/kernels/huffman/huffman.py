"""Pallas TPU kernels: LUT-driven JPEG subsequence decoding.

One lane per subsequence (chunk). The CUDA original runs a divergent
per-thread bit loop; the TPU-native shape (DESIGN.md §3) is a lane-
vectorized loop with three primitives per symbol:

  1. 32-bit window fetch from the lane's *local* word window (the wrapper
     pre-gathers each chunk's words into a (C, W) tile so the kernel's
     VMEM working set is a regular BlockSpec tile, not scattered HBM),
  2. one 2^16-entry LUT gather (the decode table lives in VMEM: 256 KiB per
     distinct Huffman table — the dominant VMEM tenant),
  3. integer state update (p, u, z, n) under an activity mask.

Two kernels share the symbol step:

* :func:`decode_exits_pallas` — the sync-phase decode: exit states only
  (paper Algorithm 2 / the inner loop of Algorithm 3).
* :func:`decode_coeffs_pallas` — the write pass (Algorithm 1 lines 9–15):
  the same loop additionally emits, per lane and per symbol step, the
  local zig-zag write offset and the decoded coefficient. The global
  scatter (write_base + offset) stays outside the kernel as one bulk
  jnp scatter: lanes own disjoint output ranges once entries have
  converged, so scatter order is irrelevant, and a regular (C, s_max)
  tile keeps the kernel free of data-dependent HBM stores.

VMEM per grid step (TILE_C=1024 lanes, 1024-bit chunks, 4 LUTs):
  words  (1024, 34) u32 ~ 136 KiB
  luts   4*65536    i32 = 1  MiB
  rows   (1024, 12) i32 ~ 48 KiB
  states 6*(1024,)  i32 ~ 24 KiB          total ~1.2 MiB << 16 MiB VMEM.
The write kernel adds 2*(TILE, s_max) i32 output tiles, so it runs with
a smaller lane tile (WRITE_TILE_C) to stay inside the same budget.

TPU lowering note: the LUT lookup and the per-lane word fetch are dynamic
VMEM gathers, and Mosaic (JAX 0.9, v5e) refuses both: its
``_gather_lowering_rule`` asserts ``indices_aval.shape == in_aval.shape +
(1,)``, which neither the 2-D per-lane word gather ``words[lanes, w]`` nor
the flat LUT gather ``luts_ref[row * 65536 + win16]`` meets. So both
kernels here (and ``kernels/fused/store.py``, which shares the symbol
step) run in interpret mode only, and ``backend="pallas"`` is refused on
a TPU (:func:`compile_refusal`, ``kernels.backend.check_pallas_compiles``)
until the symbol step is redesigned. The kernel bodies are validated in
interpret mode against the pure-jnp decoder (itself bit-exact vs the
sequential oracle).
"""
from __future__ import annotations

import functools
import traceback
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...jpeg import tables as T
from ..backend import default_interpret

TILE_C = 1024
WRITE_TILE_C = 256
U32 = jnp.uint32


def _symbol_step(words, lanes, luts_ref, rows_ref, limit, upm, min_code_bits,
                 carry):
    """One Huffman symbol per lane: the shared body of both kernels.

    Returns the updated (p, u, z, n) carry plus the per-step outputs the
    write pass needs (coefficient, effective run, activity/validity).
    """
    p, u, z, n = carry
    active = p < limit

    w = p >> 5
    off = (p & 31).astype(U32)
    hi = words[lanes, w]
    lo = words[lanes, w + 1]
    lo_shift = jnp.where(off == 0, U32(0), lo >> ((U32(32) - off) & U32(31)))
    win32 = (hi << off) | lo_shift
    win16 = (win32 >> U32(16)).astype(jnp.int32)

    is_dc = (z == 0).astype(jnp.int32)
    row = rows_ref[lanes, u * 2 + is_dc]
    entry = luts_ref[row * 65536 + win16]

    clen = entry & 0x1F
    size = (entry >> T.LUT_SIZE_SHIFT) & 0xF
    run = (entry >> T.LUT_RUN_SHIFT) & 0xF
    eob = (entry & T.LUT_EOB_BIT) != 0
    invalid = clen == 0

    # magnitude bits: the `size` bits following the codeword
    shift = (U32(32) - clen.astype(U32) - size.astype(U32)) & U32(31)
    mask = (U32(1) << size.astype(U32)) - U32(1)
    vbits = ((win32 >> shift) & mask).astype(jnp.int32)
    half = jnp.left_shift(jnp.int32(1), jnp.maximum(size - 1, 0))
    full = jnp.left_shift(jnp.int32(1), size)
    coef = jnp.where(vbits < half, vbits - full + 1, vbits)
    coef = jnp.where(size == 0, 0, coef)

    run_eff = jnp.where(eob, 63 - z, run)
    run_eff = jnp.where(invalid, 0, run_eff)
    zstep = run_eff + 1
    adv = jnp.where(invalid, min_code_bits, clen + size)

    new_z = z + zstep
    blk = new_z >= 64
    z_n = jnp.where(blk, 0, new_z)
    u_n = jnp.where(blk, jnp.where(u + 1 >= upm, 0, u + 1), u)
    nxt = (
        jnp.where(active, p + adv, p),
        jnp.where(active, u_n, u),
        jnp.where(active, z_n, z),
        jnp.where(active, n + zstep, n),
    )
    return nxt, coef, run_eff, active, invalid


def _lane_inputs(words_ref, meta_ref, upm_ref):
    words = words_ref[...]
    lanes = jnp.arange(words.shape[0], dtype=jnp.int32)
    carry0 = (meta_ref[:, 0], meta_ref[:, 1], meta_ref[:, 2],
              jnp.zeros_like(meta_ref[:, 0]))
    return words, lanes, carry0, meta_ref[:, 3], upm_ref[:, 0]


def _exits_kernel(
    words_ref,    # (TILE, W) uint32 per-lane word windows
    luts_ref,     # (L * 65536,) int32 flattened decode LUTs
    rows_ref,     # (TILE, 2*MAX_UPM) int32 LUT row per (u, is_dc)
    meta_ref,     # (TILE, 4) int32: [p_entry, u_entry, z_entry, limit_local]
    upm_ref,      # (TILE, 1) int32
    out_ref,      # (TILE, 4) int32: exit [p, u, z, n] (p local to chunk)
    *,
    s_max: int,
    min_code_bits: int,
):
    words, lanes, carry0, limit, upm = _lane_inputs(words_ref, meta_ref, upm_ref)

    def body(_, carry):
        nxt, _, _, _, _ = _symbol_step(
            words, lanes, luts_ref, rows_ref, limit, upm, min_code_bits, carry
        )
        return nxt

    p, u, z, n = jax.lax.fori_loop(0, s_max, body, carry0)
    out_ref[:, 0] = p
    out_ref[:, 1] = u
    out_ref[:, 2] = z
    out_ref[:, 3] = n


def _write_kernel(
    words_ref, luts_ref, rows_ref, meta_ref, upm_ref,
    out_ref,      # (TILE, 4) int32 exit states (as in _exits_kernel)
    pos_ref,      # (TILE, s_max) int32 local zig-zag write offset, -1 = none
    val_ref,      # (TILE, s_max) int32 decoded coefficient
    *,
    s_max: int,
    min_code_bits: int,
):
    words, lanes, carry0, limit, upm = _lane_inputs(words_ref, meta_ref, upm_ref)

    def body(i, carry):
        nxt, coef, run_eff, active, invalid = _symbol_step(
            words, lanes, luts_ref, rows_ref, limit, upm, min_code_bits, carry
        )
        n = carry[3]
        rec = active & ~invalid
        pos = jnp.where(rec, n + run_eff, -1)
        pos_ref[:, pl.ds(i, 1)] = pos[:, None]
        val_ref[:, pl.ds(i, 1)] = coef[:, None]
        return nxt

    p, u, z, n = jax.lax.fori_loop(0, s_max, body, carry0)
    out_ref[:, 0] = p
    out_ref[:, 1] = u
    out_ref[:, 2] = z
    out_ref[:, 3] = n


def _prep_lanes(words, word_base, chunk_start, entry_p, entry_u, entry_z,
                limit, upm, chunk_words, tile):
    """Pre-gather per-lane word windows + pack per-lane metadata, tile-padded."""
    c = entry_p.shape[0]
    w = chunk_words + 2  # +1 straddle word, +1 safety

    # Pre-gather each chunk's word window: (C, W). Chunks are 32-bit aligned.
    first_word = word_base + (chunk_start >> 5)
    gidx = first_word[:, None] + jnp.arange(w, dtype=jnp.int32)[None, :]
    gidx = jnp.minimum(gidx, words.shape[0] - 1)
    local_words = words[gidx]

    pad = (-c) % tile

    def padc(a, v=0):
        return jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1),
                       constant_values=v)

    meta = jnp.stack(
        [entry_p - chunk_start, entry_u, entry_z, limit - chunk_start], axis=1
    )
    # padding lanes get limit_local = 0 <= p = 0, i.e. never active
    return padc(local_words), padc(meta), padc(
        jnp.maximum(upm, 1)[:, None], v=1), pad, w


def _tile_for(c: int, cap: int) -> int:
    """Lane tile: cap for big batches, an 8-multiple cover for small ones
    (keeps sublane alignment without padding a 3-chunk batch to 1024)."""
    return min(cap, -(-c // 8) * 8)


def _check_lane_tiling(c: int, pad: int, tile: int) -> None:
    """Runtime twin of the kernel-tiling contract (analysis/kernel_check).

    The grid math below assumes the lane tile divides the padded lane
    capacity exactly — a non-dividing tile would make the last grid step
    read/write past the operands (or silently drop the remainder lanes).
    _tile_for keeps this true for every capacity, so tripping here means
    a tile override or ladder change broke the invariant; fail loudly
    with the numbers instead of corrupting coefficients.
    """
    if tile <= 0 or (c + pad) % tile:
        from ...core.bitstream import bucket_capacity
        raise ValueError(
            f"lane tiling broken: capacity {c} + pad {pad} = {c + pad} "
            f"is not a multiple of lane tile {tile} (bucket ladder rung "
            f"{bucket_capacity(c)}); pick a tile that divides the padded "
            f"capacity (see _tile_for)")


@functools.partial(
    jax.jit,
    static_argnames=("s_max", "min_code_bits", "chunk_words", "tile",
                     "interpret"),
)
def decode_exits_pallas(
    words: jnp.ndarray,        # (W_total,) uint32 global word buffer
    luts: jnp.ndarray,         # (L, 65536) int32
    lut_rows: jnp.ndarray,     # (C, MAX_UPM, 2) int32 per-chunk schedule
    word_base: jnp.ndarray,    # (C,) int32 segment word base per chunk
    chunk_start: jnp.ndarray,  # (C,) int32 bit offset of chunk in segment
    entry_p: jnp.ndarray,      # (C,) absolute (segment-relative) entry bit
    entry_u: jnp.ndarray,
    entry_z: jnp.ndarray,
    limit: jnp.ndarray,        # (C,) segment-relative end bit
    upm: jnp.ndarray,          # (C,)
    *,
    s_max: int,
    min_code_bits: int,
    chunk_words: int,
    tile: int = None,          # lane-tile cap override (autotune)
    interpret: bool,
):
    """Returns exit (p, u, z, n); p is segment-relative like the input."""
    c = entry_p.shape[0]
    tile = _tile_for(c, tile if tile is not None else TILE_C)
    local_words, meta, upm2, pad, w = _prep_lanes(
        words, word_base, chunk_start, entry_p, entry_u, entry_z, limit, upm,
        chunk_words, tile,
    )
    rows = jnp.pad(lut_rows.reshape(c, -1), ((0, pad), (0, 0)))

    _check_lane_tiling(c, pad, tile)
    n_tiles = (c + pad) // tile
    max_upm = lut_rows.shape[1]
    out = pl.pallas_call(
        functools.partial(
            _exits_kernel, s_max=s_max, min_code_bits=min_code_bits
        ),
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((tile, w), lambda i: (i, 0)),
            pl.BlockSpec((luts.size,), lambda i: (0,)),
            pl.BlockSpec((tile, 2 * max_upm), lambda i: (i, 0)),
            pl.BlockSpec((tile, 4), lambda i: (i, 0)),
            pl.BlockSpec((tile, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((tile, 4), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((c + pad, 4), jnp.int32),
        interpret=default_interpret(interpret),
    )(local_words, luts.reshape(-1), rows, meta, upm2)

    out = out[:c]
    return (
        out[:, 0] + chunk_start,  # back to segment-relative bits
        out[:, 1],
        out[:, 2],
        out[:, 3],
    )


@functools.partial(
    jax.jit,
    static_argnames=("s_max", "min_code_bits", "chunk_words", "tile",
                     "interpret"),
)
def decode_coeffs_pallas(
    words: jnp.ndarray,
    luts: jnp.ndarray,
    lut_rows: jnp.ndarray,
    word_base: jnp.ndarray,
    chunk_start: jnp.ndarray,
    entry_p: jnp.ndarray,
    entry_u: jnp.ndarray,
    entry_z: jnp.ndarray,
    limit: jnp.ndarray,
    upm: jnp.ndarray,
    *,
    s_max: int,
    min_code_bits: int,
    chunk_words: int,
    tile: int = None,          # lane-tile cap override (autotune)
    interpret: bool,
):
    """Write pass: exits plus per-symbol (local offset, coefficient) streams.

    ``pos[c, s]`` is the zig-zag offset (relative to the lane's write base)
    written by symbol step ``s`` of lane ``c``, or -1 when the step decoded
    nothing (inactive past the chunk end, or garbage phase).

    The lane-tile cap is no longer hardcoded to ``WRITE_TILE_C``: the
    autotuner (``kernels/autotune``) routes a per-bucket cap through
    ``tile`` and :func:`_check_lane_tiling` rejects — loudly — any tile
    that fails to divide the padded lane capacity.
    """
    c = entry_p.shape[0]
    tile = _tile_for(c, tile if tile is not None else WRITE_TILE_C)
    local_words, meta, upm2, pad, w = _prep_lanes(
        words, word_base, chunk_start, entry_p, entry_u, entry_z, limit, upm,
        chunk_words, tile,
    )
    rows = jnp.pad(lut_rows.reshape(c, -1), ((0, pad), (0, 0)))

    _check_lane_tiling(c, pad, tile)
    n_tiles = (c + pad) // tile
    max_upm = lut_rows.shape[1]
    exits, pos, val = pl.pallas_call(
        functools.partial(
            _write_kernel, s_max=s_max, min_code_bits=min_code_bits
        ),
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((tile, w), lambda i: (i, 0)),
            pl.BlockSpec((luts.size,), lambda i: (0,)),
            pl.BlockSpec((tile, 2 * max_upm), lambda i: (i, 0)),
            pl.BlockSpec((tile, 4), lambda i: (i, 0)),
            pl.BlockSpec((tile, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tile, 4), lambda i: (i, 0)),
            pl.BlockSpec((tile, s_max), lambda i: (i, 0)),
            pl.BlockSpec((tile, s_max), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((c + pad, 4), jnp.int32),
            jax.ShapeDtypeStruct((c + pad, s_max), jnp.int32),
            jax.ShapeDtypeStruct((c + pad, s_max), jnp.int32),
        ],
        interpret=default_interpret(interpret),
    )(local_words, luts.reshape(-1), rows, meta, upm2)

    exits = exits[:c]
    return (
        (exits[:, 0] + chunk_start, exits[:, 1], exits[:, 2], exits[:, 3]),
        pos[:c],
        val[:c],
    )


def compile_refusal(device) -> Optional[str]:
    """Compile :func:`decode_exits_pallas` for ``device`` (attached, or
    described by ``jax.experimental.topologies``) at one 8-lane tile.

    Returns the compiler's refusal — exception type, the frame that
    raised and its message — or None when the kernel compiles. The
    refusal does not depend on the tile, so the smallest one is checked.
    """
    from jax.sharding import SingleDeviceSharding
    one = SingleDeviceSharding(device)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    c = 8
    args = ([sds((64,), jnp.uint32), sds((1, 65536), jnp.int32),
             sds((c, 6, 2), jnp.int32)] + [sds((c,), jnp.int32)] * 7)
    try:
        decode_exits_pallas.lower(*args, s_max=4, min_code_bits=2,
                                  chunk_words=32, interpret=False).compile()
    # any refusal is the answer, reported whole to the caller
    except Exception as e:  # repro: allow[swallowed-format-error]
        frame = traceback.extract_tb(e.__traceback__)[-1]
        first = (str(e).strip().splitlines() or [""])[0]
        return (f"{type(e).__name__} in {frame.name} ({frame.line}) "
                f"{first}").strip()
    return None
