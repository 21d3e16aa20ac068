"""Pallas TPU kernel: write pass with an in-kernel coefficient store.

The stream-form write pass (``kernels/huffman``) spills a per-symbol
``(C, s_max)`` (offset, coefficient) stream pair to HBM purely so a
trailing bulk jnp scatter can place the values — 2 * C * s_max * 4 bytes
of round-trip traffic per decode. With converged entries the verifier's
scatter-race proof (``analysis/kernel_check``, kernel-scatter-race
family) establishes that per-lane positions strictly increase and lane
segments own disjoint output ranges; under exactly that invariant the
scatter can move *inside* the kernel: the whole dense coefficient buffer
is the (revisited) output block, zero-initialized on the first grid
step, and each symbol step stores its coefficient at the clamped global
offset under the same in-bounds mask the stream form applies outside.

The per-step store runs as a sequential per-lane ``fori_loop`` — TPU
grid steps are sequential and the loop is sequential, so there is no
intra-kernel race to prove beyond what the stream form already proves
(same ``_symbol_step`` recurrence, same disjointness); ``kernel_check``
enforces the reduction by only accepting the fused-store cell when the
stream cell's monotonicity proof passed in the same run. The store index
is clamped to the buffer (``jnp.clip``) so the bounds family can verify
every in-kernel store from the interval lattice alone; clamped-but-masked
lanes write nothing (the read-modify-write keeps the old value).

The fused store keeps the whole coefficient buffer resident per grid
step, so it only engages when the buffer fits a VMEM budget and the
decode is not lane-sharded over a mesh (a shard owns a lane subset but
the store targets the whole buffer); ``ops.store_fusible`` gates this
and the decoder falls back to the stream form — bit-identically —
everywhere else.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..backend import default_interpret
from ..huffman.huffman import (_check_lane_tiling, _lane_inputs,
                               _prep_lanes, _symbol_step, _tile_for)
from ..autotune import DEFAULT_TILES


def _store_kernel(
    words_ref,    # (TILE, W) uint32 per-lane word windows
    luts_ref,     # (L * 65536,) int32 flattened decode LUTs
    rows_ref,     # (TILE, 2*MAX_UPM) int32 LUT row per (u, is_dc)
    meta_ref,     # (TILE, 4) int32: [p_entry, u_entry, z_entry, limit_local]
    upm_ref,      # (TILE, 1) int32
    wb_ref,       # (TILE, 1) int32 absolute write base per lane
    wm_ref,       # (TILE, 1) int32 inclusive write clamp (-1 on pad lanes)
    out_ref,      # (TILE, 4) int32 exit states (as in _exits_kernel)
    coef_ref,     # (n_coef,) int32 — the WHOLE dense coefficient buffer,
                  # revisited by every grid step (index_map i -> 0)
    *,
    s_max: int,
    min_code_bits: int,
    n_coef: int,
):
    words, lanes, carry0, limit, upm = _lane_inputs(words_ref, meta_ref,
                                                    upm_ref)
    tile = words.shape[0]
    wb = wb_ref[:, 0]
    wm = wm_ref[:, 0]

    @pl.when(pl.program_id(0) == 0)
    def _zero():
        # the buffer block persists across (sequential) grid steps; only
        # the first step may zero it or later tiles would erase earlier
        # lanes' coefficients
        coef_ref[...] = jnp.zeros_like(coef_ref)

    def body(i, carry):
        nxt, coef, run_eff, active, invalid = _symbol_step(
            words, lanes, luts_ref, rows_ref, limit, upm, min_code_bits,
            carry,
        )
        n = carry[3]
        rec = active & ~invalid
        pos = n + run_eff
        tgt = wb + pos
        # identical in-bounds mask to the stream form's bulk scatter
        # (ops.decode_coeffs): recording step, non-negative target,
        # inside the lane's segment clamp
        ok = rec & (pos >= 0) & (tgt >= 0) & (tgt <= wm)
        idx = jnp.clip(tgt, 0, n_coef - 1)

        def lane_body(l, _):
            cur = coef_ref[pl.ds(idx[l], 1)]
            new = jnp.where(ok[l], coef[l], cur[0])
            coef_ref[pl.ds(idx[l], 1)] = new[None]
            return _

        jax.lax.fori_loop(0, tile, lane_body, 0)
        return nxt

    p, u, z, n = jax.lax.fori_loop(0, s_max, body, carry0)
    out_ref[:, 0] = p
    out_ref[:, 1] = u
    out_ref[:, 2] = z
    out_ref[:, 3] = n


@functools.partial(
    jax.jit,
    static_argnames=("n_coef", "s_max", "min_code_bits", "chunk_words",
                     "tile", "interpret"),
)
def decode_coeffs_store_pallas(
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
    write_base: jnp.ndarray,   # (C,) absolute dense-coefficient base
    write_max: jnp.ndarray,    # (C,) inclusive per-lane clamp
    *,
    n_coef: int,
    s_max: int,
    min_code_bits: int,
    chunk_words: int,
    tile: Optional[int] = None,
    interpret: bool = False,
):
    """Fused write pass: exits plus the fully-scattered (n_coef,) dense
    coefficient buffer — no (C, s_max) stream ever reaches HBM."""
    c = entry_p.shape[0]
    cap = tile if tile is not None else DEFAULT_TILES.write_tile
    lane_tile = _tile_for(c, cap)
    local_words, meta, upm2, pad, w = _prep_lanes(
        words, word_base, chunk_start, entry_p, entry_u, entry_z, limit, upm,
        chunk_words, lane_tile,
    )
    rows = jnp.pad(lut_rows.reshape(c, -1), ((0, pad), (0, 0)))
    # pad lanes: wb=0, wm=-1 -> `tgt <= wm` is never true, nothing writes
    wb = jnp.pad(write_base, (0, pad))[:, None]
    wm = jnp.pad(write_max, (0, pad), constant_values=-1)[:, None]

    _check_lane_tiling(c, pad, lane_tile)
    n_tiles = (c + pad) // lane_tile
    max_upm = lut_rows.shape[1]
    exits, coef = pl.pallas_call(
        functools.partial(
            _store_kernel, s_max=s_max, min_code_bits=min_code_bits,
            n_coef=n_coef,
        ),
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((lane_tile, w), lambda i: (i, 0)),
            pl.BlockSpec((luts.size,), lambda i: (0,)),
            pl.BlockSpec((lane_tile, 2 * max_upm), lambda i: (i, 0)),
            pl.BlockSpec((lane_tile, 4), lambda i: (i, 0)),
            pl.BlockSpec((lane_tile, 1), lambda i: (i, 0)),
            pl.BlockSpec((lane_tile, 1), lambda i: (i, 0)),
            pl.BlockSpec((lane_tile, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((lane_tile, 4), lambda i: (i, 0)),
            pl.BlockSpec((n_coef,), lambda i: (0,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((c + pad, 4), jnp.int32),
            jax.ShapeDtypeStruct((n_coef,), jnp.int32),
        ],
        interpret=default_interpret(interpret),
    )(local_words, luts.reshape(-1), rows, meta, upm2, wb, wm)

    exits = exits[:c]
    return (
        (exits[:, 0] + chunk_start, exits[:, 1], exits[:, 2], exits[:, 3]),
        coef,
    )
