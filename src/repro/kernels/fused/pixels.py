"""Pallas TPU kernel: the fused post-entropy pixel stage.

One launch replaces the whole dequant + de-zigzag + IDCT + plane-assembly
+ chroma-upsample + color-convert chain: each grid step consumes the
coefficient rows of ``tile_m`` whole MCUs (the plan's unit order is
image-major, MCU-major, component-interleaved, so one MCU's units are
``upm`` consecutive rows) and emits the finished RGB pixels of those
MCUs. The intermediate per-unit pixel tile and the per-component YCbCr
planes live only in VMEM/registers — the two full-size HBM round-trips
of the unfused chain (``idct`` output -> ``assemble_planes`` ->
``upsample_color`` input) disappear.

Bit-parity with the unfused path is by construction, not by tolerance:
the IDCT block is the shared ``kernels/idct/idct.idct_tile`` (one
K=64 ``dot_general`` per quantization table, per-row f32 reductions
independent of tile height, mask-select, ``clip(round(acc + 128))``),
and the color block is
the identical elementwise arithmetic of ``core/decode.upsample_color``
(replicate-upsample, BT.601 constants in the same order, final
``clip(round(.))``). The per-MCU plane slices are static: a uniform
batch's within-MCU component layout (``v*h`` units per component, row-
major) is a trace-time constant.

VMEM per grid step (4:2:0, tile_m=64, nq=2, f32):
  x tile  (384, 64)    =  96 KiB
  rows    (384, 1)     = 1.5 KiB
  M       (2, 64, 64)  =  32 KiB
  out     (64,3,16,16) =  192 KiB          total ~0.3 MiB << 16 MiB VMEM.

On v5e Mosaic refuses the per-MCU plane assembly below (the
``(tile_m, v*h, 64) -> (tile_m, v, h, 8, 8)`` lane split is an
"unsupported shape cast"); the kernel runs in interpret mode only until
the assembly is redesigned.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..autotune import DEFAULT_TILES
from ..backend import default_interpret
from ..idct.idct import idct_tile


def _pixels_kernel(
    x_ref,     # (tile_m * upm, 64) f32 zig-zag coefficients, MCU-major
    rows_ref,  # (tile_m * upm, 1) i32 folded-matrix row per unit
    m_ref,     # (nq, 64, 64) f32 folded operators
    o_ref,     # (tile_m, 3, 8*v_max, 8*h_max) f32 RGB (clipped, rounded)
    *,
    nq: int,
    upm: int,
    comp_h: Tuple[int, ...],
    comp_v: Tuple[int, ...],
    h_max: int,
    v_max: int,
    tile_m: int,
):
    # -- IDCT: the exact op sequence of idct.idct._kernel -----------------
    pix = idct_tile(x_ref[...], rows_ref[...], m_ref, nq)

    # -- per-MCU plane assembly + replicate upsample ----------------------
    # Units within an MCU are component-blocked: comp 0's v*h units (row-
    # major over the MCU's block grid), then comp 1's, ... — the same
    # static layout scan_unit_layout/assemble_planes index dynamically.
    pix = pix.reshape(tile_m, upm, 64)
    planes = []
    off = 0
    for ci in range(len(comp_h)):
        h, v = comp_h[ci], comp_v[ci]
        sub = pix[:, off:off + v * h].reshape(tile_m, v, h, 8, 8)
        off += v * h
        p = sub.transpose(0, 1, 3, 2, 4).reshape(tile_m, v * 8, h * 8)
        fv, fh = v_max // v, h_max // h
        if fv > 1:
            p = jnp.repeat(p, fv, axis=1)
        if fh > 1:
            p = jnp.repeat(p, fh, axis=2)
        planes.append(p)

    # -- color convert: the exact arithmetic of decode.upsample_color -----
    y, cb, cr = planes[0], planes[1] - 128.0, planes[2] - 128.0
    r = y + 1.402 * cr
    g = y - 0.344136286 * cb - 0.714136286 * cr
    b = y + 1.772 * cb
    rgb = jnp.stack([r, g, b], axis=1)
    o_ref[...] = jnp.clip(jnp.round(rgb), 0.0, 255.0)


def _tile_for_mcus(n: int, cap: int) -> int:
    """MCU tile: cap for big batches, an even cover for small ones."""
    return min(cap, -(-n // 2) * 2)


def _check_mcu_tiling(n: int, pad: int, tile: int, upm: int) -> None:
    """Runtime twin of the kernel-tiling contract for the fused pixel
    grid (see huffman._check_lane_tiling for the lane-axis analogue)."""
    if tile <= 0 or (n + pad) % tile:
        raise ValueError(
            f"fused pixel tiling broken: {n} MCUs + pad {pad} vs MCU "
            f"tile {tile} (upm={upm}); the tile must divide the padded "
            f"MCU count (see autotune.check_tile)")


@functools.partial(
    jax.jit,
    static_argnames=("comp_h", "comp_v", "h_max", "v_max", "upm", "tile",
                     "interpret"),
)
def fused_pixels_pallas(
    coeffs: jnp.ndarray,      # (n_mcus*upm, 64) int32/f32 zig-zag coeffs
    m_matrices: jnp.ndarray,  # (NQ, 64, 64) float32 folded operators
    unit_mrow: jnp.ndarray,   # (n_mcus*upm,) int32
    *,
    comp_h: Tuple[int, ...],
    comp_v: Tuple[int, ...],
    h_max: int,
    v_max: int,
    upm: int,
    tile: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Fused pixel stage over whole MCUs: returns (n_mcus, 3, 8*v_max,
    8*h_max) float32 RGB MCU blocks (already clipped and rounded); the
    wrapper in ``ops.py`` reshapes them into (B, H, W, 3) images."""
    interpret = default_interpret(interpret)
    u, width = coeffs.shape
    if width != 64 or len(comp_h) != 3 or upm != sum(
            h * v for h, v in zip(comp_h, comp_v)) or u % upm:
        raise ValueError(
            f"fused_pixels_pallas needs (n_mcus*upm, 64) coefficients "
            f"for a 3-component layout; got width {width}, upm {upm}, "
            f"comp_h {comp_h}, comp_v {comp_v}, {u} units")
    n_mcus = u // upm
    cap = tile if tile is not None else DEFAULT_TILES.mcu_tile
    tile_m = _tile_for_mcus(n_mcus, cap)
    pad = (-n_mcus) % tile_m
    _check_mcu_tiling(n_mcus, pad, tile_m, upm)

    nq = m_matrices.shape[0]

    x = jnp.pad(coeffs.astype(jnp.float32), ((0, pad * upm), (0, 0)))
    rows = jnp.pad(unit_mrow.astype(jnp.int32), (0, pad * upm))[:, None]

    mcu_h, mcu_w = 8 * v_max, 8 * h_max
    tu = tile_m * upm
    grid = ((n_mcus + pad) // tile_m,)
    out = pl.pallas_call(
        functools.partial(
            _pixels_kernel, nq=nq, upm=upm, comp_h=comp_h, comp_v=comp_v,
            h_max=h_max, v_max=v_max, tile_m=tile_m,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tu, 64), lambda i: (i, 0)),
            pl.BlockSpec((tu, 1), lambda i: (i, 0)),
            pl.BlockSpec((nq, 64, 64), lambda i: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((tile_m, 3, mcu_h, mcu_w),
                               lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (n_mcus + pad, 3, mcu_h, mcu_w), jnp.float32),
        interpret=interpret,
    )(x, rows, m_matrices.astype(jnp.float32))
    return out[:n_mcus]
