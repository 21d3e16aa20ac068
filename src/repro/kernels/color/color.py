"""Pallas TPU kernel: fused chroma upsampling + YCbCr->RGB conversion.

Pure VPU work (FMA + clamp) on (8k, 128)-aligned pixel tiles. The chroma
operands use *smaller* BlockSpec tiles than luma — the index maps divide by
the sampling factors, so upsampling is free VMEM addressing plus an
in-register repeat, never an HBM round-trip (the paper's trailing stage does
this as separate kernels; fusing removes two full-plane HBM passes).

The luma tile is ``8*fv`` rows, so every chroma block keeps the 8
sublanes the TPU block rule asks for. Block shapes (4:2:0): y (16, 256),
cb/cr (8, 128) -> out (3, 16, 256). VMEM per step ~ 48 KiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..backend import default_interpret

TILE_H = 8
TILE_W = 256


def _kernel(y_ref, cb_ref, cr_ref, o_ref, *, fh: int, fv: int):
    y = y_ref[0]
    cb = cb_ref[0]
    cr = cr_ref[0]
    if fv > 1:
        cb = jnp.repeat(cb, fv, axis=0)
        cr = jnp.repeat(cr, fv, axis=0)
    if fh > 1:
        cb = jnp.repeat(cb, fh, axis=1)
        cr = jnp.repeat(cr, fh, axis=1)
    cb = cb - 128.0
    cr = cr - 128.0
    r = y + 1.402 * cr
    g = y - 0.344136286 * cb - 0.714136286 * cr
    b = y + 1.772 * cb
    rgb = jnp.stack([r, g, b], axis=0)
    o_ref[0] = jnp.clip(jnp.round(rgb), 0.0, 255.0)


@functools.partial(jax.jit, static_argnames=("fh", "fv", "interpret"))
def upsample_color(
    y: jnp.ndarray,   # (B, H, W) float32, padded here to (8*fv, 256) tiles
    cb: jnp.ndarray,  # (B, H/fv, W/fh)
    cr: jnp.ndarray,
    fh: int = 1,
    fv: int = 1,
    interpret: bool = None,
) -> jnp.ndarray:
    interpret = default_interpret(interpret)
    if fv <= 0 or fh <= 0 or TILE_W % fh:
        # e.g. fh=3: the chroma BlockSpec width TILE_W//fh would floor and
        # silently skip chroma columns — the kernel-tiling contract's
        # runtime twin (analysis/kernel_check.py)
        raise ValueError(
            f"sampling factors (fh={fh}, fv={fv}) must be positive and fh "
            f"must divide the luma tile width {TILE_W}; a non-dividing "
            f"factor truncates the chroma BlockSpec ({TILE_W}//{fh})")
    tile_h = TILE_H * fv
    b, h, w = y.shape
    ph = (-h) % tile_h
    pw = (-w) % TILE_W
    yp = jnp.pad(y, ((0, 0), (0, ph), (0, pw)))
    pch = (yp.shape[1] // fv) - cb.shape[1]
    pcw = (yp.shape[2] // fh) - cb.shape[2]
    cbp = jnp.pad(cb, ((0, 0), (0, pch), (0, pcw)))
    crp = jnp.pad(cr, ((0, 0), (0, pch), (0, pcw)))

    hh, ww = yp.shape[1], yp.shape[2]
    grid = (b, hh // tile_h, ww // TILE_W)
    out = pl.pallas_call(
        functools.partial(_kernel, fh=fh, fv=fv),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tile_h, TILE_W), lambda i, j, k: (i, j, k)),
            pl.BlockSpec((1, TILE_H, TILE_W // fh), lambda i, j, k: (i, j, k)),
            pl.BlockSpec((1, TILE_H, TILE_W // fh), lambda i, j, k: (i, j, k)),
        ],
        out_specs=pl.BlockSpec((1, 3, tile_h, TILE_W), lambda i, j, k: (i, 0, j, k)),
        out_shape=jax.ShapeDtypeStruct((b, 3, hh, ww), jnp.float32),
        interpret=interpret,
    )(yp, cbp, crp)
    return out[:, :, :h, :w].transpose(0, 2, 3, 1).astype(jnp.uint8)
