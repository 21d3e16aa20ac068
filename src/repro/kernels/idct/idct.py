"""Pallas TPU kernel: fused dequantize + de-zigzag + 2-D IDCT.

The paper implements this stage as one CUDA kernel with a thread per 8x8
data unit. The TPU-native formulation (DESIGN.md §3) folds the whole stage
into a single matmul: ``pixels = M @ zigzag_coeffs`` with
``M = (C^T (x) C^T) diag(q) P``, one (T, 64) x (64, 64) product per
distinct quantization table, selected per unit by a mask — the same op
sequence as the jnp reference ``core/decode.idct_units_folded``. (Pairing
two units into a 128-lane row would fill the MXU width, but Mosaic
refuses the ``(T, 64) -> (T/2, 128)`` reshape on v5e.)

VMEM budget per grid step (TILE_U=512, NQ=2, f32):
  x tile  (512, 64)   = 128 KiB
  rows    (512, 1)    =   2 KiB
  M       (2, 64, 64) =  32 KiB
  out     (512, 64)   = 128 KiB            total ~0.3 MiB << 16 MiB VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..backend import default_interpret

TILE_U = 512  # units per grid step; multiple of 8 (sublanes)


def idct_tile(x, rows, m_ref, nq: int):
    """(T, 64) f32 zig-zag coefficients -> (T, 64) clipped, rounded pixels.

    Shared with the fused pixel kernel so both stay the op sequence of
    ``core/decode.idct_units_folded``.
    """
    acc = jnp.zeros_like(x)
    for q in range(nq):                  # nq is tiny (distinct quant tables)
        y = jax.lax.dot_general(
            x, m_ref[q],
            dimension_numbers=(((1,), (1,)), ((), ())),  # x @ M[q].T
            preferred_element_type=jnp.float32,
        )
        acc = jnp.where(rows == q, y, acc)   # (T, 1) mask per unit
    return jnp.clip(jnp.round(acc + 128.0), 0.0, 255.0)


def _kernel(x_ref, rows_ref, m_ref, o_ref, *, nq: int):
    o_ref[...] = idct_tile(x_ref[...], rows_ref[...], m_ref, nq)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def fused_idct(
    coeffs: jnp.ndarray,      # (U, 64) int32/float zig-zag coefficients
    m_matrices: jnp.ndarray,  # (NQ, 64, 64) float32 folded operators
    unit_mrow: jnp.ndarray,   # (U,) int32
    tile: int = None,         # unit-tile override (autotune)
    interpret: bool = None,
) -> jnp.ndarray:
    interpret = default_interpret(interpret)
    tile_u = tile if tile is not None else TILE_U
    u, width = coeffs.shape
    if width != 64 or tile_u % 8 or tile_u <= 0:
        # 64 lanes per unit and a sublane-aligned tile — kernel-tiling
        # contract twin (analysis/kernel_check)
        raise ValueError(
            f"fused_idct needs (U, 64) coefficients and a positive unit "
            f"tile that is a multiple of 8; got width {width}, "
            f"tile {tile_u}")
    nq = m_matrices.shape[0]

    pad = (-u) % tile_u
    x = jnp.pad(coeffs.astype(jnp.float32), ((0, pad), (0, 0)))
    rows = jnp.pad(unit_mrow.astype(jnp.int32), (0, pad))[:, None]

    grid = (x.shape[0] // tile_u,)
    out = pl.pallas_call(
        functools.partial(_kernel, nq=nq),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_u, 64), lambda i: (i, 0)),
            pl.BlockSpec((tile_u, 1), lambda i: (i, 0)),
            pl.BlockSpec((nq, 64, 64), lambda i: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((tile_u, 64), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((x.shape[0], 64), jnp.float32),
        interpret=interpret,
    )(x, rows, m_matrices.astype(jnp.float32))
    return out[:u]
