"""Jitted wrappers integrating the Pallas subsequence decoder with the core
decoder's data layout.

:func:`decode_exits` is a drop-in for the sync-phase ``decode_span`` and
implements the pluggable decode protocol of ``core/sync.py``: it accepts an
optional chunk-index subset (``idx``) so ``faithful_sync``'s per-chain
``decode_at`` gathers run in the kernel too. :func:`decode_coeffs` is the
write pass (paper Algorithm 1 lines 9–15): the kernel emits per-symbol
(offset, coefficient) streams and one bulk jnp scatter places them.

On a mesh the wrappers run the kernel under ``shard_map`` over the
chunk-lane axis: per-lane operands are split across devices (padded to a
multiple of the axis size with inert lanes), the word buffer and LUTs are
replicated, and each device runs the identical Pallas program on its lane
shard — the kernel equivalent of the GSPMD-sharded jnp hot path.

Lane order is whatever the plan says, never positional: chain adjacency
lives in the plan's explicit ``chunk_prev``/``chunk_next`` graph (gathered
by ``core/sync.chain_entries`` outside the kernel), so the kernels are
invariant under the lane permutations a balanced plan
(``repro.dist.plan.balance_lanes``) applies. Such plans arrive already
padded to a lane multiple with inert lanes (start == limit), which the
kernels treat exactly like the shard_map padding below — ``pad`` is then 0
when the balance lane count matches the mesh.

Capacity-bucketed plans (``core/bitstream.PlanShape`` / ``PlanData``, the
compile-once streaming path) extend the same contract: every lane-axis
operand arrives padded to the bucket's per-block capacity with inert lanes
and every table operand padded with inert rows, so one shard_map program
per (shape, mesh) serves a whole stream of batches. When the bucket's lane
capacity already divides the mesh (the steady-state case), the wrappers
skip the pad entirely.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ...core.decode import chunk_meta
from ...core.state import DecodeState
from ..backend import default_interpret
from .huffman import decode_coeffs_pallas, decode_exits_pallas
from .ref import decode_exits_ref  # noqa: F401  (re-exported oracle)


def _lane_meta(dev: Dict[str, jnp.ndarray], idx) -> Tuple[jnp.ndarray, ...]:
    """Per-lane kernel operands, optionally gathered at a chunk subset."""
    m = chunk_meta(dev, idx)
    start = dev["chunk_start"] if idx is None else dev["chunk_start"][idx]
    return (
        dev["unit_lut_row"][m["ts"]],  # (C, MAX_UPM, 2)
        m["word_base"],                # (C,)
        start,                         # (C,)
    ), m["limit"], m["upm"]


def _run(fn, dev, entry, idx, kw, mesh, lane_axis, out_specs_fn):
    """Invoke a lane kernel, via shard_map over `lane_axis` when on a mesh."""
    (lut_rows, word_base, start), limit, upm = _lane_meta(dev, idx)
    lane_args = (lut_rows, word_base, start, entry.p, entry.u, entry.z,
                 limit, upm)
    if mesh is None or lane_axis is None or mesh.shape[lane_axis] <= 1:
        return fn(dev["words"], dev["luts"], *lane_args, **kw), None

    n_dev = mesh.shape[lane_axis]
    c = entry.p.shape[0]
    bad = {a.shape[0] for a in lane_args if a.shape[0] != c}
    if bad:
        # every lane operand shards over the same axis below; a length
        # mismatch would otherwise surface as a cryptic shard_map/pallas
        # shape error (or, with independent padding, silent lane skew)
        raise ValueError(
            f"lane operands disagree on capacity: entry has {c} lanes "
            f"but co-operands have leading dims {sorted(bad)} — the "
            f"plan's lane-axis arrays were built for a different "
            f"capacity (see core/bitstream pack/split_plan)")
    pad = (-c) % n_dev

    def padl(a):
        # padding lanes are inert: p=0, limit=0 -> never active in-kernel
        return jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))

    # bucketed PlanData lanes already arrive as a multiple of the mesh's
    # lane count (capacities are per-block) — no pad ops in steady state
    padded = tuple(padl(a) for a in lane_args) if pad else lane_args
    lane_specs = tuple(
        P(lane_axis, *([None] * (a.ndim - 1))) for a in padded
    )
    f = jax.shard_map(
        lambda words, luts, *la: fn(words, luts, *la, **kw),
        mesh=mesh,
        in_specs=(P(), P()) + lane_specs,
        out_specs=out_specs_fn(lane_axis),
        check_vma=False,
    )
    return f(dev["words"], dev["luts"], *padded), c


def decode_exits(
    dev: Dict[str, jnp.ndarray],
    entry: DecodeState,
    idx: Optional[jnp.ndarray] = None,
    *,
    s_max: int,
    min_code_bits: int,
    chunk_bits: int,
    tile: Optional[int] = None,
    interpret: Optional[bool] = None,
    mesh=None,
    lane_axis: Optional[str] = None,
) -> DecodeState:
    """Exit states for every lane (or the `idx` subset) — sync-phase decode."""
    kw = dict(s_max=s_max, min_code_bits=min_code_bits,
              chunk_words=chunk_bits // 32, tile=tile,
              interpret=default_interpret(interpret))
    (p, u, z, n), c = _run(
        decode_exits_pallas, dev, entry, idx, kw, mesh, lane_axis,
        lambda ax: (P(ax),) * 4,
    )
    if c is not None:  # un-pad the shard_map path
        p, u, z, n = p[:c], u[:c], z[:c], n[:c]
    return DecodeState(p, u, z, n)


def decode_coeffs(
    dev: Dict[str, jnp.ndarray],
    entry: DecodeState,
    *,
    out: jnp.ndarray,          # (total_units*64,) int32 zero-initialized
    write_base: jnp.ndarray,   # (C,) absolute dense-coefficient base per lane
    write_max: jnp.ndarray,    # (C,) inclusive per-lane clamp (segment end)
    s_max: int,
    min_code_bits: int,
    chunk_bits: int,
    tile: Optional[int] = None,
    interpret: Optional[bool] = None,
    mesh=None,
    lane_axis: Optional[str] = None,
) -> Tuple[DecodeState, jnp.ndarray]:
    """Write pass: decode every lane from `entry` and scatter coefficients.

    The kernel produces per-lane (offset, value) streams; with converged
    entries each lane owns a disjoint output range, so the trailing bulk
    scatter is order-independent and bit-identical to the sequential
    per-symbol scatter of the jnp path.
    """
    kw = dict(s_max=s_max, min_code_bits=min_code_bits,
              chunk_words=chunk_bits // 32, tile=tile,
              interpret=default_interpret(interpret))
    ((p, u, z, n), pos, val), c = _run(
        decode_coeffs_pallas, dev, entry, None, kw, mesh, lane_axis,
        lambda ax: ((P(ax),) * 4, P(ax, None), P(ax, None)),
    )
    if c is not None:
        p, u, z, n = p[:c], u[:c], z[:c], n[:c]
        pos, val = pos[:c], val[:c]
    tgt = write_base[:, None] + pos
    ok = (pos >= 0) & (tgt <= write_max[:, None])
    # NB: sentinel must be past-the-end, not -1 (negative indices wrap).
    tgt = jnp.where(ok, tgt, out.shape[0])
    # unique_indices: in-bounds targets are duplicate-free by construction
    # (per-lane positions strictly increase; segments own disjoint ranges)
    # and the shared sentinel is dropped before writing, so XLA may skip
    # the scatter sort. Machine-checked: `python -m repro.analysis kernels`
    # (the kernel-scatter-race family; docs/KERNELS.md).
    out = out.at[tgt.reshape(-1)].set(val.reshape(-1), mode="drop",
                                      unique_indices=True)
    return DecodeState(p, u, z, n), out


def make_decode_exits(
    *,
    s_max: int,
    min_code_bits: int,
    chunk_bits: int,
    tile: Optional[int] = None,
    interpret: Optional[bool] = None,
    mesh=None,
    lane_axis: Optional[str] = None,
):
    """Bind plan statics into the ``decode_exits(dev, entry, idx)`` protocol
    consumed by the sync schedules (core/sync.py)."""
    def fn(dev, entry, idx=None):
        return decode_exits(
            dev, entry, idx, s_max=s_max, min_code_bits=min_code_bits,
            chunk_bits=chunk_bits, tile=tile, interpret=interpret,
            mesh=mesh, lane_axis=lane_axis,
        )
    return fn
