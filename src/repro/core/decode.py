"""Device-side parallel JPEG decoding (pure JAX; Pallas variants in kernels/).

The decode primitive is :func:`decode_span`: a bulk-synchronous, lane-
vectorized version of the paper's ``decode_subsequence`` (Algorithm 2). One
"lane" per chunk; each loop iteration decodes one Huffman symbol per lane via
a 16-bit-lookahead LUT gather — the TPU-shaped equivalent of the CUDA
per-thread bit loop (DESIGN.md §3). Where chunks are short, each lane's
words and LUT rows are staged once per batch (:func:`stage_lanes`) and the
step reads them by a one-hot select, so the LUT lookup is its only gather.

All functions take `dev`, the device pytree from BatchPlan.device_arrays().
"""
from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..jpeg import tables as T
from .state import DecodeState

U32 = jnp.uint32
I32 = jnp.int32


# ---------------------------------------------------------------------------
# Bit window fetch
# ---------------------------------------------------------------------------

def _window32(hi: jnp.ndarray, lo: jnp.ndarray, p: jnp.ndarray):
    off = (p & 31).astype(U32)
    lo_shift = jnp.where(off == 0, U32(0), lo >> ((U32(32) - off) & U32(31)))
    return (hi << off) | lo_shift


def fetch_window32(words: jnp.ndarray, word_base: jnp.ndarray, p: jnp.ndarray):
    """32-bit MSB-aligned window starting at bit `p` of each lane's segment."""
    w = word_base + (p >> 5)
    return _window32(words[w], words[w + 1], p)


# ---------------------------------------------------------------------------
# Per-lane operands staged once per batch
# ---------------------------------------------------------------------------
#
# A gather on the chip costs per index, whatever the table's size: on a
# v5e about 6 ns a lane for a word and 10 ns for the LUT row, from a table
# of 12 entries. A one-hot select over a lane's W staged words costs W
# compare-selects per lane on the vector unit: at W = 34 and 11,547 lanes
# the word select takes 0.66 us a step on a v5e and the row select 0.21,
# where the three gathers took about 260 us. The select's cost grows with
# W and the gathers' does not; up to STAGE_MAX_WORDS (chunks of up to
# 4,096 bits) it reads at most 520 bytes a lane, under a nanosecond at HBM
# speed. Longer chunks (the sequential schedule's one chunk per segment,
# W in the thousands, every lane padded to the longest segment) keep the
# gather form.
STAGE_MAX_WORDS = 130


class LaneStage(NamedTuple):
    """A lane's operands for the gather-free step, lanes minor."""
    words: jnp.ndarray   # (W, C) uint32: word k of the lane's chunk
    rows: jnp.ndarray    # (2 * MAX_UPM, C) int32: LUT row at u * 2 + is_dc
    word0: jnp.ndarray   # (C,) int32: chunk_start >> 5, the lane's word 0


def stage_words(chunk_bits: int) -> Optional[int]:
    """W, the words a lane of `chunk_bits` chunks reads (its chunk's, the
    word a window straddles into, and one spare), or None where W is past
    :data:`STAGE_MAX_WORDS` and the step keeps its gathers."""
    w = chunk_bits // 32 + 2
    return w if w <= STAGE_MAX_WORDS else None


def stage_lanes(dev: Dict[str, jnp.ndarray], w: int):
    """``(lane_words, lane_rows)`` of every chunk lane, lanes minor, for
    windows of ``w`` words (:func:`stage_words`).

    Word k of lane c is ``words[word_base[c] + (chunk_start[c] >> 5) + k]``,
    clamped to the buffer as jnp's gather clamps, so a select of word
    ``(p >> 5) - (chunk_start >> 5)`` equals the gather form bit for bit.
    The rows are ``unit_lut_row[ts[c]]`` flattened over (u, is_dc).
    """
    seg = dev["chunk_seg"]
    first = dev["seg_word_base"][seg] + (dev["chunk_start"] >> 5)
    gidx = first[None, :] + jnp.arange(w, dtype=I32)[:, None]
    words = dev["words"]
    lane_words = words[jnp.minimum(gidx, words.shape[0] - 1)]
    rows = dev["unit_lut_row"][dev["seg_tableset"][seg]]
    return lane_words, rows.reshape(rows.shape[0], -1).T


def _select_rows(table: jnp.ndarray, k: jnp.ndarray):
    """``table[k[c], c]`` for every lane c by a one-hot compare-select over
    the rows (0 where k[c] is out of range)."""
    at = jnp.arange(table.shape[0], dtype=I32)[:, None] == k
    return jnp.sum(jnp.where(at, table, 0), axis=0, dtype=table.dtype)


def fetch_window32_staged(stage: LaneStage, p: jnp.ndarray):
    """:func:`fetch_window32` from the staged words, with no gather.

    The select relies on this invariant: for every *active* lane
    (p < limit), 0 <= (p >> 5) - (chunk_start >> 5) <= W - 2. An entry is
    never before its chunk's start (a cold entry is the start; a chained
    one is the predecessor's exit, at or past the predecessor's limit,
    which is this chunk's start), and p < limit <= chunk_start +
    chunk_bits, so the window's two words lie inside the chunk's
    chunk_bits // 32 + 1 words. An inactive lane may select nothing and
    read 0: its state does not move and the write pass drops its
    coefficient, both masked on ``active``.
    """
    k = (p >> 5) - stage.word0
    hi = _select_rows(stage.words, k)
    lo = _select_rows(stage.words, k + 1)
    return _window32(hi, lo, p)


# ---------------------------------------------------------------------------
# One symbol decode step (vectorized over lanes)
# ---------------------------------------------------------------------------

class StepOut(NamedTuple):
    state: DecodeState
    coef: jnp.ndarray      # int32 decoded coefficient (0 for EOB/ZRL/garbage)
    run: jnp.ndarray       # int32 effective zero-run before the coefficient
    active: jnp.ndarray    # bool: this lane decoded a symbol this step
    invalid: jnp.ndarray   # bool: window had no valid codeword (garbage phase)


def decode_symbol(
    dev: Dict[str, jnp.ndarray],
    st: DecodeState,
    word_base: jnp.ndarray,
    limit: jnp.ndarray,
    ts: jnp.ndarray,
    upm: jnp.ndarray,
    min_code_bits: int,
    stage: Optional[LaneStage] = None,
) -> StepOut:
    """decode_next_symbol() from the paper, for all lanes at once.

    With a `stage` the words and the LUT row come from the staged operands
    by a one-hot select (`word_base` and `ts` then go unread); without, by
    gathers. The two give the same step bit for bit.
    """
    active = st.p < limit
    is_dc = (st.z == 0).astype(I32)
    if stage is None:
        win32 = fetch_window32(dev["words"], word_base, st.p)
        row = dev["unit_lut_row"][ts, st.u, is_dc]
    else:
        win32 = fetch_window32_staged(stage, st.p)
        row = _select_rows(stage.rows, st.u * 2 + is_dc)
    win16 = (win32 >> U32(16)).astype(I32)
    entry = dev["luts"][row, win16]

    clen = entry & 0x1F
    size = (entry >> T.LUT_SIZE_SHIFT) & 0xF
    run = (entry >> T.LUT_RUN_SHIFT) & 0xF
    eob = (entry & T.LUT_EOB_BIT) != 0
    invalid = clen == 0

    # magnitude bits: the `size` bits following the codeword
    shift = (U32(32) - clen.astype(U32) - size.astype(U32)) & U32(31)
    mask = (U32(1) << size.astype(U32)) - U32(1)
    vbits = ((win32 >> shift) & mask).astype(I32)
    half = jnp.left_shift(I32(1), jnp.maximum(size - 1, 0))
    full = jnp.left_shift(I32(1), size)
    coef = jnp.where(vbits < half, vbits - full + 1, vbits)
    coef = jnp.where(size == 0, 0, coef)

    run_eff = jnp.where(eob, 63 - st.z, run)
    run_eff = jnp.where(invalid, 0, run_eff)
    zstep = run_eff + 1
    adv = jnp.where(invalid, min_code_bits, clen + size)

    new_z = st.z + zstep
    blk_done = new_z >= 64
    z_next = jnp.where(blk_done, 0, new_z)
    u_next = jnp.where(blk_done, jnp.where(st.u + 1 >= upm, 0, st.u + 1), st.u)

    nxt = DecodeState(
        p=jnp.where(active, st.p + adv, st.p),
        u=jnp.where(active, u_next, st.u),
        z=jnp.where(active, z_next, st.z),
        n=jnp.where(active, st.n + zstep, st.n),
    )
    return StepOut(nxt, coef, run_eff, active, invalid)


# ---------------------------------------------------------------------------
# Chunk decode: the paper's decode_subsequence over all lanes
# ---------------------------------------------------------------------------

def decode_span(
    dev: Dict[str, jnp.ndarray],
    entry: DecodeState,
    word_base: jnp.ndarray,
    limit: jnp.ndarray,
    ts: jnp.ndarray,
    upm: jnp.ndarray,
    *,
    s_max: int,
    min_code_bits: int,
    write: bool = False,
    out: Optional[jnp.ndarray] = None,
    write_base: Optional[jnp.ndarray] = None,
    write_max: Optional[jnp.ndarray] = None,
    stage: Optional[LaneStage] = None,
) -> Tuple[DecodeState, Optional[jnp.ndarray]]:
    """Decode every lane from its entry state to the end of its bit range.

    Returns the exit states (with per-chunk n counts). When `write=True`,
    coefficients are scattered into `out` at write_base + local_n + run and
    the updated buffer is returned. `stage` (``chunk_meta(...)["stage"]``)
    selects the gather-free step.
    """
    st0 = DecodeState(entry.p, entry.u, entry.z, jnp.zeros_like(entry.p))

    if write:
        assert out is not None and write_base is not None and write_max is not None

        def body(_, carry):
            st, buf = carry
            o = decode_symbol(dev, st, word_base, limit, ts, upm,
                              min_code_bits, stage)
            idx = write_base + st.n + o.run
            ok = o.active & (~o.invalid) & (idx <= write_max)
            # NB: sentinel must be past-the-end, not -1 (negative indices wrap).
            idx = jnp.where(ok, idx, buf.shape[0])
            # unique_indices: within one symbol step every lane writes a
            # distinct index (lanes' write ranges are disjoint: bases are
            # per-segment cumulative and n strictly increases), and the
            # shared sentinel is dropped before writing. Machine-checked
            # by `python -m repro.analysis kernels` (kernel-scatter-race).
            buf = buf.at[idx].set(o.coef, mode="drop", unique_indices=True)
            return o.state, buf

        st, out = jax.lax.fori_loop(0, s_max, body, (st0, out))
        return st, out

    def body(_, st):
        return decode_symbol(dev, st, word_base, limit, ts, upm,
                             min_code_bits, stage).state

    st = jax.lax.fori_loop(0, s_max, body, st0)
    return st, None


def chunk_meta(dev: Dict[str, jnp.ndarray], idx: Optional[jnp.ndarray] = None):
    """Gather per-chunk decode metadata (optionally at a chunk-index subset).

    ``stage`` is the lanes' :class:`LaneStage` where `dev` holds the staged
    operands (``lane_words``, ``lane_rows``), else None; at a subset it is
    gathered once here, outside the symbol loop.
    """
    seg = dev["chunk_seg"] if idx is None else dev["chunk_seg"][idx]
    limit = dev["chunk_limit"] if idx is None else dev["chunk_limit"][idx]
    ts = dev["seg_tableset"][seg]
    stage = None
    if "lane_words" in dev:
        stage = LaneStage(dev["lane_words"], dev["lane_rows"],
                          dev["chunk_start"] >> 5)
        if idx is not None:
            stage = LaneStage(stage.words[:, idx], stage.rows[:, idx],
                              stage.word0[idx])
    return dict(
        word_base=dev["seg_word_base"][seg],
        limit=limit,
        ts=ts,
        upm=dev["ts_upm"][ts],
        stage=stage,
    )


def make_decode_exits(*, s_max: int, min_code_bits: int):
    """Bind loop statics into the pluggable exit-decode protocol.

    The returned ``fn(dev, entry, idx=None) -> DecodeState`` decodes every
    chunk lane (or the ``idx`` subset) from its entry state to its chunk
    end. The sync schedules (core/sync.py) are written against exactly
    this signature, so the Pallas backend
    (``repro.kernels.huffman.ops.make_decode_exits``) is a drop-in.
    """
    def fn(dev, entry, idx=None):
        m = chunk_meta(dev, idx)
        st, _ = decode_span(
            dev, entry, m["word_base"], m["limit"], m["ts"], m["upm"],
            s_max=s_max, min_code_bits=min_code_bits, stage=m["stage"],
        )
        return st
    return fn


# ---------------------------------------------------------------------------
# Output placement: segmented exclusive prefix sum over per-chunk n
# ---------------------------------------------------------------------------

def _seg_scan_op(a, b):
    (va, fa), (vb, fb) = a, b
    return (jnp.where(fb, vb, va + vb), fa | fb)


def segmented_exclusive_cumsum(values: jnp.ndarray, first_flags: jnp.ndarray):
    """Exclusive per-segment prefix sum (paper Alg. 1 lines 7-8, batched)."""
    shifted = jnp.concatenate([jnp.zeros_like(values[:1]), values[:-1]])
    flags = jnp.concatenate([jnp.array([True]), first_flags[1:]])
    # the first element of each segment must start the sum at 0
    shifted = jnp.where(first_flags, 0, shifted)
    out, _ = jax.lax.associative_scan(_seg_scan_op, (shifted, flags))
    return out


def chunk_write_bases(dev, exit_n: jnp.ndarray, permuted: bool = True):
    """Absolute dense-coefficient write base for every chunk lane.

    The segmented prefix sum runs over *bitstream* chunk order — lanes may
    be permuted by a lane-balance plan, so gather ``n`` into chunk order
    via ``chunk_order``, scan, and gather the bases back to lanes via
    ``lane_perm``. Inert padding chunks order after every real chunk and
    are segment-firsts, so they contribute nothing — this holds for both
    balance_lanes padding and the capacity padding of a bucketed
    ``PlanData`` (whose fresh inert lanes take bitstream ids past every
    real id). ``permuted=False`` (static, for identity plans) skips both
    gathers and scans the sharded lane order directly.
    """
    if permuted:
        order = dev["chunk_order"]   # bitstream chunk id -> lane
        local_o = segmented_exclusive_cumsum(
            exit_n[order], dev["chunk_first"][order])
        local = local_o[dev["lane_perm"]]
    else:
        local = segmented_exclusive_cumsum(exit_n, dev["chunk_first"])
    return dev["seg_coeff_base"][dev["chunk_seg"]] + local


# ---------------------------------------------------------------------------
# DC difference decoding (paper §IV-B): segmented prefix sum per component
# ---------------------------------------------------------------------------

def undiff_dc(dev, coeffs: jnp.ndarray, n_components: int = 3) -> jnp.ndarray:
    """Reverse DC prediction over the flat (U, 64) zig-zag coefficient array.

    Capacity-safe: pad units (bucketed plans) are flagged segment-first
    with zero coefficients and sit after every real unit, so the forward
    segmented scans leave the real prefix bit-identical to the exact-fit
    array.
    """
    dc = coeffs[:, 0]
    first = dev["unit_seg_first"]
    total = jnp.zeros_like(dc)
    for c in range(n_components):
        mask = dev["unit_comp"] == c
        vals = jnp.where(mask, dc, 0)
        flags = first  # segment starts reset *all* component predictors
        acc, _ = jax.lax.associative_scan(_seg_scan_op, (vals, flags))
        total = jnp.where(mask, acc, total)
    return coeffs.at[:, 0].set(total)


# ---------------------------------------------------------------------------
# Pixel stage: fused dequant + de-zigzag + IDCT as one matmul (DESIGN.md §3)
# ---------------------------------------------------------------------------

def idct_units_folded(
    coeffs: jnp.ndarray, m_matrices: jnp.ndarray, unit_mrow: jnp.ndarray
) -> jnp.ndarray:
    """(U, 64) zig-zag int coeffs -> (U, 64) row-major pixel values (uint8 range).

    Computes every folded matrix's transform and selects per unit — the
    number of distinct quantization matrices per batch is tiny (usually 2),
    and dense MXU matmuls beat per-unit gathers of 64x64 operands.
    """
    x = coeffs.astype(jnp.float32)
    nq = m_matrices.shape[0]
    out = jnp.zeros_like(x)
    for q in range(nq):
        y = x @ m_matrices[q].T
        out = jnp.where((unit_mrow == q)[:, None], y, out)
    return jnp.clip(jnp.round(out + 128.0), 0.0, 255.0)


def assemble_planes(
    pixels: jnp.ndarray,
    n_images: int,
    comp_unit_idx,
    comp_block_idx,
    comp_grid,
):
    """(U_total, 64) pixels -> list of per-component (B, Hc, Wc) planes.

    Uniform-batch path: every image shares the same scan layout.
    """
    upi = pixels.shape[0] // n_images
    pix = pixels.reshape(n_images, upi, 64)
    planes = []
    for ci in range(len(comp_unit_idx)):
        sel = comp_unit_idx[ci]
        blocks = pix[:, sel, :]  # (B, Uc, 64)
        by, bx = comp_grid[ci]
        plane = jnp.zeros((n_images, by * bx, 64), blocks.dtype)
        plane = plane.at[:, comp_block_idx[ci], :].set(blocks)
        plane = plane.reshape(n_images, by, bx, 8, 8)
        plane = plane.transpose(0, 1, 3, 2, 4).reshape(n_images, by * 8, bx * 8)
        planes.append(plane)
    return planes


def upsample_color(planes, comp_h, comp_v, h_max, v_max, height, width):
    """Replicate-upsample chroma + YCbCr->RGB, cropped to true image size."""
    if len(planes) == 1:
        return jnp.round(planes[0][:, :height, :width]).astype(jnp.uint8)
    full = []
    for ci, p in enumerate(planes):
        fv, fh = v_max // comp_v[ci], h_max // comp_h[ci]
        if fv > 1:
            p = jnp.repeat(p, fv, axis=1)
        if fh > 1:
            p = jnp.repeat(p, fh, axis=2)
        full.append(p[:, : planes[0].shape[1] * (v_max // comp_v[0]),
                      : planes[0].shape[2] * (h_max // comp_h[0])])
    y, cb, cr = full[0], full[1] - 128.0, full[2] - 128.0
    r = y + 1.402 * cr
    g = y - 0.344136286 * cb - 0.714136286 * cr
    b = y + 1.772 * cb
    rgb = jnp.stack([r, g, b], axis=-1)
    rgb = jnp.clip(jnp.round(rgb), 0, 255).astype(jnp.uint8)
    return rgb[:, :height, :width]


# ---------------------------------------------------------------------------
# Crop stage: any batch's frames to fixed-size crops, the layout as data
# ---------------------------------------------------------------------------

def crop_taps(n_in: jnp.ndarray, n_out: int):
    """Bilinear taps along one axis of boxes ``n_in`` (B,) samples long,
    resized to ``n_out``: (lower, upper, weight of the upper), each (B,
    n_out). Half-pixel centres, the position clamped to the box's edge
    samples, no antialiasing. The position ((2i + 1) n_in - n_out) /
    (2 n_out) is floored in integers, so only the weight rounds."""
    den = 2 * n_out
    n_in = n_in[:, None]
    num = (2 * jnp.arange(n_out, dtype=jnp.int32) + 1)[None, :] * n_in - n_out
    num = jnp.clip(num, 0, (n_in - 1) * den)
    lo = num // den
    return (lo, jnp.minimum(lo + 1, n_in - 1),
            (num - lo * den).astype(jnp.float32) / den)


def crop_resize_units(pixels: jnp.ndarray, layout: jnp.ndarray,
                      boxes: jnp.ndarray, out_h: int, out_w: int):
    """(U, 64) row-major block pixels of any batch -> (B, out_h, out_w, 3)
    uint8 crops.

    ``layout`` is :func:`repro.core.bitstream.crop_layout`'s (B, cols)
    per-image layout and ``boxes`` (B, 4) int32 (top, left, height, width)
    in frame pixels, both device data. Each output sample is the frame's
    RGB as :func:`upsample_color` computes it (chroma replicated, float32
    conversion, rounded; a gray frame's plane in all three channels) at the
    box's 2x2 bilinear taps, mixed and rounded once. Only the tap samples
    are gathered: the row and column parts of each flat index are computed
    apart, per component, and added.
    """
    from .bitstream import LAYOUT_COMP, LAYOUT_IMAGE
    img = {k: layout[:, i][:, None] for i, k in enumerate(LAYOUT_IMAGE)}
    y0, y1, wy = crop_taps(boxes[:, 2], out_h)
    x0, x1, wx = crop_taps(boxes[:, 3], out_w)
    ys = boxes[:, :1] + jnp.concatenate([y0, y1], axis=1)   # (B, 2 out_h)
    xs = boxes[:, 1:2] + jnp.concatenate([x0, x1], axis=1)  # (B, 2 out_w)
    idx = []
    for c in range(3):
        k = len(LAYOUT_IMAGE) + c * len(LAYOUT_COMP)
        h, v, slot, fy, fx = (layout[:, k + j][:, None]
                              for j in range(len(LAYOUT_COMP)))
        yc, xc = ys // fy, xs // fx           # the component's own samples
        row = ((img["unit_base"] + (yc // (8 * v)) * img["mcus_x"] * img["upm"]
                + slot + (yc // 8 % v) * h) * 64 + yc % 8 * 8)
        col = ((xc // (8 * h)) * img["upm"] + xc // 8 % h) * 64 + xc % 8
        idx.append(row[:, :, None] + col[:, None, :])
    ycc = jnp.take(pixels.reshape(-1), jnp.stack(idx), mode="clip")
    gray = (img["gray"] == 1)[:, :, None]
    y = ycc[0]
    cb = jnp.where(gray, 0.0, ycc[1] - 128.0)
    cr = jnp.where(gray, 0.0, ycc[2] - 128.0)
    rgb = jnp.stack([y + 1.402 * cr,
                     y - 0.344136286 * cb - 0.714136286 * cr,
                     y + 1.772 * cb])
    rgb = jnp.clip(jnp.round(rgb), 0, 255)  # (3, B, 2 out_h, 2 out_w)
    wy = wy[None, :, :, None]
    wx = wx[None, :, None, :]
    rows = rgb[:, :, :out_h] * (1 - wy) + rgb[:, :, out_h:] * wy
    out = rows[..., :out_w] * (1 - wx) + rows[..., out_w:] * wx
    out = jnp.clip(jnp.round(out), 0, 255).astype(jnp.uint8)
    out = jnp.where((img["live"] == 1)[None, :, :, None], out, 0)
    return out.transpose(1, 2, 3, 0)
