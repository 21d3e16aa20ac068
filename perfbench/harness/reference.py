"""The plain reference: a sequential baseline JPEG decoder (ITU-T T.81).

It parses the file, decodes the Huffman scan one symbol after another,
reverses the DC prediction, and computes pixels in float64: dequantize,
8x8 inverse DCT, level shift, round and clip each sample, replicate the
chroma up to full size, convert BT.601 YCbCr to RGB, round and clip. A
one-component (grayscale) file gives its (H, W) plane. It imports nothing
of the program.

``crop_resize`` makes a crop answer from that: the box cut from the
image's RGB and resized (``RESIZE``: bilinear with half-pixel centres, no
antialiasing, in float64, rounded once).

``pixels(..., precision=...)`` computes the same pixels at a lower
precision (``PRECISIONS``):

- ``"bfloat16"``: every operand and result rounded to bfloat16, the step
  below the float32 colour conversion; a control;
- ``"float8_idct"``: the inverse DCT as one 64x64 matrix product (dequant
  folded in) whose operands are rounded to float8 e4m3, each row of
  coefficients and the matrix scaled to its range, products summed in
  float32: the step below the bfloat16 operands the chip's matmul takes;
  a control;
- ``"bfloat16_idct"``: that product with its operands rounded to
  bfloat16, what the chip's default matmul precision computes; no
  control, a witness of what the program should read.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from . import jpeg_tables as T

# IDCT basis: 8x8 orthonormal DCT-II matrix C; IDCT(F) = C.T @ F @ C.
_K = np.arange(8)[:, None]
_N = np.arange(8)[None, :]
_C = np.cos((2 * _N + 1) * _K * np.pi / 16) * np.sqrt(2.0 / 8.0)
_C[0] /= np.sqrt(2.0)


class JpegError(ValueError):
    """The file is not a baseline JPEG this reference decodes."""


@dataclasses.dataclass
class Frame:
    width: int
    height: int
    comps: list            # (h, v, quant id, dc table, ac table) per component
    quant: dict            # id -> (64,) natural order
    huffman: dict          # ("dc"|"ac", id) -> (bits, vals)
    restart_interval: int
    scan: bytes            # entropy-coded data, still stuffed

    @property
    def h_max(self):
        return max(c[0] for c in self.comps)

    @property
    def v_max(self):
        return max(c[1] for c in self.comps)

    @property
    def mcus(self):
        return (-(-self.height // (8 * self.v_max)),
                -(-self.width // (8 * self.h_max)))

    @property
    def n_units(self):
        my, mx = self.mcus
        return my * mx * sum(c[0] * c[1] for c in self.comps)


def parse(data: bytes) -> Frame:
    """Headers of a baseline (SOF0) JPEG and its one interleaved scan."""
    if data[:2] != b"\xff\xd8":
        raise JpegError("no SOI")
    pos, quant, huffman, comps = 2, {}, {}, []
    size = restart = 0
    while pos + 4 <= len(data):
        if data[pos] != 0xFF:
            raise JpegError(f"no marker at byte {pos}")
        marker = data[pos + 1]
        length = int.from_bytes(data[pos + 2:pos + 4], "big")
        body = data[pos + 4:pos + 2 + length]
        pos += 2 + length
        if marker == 0xDB:
            p = 0
            while p < len(body):
                if body[p] >> 4:
                    raise JpegError("16-bit quantization table")
                q = np.zeros(64, np.int64)
                q[T.ZIGZAG] = np.frombuffer(body[p + 1:p + 65], np.uint8)
                quant[body[p] & 15] = q
                p += 65
        elif marker == 0xC4:
            p = 0
            while p < len(body):
                bits = tuple(body[p + 1:p + 17])
                vals = tuple(body[p + 17:p + 17 + sum(bits)])
                T.check_huffman(bits, vals)
                huffman[("dc" if body[p] >> 4 == 0 else "ac", body[p] & 15)] = (bits, vals)
                p += 17 + sum(bits)
        elif marker == 0xC0:
            if body[0] != 8:
                raise JpegError("sample precision is not 8 bits")
            size = (int.from_bytes(body[3:5], "big"), int.from_bytes(body[1:3], "big"))
            comps = [[body[6 + 3 * i] , body[7 + 3 * i] >> 4, body[7 + 3 * i] & 15,
                      body[8 + 3 * i]] for i in range(body[5])]
        elif 0xC1 <= marker <= 0xCF and marker not in (0xC4, 0xC8, 0xCC):
            raise JpegError(f"frame type 0xFF{marker:02X} is not baseline")
        elif marker == 0xDD:
            restart = int.from_bytes(body[:2], "big")
        elif marker == 0xDA:
            if body[0] != len(comps):
                raise JpegError("scan does not interleave every component")
            ids = [c[0] for c in comps]
            tables = {body[1 + 2 * i]: body[2 + 2 * i] for i in range(body[0])}
            end = pos
            while True:
                end = data.index(b"\xff", end)
                if data[end + 1] != 0 and not 0xD0 <= data[end + 1] <= 0xD7:
                    break
                end += 2
            return Frame(
                width=size[0], height=size[1],
                comps=[(h, v, tq, tables[cid] >> 4, tables[cid] & 15)
                       for (cid, h, v, tq) in comps if cid in ids],
                quant=quant, huffman=huffman, restart_interval=restart,
                scan=data[pos:end])
    raise JpegError("no scan")


def _segments(scan: bytes) -> list:
    """Unstuffed entropy segments, split at the restart markers."""
    out, cur, i = [], bytearray(), 0
    while i < len(scan):
        b = scan[i]
        if b == 0xFF:
            nxt = scan[i + 1]
            if nxt == 0:
                cur.append(0xFF)
            elif 0xD0 <= nxt <= 0xD7:
                out.append(bytes(cur))
                cur = bytearray()
            i += 2
            continue
        cur.append(b)
        i += 1
    out.append(bytes(cur))
    return out


def _lut(bits, vals) -> list:
    """16-bit lookahead table: entry = code length | symbol << 5 (0: no code)."""
    lut = [0] * 65536
    for sym, (code, length) in T.canonical_codes(bits, vals).items():
        lo = code << (16 - length)
        lut[lo:lo + (1 << (16 - length))] = [length | sym << 5] * (1 << (16 - length))
    return lut


def _decode_segment(seg: bytes, n_units: int, unit_tables: list, out: list,
                    base: int) -> None:
    """Decode ``n_units`` blocks of one entropy segment into ``out`` from
    ``base`` (zig-zag order, DC still differential)."""
    # 40-bit window at every byte: enough for a 16-bit code plus 15 magnitude
    # bits at any bit offset within the byte.
    b = np.frombuffer(seg + b"\x00" * 8, np.uint8).astype(np.uint64)
    win = ((b[:-4] << np.uint64(32)) | (b[1:-3] << np.uint64(24))
           | (b[2:-2] << np.uint64(16)) | (b[3:-1] << np.uint64(8)) | b[4:]).tolist()
    limit = len(seg) * 8
    p = 0
    for u in range(n_units):
        dc_lut, ac_lut = unit_tables[u]
        w = win[p >> 3]
        s = 40 - (p & 7)
        e = dc_lut[(w >> (s - 16)) & 0xFFFF]
        if not e:
            raise JpegError(f"no DC code at bit {p}")
        clen, size = e & 31, e >> 5
        v = (w >> (s - clen - size)) & ((1 << size) - 1)
        if size and v < 1 << (size - 1):
            v -= (1 << size) - 1
        out[base] = v
        p += clen + size
        k = 1
        while k < 64:
            w = win[p >> 3]
            s = 40 - (p & 7)
            e = ac_lut[(w >> (s - 16)) & 0xFFFF]
            if not e:
                raise JpegError(f"no AC code at bit {p}")
            clen, sym = e & 31, e >> 5
            if sym == 0:
                p += clen
                break
            if sym == 0xF0:
                p += clen
                k += 16
                continue
            size = sym & 15
            k += sym >> 4
            if k > 63:
                raise JpegError("AC run past the end of a block")
            v = (w >> (s - clen - size)) & ((1 << size) - 1)
            if v < 1 << (size - 1):
                v -= (1 << size) - 1
            out[base + k] = v
            p += clen + size
            k += 1
        base += 64
    if p > limit:
        raise JpegError("segment decoded past its end")


def coefficients(data: bytes):
    """(frame, (n_units, 64) int32): every block in scan order, zig-zag
    order, DC prediction reversed (reset at each restart marker)."""
    fr = parse(data)
    luts = {k: _lut(*v) for k, v in fr.huffman.items()}
    my, mx = fr.mcus
    per_mcu = [ci for ci, c in enumerate(fr.comps) for _ in range(c[0] * c[1])]
    tables = [(luts[("dc", fr.comps[ci][3])], luts[("ac", fr.comps[ci][4])])
              for ci in per_mcu]
    n_mcus = my * mx
    interval = fr.restart_interval or n_mcus
    segs = _segments(fr.scan)
    if len(segs) != -(-n_mcus // interval):
        raise JpegError("restart markers do not match the restart interval")
    flat = [0] * (fr.n_units * 64)
    upm = len(per_mcu)
    for i, seg in enumerate(segs):
        m0 = i * interval
        m1 = min(m0 + interval, n_mcus)
        _decode_segment(seg, (m1 - m0) * upm, tables * (m1 - m0), flat,
                        m0 * upm * 64)
    coeff = np.asarray(flat, np.int32).reshape(-1, 64)
    comp = np.tile(per_mcu, n_mcus)
    seg_of_unit = np.repeat(np.arange(n_mcus) // interval, upm)
    for ci in range(len(fr.comps)):
        for s in range(len(segs)):
            idx = np.where((comp == ci) & (seg_of_unit == s))[0]
            coeff[idx, 0] = np.cumsum(coeff[idx, 0])
    return fr, coeff


def _bf16(x: np.ndarray) -> np.ndarray:
    """Round float values to the nearest bfloat16 (ties to even)."""
    import ml_dtypes
    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(np.float64)


def _fp8(x: np.ndarray, axis=None) -> np.ndarray:
    """Round to float8 e4m3 after scaling to its range (per ``axis``)."""
    import ml_dtypes
    top = float(ml_dtypes.finfo(ml_dtypes.float8_e4m3fn).max)
    scale = np.abs(x).max(axis=axis, keepdims=axis is not None) / top
    scale = np.where(scale > 0, scale, 1.0)
    return (np.asarray(x / scale, np.float32).astype(ml_dtypes.float8_e4m3fn)
            .astype(np.float64) * scale)


# the inverse DCT of a natural-order 8x8 block as one (64 pixels) x (64
# coefficients) matrix: pix[i, l] = sum_jk C[j, i] F[j, k] C[k, l]
_FOLD = np.einsum("ji,kl->iljk", _C, _C).reshape(64, 64)
_FOLDED = {"float8_idct": (lambda x: _fp8(x, axis=1), _fp8),
           "bfloat16_idct": (_bf16, _bf16)}
PRECISIONS = ("float64", "bfloat16") + tuple(_FOLDED)


def _idct(nat: np.ndarray, quant, precision: str, rnd) -> np.ndarray:
    """(..., 64) natural-order coefficients -> (n, 8, 8) pixels, unshifted."""
    if precision in _FOLDED:
        rnd_x, rnd_m = _FOLDED[precision]
        m = rnd_m(_FOLD * np.asarray(quant, np.float64)[None, :])
        pix = (rnd_x(nat.reshape(-1, 64)) @ m.T).astype(np.float32)
        return pix.astype(np.float64).reshape(-1, 8, 8)
    deq = rnd(nat * quant).reshape(-1, 8, 8)
    basis = rnd(_C)
    if precision == "bfloat16":
        return rnd(np.einsum("nik,kl->nil",
                             rnd(np.einsum("ji,njk->nik", basis, deq)), basis))
    return np.einsum("ji,njk,kl->nil", basis, deq, basis)


def pixels(fr: Frame, coeff: np.ndarray, precision: str = "float64") -> np.ndarray:
    """(H, W, 3) uint8 RGB from absolute zig-zag coefficients; (H, W) for
    a one-component file."""
    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}")
    rnd = _bf16 if precision == "bfloat16" else (lambda x: x)
    my, mx = fr.mcus
    upm = sum(c[0] * c[1] for c in fr.comps)
    units = coeff.reshape(my, mx, upm, 64)
    planes, slot = [], 0
    for h, v, tq, _, _ in fr.comps:
        nat = np.zeros((my, mx, v * h, 64))
        nat[..., T.ZIGZAG] = units[:, :, slot:slot + v * h, :]
        slot += v * h
        pix = np.clip(np.round(_idct(nat, fr.quant[tq], precision, rnd) + 128.0),
                      0, 255)
        plane = (pix.reshape(my, mx, v, h, 8, 8).transpose(0, 2, 4, 1, 3, 5)
                 .reshape(my * v * 8, mx * h * 8))
        fy, fx = fr.v_max // v, fr.h_max // h
        planes.append(np.repeat(np.repeat(plane, fy, axis=0), fx, axis=1))
    if len(planes) == 1:
        return planes[0].astype(np.uint8)[:fr.height, :fr.width]
    y, cb, cr = planes[0], planes[1] - 128.0, planes[2] - 128.0
    rgb = np.stack([rnd(y + rnd(1.402 * cr)),
                    rnd(y - rnd(0.344136286 * cb) - rnd(0.714136286 * cr)),
                    rnd(y + rnd(1.772 * cb))], axis=-1)
    rgb = np.clip(np.round(rgb), 0, 255).astype(np.uint8)
    return rgb[:fr.height, :fr.width]


def decode(data: bytes, precision: str = "float64"):
    """(coefficients, RGB) of one JPEG."""
    fr, coeff = coefficients(data)
    return coeff, pixels(fr, coeff, precision)


RESIZE = "bilinear_half_pixel"


def _taps(n_in: int, n_out: int):
    """(lower index, upper index, weight of the upper) of each output sample
    along one axis: half-pixel centres, the position clamped to the edge
    samples."""
    x = np.clip((np.arange(n_out) + 0.5) * (n_in / n_out) - 0.5, 0, n_in - 1)
    lo = np.floor(x).astype(np.int64)
    return lo, np.minimum(lo + 1, n_in - 1), x - lo


def crop_resize(img: np.ndarray, crop) -> np.ndarray:
    """The ``(top, left, height, width, out_height, out_width)`` crop of an
    (H, W, 3) or (H, W) uint8 image as (out_height, out_width, 3) uint8 RGB:
    the box resized by ``RESIZE``. A one-component image's RGB is its plane
    in all three channels (Cb = Cr = 128)."""
    top, left, h, w, oh, ow = (int(v) for v in crop)
    if min(h, w, oh, ow) < 1 or top < 0 or left < 0 or top + h > img.shape[0] \
            or left + w > img.shape[1]:
        raise ValueError(f"crop {crop} does not fit a {img.shape} image")
    box = img[top:top + h, left:left + w].astype(np.float64)
    if box.ndim == 2:
        box = np.repeat(box[..., None], 3, axis=-1)
    y0, y1, fy = _taps(h, oh)
    x0, x1, fx = _taps(w, ow)
    rows = box[y0] * (1 - fy)[:, None, None] + box[y1] * fy[:, None, None]
    out = rows[:, x0] * (1 - fx)[None, :, None] + rows[:, x1] * fx[None, :, None]
    return np.clip(np.round(out), 0, 255).astype(np.uint8)
