"""The benchmark's corpus: synthetic frames encoded as baseline JPEGs.

A copy of the program's generator (``synth_frame`` and the reference
baseline encoder), byte for byte the same output, kept here so that a
change to the program cannot move the yardstick.

A configuration's corpus is one fixed set of frames, drawn from the seed
its file states (``corpus.seed``), as the paper's corpora are fixed sets:
a run's ``--seed`` orders and samples it and never changes the work. Frames
are encoded in a process pool whose workers import numpy only, never JAX,
and the corpus is cached under ``.bench_datasets/`` by (configuration,
its kinds, size, digest of this file and of the tables it uses), so that
only the first run in a checkout encodes it.

The configuration's ``corpus`` states its frames in one of two forms:

- one kind: ``width``, ``height``, ``quality``, ``subsampling``, and
  optionally ``restart_interval`` (MCUs, 0 for none) and ``tables``;
- ``kinds``: a list of kinds, each with ``share``, ``quality``,
  ``subsampling``, ``restart_interval`` and ``geometries``, a list of
  ``[width, height, weight]``; image *i* draws its kind by share and then
  its geometry by weight from its own generator ``default_rng([seed, i])``,
  which then makes its pixels. Where there is one choice nothing is drawn,
  so the one-kind form gives the same bytes as it always has.

``subsampling`` is ``gray`` (one component, luma tables, the frame's
rounded BT.601 luma), ``4:4:4``, ``4:2:2`` or ``4:2:0``; ``tables`` can only
be ``Annex K``. A key the generator cannot honour raises when the corpus
starts.
"""
from __future__ import annotations

import hashlib
import os
import pickle

import numpy as np

from . import jpeg_tables as T

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".bench_datasets")

# units whose symbols are built at once (about 8 MB per array)
UNITS_PER_RUN = 4096

# fDCT basis: 8x8 orthonormal DCT-II.
_K = np.arange(8)[:, None]
_N = np.arange(8)[None, :]
_C = np.cos((2 * _N + 1) * _K * np.pi / 16) * np.sqrt(2.0 / 8.0)
_C[0] /= np.sqrt(2.0)


def synth_frame(rng: np.random.Generator, width: int, height: int,
                t: float) -> np.ndarray:
    """One photographic-like RGB frame: illumination gradients, oriented
    textures and film grain; ``t`` slides the phases like video."""
    yy, xx = np.mgrid[0:height, 0:width].astype(np.float64)
    xn, yn = xx / width, yy / height
    base = 120 + 60 * np.sin(2.2 * xn + 0.7 * t) * np.cos(1.7 * yn - 0.3 * t)
    tex = np.zeros_like(base)
    for k in range(4):
        fx = 2 ** (k + 2) * np.pi
        ang = 0.6 * k + 0.2 * t
        tex += (18.0 / (k + 1)) * np.sin(
            fx * (xn * np.cos(ang) + yn * np.sin(ang)) + 3.1 * t)
    grain = rng.normal(0, 6.0, size=(height, width))
    luma = base + tex + grain
    cb = 16 * np.sin(3.1 * xn + t) + 10 * np.cos(2.3 * yn)
    cr = 14 * np.cos(2.7 * xn - 0.5 * t) + 9 * np.sin(3.7 * yn + t)
    rgb = np.stack([luma + 1.402 * cr, luma - 0.344 * cb - 0.714 * cr,
                    luma + 1.772 * cb], axis=-1)
    return np.clip(rgb, 0, 255).astype(np.uint8)


def _magnitude(values: np.ndarray):
    """(size category, ones'-complement magnitude bits) of each value."""
    a = np.abs(values.astype(np.int64))
    cat = np.zeros_like(a)
    nz = a > 0
    cat[nz] = np.floor(np.log2(a[nz])).astype(np.int64) + 1
    v = values.astype(np.int64)
    bits = np.where(v >= 0, v, v + (np.int64(1) << cat) - 1)
    return cat, bits


def _code_arrays(table):
    codes = np.zeros(256, np.uint64)
    lens = np.zeros(256, np.int64)
    for sym, (code, length) in T.canonical_codes(*table).items():
        codes[sym], lens[sym] = code, length
    return codes, lens


def _symbol_stream(coeff: np.ndarray, table_of_unit: np.ndarray):
    """(values, lengths) of every Huffman code and magnitude field of the
    scan, in order: per unit DC, then per nonzero AC up to 3 ZRL codes and
    its (run, size) code, then EOB where the block ends early."""
    n = coeff.shape[0]
    dc_cat, dc_bits = _magnitude(coeff[:, 0])
    ac = coeff[:, 1:]
    nz = ac != 0
    pos = np.broadcast_to(np.arange(1, 64), ac.shape)
    prev = np.maximum.accumulate(np.where(nz, pos, 0), axis=1)
    prev0 = np.concatenate([np.zeros((n, 1), np.int64), prev[:, :-1]], 1)
    run = np.where(nz, pos - prev0 - 1, 0)
    ac_cat, ac_bits = _magnitude(ac)
    ac_sym = ((run % 16) << 4) | ac_cat
    dc_code = np.zeros(n, np.uint64)
    dc_len = np.zeros(n, np.int64)
    ac_code = np.zeros(ac.shape, np.uint64)
    ac_len = np.zeros(ac.shape, np.int64)
    zrl = np.zeros((n, 2), np.int64)     # (code, length) per unit
    eob = np.zeros((n, 2), np.int64)
    for tid in np.unique(table_of_unit):
        sel = table_of_unit == tid
        codes, lens = _code_arrays(T.STD_HUFFMAN[("dc", int(tid))])
        dc_code[sel], dc_len[sel] = codes[dc_cat[sel]], lens[dc_cat[sel]]
        codes, lens = _code_arrays(T.STD_HUFFMAN[("ac", int(tid))])
        ac_code[sel], ac_len[sel] = codes[ac_sym[sel]], lens[ac_sym[sel]]
        zrl[sel] = (codes[0xF0], lens[0xF0])
        eob[sel] = (codes[0x00], lens[0x00])
    slots = 1 + 63 * 4 + 1
    vals = np.zeros((n, slots), np.uint64)
    lens = np.zeros((n, slots), np.int64)
    vals[:, 0] = (dc_code << dc_cat.astype(np.uint64)) | dc_bits.astype(np.uint64)
    lens[:, 0] = dc_len + dc_cat
    ac_slot = 4 + np.arange(63) * 4
    for zi in range(3):
        active = (run // 16 > zi) & nz
        vals[:, ac_slot - 3 + zi] = np.where(active, zrl[:, :1], 0).astype(np.uint64)
        lens[:, ac_slot - 3 + zi] = np.where(active, zrl[:, 1:], 0)
    vals[:, ac_slot] = (ac_code << ac_cat.astype(np.uint64)) | ac_bits.astype(np.uint64)
    lens[:, ac_slot] = np.where(nz, ac_len + ac_cat, 0)
    need_eob = prev[:, -1] < 63
    vals[:, -1] = eob[:, 0].astype(np.uint64)
    lens[:, -1] = np.where(need_eob, eob[:, 1], 0)
    keep = lens.reshape(-1) > 0
    return vals.reshape(-1)[keep], lens.reshape(-1)[keep]


def _pack(vals: np.ndarray, lens: np.ndarray) -> bytes:
    """MSB-first bit packing, the last byte padded with ones, then byte
    stuffing (0x00 after every 0xFF)."""
    offs = np.cumsum(lens) - lens
    total = int(offs[-1] + lens[-1])
    nbytes = (total + 7) // 8
    out = np.zeros(nbytes + 8, np.uint8)
    shift = (offs % 8).astype(np.uint64)
    place = vals << (np.uint64(64) - shift - lens.astype(np.uint64))
    byte0 = offs // 8
    for k in range(5):
        np.add.at(out, byte0 + k,
                  ((place >> np.uint64(56 - 8 * k)) & np.uint64(0xFF)).astype(np.uint8))
    if total % 8:
        out[nbytes - 1] |= (1 << (8 - total % 8)) - 1
    clean = out[:nbytes]
    ff = clean == 0xFF
    if not ff.any():
        return clean.tobytes()
    stuffed = np.zeros(nbytes + int(ff.sum()), np.uint8)
    stuffed[np.arange(nbytes) + np.concatenate([[0], np.cumsum(ff)[:-1]])] = clean
    return stuffed.tobytes()


def _segment(marker: int, payload: bytes) -> bytes:
    return bytes([0xFF, marker]) + (len(payload) + 2).to_bytes(2, "big") + payload


def encode(img: np.ndarray, quality: int, subsampling: str,
           restart_interval: int = 0) -> bytes:
    """Baseline JFIF bytes of an (H, W, 3) uint8 image, or an (H, W) one
    for ``gray``: BT.601 YCbCr, box subsampling, float64 fDCT, Annex K
    tables at libjpeg ``quality``, one interleaved scan. Where
    ``restart_interval`` > 0, a DRI segment, and every that many MCUs the
    bits padded with ones, RST0-RST7 in turn, and the DC predictions reset
    (T.81 F.1.1.5, F.1.2.3)."""
    factors = T.SUBSAMPLING[subsampling]
    if (img.ndim == 2) != (len(factors) == 1):
        raise ValueError(f"a {img.shape} image cannot be coded as {subsampling}")
    h_max = max(f[0] for f in factors)
    v_max = max(f[1] for f in factors)
    height, width = img.shape[:2]
    mcus_y, mcus_x = -(-height // (8 * v_max)), -(-width // (8 * h_max))
    ph, pw = mcus_y * 8 * v_max, mcus_x * 8 * h_max
    if img.ndim == 2:
        ycc = img.astype(np.float64)[..., None]
    else:
        rgb = img.astype(np.float64)
        r, g, b = rgb[..., 0], rgb[..., 1], rgb[..., 2]
        ycc = np.stack([0.299 * r + 0.587 * g + 0.114 * b,
                        -0.168735892 * r - 0.331264108 * g + 0.5 * b + 128.0,
                        0.5 * r - 0.418687589 * g - 0.081312411 * b + 128.0], -1)
    quant = (T.quality_quant(T.LUMA_QUANT, quality),
             T.quality_quant(T.CHROMA_QUANT, quality))
    upm = sum(h * v for h, v in factors)
    coeff = np.zeros((mcus_y * mcus_x * upm, 64), np.int32)
    table_of_unit = np.zeros(len(coeff), np.int64)
    slot = 0
    for ci, (fh, fv) in enumerate(factors):
        plane = np.pad(ycc[..., ci], ((0, ph - height), (0, pw - width)),
                       mode="edge")
        sy, sx = v_max // fv, h_max // fh
        if sx > 1 or sy > 1:
            plane = plane.reshape(ph // sy, sy, pw // sx, sx).mean(axis=(1, 3))
        bh, bw = plane.shape[0] // 8, plane.shape[1] // 8
        blocks = (plane.reshape(bh, 8, bw, 8).transpose(0, 2, 1, 3)
                  .reshape(-1, 8, 8)) - 128.0
        f = np.einsum("ij,njk,lk->nil", _C, blocks, _C)
        q = quant[min(ci, 1)].reshape(8, 8)
        qc = (np.sign(f) * np.floor(np.abs(f) / q + 0.5)).astype(np.int32)
        qc = qc.reshape(bh, bw, 64)[..., T.ZIGZAG]
        # scan order: MCU raster, then this component's fv x fh units
        for i in range(fv * fh):
            by, bx = i // fh, i % fh
            units = qc[by::fv, bx::fh].reshape(-1, 64)
            coeff[slot::upm] = units
            table_of_unit[slot::upm] = min(ci, 1)
            slot += 1
    # DC prediction per component, over its units in scan order, from 0 at
    # the start of the scan and of each restart interval
    n_units = len(coeff)
    seg_units = restart_interval * upm if restart_interval else n_units
    segment = np.arange(n_units) // seg_units
    comp = np.tile(np.repeat(np.arange(len(factors)), [h * v for h, v in factors]),
                   n_units // upm)
    for ci in range(len(factors)):
        idx = np.where(comp == ci)[0]
        dc = coeff[idx, 0]
        diff = np.diff(dc, prepend=0)
        first = np.concatenate([[True], segment[idx][1:] != segment[idx][:-1]])
        diff[first] = dc[first]
        coeff[idx, 0] = diff
    # each interval's symbol stream in runs of units, so that a worker's
    # memory stays small at any frame size (each unit's symbols depend on it
    # alone), packed on its own and followed by its marker
    scan = bytearray()
    for k, s in enumerate(range(0, n_units, seg_units)):
        e = min(s + seg_units, n_units)
        parts = [_symbol_stream(coeff[i:min(i + UNITS_PER_RUN, e)],
                                table_of_unit[i:min(i + UNITS_PER_RUN, e)])
                 for i in range(s, e, UNITS_PER_RUN)]
        if k:
            scan += bytes([0xFF, 0xD0 + (k - 1) % 8])
        scan += _pack(np.concatenate([v for v, _ in parts]),
                      np.concatenate([n for _, n in parts]))
    return (_headers(width, height, factors, quant, restart_interval)
            + bytes(scan) + b"\xff\xd9")


def _headers(width, height, factors, quant, restart_interval) -> bytes:
    n_comp = len(factors)
    out = bytearray(b"\xff\xd8")
    out += _segment(0xE0, b"JFIF\x00" + bytes([1, 2, 0]) + (1).to_bytes(2, "big") * 2
                    + bytes([0, 0]))
    for qid in range(min(n_comp, 2)):
        out += _segment(0xDB, bytes([qid]) + bytes(int(quant[qid][T.ZIGZAG[k]])
                                                   for k in range(64)))
    sof = bytes([8]) + height.to_bytes(2, "big") + width.to_bytes(2, "big")
    sof += bytes([n_comp])
    for ci, (h, v) in enumerate(factors):
        sof += bytes([ci + 1, (h << 4) | v, min(ci, 1)])
    out += _segment(0xC0, sof)
    for kind, tid in sorted(k for k in T.STD_HUFFMAN if k[1] < min(n_comp, 2)):
        bits, vals = T.STD_HUFFMAN[(kind, tid)]
        out += _segment(0xC4, bytes([(0 if kind == "dc" else 1) << 4 | tid])
                        + bytes(bits) + bytes(vals))
    if restart_interval:
        out += _segment(0xDD, restart_interval.to_bytes(2, "big"))
    sos = bytes([n_comp])
    for ci in range(n_comp):
        sos += bytes([ci + 1, (min(ci, 1) << 4) | min(ci, 1)])
    out += _segment(0xDA, sos + bytes([0, 63, 0]))
    return bytes(out)


def gray(rgb: np.ndarray) -> np.ndarray:
    """The (H, W) uint8 BT.601 luma of an RGB frame: a grayscale photo."""
    rgb = rgb.astype(np.float64)
    y = 0.299 * rgb[..., 0] + 0.587 * rgb[..., 1] + 0.114 * rgb[..., 2]
    return np.clip(np.round(y), 0, 255).astype(np.uint8)


def _draw(rng: np.random.Generator, weights) -> int:
    """An index drawn by ``weights``; nothing is drawn from a single one."""
    if len(weights) == 1:
        return 0
    p = np.asarray(weights, np.float64)
    return int(rng.choice(len(p), p=p / p.sum()))


def frame(seed: int, i: int, kinds: tuple):
    """(kind, pixels) of frame ``i`` of a corpus of ``kinds`` (``corpus_spec``):
    its own generator draws the kind, the geometry and the pixels, so that
    frames are independent of how a pool splits them."""
    rng = np.random.default_rng([seed, i])
    kind = kinds[_draw(rng, [k[0] for k in kinds])]
    geometries = kind[4]
    width, height, _ = geometries[_draw(rng, [g[2] for g in geometries])]
    img = synth_frame(rng, width, height, t=0.13 * i)
    return kind, gray(img) if kind[2] == "gray" else img


def _encode_frame(args) -> bytes:
    """Pool task: the bytes of frame ``i`` of a corpus."""
    seed, i, kinds = args
    (_, quality, subsampling, restart, _), img = frame(seed, i, kinds)
    return encode(img, quality, subsampling, restart)


def digest() -> str:
    """Digest of the generator's source: a cached corpus is reused only
    while it matches."""
    h = hashlib.sha1()
    for mod in (__file__, T.__file__):
        with open(mod, "rb") as f:
            h.update(f.read())
    return h.hexdigest()[:16]


ONE_KIND_KEYS = {"width", "height", "quality", "subsampling", "restart_interval",
                 "tables", "seed"}
KINDS_KEYS = {"kinds", "tables", "seed"}
KIND_KEYS = {"share", "quality", "subsampling", "restart_interval", "geometries"}
TABLES = "Annex K"


def _keys(d: dict, allowed: set, required: set, where: str) -> None:
    if not isinstance(d, dict):
        raise ValueError(f"{where} is not an object")
    if set(d) - allowed:
        raise ValueError(f"{where}: the generator cannot honour "
                         f"{sorted(set(d) - allowed)}")
    if required - set(d):
        raise ValueError(f"{where}: {sorted(required - set(d))} missing")


def _whole(x, lo: int, hi: int, where: str) -> int:
    if isinstance(x, bool) or not isinstance(x, int) or not lo <= x <= hi:
        raise ValueError(f"{where}: {x!r} is not a whole number in [{lo}, {hi}]")
    return x


def _weights(ws, where: str) -> None:
    if not all(isinstance(w, (int, float)) and not isinstance(w, bool) and w > 0
               for w in ws):
        raise ValueError(f"{where}: every weight must be a number above 0")
    if abs(sum(ws) - 1.0) > 1e-6:
        raise ValueError(f"{where}: the weights sum to {sum(ws)!r}, not 1")


def corpus_spec(config: dict) -> tuple:
    """The configuration's corpus as a tuple of kinds, each ``(share,
    quality, subsampling, restart_interval, ((width, height, weight), ...))``;
    ValueError on anything the generator cannot honour."""
    c = config["corpus"]
    if isinstance(c, dict) and "kinds" in c:
        _keys(c, KINDS_KEYS, {"kinds", "seed"}, "corpus")
        kinds = c["kinds"]
        if not isinstance(kinds, list) or not kinds:
            raise ValueError("corpus.kinds: a list of one kind or more")
    else:
        _keys(c, ONE_KIND_KEYS, {"width", "height", "quality", "subsampling", "seed"},
              "corpus")
        kinds = [{"share": 1.0, "quality": c["quality"],
                  "subsampling": c["subsampling"],
                  "restart_interval": c.get("restart_interval", 0),
                  "geometries": [[c["width"], c["height"], 1.0]]}]
    if c.get("tables", TABLES) != TABLES:
        raise ValueError(f"corpus.tables: only {TABLES!r} tables are written, "
                         f"not {c['tables']!r}")
    for n, k in enumerate(kinds):
        _keys(k, KIND_KEYS, KIND_KEYS, f"corpus.kinds[{n}]")
    _weights([k["share"] for k in kinds], "corpus.kinds shares")
    out = []
    for n, k in enumerate(kinds):
        where = f"corpus.kinds[{n}]"
        if k["subsampling"] not in T.SUBSAMPLING:
            raise ValueError(f"{where}.subsampling: {k['subsampling']!r} is none of "
                             f"{sorted(T.SUBSAMPLING)}")
        geoms = k["geometries"]
        if not isinstance(geoms, list) or not geoms or not all(
                isinstance(g, list) and len(g) == 3 for g in geoms):
            raise ValueError(f"{where}.geometries: a list of [width, height, weight]")
        _weights([g[2] for g in geoms], f"{where}.geometries")
        out.append((float(k["share"]), _whole(k["quality"], 1, 100, f"{where}.quality"),
                    k["subsampling"],
                    _whole(k["restart_interval"], 0, 65535, f"{where}.restart_interval"),
                    tuple((_whole(w, 1, 65535, f"{where} width"),
                           _whole(h, 1, 65535, f"{where} height"), float(p))
                          for w, h, p in geoms)))
    return tuple(out)


def tagged(blob: bytes, tag: str) -> bytes:
    """``blob`` with a comment segment (COM) holding ``tag`` after its SOI:
    the same image and scan under other bytes, so that no answer can be
    served again from a cache of earlier inputs."""
    body = tag.encode()
    return blob[:2] + _segment(0xFE, body) + blob[2:]


class _Pending:
    """A corpus on its way: read from the cache, or being encoded."""

    def __init__(self, path, result, how):
        self.path, self._result, self.how = path, result, how

    def get(self) -> list:
        if self.path is None:
            return self._result
        blobs = self._result.get()
        os.makedirs(CACHE_DIR, exist_ok=True)
        tmp = f"{self.path}.{os.getpid()}.tmp"
        with open(tmp, "wb") as f:
            pickle.dump(blobs, f)
        os.replace(tmp, self.path)
        return blobs


def start(config: dict, pool) -> _Pending:
    """The ``n_images`` JPEGs of ``config``'s corpus: read from the cache,
    or encoded in ``pool`` while the caller goes on. Raises ValueError on a
    corpus the generator cannot honour, before any work."""
    kinds = corpus_spec(config)
    seed, n_images = config["corpus"]["seed"], config["n_images"]
    key = hashlib.sha1(repr((config["name"], kinds, seed,
                             n_images, digest())).encode()).hexdigest()[:20]
    path = os.path.join(CACHE_DIR, f"{config['name']}_{key}.pkl")
    if os.path.exists(path):
        with open(path, "rb") as f:
            return _Pending(None, pickle.load(f), "read from the corpus cache")
    tasks = [(seed, i, kinds) for i in range(n_images)]
    return _Pending(path, pool.map_async(_encode_frame, tasks, chunksize=1),
                    "encoded in the worker pool")
