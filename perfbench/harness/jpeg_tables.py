"""JPEG tables from ITU-T T.81: zig-zag order, the Annex K quantization and
Huffman tables, libjpeg quality scaling and canonical codes.

The benchmark's own copy, shared by its corpus generator and its reference
decoder, so that no change to the program's tables moves the yardstick.
"""
from __future__ import annotations

import numpy as np

# ZIGZAG[k] = natural (row-major) index of the k-th coefficient in zig-zag order.
ZIGZAG = np.array([
    0, 1, 8, 16, 9, 2, 3, 10, 17, 24, 32, 25, 18, 11, 4, 5,
    12, 19, 26, 33, 40, 48, 41, 34, 27, 20, 13, 6, 7, 14, 21, 28,
    35, 42, 49, 56, 57, 50, 43, 36, 29, 22, 15, 23, 30, 37, 44, 51,
    58, 59, 52, 45, 38, 31, 39, 46, 53, 60, 61, 54, 47, 55, 62, 63,
], dtype=np.int32)

# Annex K.1, natural order.
LUMA_QUANT = np.array([
    16, 11, 10, 16, 24, 40, 51, 61, 12, 12, 14, 19, 26, 58, 60, 55,
    14, 13, 16, 24, 40, 57, 69, 56, 14, 17, 22, 29, 51, 87, 80, 62,
    18, 22, 37, 56, 68, 109, 103, 77, 24, 35, 55, 64, 81, 104, 113, 92,
    49, 64, 78, 87, 103, 121, 120, 101, 72, 92, 95, 98, 112, 100, 103, 99,
], dtype=np.int32)

CHROMA_QUANT = np.array([
    17, 18, 24, 47, 99, 99, 99, 99, 18, 21, 26, 66, 99, 99, 99, 99,
    24, 26, 56, 99, 99, 99, 99, 99, 47, 66, 99, 99, 99, 99, 99, 99,
] + [99] * 32, dtype=np.int32)

# Annex K.3: (bits, vals) per table; bits[i] = number of codes of length i+1.
_AC_LUMA_VALS = bytes.fromhex(
    "01020300041105122131410613516107227114328191a1082342b1c11552d1f0"
    "2433627282090a161718191a25262728292a3435363738393a43444546474849"
    "4a535455565758595a636465666768696a737475767778797a83848586878889"
    "8a92939495969798999aa2a3a4a5a6a7a8a9aab2b3b4b5b6b7b8b9bac2c3c4c5"
    "c6c7c8c9cad2d3d4d5d6d7d8d9dae1e2e3e4e5e6e7e8e9eaf1f2f3f4f5f6f7f8"
    "f9fa")
_AC_CHROMA_VALS = bytes.fromhex(
    "000102031104052131061241510761711322328108144291a1b1c109233352f0"
    "156272d10a162434e125f11718191a262728292a35363738393a434445464748"
    "494a535455565758595a636465666768696a737475767778797a828384858687"
    "88898a92939495969798999aa2a3a4a5a6a7a8a9aab2b3b4b5b6b7b8b9bac2c3"
    "c4c5c6c7c8c9cad2d3d4d5d6d7d8d9dae2e3e4e5e6e7e8e9eaf2f3f4f5f6f7f8"
    "f9fa")
STD_HUFFMAN = {
    ("dc", 0): ((0, 1, 5, 1, 1, 1, 1, 1, 1, 0, 0, 0, 0, 0, 0, 0), tuple(range(12))),
    ("ac", 0): ((0, 2, 1, 3, 3, 2, 4, 3, 5, 5, 4, 4, 0, 0, 1, 0x7D),
                tuple(_AC_LUMA_VALS)),
    ("dc", 1): ((0, 3, 1, 1, 1, 1, 1, 1, 1, 1, 1, 0, 0, 0, 0, 0), tuple(range(12))),
    ("ac", 1): ((0, 2, 1, 2, 4, 4, 3, 4, 7, 5, 4, 4, 0, 1, 2, 0x77),
                tuple(_AC_CHROMA_VALS)),
}

# (h, v) sampling factors per component; "gray" is one component, coded
# with the luma tables.
SUBSAMPLING = {
    "gray": ((1, 1),),
    "4:4:4": ((1, 1), (1, 1), (1, 1)),
    "4:2:2": ((2, 1), (1, 1), (1, 1)),
    "4:2:0": ((2, 2), (1, 1), (1, 1)),
}


def quality_quant(base: np.ndarray, quality: int) -> np.ndarray:
    """libjpeg quality scaling of a base table (50 = base, 100 = all ones)."""
    quality = int(np.clip(quality, 1, 100))
    scale = 5000 // quality if quality < 50 else 200 - quality * 2
    q = (base.astype(np.int64) * scale + 50) // 100
    return np.clip(q, 1, 255).astype(np.int32)


def canonical_codes(bits, vals):
    """{symbol: (code, length)} of a (bits, vals) table (T.81 Annex C)."""
    out = {}
    code, k = 0, 0
    for length in range(1, 17):
        for _ in range(bits[length - 1]):
            out[vals[k]] = (code, length)
            code += 1
            k += 1
        code <<= 1
    return out


def check_huffman(bits, vals) -> None:
    """Raise on a (bits, vals) pair that is no prefix code."""
    if len(bits) != 16 or sum(bits) != len(vals):
        raise ValueError("Huffman table: counts do not match its values")
    if sum(n / (1 << (i + 1)) for i, n in enumerate(bits)) > 1.0:
        raise ValueError("Huffman table breaks the Kraft inequality")
