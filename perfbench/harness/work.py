"""The work a decode needs, counted from the JPEGs themselves (scan bytes,
blocks, pixels) and never from the implementation, so that a later change
to a stage is read against the same work.

Entropy stage: read the compressed scan, write every coefficient as int16.
Pixel stage: read the int16 coefficients, write uint8 RGB (one channel
for a grayscale frame; out_height x out_width x 3 for a crop answer); a
separable 8x8 inverse DCT is 2 passes x 8 rows x 8 outputs x 8
multiply-adds, 2,048 operations per block.
"""
from __future__ import annotations

from . import reference

IDCT_FLOPS_PER_BLOCK = 2 * 8 * 8 * 8 * 2
COEFF_BYTES = 64 * 2


def image_work(blob: bytes, crop=None) -> dict:
    """The work of one image; ``crop`` as ``window.Sample.crop``."""
    fr = reference.parse(blob)
    out = (fr.width * fr.height * len(fr.comps) if crop is None
           else int(crop[4]) * int(crop[5]) * 3)
    return {"scan_bytes": len(fr.scan), "blocks": fr.n_units,
            "pixels": fr.width * fr.height, "channels": len(fr.comps),
            "out_bytes": out}


def entropy_work(images: list) -> dict:
    """flops and bytes of the entropy stage over ``image_work`` records."""
    return {"flops": 0.0,
            "bytes": float(sum(w["scan_bytes"] + w["blocks"] * COEFF_BYTES
                               for w in images))}


def pixels_work(images: list) -> dict:
    return {"flops": float(sum(w["blocks"] * IDCT_FLOPS_PER_BLOCK
                               for w in images)),
            "bytes": float(sum(w["blocks"] * COEFF_BYTES + w["out_bytes"]
                               for w in images))}
