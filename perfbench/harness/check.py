"""The comparison that decides ``correct``.

Each answer kept from the window is decoded again by the plain reference
(``reference.py``) in a worker process, and three numbers are compared,
each with the limit its configuration file gives (``limits``):

- ``missing``: answers due in the window that never came;
- ``coeff_wrong``: coefficients that differ from the reference, where the
  entry returns coefficients (the entropy stage is exact);
- ``rgb_off``: RGB samples more than 1 away from the float64 reference,
  per million samples (the pixel stage's rounding).

``rgb_max``, the largest difference of any sample, and ``rgb_ne``, the
samples per million that differ at all, are reported and not compared:
the bfloat16 control reads 3 where the program reads up to 2, too close
for a limit to part them, and the bfloat16 operands of the chip's matmul
move about one sample in seventy by 1 (the reference at that precision).

``control=<precision>`` puts the reference itself in the program's place,
with its pixels computed at one of ``CONTROLS`` (``reference.pixels``), a
step below what the configurations state: the comparison must refuse each.

An answer with a ``crop`` (``window.Sample``) is compared with the
reference's crop of its own RGB, resized by the method the configuration
names under ``answers.resize`` (``reference.crop_resize``); a control's
pixels go through the same crop. A one-component file's answer is its
(H, W) plane.
"""
from __future__ import annotations

import numpy as np

from . import reference

NUMBERS = ("missing", "coeff_wrong", "rgb_off")
# below the float32 colour conversion; below the bfloat16 matmul operands
CONTROLS = ("bfloat16", "float8_idct")


ANSWERS_KEYS = {"resize"}
RESIZE_METHODS = (reference.RESIZE,)


def answers_spec(config: dict):
    """The resize method the configuration states for crop answers, or
    None; ValueError on a key or a method the comparison cannot honour."""
    answers = config.get("answers", {})
    if not isinstance(answers, dict) or set(answers) - ANSWERS_KEYS:
        raise ValueError(f"answers: only {sorted(ANSWERS_KEYS)} can be stated")
    resize = answers.get("resize")
    if resize is not None and resize not in RESIZE_METHODS:
        raise ValueError(f"answers.resize: {resize!r} is none of {RESIZE_METHODS}")
    return resize


def _answer(img, crop):
    return img if crop is None else reference.crop_resize(img, crop)


def judge(task) -> dict:
    """Pool task: compare one answer with the reference."""
    blob, coeffs, rgb, crop, control = task
    fr, ref_c = reference.coefficients(blob)
    ref_rgb = _answer(reference.pixels(fr, ref_c), crop)
    if control:
        coeffs, rgb = ref_c, _answer(reference.pixels(fr, ref_c, control), crop)
    out = {"coeff_wrong": 0, "rgb_off": 0, "rgb_ne": 0, "rgb_max": 0,
           "samples": int(ref_rgb.size)}
    if coeffs is not None:
        coeffs = np.asarray(coeffs)
        out["coeff_wrong"] = (int((coeffs != ref_c).sum())
                              if coeffs.shape == ref_c.shape else int(ref_c.size))
    rgb = np.asarray(rgb)
    if rgb.shape != ref_rgb.shape:
        out["rgb_off"] = out["rgb_ne"] = out["samples"]
        out["rgb_max"] = 255
    else:
        d = np.abs(rgb.astype(np.int16) - ref_rgb.astype(np.int16))
        out["rgb_off"] = int((d > 1).sum())
        out["rgb_ne"] = int((d > 0).sum())
        out["rgb_max"] = int(d.max())
    return out


def compare(samples, pool, control=None, resize=None) -> dict:
    """The compared numbers over ``samples`` (``window.Sample``), and what
    stands behind them. ``resize``: the configuration's ``answers_spec``,
    which crop answers need."""
    if resize not in RESIZE_METHODS and any(s.crop is not None for s in samples):
        raise ValueError("crop answers, and the configuration states no "
                         "answers.resize")
    missing = sum(s.missing for s in samples)
    tasks = [(s.blob, s.coeffs, s.rgb, s.crop, control)
             for s in samples if not s.missing]
    parts = pool.map(judge, tasks, chunksize=1) if tasks else []
    n = sum(p["samples"] for p in parts)
    return {
        "missing": missing,
        "coeff_wrong": sum(p["coeff_wrong"] for p in parts),
        "rgb_off": 1e6 * sum(p["rgb_off"] for p in parts) / max(n, 1),
        "rgb_ne": 1e6 * sum(p["rgb_ne"] for p in parts) / max(n, 1),
        "rgb_max": max((p["rgb_max"] for p in parts), default=0),
        "images": len(parts),
    }


def verdict(numbers: dict, limits: dict):
    """(correct, {name: {"value", "limit"}}) for the compared numbers."""
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in NUMBERS}
    return all(c["value"] <= c["limit"] for c in checks.values()), checks
