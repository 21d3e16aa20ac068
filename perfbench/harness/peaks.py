"""Published peaks of each chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s
bf16, 394 TOP/s int8, 16 GB HBM at 819 GB/s). A kind that is not here is
an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def least_seconds(flops: float, nbytes: float, device_kind: str,
                  chips: int = 1):
    """(seconds, bound): the least time ``chips`` chips need for the work,
    the larger of its compute and its memory time, and which one it is."""
    p = peaks(device_kind)
    compute = flops / (chips * p["bf16_flops_per_s"])
    memory = nbytes / (chips * p["hbm_bytes_per_s"])
    return (compute, "compute") if compute > memory else (memory, "memory")
