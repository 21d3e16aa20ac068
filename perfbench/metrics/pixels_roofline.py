"""Pixel stage: the least time its work needs on this chip (int16
coefficients in, uint8 RGB out, 2,048 operations per block for a separable
inverse DCT; the larger of the memory and the compute bound) over the device
time of the programs named ``_pixels`` in the trace, in percent."""
from harness import stages


def read(ctx):
    return stages.roofline_pct(ctx, r"jit__pixels\b", "pixels")
