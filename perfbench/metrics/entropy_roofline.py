"""Entropy stage: the least time its work needs on this chip (every scan
byte read, every coefficient written as int16, at the HBM peak) over the
device time of the programs named ``_coeffs`` in the trace, in percent."""
from harness import stages


def read(ctx):
    return stages.roofline_pct(ctx, r"jit__coeffs\b", "entropy")
