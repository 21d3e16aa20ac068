"""Device idle share of the traced window while the host is inside the
program's ``repro.from_bytes`` span (parse, plan, pad, upload), in percent,
the mean over the chips used."""
from harness import phases


def read(ctx):
    return phases.idle_under_pct(ctx, "repro.from_bytes")
