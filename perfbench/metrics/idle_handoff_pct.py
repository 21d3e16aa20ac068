"""Device idle share of the traced window while the host is inside the
program's ``repro.decode`` span (dispatching the entropy stage, waiting for
its rounds, dispatching the pixel stage), in percent, the mean over the
chips used."""
from harness import phases


def read(ctx):
    return phases.idle_under_pct(ctx, "repro.decode")
