"""Entropy stage: device microseconds per sequential symbol step of the
sync phase. The device time of ``repro.entropy.sync`` over the window's
batches, divided by the sum over batches of rounds x ``s_max`` (the
symbol steps each round runs, from the batch's
``repro.dispatch.entropy`` span)."""
from harness import phases


def read(ctx):
    found = phases.entropy(ctx)
    if found is None:
        return None
    seconds, batches = found
    steps = sum(b["rounds"] * b["s_max"] for b in batches)
    return 1e6 * seconds[phases.SYNC] / steps
