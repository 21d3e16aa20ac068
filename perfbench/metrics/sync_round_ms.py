"""Entropy stage: device milliseconds per sync round. The device time of
the program's ``repro.entropy.sync`` phase (the schedule's loop, with
jacobi's initial speculative pass, which ``sync_rounds`` counts as round
1) over the window's batches, divided by the sum of their rounds (the
``rounds`` of each batch's ``repro.rounds`` span)."""
from harness import phases


def read(ctx):
    found = phases.entropy(ctx)
    if found is None:
        return None
    seconds, batches = found
    return 1e3 * seconds[phases.SYNC] / sum(b["rounds"] for b in batches)
