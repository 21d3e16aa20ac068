"""Entropy stage: device milliseconds per batch of the program's
``repro.entropy.write`` phase (write bases, chain entries, the write pass
and the DC undifferencing), the mean over the window's batches."""
from harness import phases


def read(ctx):
    found = phases.entropy(ctx)
    if found is None:
        return None
    seconds, batches = found
    return 1e3 * seconds[phases.WRITE] / len(batches)
