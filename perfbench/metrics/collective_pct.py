"""Device time of collective operations (all-reduce, all-gather,
reduce-scatter, all-to-all, collective-permute and their asynchronous
start and done halves) over busy time, in percent, the mean over the chips
used: each chip's union of collective intervals over its union of
operation intervals in the traced window. An operation is a collective by
its own name and opcode (``trace.short_name``), never by its operands.
None where the trace holds no collective."""
import re

from harness.trace import _union, short_name

COLLECTIVE = re.compile(r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|"
                        r"collective-permute|collective-broadcast)")


def read(ctx):
    red = ctx["trace"]
    shares, found = [], False
    for dev in red.devices:
        busy = sum(e - s for s, e in _union(dev.ops))
        coll = [op for op in dev.ops if COLLECTIVE.search(short_name(op[0]))]
        found = found or bool(coll)
        t = sum(e - s for s, e in _union(coll))
        shares.append(100.0 * t / busy if busy > 0 else 0.0)
    return sum(shares) / len(shares) if found else None
