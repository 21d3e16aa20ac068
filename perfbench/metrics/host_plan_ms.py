"""Host parse, plan, pad and metadata upload: the mean milliseconds per
batch of ``ParallelDecoder.from_bytes`` over the window, on the host clock."""


def read(ctx):
    return ctx["counters"].get("host_plan_ms")
