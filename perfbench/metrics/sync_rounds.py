"""Entropy stage: the mean ``DecodeOutput.sync_rounds`` per batch of the
window, the Jacobi rounds the chunk lanes took to agree."""


def read(ctx):
    return ctx["counters"].get("sync_rounds")
