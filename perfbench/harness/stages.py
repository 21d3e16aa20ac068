"""A stage's share of its roofline, from the trace and the work counts."""
from __future__ import annotations

from . import peaks, work

_WORK = {"entropy": work.entropy_work, "pixels": work.pixels_work}


def roofline_pct(ctx, program: str, stage: str):
    """100 x least time / device time of the programs matching ``program``
    over the window's batches; None where the trace holds none of them, or
    not one per batch."""
    red, counters = ctx["trace"], ctx["counters"]
    seconds = red.module_s(program)
    batches = counters.get("batch_blobs")
    if seconds <= 0 or not batches or red.module_count(program) != len(batches):
        return None
    # a driver whose answers are crops gives them per batch as batch_crops
    crops = counters.get("batch_crops") or [[None] * len(b) for b in batches]
    images = [work.image_work(b, c) for batch, cs in zip(batches, crops)
              for b, c in zip(batch, cs)]
    w = _WORK[stage](images)
    least, _ = peaks.least_seconds(w["flops"], w["bytes"], ctx["device_kind"],
                                   chips=len(red.devices))
    return 100.0 * least / seconds
