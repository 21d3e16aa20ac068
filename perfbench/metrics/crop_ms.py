"""Crop stage: device milliseconds per batch in the pixel program's
``repro.pixels.crop`` scope (the tap gather, colour conversion and resize
of ``ParallelDecoder.decode(emit="crop")``). The program maps its
entry-level instructions to the scope (``DecodeProgram.pixel_phases()``);
their device time inside each ``jit__pixels`` execution is summed, the
mean over devices, and divided by the window's batches. None where no
program has the scope, or executions and batches differ in number."""
import bisect

PHASE = "repro.pixels.crop"
MODULE = "jit__pixels"


def _crop_instructions():
    """Instruction names of the crop scope over every program this process
    ran; None where there is none."""
    from repro.core import api
    names = set()
    for prog in api.decode_programs():
        phases = getattr(prog, "pixel_phases", None)
        if phases:
            names |= {n for n, p in phases().items() if p == PHASE}
    return names or None


def read(ctx):
    batches = ctx["counters"].get("batch_blobs")
    names = _crop_instructions() if batches else None
    if not names:
        return None
    red = ctx["trace"]
    seconds, counts = 0.0, set()
    for dev in red.devices:
        runs = sorted((s, e) for n, s, e in dev.modules
                      if n.split("(")[0] == MODULE)
        starts = [s for s, _ in runs]
        for n, s, e in dev.ops:
            if n.partition(" = ")[0].lstrip("%") in names:
                i = bisect.bisect_right(starts, s) - 1
                if i >= 0 and s <= runs[i][1]:
                    seconds += (e - s) / len(red.devices)
        counts.add(len(runs))
    if counts != {len(batches)}:
        return None
    return 1e3 * seconds / len(batches)
