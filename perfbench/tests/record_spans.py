"""Record the small trace the span readers are checked on.

    python3 perfbench/tests/record_spans.py <out_dir>

on one TPU. Two batches of two 64x48 frames (q95, 4:2:0, 1,024-bit
subsequences, jacobi), each through ``ParallelDecoder.from_bytes``
(``bench.plan``) and ``decode(emit="rgb")`` (``bench.decode``), a 10 ms
sleep between them, inside ``bench.window``; the profiler's Python tracer
off. Every batch is decoded once before the profiler starts, and its
coefficients and RGB must come out the same under it. Writes
``spans.xplane.pb.gz`` and ``spans.phases.json`` (the entropy program's
``device_phases()``) to ``out_dir``.
"""
import glob
import gzip
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src"))


def main(out_dir: str) -> int:
    import jax
    import numpy as np
    from harness import corpus
    from harness.window import annotate
    from repro.core.api import ParallelDecoder

    if jax.devices()[0].platform != "tpu":
        print("record_spans: no TPU found", file=sys.stderr)
        return 1
    frames = [corpus.encode(corpus.synth_frame(np.random.default_rng(i), 64, 48,
                                               0.1 * i), 95, "4:2:0")
              for i in range(4)]
    batches = [frames[:2], frames[2:]]

    def decode(batch):
        with annotate("bench.plan"):
            dec = ParallelDecoder.from_bytes(batch, chunk_bits=1024)
        with annotate("bench.decode"):
            out = dec.decode(emit="rgb")
            out.rgb.block_until_ready()
        return dec, out

    before = [decode(b)[1] for b in batches]
    trace_dir = tempfile.mkdtemp(prefix="record-spans-")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    with annotate("bench.window"):
        after = []
        for b in batches:
            after.append(decode(b))
            time.sleep(0.01)
    jax.profiler.stop_trace()
    for a, (_, b) in zip(before, after):
        assert np.array_equal(np.asarray(a.coeffs), np.asarray(b.coeffs))
        assert np.array_equal(np.asarray(a.rgb), np.asarray(b.rgb))
    os.makedirs(out_dir, exist_ok=True)
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    with open(path, "rb") as src, gzip.open(
            os.path.join(out_dir, "spans.xplane.pb.gz"), "wb") as dst:
        shutil.copyfileobj(src, dst)
    shutil.rmtree(trace_dir)
    with open(os.path.join(out_dir, "spans.phases.json"), "w") as f:
        json.dump(after[0][0].program.device_phases(), f, indent=0,
                  sort_keys=True)
    print(json.dumps({"ok": True, "rounds": [o.sync_rounds for _, o in after],
                      "batch_ids": [d.batch_id for d, _ in after]}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
