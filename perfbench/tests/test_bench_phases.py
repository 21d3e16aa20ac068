"""The readers of the program's own spans and phases: device time per
phase of the entropy stage, device idle time by host span. Checked on a
hand-built reduction, on the recorded trace that holds no program spans,
and on the lookup of the run's trace file by its window."""
import gzip
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from harness import phases, spec, trace  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
ENTROPY = ["sync_round_ms", "sync_step_us", "write_pass_ms"]
IDLE = ["idle_plan_pct", "idle_handoff_pct"]


def hand_built():
    """Two batches in a 10 s window. Entropy program runs at [1, 3] and
    [5, 7]: its entry-level ``while.1`` (sync) and ``fusion.9`` (write),
    a nested fusion and an unmapped copy; the pixel program at [3.5, 3.6]
    holds an op of the same name as a mapped one."""
    ops = [("%while.1 = (s32[]) while(%t), body=%b", 1.0, 2.5),
           ("%fusion.5 = s32[8] fusion(%p), kind=kLoop", 1.1, 1.2),
           ("%fusion.9 = s32[8] fusion(%w), kind=kLoop", 2.5, 2.9),
           ("%copy.1 = s32[8] copy(%fusion.9)", 2.9, 3.0),
           ("%fusion.9 = u8[8] fusion(%c), kind=kLoop", 3.5, 3.6),
           ("%while.1 = (s32[]) while(%t), body=%b", 5.0, 6.0),
           ("%fusion.9 = s32[8] fusion(%w), kind=kLoop", 6.0, 6.5)]
    modules = [("jit__coeffs(42)", 1.0, 3.0), ("jit__pixels(43)", 3.5, 3.6),
               ("jit__coeffs(42)", 5.0, 7.0)]
    red = trace.Reduced((0.0, 10.0),
                        [trace.DeviceTrace("/device:TPU:0", ops, modules)],
                        [("bench.window", 0.0, 10.0)])
    spans = [("repro.from_bytes", 0.2, 0.9, {"batch": 7}),
             ("repro.decode", 0.95, 3.4, {"batch": 7}),
             ("repro.dispatch.entropy", 0.95, 0.96, {"batch": 7, "s_max": 100}),
             ("repro.rounds", 0.97, 3.05, {"batch": 7, "rounds": 3}),
             ("repro.from_bytes", 3.4, 4.8, {"batch": 8}),
             ("repro.decode", 4.85, 6.6, {"batch": 8}),
             ("repro.dispatch.entropy", 4.85, 4.86, {"batch": 8, "s_max": 200}),
             ("repro.rounds", 4.87, 6.55, {"batch": 8, "rounds": 2})]
    program = phases.ProgramTrace(spans, {"while.1": phases.SYNC,
                                          "fusion.9": phases.WRITE})
    return {"trace": red, "counters": {}, "program": program}


# sync: 1.5 + 1.0 s over 3 + 2 rounds, over 3 x 100 + 2 x 200 steps;
# write: 0.4 + 0.5 s over 2 batches. Idle: [0, 1], [3, 3.5], [3.6, 5],
# [6.5, 10]; under from_bytes 0.7 + 0.1 + 1.2 s, under decode
# 0.05 + 0.4 + 0.15 + 0.1 s, of 10 s
WANT = {"sync_round_ms": 500.0, "sync_step_us": 2.5e6 / 700,
        "write_pass_ms": 450.0, "idle_plan_pct": 20.0,
        "idle_handoff_pct": 7.0}


@pytest.mark.parametrize("metric", ENTROPY + IDLE)
def test_reader_on_a_hand_built_reduction(metric):
    assert spec.load_reader(metric)(hand_built()) == pytest.approx(WANT[metric])


def test_an_execution_without_a_phase_reads_nothing():
    ctx = hand_built()
    ops = ctx["trace"].devices[0].ops
    ops.remove(ops[-1])                  # the second batch's write pass
    for metric in ENTROPY:
        assert spec.load_reader(metric)(ctx) is None
    assert spec.load_reader("idle_plan_pct")(ctx) == pytest.approx(20.0)


def test_batches_and_executions_of_other_counts_read_nothing():
    ctx = hand_built()
    program = ctx["program"]
    program.spans = [s for s in program.spans
                     if not (s[0] == "repro.rounds" and s[3]["batch"] == 8)]
    for metric in ENTROPY:
        assert spec.load_reader(metric)(ctx) is None


def unpack(gz, dest):
    os.makedirs(os.path.dirname(dest), exist_ok=True)
    with gzip.open(gz, "rb") as src, open(dest, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return dest


def test_readers_find_no_program_spans_in_the_recorded_trace(tmp_path, monkeypatch):
    """The run's trace file is found by its window; the v5e trace recorded
    before the program had spans gives every new reader None."""
    monkeypatch.setattr(phases.tempfile, "tempdir", str(tmp_path))
    path = unpack(os.path.join(DATA, "tiny.xplane.pb.gz"),
                  str(tmp_path / "bench-trace-1" / "p" / "h.xplane.pb"))
    red = trace.reduce_xplane(path)
    assert phases.read_spans(path) == (red.window, [])
    ctx = {"trace": red, "counters": {}}
    for metric in ENTROPY + IDLE:
        assert spec.load_reader(metric)(ctx) is None


def test_the_trace_file_is_the_one_with_the_window(tmp_path, monkeypatch):
    import jax
    from harness.window import annotate
    monkeypatch.setattr(phases.tempfile, "tempdir", str(tmp_path))
    windows = []
    for k in range(2):
        jax.profiler.start_trace(str(tmp_path / f"bench-trace-{k}"))
        with annotate("bench.window"):
            with jax.profiler.TraceAnnotation("repro.decode", batch=k):
                pass
        jax.profiler.stop_trace()
    for path in sorted(tmp_path.glob("bench-trace-*/**/*.xplane.pb")):
        window, spans = phases.read_spans(str(path))
        windows.append(window)
        assert [(s[0], s[3]) for s in spans] == [
            ("repro.decode", {"batch": len(windows) - 1})]
    assert phases.find_spans(windows[1])[0][3] == {"batch": 1}
    assert phases.find_spans((0.0, 1.0)) == []


def test_readers_on_the_recorded_spans_trace(tmp_path):
    """A trace recorded on one TPU v5e by ``record_spans.py``: two batches
    of two 64x48 frames (8 and 9 rounds of 514 steps) with the program's
    spans, and its entropy program's phase map."""
    import json
    path = unpack(os.path.join(DATA, "spans.xplane.pb.gz"),
                  str(tmp_path / "spans.xplane.pb"))
    red = trace.reduce_xplane(path)
    window, spans = phases.read_spans(path)
    assert window == red.window and len(spans) == 20
    with open(os.path.join(DATA, "spans.phases.json")) as f:
        phase_map = json.load(f)
    assert set(phase_map.values()) == {phases.SYNC, phases.WRITE}
    ctx = {"trace": red, "program": phases.ProgramTrace(spans, phase_map)}
    sync_s, write_s = 0.023611663, 0.003342847
    assert phases.phase_seconds(red, phase_map) == (
        {phases.SYNC: pytest.approx(sync_s), phases.WRITE: pytest.approx(write_s)}, 2)
    # the two phases hold all but 0.1% of the entropy program's device time
    assert (sync_s + write_s) / red.module_s(r"jit__coeffs\b") > 0.99
    want = {"sync_round_ms": 1e3 * sync_s / 17,
            "sync_step_us": 1e6 * sync_s / (17 * 514),
            "write_pass_ms": 1e3 * write_s / 2,
            "idle_plan_pct": 26.727798, "idle_handoff_pct": 7.928943}
    for metric, value in want.items():
        assert spec.load_reader(metric)(ctx) == pytest.approx(value, rel=1e-6)
    idle = spec.load_reader("idle_pct.ingest")(ctx)
    assert want["idle_plan_pct"] + want["idle_handoff_pct"] <= idle
