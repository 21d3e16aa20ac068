"""The decoder's spans and phases on the profiler's clock.

Host spans (``repro.*`` ``jax.profiler.TraceAnnotation``s) nest on the
calling thread and carry each batch's id and counters; the entropy
program's two named scopes reach its compiled HLO, where
``DecodeProgram.device_phases()`` finds them. Neither moves a result.
"""
import glob
import json

import numpy as np
import pytest

import jax

from repro.core import ParallelDecoder
from repro.core import api
from repro.jpeg import codec_ref as cr

from _multiproc import run_sub
from conftest import synth_image

FROM_BYTES = ["repro.parse", "repro.plan", "repro.pad", "repro.upload"]
DECODE = ["repro.dispatch.entropy", "repro.slice", "repro.rounds",
          "repro.dispatch.pixels"]
COUNTERS = {"s_max", "lanes", "lanes_live", "units", "units_cap", "step"}


def batch(seed):
    return [cr.encode_baseline(synth_image(48, 64, seed=seed + i),
                               quality=q).jpeg_bytes
            for i, q in enumerate((70, 90))]


def decode(blobs):
    dec = ParallelDecoder.from_bytes(blobs, chunk_bits=256)
    out = dec.decode(emit="rgb")
    out.rgb.block_until_ready()
    return dec, out


def program_spans(trace_dir):
    from jax.profiler import ProfileData
    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    spans.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns,
                                  dict(ev.stats), line.name))
    return spans


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """Two batches decoded before the profiler starts, then the same two
    under it: (outputs without, outputs with, decoders, spans)."""
    batches = [batch(0), batch(10)]
    plain = [decode(b)[1] for b in batches]
    trace_dir = str(tmp_path_factory.mktemp("profile"))
    jax.profiler.start_trace(trace_dir)
    try:
        traced = [decode(b) for b in batches]
    finally:
        jax.profiler.stop_trace()
    return plain, [o for _, o in traced], [d for d, _ in traced], \
        program_spans(trace_dir)


def test_profiling_moves_no_result(profiled):
    plain, traced, _, _ = profiled
    for a, b in zip(plain, traced):
        assert np.array_equal(np.asarray(a.coeffs), np.asarray(b.coeffs))
        assert np.array_equal(np.asarray(a.rgb), np.asarray(b.rgb))
        assert a.sync_rounds == b.sync_rounds


def test_every_span_nests_under_its_batch(profiled):
    _, traced, decs, spans = profiled
    ids = [d.batch_id for d in decs]
    assert len(set(ids)) == 2
    assert {s[3]["batch"] for s in spans} == set(ids)
    for dec, out in zip(decs, traced):
        mine = {s[0]: s for s in spans if s[3]["batch"] == dec.batch_id}
        assert sorted(mine) == sorted(["repro.from_bytes", "repro.decode"]
                                      + FROM_BYTES + DECODE)
        assert len({s[4] for s in mine.values()}) == 1    # one thread
        for parent, children in (("repro.from_bytes", FROM_BYTES),
                                 ("repro.decode", DECODE)):
            _, p0, p1, _, _ = mine[parent]
            ends = [p0]
            for child in children:
                _, c0, c1, _, _ = mine[child]
                assert ends[-1] <= c0 <= c1 <= p1    # inside, in order
                ends.append(c1)
        assert mine["repro.from_bytes"][2] <= mine["repro.decode"][1]
        args = mine["repro.dispatch.entropy"][3]
        assert COUNTERS <= set(args)
        assert args["s_max"] == dec.shape.s_max
        assert args["lanes"] == dec.shape.n_chunks
        assert args["lanes_live"] == dec.plan.n_chunks
        assert args["units"] == dec.plan.total_units
        assert args["units_cap"] == dec.shape.n_units
        assert args["step"] == dec.program.step
        assert mine["repro.rounds"][3]["rounds"] == out.sync_rounds


def test_the_entropy_span_names_the_step_form(tmp_path):
    """A 1,024-bit plan runs the staged step and a sequential plan of
    segment-long chunks the gather form; the span and the program stats
    say which ran."""
    blobs = [cr.encode_baseline(synth_image(48, 64, seed=40),
                                quality=90).jpeg_bytes]
    decs = [ParallelDecoder.from_bytes(blobs, chunk_bits=1024),
            ParallelDecoder.from_bytes(blobs, sync="sequential")]
    jax.profiler.start_trace(str(tmp_path))
    try:
        for dec in decs:
            dec.coefficients().coeffs.block_until_ready()
    finally:
        jax.profiler.stop_trace()
    steps = {s[3]["batch"]: s[3]["step"] for s in program_spans(tmp_path)
             if s[0] == "repro.dispatch.entropy"}
    assert [steps[d.batch_id] for d in decs] == ["staged", "gather"]
    buckets = {(b["bucket"], b["sync"]): b["step"]
               for b in api.decode_program_stats()["buckets"]}
    assert [buckets[(d.shape.label(), d.program.sync)] for d in decs] == [
        "staged", "gather"]


def test_a_decoder_built_from_a_plan_draws_its_own_batch_id():
    from repro.core.bitstream import build_batch_plan
    plan = build_batch_plan(batch(20), chunk_bits=256)
    a, b = ParallelDecoder(plan), ParallelDecoder(plan)
    assert a.batch_id != b.batch_id


def test_the_entropy_program_maps_every_entry_while_to_a_phase(profiled):
    _, _, decs, _ = profiled
    prog = decs[0].program
    traces = prog.coeffs_traces
    phases = prog.device_phases()
    assert prog.coeffs_traces == traces          # the cached trace
    assert set(phases.values()) == {api.SYNC_PHASE, api.WRITE_PHASE}
    text = prog.coeffs_fn.lower(*prog.coeffs_args).compile().as_text()
    entry = text[text.index("\nENTRY "):].split("\n}")[0].splitlines()[2:]
    whiles = [line.strip().removeprefix("ROOT ").split(" = ")[0].lstrip("%")
              for line in entry if " while(" in line]
    assert whiles and all(w in phases for w in whiles)


def test_a_mesh_program_maps_its_phases_in_its_own_context():
    """A program first run under ``decode_on`` lowers again in that
    (mesh, rules) context, without a trace counted twice."""
    out = run_sub("""
        import json
        import jax
        from repro.core import ParallelDecoder, api
        from repro.jpeg import codec_ref as cr
        from conftest import synth_image
        blobs = [cr.encode_baseline(synth_image(48, 64, seed=s)).jpeg_bytes
                 for s in range(2)]
        dec = ParallelDecoder.from_bytes(blobs, chunk_bits=256)
        dec.decode_on(jax.make_mesh((2,), ("data",)), emit="coeffs")
        prog = dec.program
        traces = prog.coeffs_traces
        phases = prog.device_phases()
        print("RESULT " + json.dumps({
            "mesh": prog.coeffs_args[2] is not None,
            "traces": [traces, prog.coeffs_traces],
            "phases": sorted(set(phases.values()))}))
    """, devices=2)
    res = json.loads(out.split("RESULT ")[-1])
    assert res["mesh"] and res["traces"] == [1, 1]
    assert res["phases"] == [api.SYNC_PHASE, api.WRITE_PHASE]


def test_a_program_never_run_has_no_phases():
    from repro.core.bitstream import build_batch_plan, plan_shape
    shape = plan_shape(build_batch_plan(batch(30), chunk_bits=256))
    prog = api.DecodeProgram(shape=shape, sync="jacobi", backend="jnp",
                             interpret=None)
    assert prog.device_phases() == {}


def test_hlo_phases_reads_entry_level_op_names():
    text = "\n".join([
        "HloModule jit__coeffs",
        "%body (p: s32[]) -> s32[] {",
        '  %inner = s32[] add(%p, %p), metadata={op_name="jit(_coeffs)/'
        'repro.entropy.sync/while/body/add"}',
        "}",
        "",
        "ENTRY %main (x: s32[]) -> s32[] {",
        '  %while.3 = s32[] while(%x), body=%body, metadata={op_name='
        '"jit(_coeffs)/repro.entropy.sync/while"}',
        '  %fusion.9 = s32[] fusion(%while.3), metadata={op_name='
        '"jit(_coeffs)/repro.entropy.write/jit(sort)/sort"}',
        "  %copy = s32[] copy(%fusion.9)",
        '  ROOT %sort.1 = s32[] sort(%copy), metadata={op_name='
        '"jit(_coeffs)/repro.entropy.writer/x"}',
        "}",
    ])
    assert api.hlo_phases(text) == {"while.3": api.SYNC_PHASE,
                                    "fusion.9": api.WRITE_PHASE}


def _mixed(seed):
    return [batch(seed)[0],
            cr.encode_baseline(synth_image(40, 24, seed=seed + 5), quality=80,
                               subsampling="4:4:4").jpeg_bytes]


@pytest.mark.parametrize("emit,mixed", [("rgb", False), ("crop", False),
                                        ("crop", True)],
                         ids=["uniform-rgb", "uniform-crop", "mixed-crop"])
def test_the_pixel_span_names_its_layout_crops_and_units(tmp_path, emit,
                                                         mixed):
    dec = ParallelDecoder.from_bytes(_mixed(50) if mixed else batch(50),
                                     chunk_bits=256)
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = dec.decode(emit=emit, out_size=(8, 8))
        jax.block_until_ready(out.crop if emit == "crop" else out.rgb)
    finally:
        jax.profiler.stop_trace()
    (args,) = [s[3] for s in program_spans(tmp_path)
               if s[0] == "repro.dispatch.pixels"]
    assert args["layout"] == ("mixed" if mixed else "uniform")
    assert args["crops"] == (2 if emit == "crop" else 0)
    assert args["units"] == dec.plan.total_units
    assert args["units_cap"] == dec.shape.n_units


def test_the_crop_program_maps_its_instructions_to_the_crop_scope():
    """``pixel_phases()`` reads the crop scope from the cached trace: no
    second trace, nothing before the first crop decode."""
    api.clear_decode_programs()
    dec = ParallelDecoder.from_bytes(_mixed(60), chunk_bits=256)
    prog = dec.program
    assert prog.pixel_phases() == {}
    dec.decode(emit="crop", out_size=(8, 8)).crop.block_until_ready()
    traces = prog.pixels_traces
    phases = prog.pixel_phases()
    assert prog.pixels_traces == traces
    assert phases and set(phases.values()) == {api.CROP_PHASE}
