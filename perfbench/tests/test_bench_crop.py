"""The training-ingest and four-chip cells, driven through a whole run on
the CPU at a small size with the look for a chip skipped: the program
passes and each control fails; the random-resized-crop boxes follow the
recipe; the new readers read hand-built traces as stated."""
import json
import os
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from harness import cell as C, check, spec, trace  # noqa: E402


def tiny_crop_cell() -> spec.Cell:
    """``ilsvrc2012_train.rrc224`` with every geometry an eighth a side
    (odd sizes kept), 12 images in batches of 6, 28x28 crops."""
    cell = spec.resolve(spec.load_benchmark(ROOT), "ilsvrc2012_train.rrc224")
    cfg = cell.config
    for kind in cfg["corpus"]["kinds"]:
        kind["geometries"] = [[max(8, w // 8), max(8, h // 8), p]
                              for w, h, p in kind["geometries"]]
    cfg["crop"]["out_size"] = [28, 28]
    cfg.update(name="ilsvrc2012_train_tiny", n_images=12)
    cell.traffic = dict(cell.traffic, batch=6)
    return cell


@pytest.fixture(scope="module")
def pool():
    with C.make_pool(2) as p:
        yield p


def run(cell, pool, control=None):
    result, checks, numbers, samples = C.run(
        cell, 3000000007, 1.0, False, time.perf_counter(), pool,
        require_tpu=False, control=control, out=open(os.devnull, "w"))
    return result, numbers, samples


def test_crop_cell_passes_and_each_control_fails(pool):
    cell = tiny_crop_cell()
    result, numbers, samples = run(cell, pool)
    assert result["correct"], numbers
    assert samples and all(s.crop[4:] == (28, 28) for s in samples)
    assert {s.rgb.shape for s in samples} == {(28, 28, 3)}
    for control in check.CONTROLS:
        result, numbers, _ = run(cell, pool, control=control)
        assert not result["correct"]
        assert numbers["rgb_off"] > cell.config["limits"]["rgb_off"]


def _rrc_box():
    from harness import spec as S
    return S._load(S.driver_path("ingest_crop"), "driver").rrc_box


@pytest.mark.parametrize("size", [(500, 375), (333, 500), (2048, 1536),
                                  (9, 9)])
def test_random_resized_crops_follow_the_recipe(size):
    rrc_box = _rrc_box()
    recipe = json.load(open(spec.config_path("ilsvrc2012_train")))["crop"]
    width, height = size
    rng = np.random.default_rng(5)
    for _ in range(200):
        top, left, h, w = rrc_box(rng, width, height, recipe)
        assert 0 <= top <= height - h and 0 <= left <= width - w
        assert h >= 1 and w >= 1
        # drawn boxes keep their area share (within rounding); a centre
        # crop, after ten failed draws, is the frame cut to the ratio
        share = h * w / (width * height)
        assert share <= 1.0 + 1e-9
        assert share >= 0.08 * 0.8 or (top, left) == ((height - h) // 2,
                                                       (width - w) // 2)


def test_a_frame_far_outside_the_ratios_gets_its_centre_crop():
    rrc_box = _rrc_box()
    recipe = json.load(open(spec.config_path("ilsvrc2012_train")))["crop"]
    # a 1000x10 frame: no draw fits, so the centre crop at ratio 4/3
    box = rrc_box(np.random.default_rng(0), 1000, 10, recipe)
    assert box == (0, (1000 - 13) // 2, 10, 13)


def test_four_chip_cell_passes_on_four_virtual_devices():
    code = textwrap.dedent(f"""
        import json, os, sys, time
        sys.path.insert(0, {BENCH!r}); sys.path.insert(0, {os.path.join(ROOT, 'src')!r})
        from harness import cell as C, spec
        cell = spec.resolve(spec.load_benchmark({ROOT!r}),
                            "newyork_1080p.ingest_mesh4")
        cell.config["corpus"].update(width=64, height=48)
        cell.config.update(name="newyork_1080p_tiny", n_images=4)
        with C.make_pool(2) as pool:
            result, checks, numbers, samples = C.run(
                cell, 3000000011, 1.0, False, time.perf_counter(), pool,
                require_tpu=False, out=open(os.devnull, "w"))
        print("RESULT", json.dumps([result["correct"], len(samples),
                                    result["device"]["count"]]))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [x for x in out.stdout.splitlines() if x.startswith("RESULT ")][-1]
    assert json.loads(line[7:]) == [True, 8, 4]


def _reduced(ops, modules, n_devices=1):
    devices = [trace.DeviceTrace(f"/device:TPU:{i}", list(ops), list(modules))
               for i in range(n_devices)]
    return trace.Reduced((0.0, 10.0), devices, [])


def test_collective_share_counts_collectives_by_their_own_name():
    read = spec.load_reader("collective_pct")
    ops = [("%fusion.1 = f32[8] fusion(%p)", 0.0, 4.0),
           ("%all-gather.2 = f32[32] all-gather(%fusion.1)", 4.0, 5.0),
           ("%fusion.3 = f32[8] fusion(%all-gather.2)", 5.0, 8.0),
           ("%collective-permute-start.4 = (f32[8]) collective-permute-start(%x)",
            8.0, 9.0)]
    ctx = {"trace": _reduced(ops, [], n_devices=4), "counters": {}}
    assert read(ctx) == pytest.approx(100.0 * 2.0 / 9.0)
    ctx = {"trace": _reduced(ops[:1], []), "counters": {}}
    assert read(ctx) is None


def test_crop_time_reads_the_crop_scope_of_each_pixel_execution():
    """A real crop decode gives the program's scope map; a hand-built trace
    of two jit__pixels executions, with one crop instruction inside each
    and one outside them, reads the mean inside."""
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.core import clear_decode_programs, decode_programs
    from repro.jpeg import codec_ref as cr
    from repro.core import decode_batch
    clear_decode_programs()
    rng = np.random.default_rng(0)
    blobs = [cr.encode_baseline(rng.integers(0, 255, (h, w, 3)).astype(np.uint8),
                                quality=80).jpeg_bytes
             for h, w in ((24, 40), (40, 24))]
    decode_batch(blobs, chunk_bits=256, emit="crop", out_size=(8, 8))
    (prog,) = decode_programs()
    name = sorted(prog.pixel_phases())[0]
    op = f"%{name} = f32[8] fusion(%p)"
    modules = [("jit__pixels(7)", 1.0, 2.0), ("jit__pixels(7)", 3.0, 4.0),
               ("jit__coeffs(3)", 5.0, 6.0)]
    ops = [(op, 1.2, 1.5), (op, 3.1, 3.2), (op, 5.0, 5.5)]
    ctx = {"trace": _reduced(ops, modules), "counters": {"batch_blobs": [1, 2]}}
    assert spec.load_reader("crop_ms")(ctx) == pytest.approx(1e3 * 0.2)
    ctx["counters"]["batch_blobs"] = [1, 2, 3]
    assert spec.load_reader("crop_ms")(ctx) is None
    assert spec.load_reader("pad_units_pct")(
        {"counters": {"pad_units_pct": 12.5}}) == 12.5
