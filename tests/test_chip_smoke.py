"""``chip_smoke.py`` off the chip: it refuses a CPU, and its phases run
end to end at a tiny size (the full-size run needs a TPU)."""
import importlib.util
import os
import subprocess
import sys

from _multiproc import run_sub

ROOT = os.path.join(os.path.dirname(__file__), "..")
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_cpu_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, SMOKE], capture_output=True,
                         text=True, env=env, timeout=300)
    assert out.returncode != 0
    assert "no TPU found" in out.stderr
    assert '"ok"' not in out.stdout


def test_one_chip_phases_at_tiny_size():
    cs = _load_smoke()
    spec, blobs = cs.make_corpus(8, 64, 48)
    assert (spec.quality, spec.subsequence_bits) == (95, 1024)
    cs.run_one_chip(blobs, spec.subsequence_bits)


def test_mesh_phase_on_four_virtual_devices():
    out = run_sub(f"""
        import importlib.util
        spec = importlib.util.spec_from_file_location("cs", {SMOKE!r})
        cs = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(cs)
        _, blobs = cs.make_corpus(8, 64, 48)
        cs.run_mesh(blobs, 1024, 4)
    """, devices=4)
    assert "bit-identical to the one-chip decode: True" in out
