"""The comparison that decides ``correct``, driven through a whole run on
the CPU at a small size with the look for a chip skipped: the program
passes; each control (the reference with its pixels a step below the
stated precision, in the program's place) and each fault the cells can have, planted in the timed
path, fail."""
import json
import os
import sys
import time

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from harness import cell as C, check, spec  # noqa: E402


def tiny_cell(config: str, traffic: str) -> spec.Cell:
    """``config``'s cell shrunk to 64x48 images, 8 of them, in batches of 2."""
    cfg = json.load(open(spec.config_path(config)))
    cfg["corpus"].update(width=64, height=48)
    cfg.update(name=f"{config}_tiny", n_images=8)
    tr = dict(json.load(open(spec.traffic_path(traffic))), batch=2)
    return spec.Cell(name="tiny", chips=1, config=cfg, traffic=tr,
                     end_to_end=[{"name": "images_per_s", "unit": "images/s"},
                                 {"name": "setup_s", "unit": "s"}],
                     per_layer=[], run_seconds=1)


@pytest.fixture(scope="module")
def pool():
    with C.make_pool(2) as p:
        yield p


def run(cell, pool, control=None):
    result, checks, numbers, _ = C.run(cell, 7, 1.5, False, time.perf_counter(), pool,
                                    require_tpu=False, control=control,
                                    out=open(os.devnull, "w"))
    return result["correct"], numbers


CELLS = [("stata_480p", "ingest")]


@pytest.mark.parametrize("config,traffic", CELLS)
def test_program_passes_and_control_fails(config, traffic, pool):
    cell = tiny_cell(config, traffic)
    ok, numbers = run(cell, pool)
    assert ok, numbers
    for control in check.CONTROLS:
        ok, numbers = run(cell, pool, control=control)
        assert not ok and numbers["rgb_off"] > cell.config["limits"]["rgb_off"]


def _patch_decode(monkeypatch, fault):
    """Plant ``fault(out, calls)`` in ``ParallelDecoder.decode``'s output."""
    from repro.core import api
    real = api.ParallelDecoder.decode
    calls = []

    def decode(self, emit="rgb"):
        out = real(self, emit=emit)
        calls.append(out)
        return fault(out, calls)
    monkeypatch.setattr(api.ParallelDecoder, "decode", decode)


def _jnp():
    import jax.numpy as jnp
    return jnp


def _one_coefficient_altered(out, calls):
    import dataclasses
    return dataclasses.replace(out, coeffs=out.coeffs.at[3, 7].add(1))


def _half_the_batch_left_out(out, calls):
    import dataclasses
    jnp = _jnp()
    n = out.rgb.shape[0]
    keep = (jnp.arange(n) >= n // 2)[:, None, None, None]
    units = out.coeffs.shape[0] // n
    ckeep = (jnp.arange(out.coeffs.shape[0]) >= units * (n // 2))[:, None]
    return dataclasses.replace(out, rgb=jnp.where(keep, out.rgb, 0),
                               coeffs=jnp.where(ckeep, out.coeffs, 0))


def _state_unchanged(out, calls):
    """Every batch answers with the first batch's output."""
    return calls[0]


def _answer_altered(out, calls):
    """One image of every batch comes back brightened."""
    import dataclasses
    return dataclasses.replace(out, rgb=out.rgb.at[0].add(np.uint8(9)))


def _answers_swapped(out, calls):
    import dataclasses
    return dataclasses.replace(out, rgb=out.rgb[::-1])


FAULTS = [
    ("ingest", _one_coefficient_altered), ("ingest", _half_the_batch_left_out),
    ("ingest", _state_unchanged), ("ingest", _answer_altered),
    ("ingest", _answers_swapped),
]


@pytest.mark.parametrize("traffic,fault", FAULTS,
                         ids=[f"{t}-{f.__name__.strip('_')}" for t, f in FAULTS])
def test_fault_in_the_timed_path_fails(traffic, fault, pool, monkeypatch):
    cell = tiny_cell("stata_480p", traffic)
    _patch_decode(monkeypatch, fault)
    ok, numbers = run(cell, pool)
    assert not ok, numbers
