"""BENCHMARK.json against the benchmark's rules, and the command's refusal
to measure without a chip. CPU only."""
import json
import os
import re
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from harness import spec  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark(ROOT)


def test_every_workload_resolves_to_its_files(bench):
    for w in bench["workloads"]:
        cell = spec.resolve(bench, w["name"])
        assert os.path.exists(spec.driver_path(cell.traffic["driver"]))
        assert callable(spec.load_driver(cell.traffic["driver"]))
        assert cell.config["name"] == w["config"]
        assert {"missing", "coeff_wrong", "rgb_off"} <= set(cell.config["limits"])
        for m in cell.per_layer:
            assert callable(spec.load_reader(m["name"]))
    for c in bench["configs"]:
        assert c["file"].startswith(bench["paths"][0] + "/")
        assert os.path.exists(os.path.join(ROOT, c["file"]))


def test_names_and_units_obey_the_character_rules(bench):
    names = [c["name"] for c in bench["configs"]]
    names += [w["name"] for w in bench["workloads"]]
    names += [w[k] for w in bench["workloads"] for k in ("config", "traffic")]
    names += [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    names += [k for c in bench["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), [n for n in names if not NAME.match(n)]
    units = [m["unit"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert all(UNIT.match(u) for u in units)
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        assert len({x["name"] for x in bench[group]}) == len(bench[group])


def test_each_per_layer_metric_cell_reports_the_metric_it_moves(bench):
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        moved = next(e for e in bench["end_to_end"] if e["name"] == m["moves"])
        for c in m.get("workloads", cells):
            assert c in cells
            assert spec.reports(moved, c, set()), (m["name"], c)


def test_every_cell_reports_setup_another_end_to_end_and_a_layer(bench):
    for w in bench["workloads"]:
        cell = spec.resolve(bench, w["name"])
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer


def test_at_most_half_the_cells_ask_for_four_chips(bench):
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert all(w["chips"] in (1, 4) for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)


def test_bounds_and_run_length_are_in_range(bench):
    assert 1 <= bench["run_seconds"] <= 51
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    assert next(m for m in bench["end_to_end"] if m["name"] == "setup_s")


def _run(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, "perfbench/run.py"] + args, cwd=cwd,
                          env=env, capture_output=True, text=True, timeout=300)


def test_command_exits_nonzero_and_prints_no_result_without_a_tpu(bench):
    w = bench["workloads"][0]["name"]
    r = _run(["--workload", w, "--seed", "3", "--seconds", "1", "--trace", "0"],
             ROOT)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert '"metrics"' not in r.stdout


def test_command_refuses_a_checkout_without_the_program(bench, tmp_path):
    import shutil
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    w = bench["workloads"][0]["name"]
    r = _run(["--workload", w, "--seed", "3", "--seconds", "1", "--trace", "0"],
             tmp_path, {"PYTHONPATH": ""})
    assert r.returncode != 0
    assert '"metrics"' not in r.stdout
