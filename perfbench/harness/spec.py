"""What ``BENCHMARK.json`` says about one cell, resolved to its files.

A cell names a configuration (``configs/<config>.json``) and a traffic
mix (``traffic/<traffic>.json``), whose ``driver`` key names the loop that
runs it (``drivers/<driver>.py``); each per-layer metric is read by
``metrics/<name>.py``. Nothing here knows any cell, configuration, mix,
driver or metric by name, so a new one is a new entry and new files.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list       # BENCHMARK.json entries this cell reports
    per_layer: list
    run_seconds: int


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def config_path(name: str) -> str:
    return os.path.join(BENCH_DIR, "configs", f"{name}.json")


def traffic_path(name: str) -> str:
    return os.path.join(BENCH_DIR, "traffic", f"{name}.json")


def driver_path(name: str) -> str:
    return os.path.join(BENCH_DIR, "drivers", f"{name}.py")


def metric_path(name: str) -> str:
    return os.path.join(BENCH_DIR, "metrics", f"{name}.py")


def reports(metric: dict, cell: str, cell_end_to_end: set) -> bool:
    """Whether ``cell`` reports ``metric``: the cells it lists, or, without
    a list, every cell that reports the end-to-end metric it moves (an
    end-to-end metric without a list is reported everywhere)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in cell_end_to_end


def resolve(bench: dict, workload: str) -> Cell:
    """The cell named ``workload``; KeyError if BENCHMARK.json has none."""
    entry = {w["name"]: w for w in bench["workloads"]}[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _read_json(os.path.join(ROOT, configs[entry["config"]]["file"]))
    traffic = _read_json(traffic_path(entry["traffic"]))
    e2e = [m for m in bench["end_to_end"] if reports(m, workload, set())]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if reports(m, workload, names)]
    return Cell(name=workload, chips=int(entry["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer,
                run_seconds=int(bench["run_seconds"]))


def _load(path: str, prefix: str):
    name = os.path.basename(path)[:-3].replace(".", "_")
    spec = importlib.util.spec_from_file_location(f"perfbench_{prefix}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_driver(name: str):
    """The ``run(...)`` function of ``drivers/<name>.py`` (``window.py``)."""
    return _load(driver_path(name), "driver").run


def load_reader(name: str):
    """The ``read(ctx)`` function of ``metrics/<name>.py``."""
    return _load(metric_path(name), "metric").read
