"""Benchmark harness entry point — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.

  PYTHONPATH=src python -m benchmarks.run              # all
  PYTHONPATH=src python -m benchmarks.run breakdown    # one table
  BENCH_SCALE=0.05 PYTHONPATH=src python -m benchmarks.run datasets

With ``BENCH_JSON=path.json`` the same rows (plus the run configuration)
are also written as a JSON artifact — CI uploads one per run so perf is
diffable across commits.

With ``BENCH_TRAJECTORY`` set, one schema-versioned summary line per run
is *appended* to a JSONL trajectory file (the env value names the path;
empty/``1`` means ``benchmarks/trajectory.jsonl``). Each line carries the
git sha, backend, scale, and the headline health metrics (warm streaming
step, compiles per 100 batches, lane imbalance) so perf over the commit
history is a one-file plot, not an artifact archaeology dig.
"""
import json
import os
import subprocess
import sys

#: Bump when the trajectory line layout changes; readers filter on it.
#: Schema 2: one line per (backend, fuse) variant when the "backends"
#: suite ran (variant lines carry decode_us + launch accounting), plus
#: the global line (backend = the env default) with the health metrics.
#: Schema 3: the global line gains the "serve" suite's headline metrics
#: (serve_ips, serve_overlap, serve_p50_ms/p99_ms, deadline misses,
#: batch occupancy) when that suite ran.
TRAJECTORY_SCHEMA = 3


def _git_sha() -> str:
    sha = os.environ.get("GITHUB_SHA")
    if sha:
        return sha
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=10,
        ).stdout.strip() or "unknown"
    except Exception:
        return "unknown"


def _derived_fields(row) -> dict:
    out = {}
    for part in (row.get("derived") or "").split(";"):
        k, _, v = part.partition("=")
        if _ and k:
            out[k.strip()] = v.strip()
    return out


def trajectory_metrics(rows) -> dict:
    """Headline health metrics from whichever suites ran."""
    m = {}
    for r in rows:
        d = _derived_fields(r)
        if r["name"] == "stream/bucketed":
            m["warm_step_ms"] = round(r["us_per_call"] / 1e3, 3)
            if "compiles_per_100" in d:
                m["compiles_per_100"] = float(d["compiles_per_100"])
        elif r["name"].startswith("skew/") and "imbalance" in d:
            m[f"imbalance_{r['name'].split('/', 1)[1]}"] = \
                float(d["imbalance"])
        elif r["name"] == "serve/drain":
            m["serve_ips"] = float(d["ips"])
            m["serve_overlap"] = float(d["overlap"])
            m["serve_occupancy"] = float(d["occupancy"])
        elif r["name"] == "serve/poisson":
            m["serve_p50_ms"] = float(d["p50_ms"])
            m["serve_p99_ms"] = float(d["p99_ms"])
            m["serve_deadline_misses"] = int(d["deadline_misses"])
    return m


def backend_variant_entries(rows):
    """One trajectory entry per (backend, fuse) variant row of the
    "backends" suite — historically only the env-default backend's
    metrics were recorded; now every backend (and every Pallas fuse
    mode) gets its own line."""
    entries = []
    for r in rows:
        if not r["name"].startswith("backends/"):
            continue
        d = _derived_fields(r)
        backend = d.get("backend")
        if backend is None:
            continue
        sync = r["name"].split("/")[2] if r["name"].count("/") >= 2 else ""
        metrics = {"decode_us": round(r["us_per_call"], 1)}
        for k in ("pallas_calls", "jaxpr_eqns", "hbm_bytes",
                  "store_fused", "pixels_fused"):
            if k in d:
                metrics[k] = int(float(d[k]))
        entries.append({
            "backend": backend,
            "fuse": d.get("fuse"),
            "sync": sync,
            "metrics": metrics,
        })
    return entries


def append_trajectory(path: str, rows, suites) -> None:
    from .common import BENCH_BACKEND, BENCH_SCALE
    base = {
        "schema": TRAJECTORY_SCHEMA,
        "git_sha": _git_sha(),
        "scale": BENCH_SCALE,
        "suites": list(suites),
    }
    entries = [dict(base, backend=BENCH_BACKEND, n_rows=len(rows),
                    metrics=trajectory_metrics(rows))]
    for v in backend_variant_entries(rows):
        entries.append(dict(base, **v))
    with open(path, "a") as f:
        for entry in entries:
            f.write(json.dumps(entry, sort_keys=True) + "\n")
    print(f"# appended {len(entries)} trajectory line"
          f"{'s' if len(entries) != 1 else ''} to {path}", file=sys.stderr)


def main() -> None:
    from . import backends, breakdown, datasets, quality, serve, skew, \
        stream, subseq_size
    from .common import BENCH_BACKEND, BENCH_SCALE, emit
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    suites = {
        "datasets": datasets,     # Fig. 4/5 + Fig. 8
        "quality": quality,       # Fig. 6/7 + Fig. 9
        "breakdown": breakdown,   # Fig. 3
        "subseq_size": subseq_size,  # Table II/III subsequence column
        "backends": backends,     # beyond-paper: jnp vs Pallas kernels
        "skew": skew,             # beyond-paper: lane balancing (skewed corpus)
        "stream": stream,         # beyond-paper: compile-once steady stream
        "serve": serve,           # beyond-paper: async decode service (SLO)
    }
    wanted = sys.argv[1:] or list(suites)
    all_rows = []
    print("name,us_per_call,derived")
    for name in wanted:
        rows = suites[name].run_rows()
        emit(rows)
        all_rows.extend(rows)

    json_path = os.environ.get("BENCH_JSON")
    if json_path:
        payload = {
            "scale": BENCH_SCALE,
            # the env default; the "backends" suite sweeps both backends
            # per row regardless (see its name/derived fields)
            "default_backend": BENCH_BACKEND,
            "suites": wanted,
            "rows": all_rows,
        }
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"# wrote {json_path} ({len(all_rows)} rows)", file=sys.stderr)

    traj = os.environ.get("BENCH_TRAJECTORY")
    if traj is not None:
        if traj in ("", "1", "true"):
            traj = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "trajectory.jsonl")
        append_trajectory(traj, all_rows, wanted)


if __name__ == "__main__":
    main()
