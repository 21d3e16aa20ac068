"""Readings for the limits of ``correct``: one cell on several seeds in one
process, each run's compared numbers for the program and for each control
(the reference with its pixels a step below the stated precision, in the
program's place) on the same answers, and for the reference at the chip's
own matmul precision (``bfloat16_idct``), a witness of what the program
should read. Not part of the benchmark's runs.

    python3 perfbench/readings.py --workload <name> --seconds <s> --seeds 1,2,3
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
sys.path.insert(0, HERE)


def main(argv):
    from harness import cell as C, check, spec
    parts = ("program",) + check.CONTROLS + ("bfloat16_idct",)
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    args = ap.parse_args(argv)
    cell = spec.resolve(spec.load_benchmark(), args.workload)
    rows = []
    with C.make_pool() as pool:
        for seed in [int(s) for s in args.seeds.split(",")]:
            t0 = time.perf_counter()
            result, checks, program, samples = C.run(
                cell, seed, args.seconds, False, t0, pool)
            row = {"seed": seed, "correct": result["correct"], "program": program}
            for c in parts[1:]:
                row[c] = check.compare(samples, pool, control=c,
                                       resize=check.answers_spec(cell.config))
            row["metrics"] = {k: v["value"] for k, v in result["metrics"].items()}
            rows.append(row)
            print("READING " + json.dumps(row), flush=True)
        pool.close()
        pool.join()
    for part in parts:
        for k in ("coeff_wrong", "rgb_off", "rgb_ne", "rgb_max", "missing"):
            vals = [r[part][k] for r in rows]
            print(f"{part} {k}: min {min(vals)} max {max(vals)} {vals}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
