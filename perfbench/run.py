"""Run one benchmark cell once, on the chips of this machine.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. Exits 1 with no result when JAX finds no TPU
or fewer chips than the cell asks for; see ``harness/cell.py``.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
sys.path.insert(0, HERE)

if __name__ == "__main__":
    from harness import cell
    sys.exit(cell.main(sys.argv[1:], T_START))
