"""Run one cell of `BENCHMARK.json` once on the chips of this machine.

    python3 bench/run_cell.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Prints the result as one JSON object on the last line of standard output.
Exits with a code other than 0, and prints no result, where JAX finds no
TPU or fewer chips than the cell asks for. See `benchlib/cell.py`.
"""

import time

T_PROCESS = time.perf_counter()

import sys  # noqa: E402

from benchlib import cell  # noqa: E402

if __name__ == "__main__":
    sys.exit(cell.main(t_process=T_PROCESS))
