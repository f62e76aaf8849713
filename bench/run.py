"""Chip benchmark of the DFL trainer and its MOSGU gossip, one cell per run.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine that holds the TPU chips the cell
asks for; it exits non-zero, printing no result, anywhere else. The cell is
resolved through BENCHMARK.json to its files under bench/ (see harness.py).
The last line of standard output is the result as one JSON object; the numbers
the check compared, each beside its limit, are the last lines on standard
error and the last key of the result.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], T_START))
