"""Benchmark entry point: one cell, one seed, one measured window.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs from the root of a checkout.  Prints one JSON object as the last
line of standard output (see ``bench/harness.py``) and exits 0; exits
non-zero with no result where JAX finds no TPU, fewer chips than the cell
asks for, or no program beside the benchmark.
"""
import time

T0 = time.perf_counter()  # process start, for setup_s

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], t0=T0))
