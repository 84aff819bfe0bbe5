#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark once.

    python chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout on a machine that holds the chips the
cell asks for (``BENCHMARK.json``).  Prints one JSON object as the last
line of standard output, and each number the correctness check compared
beside its limit as the last lines of standard error.  Exits non-zero,
with no result, where JAX finds no TPU or fewer chips than the cell
asks for.
"""
import time

T_START = time.perf_counter()

import sys  # noqa: E402

import harness  # noqa: E402

if __name__ == "__main__":
    harness.setup()
    sys.exit(harness.main(sys.argv[1:], T_START))
