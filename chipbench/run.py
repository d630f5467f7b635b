#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> \\
        --seconds <run_seconds> --trace <0|1>

Run from the root of a checkout on a machine with the chips the cell
asks for.  It exits non-zero, printing no result, without a TPU, with
too few chips, or without the program under ``src/``.
"""
import time

CLOCK0 = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# the package and the program, never this directory itself (its module
# names must not shadow the standard library's)
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(clock0=CLOCK0))
