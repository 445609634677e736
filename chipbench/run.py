#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cells, their configurations, traffic mixes and metrics are named in
``BENCHMARK.json`` at the root of the checkout; ``harness.py`` says what
a run does.  Exit codes: 0 with a result line, 2 where the run cannot
be made here (no TPU, too few chips, a device kind that is not in
``chipbench/peaks.json``, an unknown cell), anything else on a failure.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], T_START))
