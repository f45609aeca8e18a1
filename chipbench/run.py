#!/usr/bin/env python3
"""Run one cell of the benchmark on this machine's chips.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are found
by name from ``BENCHMARK.json`` at the root of the checkout.  The last
line of standard output is the result as one JSON object; the numbers
the correctness check compared, each beside its limit, are the last
lines of standard error.  Without a TPU (or with fewer chips than the
cell asks for), or without the system under test beside it, it exits
non-zero and prints no result.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness.bench import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
