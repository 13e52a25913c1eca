#!/usr/bin/env python3
"""The benchmark's one command (see harness/main.py):

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
"""
import time

T_START = time.monotonic()  # set-up is counted from here

import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
# the benchmark's own packages (harness, reference), then the program
sys.path[:0] = [p for p in (BENCH_DIR, os.path.dirname(BENCH_DIR))
                if p not in sys.path]

if __name__ == "__main__":
    from harness import main

    sys.exit(main.main(T_START))
