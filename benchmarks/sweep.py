#!/usr/bin/env python3
"""sweep.py: one traffic parameter over several values, in one process.

    python3 benchmarks/sweep.py --workload valset-1k.votes \
        --param rate_per_s --values 50,100,150,200,300,400 --seconds 10

Run by hand, once, to find the highest rate a cell's system sustains
(the knee: the highest rate at which the backlog does not grow over the
window); the cell's rate is then fixed in its traffic file at about
four fifths of it. Never part of a measured run. Each value goes through
the cell's own driver and readers, with only that parameter replaced,
and prints one line: the end-to-end metrics and the driver's counters.
"""
import argparse
import json
import os
import shutil
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [p for p in (BENCH_DIR, os.path.dirname(BENCH_DIR))
                if p not in sys.path]


def main() -> int:
    from harness import catalog, main as hmain

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--param", required=True)
    ap.add_argument("--values", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args()
    args.trace, args.rehearse = 0, False
    cell = catalog.Cell(args.workload)
    info = hmain.boot(False, cell.chips)
    for text in args.values.split(","):
        ctx = hmain.Ctx(cell, args, time.monotonic())
        ctx.traffic[args.param] = type(ctx.traffic[args.param])(text)
        try:
            head, last = hmain.measure(cell, ctx, info,
                                       cell.driver.prepare(ctx))
        finally:
            shutil.rmtree(ctx.tmpdir, ignore_errors=True)
        print(json.dumps({
            args.param: ctx.traffic[args.param],
            "metrics": {k: v for k, (v, _) in last["metrics"].items()},
            "attempted": last["attempted"], "failed": last["failed"],
            "correct": last["correct"], "samples": head["samples"],
            "counters": head["counters"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
