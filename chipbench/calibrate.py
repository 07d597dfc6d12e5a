"""Readings that set a cell's limits: the program and its control, on many
seeds in one process.

    python3 chipbench/calibrate.py --workload <cell> --seconds <s> \
        --seeds 1 2 3 ... [--control] [--fault <name>]

For each seed it sets the cell up, measures a short window at the cell's
own load, and prints one JSON line with each compared number as the run
reads it (``program``) and, with ``--control``, as the cell's control
reads it on the same answers (``control``).  ``--fault`` plants one of
``yardstick.faults.ROUND`` in the program first.  It exits 3 without a
TPU.  Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault")
    args = ap.parse_args(argv)

    from yardstick import harness
    cell = harness.resolve(args.workload)
    try:
        harness.chips(cell.chips)
    except harness.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 3
    harness.compile_cache(cell.root)
    drv = harness.driver(cell)
    if args.fault:
        from yardstick import faults
        faults.plant(args.fault)

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    for seed in args.seeds:
        t0 = time.perf_counter()
        state = drv.setup(cell, seed, log)
        win = drv.window(state, args.seconds)
        checks = drv.check(state, cell, seed, log)
        out = {"workload": cell.name, "seed": seed, "fault": args.fault,
               "attempted": win.attempted, "failed": win.failed,
               "program": {c.name: c.value for c in checks}}
        if args.control:
            out["control"] = drv.control(state, cell, seed, log)
        out["seconds"] = time.perf_counter() - t0
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
