"""Run one benchmark cell once and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is looked up by name in the checkout's ``BENCHMARK.json``.  The
run sets up (weights from ``--seed`` on the device, the cell's shapes
warmed from the persistent compile cache), measures for ``--seconds``,
compares what the window produced with the plain reference, and prints
one JSON object as the last line of standard output: the end-to-end
metrics with ``--trace 0``, the per-layer metrics from a profiler trace of
the window with ``--trace 1``.  Each compared number is printed beside its
limit, last on standard error and last in the result line.  Without a TPU,
or with fewer chips than the cell asks for, it exits 3 and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from yardstick import harness
    try:
        cell = harness.resolve(args.workload)
    except (KeyError, FileNotFoundError) as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    try:
        result = harness.run(cell, args.seed, args.seconds,
                             bool(args.trace), T_START)
    except harness.NoChip as e:
        print(f"chipbench: {e} — no result", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
