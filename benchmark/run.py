#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chip this process finds.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads and warms (set-up), measures for ``--seconds``, checks the answers, and
prints one JSON object as the last line of standard output: the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics, the device's
busy time and a breakdown with ``--trace 1``. Exits non-zero, printing no
result, off the chip, with fewer chips than the cell asks for, on a degraded
capability or a fall-back warning. ``BENCHMARK.json`` lists the cells.
"""

import time

_T_PROCESS_START = time.perf_counter()  # before JAX: set-up counts from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))  # the checkout: xgboost_tpu
sys.path.insert(0, _HERE)

import harness  # noqa: E402


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="copy the profiler's files and the reduced event "
                         "table under DIR (the builder's tool; the driver "
                         "never passes it)")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        cell = harness.load_cell(_HERE, args.workload)
    except (harness.BenchFailure, OSError, ValueError) as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    return harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                            trace=bool(args.trace),
                            t_process_start=_T_PROCESS_START,
                            keep_trace=args.keep_trace)


if __name__ == "__main__":
    sys.exit(main())
