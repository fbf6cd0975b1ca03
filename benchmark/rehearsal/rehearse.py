#!/usr/bin/env python3
"""Run the CPU stand-ins of the traffic kinds end to end: no chip time.

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python benchmark/rehearsal/rehearse.py [--workload tiny_train ...]

The cells under ``benchmark/rehearsal/`` are tiny and are not listed in
``BENCHMARK.json``. They go through the same runner, traffic kinds,
references, reduction and readers as the real cells, with the Pallas kernel
bodies interpreted. Every line they print is labelled: nothing here is a
chip result. The real cells (``benchmark/run.py``) refuse a CPU.
"""

import time

_T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_HERE = os.path.dirname(os.path.abspath(__file__))
_BENCH = os.path.dirname(_HERE)
sys.path.insert(0, os.path.dirname(_BENCH))
sys.path.insert(0, _BENCH)

import harness  # noqa: E402


def main(argv=None) -> int:
    names = sorted(os.path.basename(p)[:-5] for p in
                   glob.glob(os.path.join(_HERE, "workloads", "*.json")))
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append", choices=names,
                    help="default: every stand-in, each in its own process")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        print("rehearse: set JAX_PLATFORMS=cpu (and XLA_FLAGS="
              "--xla_force_host_platform_device_count=4 for the mesh cell)",
              file=sys.stderr)
        return 2
    todo = args.workload or names
    if len(todo) > 1:
        # one process a cell, as the driver runs the real ones (this parent
        # has not touched JAX)
        import subprocess

        rc = 0
        for name in todo:
            rc |= subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--workload",
                 name, "--seed", str(args.seed), "--seconds",
                 str(args.seconds), "--trace", str(args.trace)]).returncode
        return rc
    cell = harness.load_cell(_HERE, todo[0])
    return harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                            trace=bool(args.trace),
                            t_process_start=_T_PROCESS_START, rehearsal=True)


if __name__ == "__main__":
    sys.exit(main())
