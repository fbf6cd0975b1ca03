#!/usr/bin/env python3
"""Drive a whole run of a CPU stand-in with the timed path broken
underneath, and see ``correct`` come out false.

    JAX_PLATFORMS=cpu python benchmark/controls/broken_run.py \\
        --workload tiny_train --fault bf16

The runner, the traffic kind, the references and the result line are the
real ones (``harness.run_cell`` in rehearsal mode, which skips the look for
a chip); the fault is one of ``replay_readings.py``'s controls, planted
before the run starts: ``bf16`` (the level kernels' histograms in plain
bfloat16), ``no_mcw`` (the program grows without the configuration's
``min_child_weight``), or ``none`` (the run as it is, for comparison).
Every line is labelled: nothing here is a chip result.
"""

import time

_T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_HERE = os.path.dirname(os.path.abspath(__file__))
_BENCH = os.path.dirname(_HERE)
sys.path.insert(0, os.path.dirname(_BENCH))
sys.path.insert(0, _BENCH)

import harness  # noqa: E402

controls = harness.load_module(os.path.join(_HERE, "replay_readings.py"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", choices=controls.CONTROLS, required=True)
    args = ap.parse_args(argv)
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        print("broken_run: set JAX_PLATFORMS=cpu", file=sys.stderr)
        return 2
    cell = harness.load_cell(os.path.join(_BENCH, "rehearsal"),
                             args.workload)
    kind = harness.load_module(os.path.join(
        _BENCH, "traffic", f"{cell['mix']['kind']}.py"))
    controls.apply_control(args.fault)
    with (controls.dropped_mcw(kind, cell["config_doc"])
          if args.fault == "no_mcw" else contextlib.nullcontext()):
        return harness.run_cell(cell, seed=1, seconds=1.5, trace=False,
                                t_process_start=_T_PROCESS_START,
                                rehearsal=True)


if __name__ == "__main__":
    sys.exit(main())
