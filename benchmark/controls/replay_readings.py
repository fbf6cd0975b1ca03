#!/usr/bin/env python3
"""Read the replay's numbers over many seeds in one process, with controls.

    python benchmark/controls/replay_readings.py --workload anchor_train \\
        --seeds 1 2 3 --control none --control no_mcw --control bf16

For every seed it makes the cell's data, draws the replay's sample as the
cell's traffic kind does, boosts the replay's rounds through the program
and holds them to the numpy grower (``check_against_grower`` of the cell's
kind): one JSON line a reading, the numbers ``correct`` is decided by. It
is how the limits of the replay get their two readings (PERF.md section 6,
PR 30); the benchmark's own runs never call it.

Controls (each has to come out as not ok):

* ``no_mcw``: the program grows with ``min_child_weight`` 0 while the
  reference holds the configuration's: one guarantee of the configuration
  broken. The upper reading of ``mcw_short``.
* ``bf16``: the level kernel's two-term split of a float32 gradient
  (``hist_kernel._split_hilo``) keeps its high term only: histograms in
  plain bfloat16, the nearest precision below the configuration's. Only
  the Pallas kernels split (the chip, or ``--interpret`` on the CPU).

On the CPU (``JAX_PLATFORMS=cpu``) the program takes its XLA level route
unless ``--interpret`` is given; a cell on four chips then wants
``XLA_FLAGS=--xla_force_host_platform_device_count=4``. Nothing a CPU run
prints is a chip result.
"""

import argparse
import contextlib
import json
import os
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
_BENCH = os.path.dirname(_HERE)
sys.path.insert(0, os.path.dirname(_BENCH))
sys.path.insert(0, _BENCH)

import harness  # noqa: E402

CONTROLS = ("none", "no_mcw", "bf16")
CONTROL_SEEDS = 3  # a control reads the first so many of the seeds


def _sample(kind, ctx):
    """The replay's sample of ``ctx``'s seed, as the kind's ``run`` draws
    it: the arguments of ``check_against_grower`` after ``xgb``."""
    cfg = ctx.config
    data = cfg["data"]
    n_tr = int(data["rows_train"])
    if ctx.mix["kind"] == "rank_window":
        X, y, q = ctx.generator().generate(
            rows=n_tr, cols=int(data["cols"]), seed=ctx.seed,
            queries=int(data["queries_train"]),
            **data.get("generator_params", {}))
        return kind._sample_of_queries(X, y, kind.lambdamart.group_ptr_of(q),
                                       ctx.seed)
    X, y = ctx.make_data()
    return X[:n_tr], y[:n_tr]  # check_against_grower draws its rows itself


def apply_control(name: str) -> None:
    """Plant ``bf16`` in the program's kernels; any other name restores
    them (``no_mcw`` is planted in the parameters, a reading at a time)."""
    import jax
    import jax.numpy as jnp

    from xgboost_tpu.tree import hist_kernel as hk

    if not hasattr(apply_control, "split"):
        apply_control.split = hk._split_hilo
    hk._split_hilo = apply_control.split
    if name == "bf16":
        def hi_only(x):
            hi, _ = apply_control.split(x)
            return hi, jnp.zeros_like(hi)

        hk._split_hilo = hi_only
    jax.clear_caches()  # the kernels were traced with the other split


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", action="append", choices=CONTROLS,
                    help="default: none (the program as it is)")
    ap.add_argument("--interpret", action="store_true",
                    help="CPU only: Pallas kernel bodies interpreted")
    ap.add_argument("--rehearsal", action="store_true",
                    help="a tiny stand-in of benchmark/rehearsal/ (the "
                         "self-test's size), not a cell")
    ap.add_argument("--out", default=None, help="append the lines here too")
    args = ap.parse_args(argv)
    cell = harness.load_cell(
        os.path.join(_BENCH, "rehearsal") if args.rehearsal else _BENCH,
        args.workload)

    import jax

    import xgboost_tpu as xgb
    from xgboost_tpu.config import enable_compile_cache

    devices = jax.devices()
    on_cpu = devices[0].platform == "cpu"
    enable_compile_cache()  # the checkout's, as benchmark/run.py; off on CPU
    chips = int(cell["chips"])
    if len(devices) < chips:
        print(f"replay_readings: {args.workload} needs {chips} devices, JAX "
              f"found {len(devices)}", file=sys.stderr)
        return 2
    kind = harness.load_module(os.path.join(
        _BENCH, "traffic", f"{cell['mix']['kind']}.py"))

    def mesh_cm():
        if chips == 1:
            return contextlib.nullcontext()
        from xgboost_tpu.parallel import make_mesh, mesh_context

        return mesh_context(make_mesh(chips))

    rc = 0
    for control in args.control or ["none"]:
        seeds = (args.seeds if control == "none"
                 else args.seeds[:CONTROL_SEEDS])
        apply_control(control)
        for seed in seeds:
            ctx = harness.Context(cell, seed, 0.0, False, time.perf_counter(),
                                  rehearsal=on_cpu)
            try:
                if on_cpu and args.interpret:
                    harness._rehearsal_stand_ins(ctx)
                t0 = time.perf_counter()
                sample = _sample(kind, ctx)
                with (dropped_mcw(kind, ctx.config) if control == "no_mcw"
                      else contextlib.nullcontext()), mesh_cm():
                    out = kind.check_against_grower(ctx, xgb, *sample)
                line = {"workload": args.workload, "seed": seed,
                        "control": control, "platform": devices[0].platform,
                        "interpret": bool(args.interpret and on_cpu),
                        "seconds": round(time.perf_counter() - t0, 1), **out}
            finally:
                ctx.close()
            text = json.dumps(line)
            print(text, flush=True)
            if args.out:
                os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                            exist_ok=True)
                with open(args.out, "a") as f:
                    f.write(text + "\n")
            if (control == "none") != bool(out["ok"]):
                rc = 1  # a sound run not ok, or a control that passed
    apply_control("none")
    return rc


@contextlib.contextmanager
def dropped_mcw(kind, config: dict):
    """Inside: the program of traffic kind ``kind`` grows with
    ``min_child_weight`` 0 while the grower holds ``config``'s."""
    grower = kind.grower
    replay_tree, params = grower.replay_tree, kind._params
    held = float(config["params"].get("min_child_weight", 1.0))

    def holding(*a, **kw):
        return replay_tree(*a, **dict(kw, min_child_weight=held))

    def broken(cfg, seed):
        return dict(params(cfg, seed), min_child_weight=0.0)

    kind._params, grower.replay_tree = broken, holding
    try:
        yield
    finally:
        kind._params, grower.replay_tree = params, replay_tree


if __name__ == "__main__":
    sys.exit(main())
