#!/usr/bin/env python3
"""The categorical replay's controls: a program broken underneath must come
out as not ok.

    python benchmark/controls/cat_controls.py --workload <cell> \\
        --seeds 1 2 --control none --control bf16

For every seed it makes the cell's data and ``DMatrix`` and, under each
control, runs ``cat_train_window``'s two checks: the cell's first chunk
grown over every training row and replayed against the numpy categorical
grower (``replay``), then the sample's three rounds (``check_sample``),
one JSON line a reading. The benchmark's own runs never call it.

Controls (each has to come out as not ok):

- ``bf16``: the level kernels' two-term split of a float32 gradient
  (``hist_kernel._split_hilo``) keeps its high term only: histograms in
  plain bfloat16, the nearest precision below the configuration's;
- ``onehot``: every categorical column one-vs-rest (``max_cat_to_onehot``
  256), replayed as the configuration's partition columns;
- ``numerical``: the codes boosted as numbers (no ``feature_types``),
  replayed as categories.

``--rehearsal`` takes the cell from ``benchmark/rehearsal/`` and, on the
CPU, interprets the Pallas kernel bodies. Nothing a CPU run prints is a
chip result.
"""

import argparse
import json
import os
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
_BENCH = os.path.dirname(_HERE)
sys.path.insert(0, os.path.dirname(_BENCH))
sys.path.insert(0, _BENCH)

import harness  # noqa: E402

CONTROLS = ("none", "bf16", "onehot", "numerical")
leafwise_controls = harness.load_module(os.path.join(_HERE,
                                                     "leafwise_controls.py"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", action="append", choices=CONTROLS,
                    help="default: none (the program as it is)")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rehearsal", action="store_true",
                    help="a stand-in of benchmark/rehearsal/ (CPU)")
    args = ap.parse_args(argv)
    cell = harness.load_cell(
        os.path.join(_BENCH, "rehearsal") if args.rehearsal else _BENCH,
        args.workload)
    import jax

    import xgboost_tpu as xgb
    from xgboost_tpu.config import enable_compile_cache

    on_cpu = jax.devices()[0].platform == "cpu"
    enable_compile_cache()
    kind = harness.load_module(os.path.join(
        _BENCH, "traffic", f"{cell['mix']['kind']}.py"))
    n_tr = int(cell["config_doc"]["data"]["rows_train"])
    chunk = int(cell["mix"]["chunk"])
    rc = 0
    for seed in args.seeds:
        ctx = harness.Context(cell, seed, 0.0, False, time.perf_counter(),
                              rehearsal=on_cpu)
        try:
            if on_cpu:
                harness._rehearsal_stand_ins(ctx)
            X, y = ctx.make_data()
            X, y = X[:n_tr], y[:n_tr]
            params = kind.tw._params(ctx.config, seed)
            types = ctx.generator().feature_types()
            dtrain = xgb.DMatrix(X, label=y, feature_types=types)
            binned = dtrain.get_binned(int(params["max_bin"]))
            bins = kind.cat_grower.bin_rows(X, binned.cuts.values)
            for control in args.control or ["none"]:
                leafwise_controls.apply_control(
                    "bf16" if control == "bf16" else "none")
                grown, dgrow = params, dtrain
                if control == "onehot":
                    grown = dict(params, max_cat_to_onehot=256)
                if control == "numerical":
                    dgrow = xgb.DMatrix(X, label=y)
                t0 = time.perf_counter()
                bst = xgb.Booster(grown, [dgrow])
                bst.update_many(dgrow, 0, chunk, chunk=chunk)
                full = kind.replay(ctx, X, y, binned, bst, chunk,
                                   kind.tw._drain(bst, dgrow),
                                   "full replay", bins=bins)
                del bst
                sample = kind.check_sample(ctx, xgb, X, y, types) \
                    if control in ("none", "bf16") else {"ok": False}
                line = {"seed": seed, "control": control,
                        "platform": jax.devices()[0].platform,
                        "seconds": round(time.perf_counter() - t0, 1),
                        "ok": full["ok"] and sample["ok"], "full": full,
                        "sample": sample}
                print(json.dumps(line), flush=True)
                if (control == "none") != bool(line["ok"]):
                    rc = 1  # a sound run not ok, or a control that passed
        finally:
            ctx.close()
            leafwise_controls.apply_control("none")
    return rc


if __name__ == "__main__":
    sys.exit(main())
