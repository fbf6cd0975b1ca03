"""Device self time of the eval loop per boosting round, mean chip: the
ops under ``xgb.predict_walk`` (the holdout's margin caught up with the
round's new trees) plus those under ``xgb.eval_metric`` (the metric's own
device ops: AUC's sort and sums). A program from before ``xgb.eval_metric``
reads the walk alone; nothing where no op carries either scope (no eval
set)."""

import os

from harness import HERE, load_module

phases = load_module(os.path.join(HERE, "reduce", "phases.py"))

SCOPES = ("xgb.predict_walk", "xgb.eval_metric")


def read(summary, record, cell):
    parts = [phases.device_ms_per_round(summary, record, scope,
                                        kinds=("xla", "mosaic"))
             for scope in SCOPES]
    found = [p for p in parts if p is not None]
    return sum(found) if found else None
