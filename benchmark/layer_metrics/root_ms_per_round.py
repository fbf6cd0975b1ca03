"""XLA self time under ``xgb.root`` per boosting round, mean chip: row
sampling, the gradient pair, column sampling, the root totals. Their
all-reduce under a mesh is a collective and is not counted here."""

import os

from harness import HERE, load_module

phases = load_module(os.path.join(HERE, "reduce", "phases.py"))


def read(summary, record, cell):
    return phases.device_ms_per_round(summary, record, "xgb.root")
