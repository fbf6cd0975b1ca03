"""XLA self time under ``xgb.gradient`` per boosting round, mean chip: the
objective's gradient, its per-group slices and their padding or masking."""

import os

from harness import HERE, load_module

phases = load_module(os.path.join(HERE, "reduce", "phases.py"))


def read(summary, record, cell):
    return phases.device_ms_per_round(summary, record, "xgb.gradient")
