"""XLA self time under ``xgb.level_hist`` per boosting round, mean chip: what
feeds the level kernel and is not the Mosaic call itself (the widening of
the bins, casts, copies, broadcasts)."""

import os

from harness import HERE, load_module

phases = load_module(os.path.join(HERE, "reduce", "phases.py"))


def read(summary, record, cell):
    return phases.device_ms_per_round(summary, record, "xgb.level_hist")
