"""XLA self time under ``xgb.split_eval`` per boosting round, mean chip:
``_level_update`` of every level (prefix sums, gains, the best split, the
heap's new rows)."""

import os

from harness import HERE, load_module

phases = load_module(os.path.join(HERE, "reduce", "phases.py"))


def read(summary, record, cell):
    return phases.device_ms_per_round(summary, record, "xgb.split_eval")
