"""XLA self time under ``xgb.partition`` per boosting round, mean chip: the
last ``partition_apply``, which routes every row to its leaf."""

import os

from harness import HERE, load_module

phases = load_module(os.path.join(HERE, "reduce", "phases.py"))


def read(summary, record, cell):
    return phases.device_ms_per_round(summary, record, "xgb.partition")
