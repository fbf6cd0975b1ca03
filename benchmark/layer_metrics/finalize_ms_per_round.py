"""XLA self time under ``xgb.finalize`` per boosting round, mean chip:
pruning by ``gamma`` and the leaf values."""

import os

from harness import HERE, load_module

phases = load_module(os.path.join(HERE, "reduce", "phases.py"))


def read(summary, record, cell):
    return phases.device_ms_per_round(summary, record, "xgb.finalize")
