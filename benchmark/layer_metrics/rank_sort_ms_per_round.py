"""XLA self time under ``xgb.rank_sort`` per boosting round, mean chip: the
ranking objective's sort by (query, -margin) and the second sort that
carries the ranks back to row order, with the discounts made from them."""

import os

from harness import HERE, load_module

phases = load_module(os.path.join(HERE, "reduce", "phases.py"))


def read(summary, record, cell):
    return phases.device_ms_per_round(summary, record, "xgb.rank_sort")
