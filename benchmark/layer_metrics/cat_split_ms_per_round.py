"""XLA self time under ``xgb.cat_split`` per boosting round, mean chip: the
categorical candidates of every level's split evaluation (the one-vs-rest
sums, the sort of a partition column's categories by ``g / (h + lambda)``
and its prefix scan). The scope sits inside ``xgb.split_eval``, whose own
reader then holds the rest of the evaluation. Nothing from a program
without the scope, or a job with no categorical column."""

import os

from harness import HERE, load_module

phases = load_module(os.path.join(HERE, "reduce", "phases.py"))


def read(summary, record, cell):
    return phases.device_ms_per_round(summary, record, "xgb.cat_split")
