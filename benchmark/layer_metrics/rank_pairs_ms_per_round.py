"""XLA self time under ``xgb.rank_pairs`` per boosting round, mean chip: the
ranking objective's sampled pairs: the uniforms, the opponents' gathers, the
lambdas and the update of both ends (a scatter-add of duplicate rows)."""

import os

from harness import HERE, load_module

phases = load_module(os.path.join(HERE, "reduce", "phases.py"))


def read(summary, record, cell):
    return phases.device_ms_per_round(summary, record, "xgb.rank_pairs")
