"""``unscoped_xla_ms_per_round`` for a ranking cell: XLA self time per round
that no phase metric claims, the two ranking scopes among the claimed.
``phases.CLAIMED`` dates from before them, so the older reader would book
``xgb.rank_sort`` and ``xgb.rank_pairs`` as what the naming misses."""

import os

from harness import HERE, load_module

phases = load_module(os.path.join(HERE, "reduce", "phases.py"))

RANK_SCOPES = ("xgb.rank_sort", "xgb.rank_pairs")


def read(summary, record, cell):
    unclaimed = phases.unclaimed_xla_ms_per_round(summary, record)
    if unclaimed is None:  # no traced round
        return None
    return unclaimed - sum(
        phases.device_ms_per_round(summary, record, scope) or 0.0
        for scope in RANK_SCOPES)
