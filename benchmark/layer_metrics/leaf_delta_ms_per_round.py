"""Self time under ``xgb.leaf_delta`` per boosting round, mean chip: the
leaf value of every row and its addition to the margin. XLA ops today (a
one-hot matmul); a Mosaic call there would be counted too."""

import os

from harness import HERE, load_module

phases = load_module(os.path.join(HERE, "reduce", "phases.py"))


def read(summary, record, cell):
    return phases.device_ms_per_round(summary, record, "xgb.leaf_delta",
                                      kinds=("xla", "mosaic"))
