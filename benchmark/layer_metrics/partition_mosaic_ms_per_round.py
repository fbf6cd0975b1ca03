"""Mosaic self time under ``xgb.partition`` per boosting round, mean chip: the
routing kernel that takes every row to its leaf once a tree
(``_route_rows_pallas``). ``partition_ms_per_round`` reads the XLA ops of the
same scope; 0 where the scope holds no Mosaic call (a program that routes in
XLA), nothing where no op carries the scope."""

import os

from harness import HERE, load_module

phases = load_module(os.path.join(HERE, "reduce", "phases.py"))


def read(summary, record, cell):
    return phases.device_ms_per_round(summary, record, "xgb.partition",
                                      kinds=("mosaic",))
