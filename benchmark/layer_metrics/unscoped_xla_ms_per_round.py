"""XLA self time per boosting round that no phase metric claims, mean chip:
ops with no ``xgb.`` scope (containers, compiler-made copies), and ops under
a scope that has no metric of its own. What the naming misses; the seven
phase metrics, this one and the collectives add up to the op line's
non-Mosaic self time."""

import os

from harness import HERE, load_module

phases = load_module(os.path.join(HERE, "reduce", "phases.py"))


def read(summary, record, cell):
    return phases.unclaimed_xla_ms_per_round(summary, record)
