"""Seconds of Python tracing, every program of the process: the set-up
ledger's ``jit_seconds_total{stage="trace"}`` summed over ``fn``, own
time (a program traced inside another is taken out of it, so the sum is wall
time). No cache saves it: a grower that doubles its Python shows here."""

import os

from harness import HERE, load_module

registry = load_module(os.path.join(HERE, "reduce", "registry.py"))


def read(summary, record, cell):
    return registry.total(record, "jit_seconds_total", stage="trace")
