"""Seconds of lowering jaxprs to MLIR modules, every program of the
process: ``jit_seconds_total{stage="lower"}`` summed over ``fn``. Paid on
a warm compile cache too."""

import os

from harness import HERE, load_module

registry = load_module(os.path.join(HERE, "reduce", "registry.py"))


def read(summary, record, cell):
    return registry.total(record, "jit_seconds_total", stage="lower")
