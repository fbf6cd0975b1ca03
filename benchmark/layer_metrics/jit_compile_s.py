"""Seconds inside the backend's compile call, every program of the
process: ``jit_seconds_total{stage="compile"}`` summed over ``fn``. A load
from the persistent cache is inside it, so on a warm run this is the loads
and on a cold one the compiles (``compile_cache_misses`` tells them apart)."""

import os

from harness import HERE, load_module

registry = load_module(os.path.join(HERE, "reduce", "registry.py"))


def read(summary, record, cell):
    return registry.total(record, "jit_seconds_total", stage="compile")
