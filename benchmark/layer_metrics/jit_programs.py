"""Programs the process handed to the backend's compile call (compiled or
loaded from the cache): ``jit_events_total{stage="compile"}`` summed over
``fn``."""

import os

from harness import HERE, load_module

registry = load_module(os.path.join(HERE, "reduce", "registry.py"))


def read(summary, record, cell):
    return registry.total(record, "jit_events_total", stage="compile")
