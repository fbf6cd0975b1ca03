"""Seconds of the quantile sketch, to the cuts on the host, less the
uploads it asked for: the set-up stage ``sketch``."""

import os

from harness import HERE, load_module

registry = load_module(os.path.join(HERE, "reduce", "registry.py"))


def read(summary, record, cell):
    return registry.total(record, "setup_stage_seconds_total", stage="sketch")
