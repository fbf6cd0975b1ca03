"""Seconds of binning the matrix against the cuts, to the joined bins on
the device, less the uploads it asked for: the set-up stage ``bins``."""

import os

from harness import HERE, load_module

registry = load_module(os.path.join(HERE, "reduce", "registry.py"))


def read(summary, record, cell):
    return registry.total(record, "setup_stage_seconds_total", stage="bins")
