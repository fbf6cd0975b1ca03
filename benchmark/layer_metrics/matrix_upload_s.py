"""Seconds the float32 matrix took to the device, by blocks or whole, for
the cuts and again for the bins, each upload closed on its arrival: the
set-up stage ``upload`` (``setup_stage_seconds_total``)."""

import os

from harness import HERE, load_module

registry = load_module(os.path.join(HERE, "reduce", "registry.py"))


def read(summary, record, cell):
    return registry.total(record, "setup_stage_seconds_total", stage="upload")
