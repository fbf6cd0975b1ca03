"""Mean host time of ``xgb.chunk.prepare`` a traced chunk: everything
``boost_rounds_scan`` does before it calls the program (the label and the
weights to the device, padding, sharding under a mesh)."""

import os

from harness import HERE, load_module

phases = load_module(os.path.join(HERE, "reduce", "phases.py"))


def read(summary, record, cell):
    return phases.host_ms_per_chunk(summary, ("xgb.chunk.prepare",))
