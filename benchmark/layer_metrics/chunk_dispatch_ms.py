"""Mean host time of ``xgb.chunk.dispatch`` a traced chunk: the call of the
scan program (tracing and compiling, when they happen, are in it)."""

import os

from harness import HERE, load_module

phases = load_module(os.path.join(HERE, "reduce", "phases.py"))


def read(summary, record, cell):
    return phases.host_ms_per_chunk(summary, ("xgb.chunk.dispatch",))
