"""Mean host time of ``xgb.chunk.commit`` + ``xgb.chunk.admit`` a traced
chunk: the trees into the model, the margin back to its rows, and the
pipeline's admission, the one place the entry layer can block."""

import os

from harness import HERE, load_module

phases = load_module(os.path.join(HERE, "reduce", "phases.py"))


def read(summary, record, cell):
    return phases.host_ms_per_chunk(summary, ("xgb.chunk.commit",
                                              "xgb.chunk.admit"))
