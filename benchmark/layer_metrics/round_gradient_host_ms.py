"""Mean host time of an ``xgb.round.gradient`` span in the traced window:
the per-round entry's first half (``Booster.update``), from the read of the
cached margin to the return of the objective's dispatch. Nothing on the
scan path, which opens no such span."""

import os

from harness import HERE, load_module

phases = load_module(os.path.join(HERE, "reduce", "phases.py"))

SPAN = "xgb.round.gradient"


def read(summary, record, cell, span=SPAN):
    out = phases.table(summary)
    count, seconds = out["host"].get(span, (0, 0.0)) if out else (0, 0.0)
    return 1e3 * seconds / count if count else None
