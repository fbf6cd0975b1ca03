"""Mean host time of an ``xgb.round.boost`` span in the traced window: the
per-round entry's second half (``Booster.update``): padding, the tree's
dispatch, the tree into the model, the margin's update. Nothing on the scan
path, which opens no such span."""

import os

from harness import HERE, load_module

gradient_span = load_module(os.path.join(HERE, "layer_metrics",
                                         "round_gradient_host_ms.py"))


def read(summary, record, cell):
    return gradient_span.read(summary, record, cell, span="xgb.round.boost")
