"""Mean host time of an ``xgb.eval`` span in the traced window: one
``Booster.eval_set`` of the eval loop (``xgb.train`` with ``evals``), from
the holdout's margin (the cached one, caught up by a walk of the round's new
trees) to the metric's value read back on the host, which is the round's
drain. Nothing where no eval set is given: the span is never opened."""

import os

from harness import HERE, load_module

gradient_span = load_module(os.path.join(HERE, "layer_metrics",
                                         "round_gradient_host_ms.py"))


def read(summary, record, cell):
    return gradient_span.read(summary, record, cell, span="xgb.eval")
