"""Seconds to build the resident one-hot, to the array on the device: the
set-up stage ``onehot`` (a matrix's first ``BinnedMatrix.fused_onehot``, one
chip: the bins' padding, the plan, the build). Where the plan hoists
nothing it is the padding and the plan alone. Under a mesh the first
chunk's program builds the one-hot and no stage sees it."""

import os

from harness import HERE, load_module

registry = load_module(os.path.join(HERE, "reduce", "registry.py"))


def read(summary, record, cell):
    return registry.total(record, "setup_stage_seconds_total", stage="onehot")
