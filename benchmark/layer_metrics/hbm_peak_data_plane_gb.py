"""The device's high-water mark as the data plane left it, in GB: the
largest ``hbm_peak_bytes{stage}`` over the stages upload, sketch, bins and
onehot, each set where a run of the stage raised the allocator's peak.
Equal to ``hbm_peak_gb`` where the data plane set the run's peak, under it
where the tree program did."""

import os

from harness import HERE, load_module

registry = load_module(os.path.join(HERE, "reduce", "registry.py"))


STAGES = ("upload", "sketch", "bins", "onehot")


def read(summary, record, cell):
    marks = [v for stage in STAGES
             for v in registry.series("hbm_peak_bytes", stage=stage)]
    return max(marks) / 1e9 if record and marks else None
