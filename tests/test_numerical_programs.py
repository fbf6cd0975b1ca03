"""A job with no categorical column compiles as it did before categorical
splits reached the scan chunk.

The anchor-shaped and the Cover-Type-shaped scan programs (the
``anchor_train`` and ``covtype_train`` cells' columns, depth, objective and
hoist plan, on 4,096 rows, the Pallas route on) are captured through
``Booster.update_many`` and lowered; the StableHLO text, without debug
locations, is held to a length and a sha256 read from the package before
the categorical branches of the split evaluation, the chunk and the VMEM
model existed. ``tests/test_depthwise_programs.py`` pins the same programs'
jaxprs; this pins what XLA is handed."""

import hashlib

import numpy as np
import pytest

import xgboost_tpu as xgb
from xgboost_tpu.tree import hist_kernel as hk

# columns, hoist plan, objective; the lowered text's length and sha256
CELLS = {
    "anchor_train": (50, 34, {"objective": "binary:logistic"}, 949502,
                     "0378a6b493cfb3a286a09a1b01ef9841"
                     "39af2651d0db39ce31822ba92f0b5c70"),
    "covtype_train": (54, 33, {"objective": "multi:softmax",
                               "num_class": 8}, 1551410,
                      "0fa80968ffe8aecbd3b52e86e93cde51"
                      "4dd65306a9fb9d4859dcbf4ba8aeca31"),
}


@pytest.mark.parametrize("cell", CELLS)
def test_a_numerical_jobs_lowered_scan_program_is_unmoved(monkeypatch, cell):
    from xgboost_tpu.gbm import gbtree

    F, plan, objective, size, digest = CELLS[cell]
    monkeypatch.setattr(hk, "use_pallas", lambda: True)
    monkeypatch.setattr(hk, "_INTERPRET", True)
    monkeypatch.setenv("XGBTPU_HOIST_BUDGET_MB", str(plan))
    rng = np.random.RandomState(1)
    X = rng.randn(4096, F).astype(np.float32)
    y = rng.randint(0, 2, 4096).astype(np.float32)
    params = dict(objective, tree_method="tpu_hist", max_depth=6,
                  max_bin=256, seed=1)
    orig = gbtree._scan_rounds_impl
    jitted = getattr(orig, "_guarded_jit", orig)
    lowered = []

    class Lowered(Exception):
        pass

    def lowering(*args, **kwargs):
        lowered.append(jitted.trace(*args, **kwargs).lower().as_text())
        raise Lowered  # nothing of the program runs

    monkeypatch.setattr(gbtree, "_scan_rounds_impl", lowering)
    with pytest.raises(Lowered):
        d = xgb.DMatrix(X, label=y)
        xgb.Booster(params, [d]).update_many(d, 0, 2, chunk=2)
    text = lowered[0].encode()
    assert len(text) == size
    assert hashlib.sha256(text).hexdigest() == digest
