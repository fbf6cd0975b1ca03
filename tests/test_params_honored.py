"""Every accepted parameter must have a behavioral use site — silent no-ops
break the validate_parameters contract (reference: learner.cc:351; review
round-2 item 4: 13 accept-and-ignore fields)."""

import os
import re

import numpy as np
import pytest

import xgboost_tpu as xgb
from xgboost_tpu.metric import create_metric


def _data(n=3000, F=10, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, F).astype(np.float32)
    y = (np.nan_to_num(X) @ rng.randn(F) + 0.3 * rng.randn(n) > 0).astype(
        np.float32
    )
    return X, y


def test_gradient_based_sampling_trains():
    X, y = _data()
    d = xgb.DMatrix(X, label=y)
    bst = xgb.train(
        {"objective": "binary:logistic", "subsample": 0.3,
         "sampling_method": "gradient_based", "max_depth": 4},
        d, 10, verbose_eval=False)
    auc = float(create_metric("auc").evaluate(bst.predict(d), y))
    assert auc > 0.8


def test_gradient_based_differs_from_uniform():
    X, y = _data()
    d = xgb.DMatrix(X, label=y)
    common = {"objective": "binary:logistic", "subsample": 0.3, "max_depth": 3}
    b1 = xgb.train({**common, "sampling_method": "gradient_based"}, d, 3,
                   verbose_eval=False)
    b2 = xgb.train({**common, "sampling_method": "uniform"}, d, 3,
                   verbose_eval=False)
    assert not np.allclose(b1.predict(d), b2.predict(d))


def test_sampling_method_unknown_raises():
    X, y = _data(500)
    d = xgb.DMatrix(X, label=y)
    with pytest.raises(ValueError):
        xgb.train({"objective": "binary:logistic",
                   "sampling_method": "nope"}, d, 1, verbose_eval=False)


def test_process_type_update_refresh_leaf():
    X, y = _data()
    d = xgb.DMatrix(X, label=y)
    base = xgb.train({"objective": "binary:logistic", "max_depth": 4}, d, 4,
                     verbose_eval=False)
    X2, y2 = _data(seed=7)
    d2 = xgb.DMatrix(X2, label=y2)
    upd = xgb.train({"objective": "binary:logistic", "max_depth": 4,
                     "process_type": "update", "refresh_leaf": 1},
                    d2, 4, verbose_eval=False, xgb_model=base)
    t0, t1 = base._gbm.model.trees[0], upd._gbm.model.trees[0]
    # structure identical, leaf values re-fit to the new data
    np.testing.assert_array_equal(t0.left_children, t1.left_children)
    np.testing.assert_array_equal(t0.split_indices, t1.split_indices)
    leaf = t0.left_children == -1
    assert not np.allclose(t0.split_conditions[leaf], t1.split_conditions[leaf])
    # refresh_leaf=0 keeps leaf values but refreshes stats
    kept = xgb.train({"objective": "binary:logistic", "max_depth": 4,
                      "process_type": "update", "refresh_leaf": 0},
                     d2, 4, verbose_eval=False, xgb_model=base)
    t2 = kept._gbm.model.trees[0]
    assert np.allclose(t0.split_conditions[leaf], t2.split_conditions[leaf])
    assert not np.allclose(t0.sum_hessian, t2.sum_hessian)


def test_process_type_update_too_many_rounds_raises():
    X, y = _data(500)
    d = xgb.DMatrix(X, label=y)
    base = xgb.train({"objective": "binary:logistic"}, d, 2, verbose_eval=False)
    with pytest.raises(ValueError):
        xgb.train({"objective": "binary:logistic", "process_type": "update"},
                  d, 3, verbose_eval=False, xgb_model=base)


def test_updater_refresh_alias():
    X, y = _data(1000)
    d = xgb.DMatrix(X, label=y)
    base = xgb.train({"objective": "binary:logistic"}, d, 2, verbose_eval=False)
    upd = xgb.train({"objective": "binary:logistic", "updater": "refresh"},
                    d, 2, verbose_eval=False, xgb_model=base)
    assert upd.num_boosted_rounds() == 2


def test_updater_unknown_raises():
    X, y = _data(500)
    d = xgb.DMatrix(X, label=y)
    with pytest.raises(ValueError):
        xgb.train({"objective": "binary:logistic", "updater": "warp_drive"},
                  d, 1, verbose_eval=False)


@pytest.mark.parametrize("selector", ["cyclic", "shuffle", "random",
                                      "greedy", "thrifty"])
def test_gblinear_feature_selectors(selector):
    X, y = _data()
    d = xgb.DMatrix(X, label=y)
    bst = xgb.train(
        {"booster": "gblinear", "objective": "binary:logistic",
         "updater": "coord_descent", "feature_selector": selector,
         "top_k": 5}, d, 5, verbose_eval=False)
    auc = float(create_metric("auc").evaluate(bst.predict(d), y))
    assert auc > 0.7


def test_gblinear_selector_unknown_raises():
    X, y = _data(500)
    d = xgb.DMatrix(X, label=y)
    with pytest.raises(ValueError):
        xgb.train({"booster": "gblinear", "objective": "binary:logistic",
                   "updater": "coord_descent", "feature_selector": "psychic"},
                  d, 1, verbose_eval=False)


def test_every_tree_param_has_a_use_site():
    """Source-level guard: each TrainParam/GBTreeParam/GBLinearParam field
    must be consumed somewhere outside params.py (implemented, warned, or
    validated) — greps the package the way the round-2 review did."""
    from xgboost_tpu.params import GBLinearParam, GBTreeParam, TrainParam

    pkg = os.path.dirname(xgb.__file__)
    src = []
    for root, _, files in os.walk(pkg):
        for fn in files:
            if fn.endswith(".py") and fn != "params.py":
                with open(os.path.join(root, fn)) as f:
                    src.append(f.read())
    blob = "\n".join(src)
    missing = []
    for P in (TrainParam, GBTreeParam, GBLinearParam):
        for name in P.FIELDS:
            if not re.search(rf"\b{re.escape(name)}\b", blob):
                missing.append(f"{P.__name__}.{name}")
    assert not missing, f"accepted-but-unused parameters: {missing}"
