"""Fidelity regression tests for review/ADVICE round-1 findings."""

import numpy as np
import pytest

import xgboost_tpu as xgb


def test_slice_respects_num_parallel_tree():
    """GBTreeModel.slice must account for num_parallel_tree (gbtree.cc:326:
    one round appends n_groups * num_parallel_tree trees)."""
    rng = np.random.RandomState(0)
    X = rng.randn(400, 4).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    d = xgb.DMatrix(X, label=y)
    bst = xgb.train({"objective": "binary:logistic", "num_parallel_tree": 3,
                     "max_depth": 2, "subsample": 0.7},
                    d, num_boost_round=4, verbose_eval=False)
    assert bst._gbm.model.num_trees == 12
    assert bst.num_boosted_rounds() == 4
    s = bst[1:3]
    assert s._gbm.model.num_trees == 6
    # sliced trees are exactly rounds 1-2's forests
    for i in range(6):
        np.testing.assert_array_equal(
            s._gbm.model.trees[i].split_conditions,
            bst._gbm.model.trees[3 + i].split_conditions,
        )
    # iteration_range prediction equals the sliced model's full prediction
    np.testing.assert_allclose(
        bst.predict(d, iteration_range=(1, 3), output_margin=True),
        # slice loses base_margin context: compare margins
        s.predict(d, output_margin=True),
        rtol=1e-5,
    )


def test_gamma_nloglik_matches_reference_formula():
    """gamma-nloglik = y/p + log(p) at psi=1 (elementwise_metric.cu
    EvalGammaNLogLik); must INCREASE as predictions move away from labels."""
    from xgboost_tpu.metric import create_metric

    m = create_metric("gamma-nloglik")
    y = np.array([1.0, 2.0, 3.0], np.float32)
    good = float(m.evaluate(y, y))
    worse = float(m.evaluate(y * 8.0, y))
    expected_good = np.mean(y / y + np.log(y))
    assert abs(good - expected_good) < 1e-5
    assert worse > good  # round-1 bug: metric decreased with worse preds


def test_gblinear_bias_residual_convergence():
    """Bias residuals must advance by the applied eta*db step; exact
    single-feature least squares should converge tightly."""
    rng = np.random.RandomState(3)
    X = rng.randn(500, 1).astype(np.float32)
    y = (2.5 * X[:, 0] + 1.5).astype(np.float32)
    d = xgb.DMatrix(X, label=y)
    bst = xgb.train({"booster": "gblinear", "objective": "reg:squarederror",
                     "eta": 0.5, "lambda": 0.0},
                    d, num_boost_round=60, verbose_eval=False)
    pred = bst.predict(d)
    assert np.sqrt(np.mean((pred - y) ** 2)) < 1e-2


def test_ntree_limit_respects_num_parallel_tree():
    rng = np.random.RandomState(1)
    X = rng.randn(300, 4).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    d = xgb.DMatrix(X, label=y)
    bst = xgb.train({"objective": "binary:logistic", "num_parallel_tree": 3,
                     "max_depth": 2, "subsample": 0.7},
                    d, num_boost_round=4, verbose_eval=False)
    np.testing.assert_allclose(
        bst.predict(d, ntree_limit=6, output_margin=True),
        bst.predict(d, iteration_range=(0, 2), output_margin=True),
    )


def test_num_parallel_tree_survives_json_round_trip():
    rng = np.random.RandomState(2)
    X = rng.randn(300, 4).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    d = xgb.DMatrix(X, label=y)
    bst = xgb.train({"objective": "binary:logistic", "num_parallel_tree": 3,
                     "max_depth": 2, "subsample": 0.7},
                    d, num_boost_round=4, verbose_eval=False)
    bst.save_model("/tmp/npt.json")
    b2 = xgb.Booster(model_file="/tmp/npt.json")
    assert b2.num_boosted_rounds() == 4
    assert b2[1:3]._gbm.model.num_trees == 6


def test_loads_reference_written_model_json(tmp_path):
    """Interop: a model file exactly as xgboost 1.6 writes it (doc/
    model.schema: string-encoded scalars like base_score '5E-1',
    num_class '0', int default_left flags, SoA tree arrays, INT_MAX root
    parent) must load and predict correctly, missing -> default-left."""
    import json
    import math

    model = {
        "version": [1, 6, 0],
        "learner": {
            "attributes": {},
            "feature_names": [],
            "feature_types": [],
            "gradient_booster": {
                "model": {
                    "gbtree_model_param": {"num_trees": "1",
                                           "size_leaf_vector": "0"},
                    "tree_info": [0],
                    "trees": [{
                        "base_weights": [0.0, -1.0, 2.0],
                        "categories": [], "categories_nodes": [],
                        "categories_segments": [], "categories_sizes": [],
                        "default_left": [1, 0, 0],
                        "id": 0,
                        "left_children": [1, -1, -1],
                        "loss_changes": [10.0, 0.0, 0.0],
                        "parents": [2147483647, 0, 0],
                        "right_children": [2, -1, -1],
                        "split_conditions": [0.5, -1.0, 2.0],
                        "split_indices": [0, 0, 0],
                        "split_type": [0, 0, 0],
                        "sum_hessian": [8.0, 4.0, 4.0],
                        "tree_param": {"num_deleted": "0",
                                       "num_feature": "1",
                                       "num_nodes": "3",
                                       "size_leaf_vector": "0"},
                    }],
                },
                "name": "gbtree",
            },
            "learner_model_param": {"base_score": "5E-1", "num_class": "0",
                                    "num_feature": "1"},
            "objective": {"name": "binary:logistic",
                          "reg_loss_param": {"scale_pos_weight": "1"}},
        },
    }
    path = tmp_path / "ref_model.json"
    path.write_text(json.dumps(model))
    bst = xgb.Booster(model_file=str(path))
    X = np.array([[0.3], [0.7], [np.nan]], np.float32)
    p = bst.predict(xgb.DMatrix(X))
    exp = [1 / (1 + math.exp(-v)) for v in (-1.0, 2.0, -1.0)]
    np.testing.assert_allclose(p, exp, rtol=1e-6)
