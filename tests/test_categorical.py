"""Categorical split tests (reference analog: tests/python
test_updaters.py categorical cases, categorical_helpers.h)."""

import numpy as np
import pytest

import xgboost_tpu as xgb


def _cat_data(n=3000, n_cats=6, seed=0):
    rng = np.random.RandomState(seed)
    cats = rng.randint(0, n_cats, size=n).astype(np.float32)
    noise = rng.randn(n).astype(np.float32)
    # category 3 is special: strong signal only one-hot splits can isolate
    y = np.where(cats == 3, 5.0, 0.0).astype(np.float32) + 0.1 * noise
    X = np.stack([cats, noise], axis=1)
    return X, y


def test_categorical_isolates_category():
    X, y = _cat_data()
    d = xgb.DMatrix(X, label=y, feature_types=["c", "q"])
    bst = xgb.train({"objective": "reg:squarederror", "max_depth": 3, "eta": 1.0},
                    d, num_boost_round=3, verbose_eval=False)
    # the first tree's root should one-hot split on category 3
    t = bst._gbm.model.trees[0]
    assert t.split_type is not None and t.split_type[0] == 1
    assert int(t.split_conditions[0]) == 3
    pred = bst.predict(xgb.DMatrix(X, feature_types=["c", "q"]))
    assert abs(pred[X[:, 0] == 3].mean() - 5.0) < 0.3
    assert abs(pred[X[:, 0] != 3].mean() - 0.0) < 0.3


def test_categorical_beats_numerical_binning_on_unordered_codes():
    # category->target mapping deliberately non-monotone in the code value:
    # numerical (threshold) splits need several levels, one-hot needs one
    rng = np.random.RandomState(1)
    cats = rng.randint(0, 8, size=4000).astype(np.float32)
    y = np.isin(cats, [1, 4, 6]).astype(np.float32) * 3.0
    X = cats.reshape(-1, 1)
    d_cat = xgb.DMatrix(X, label=y, feature_types=["c"])
    d_num = xgb.DMatrix(X, label=y)
    p = {"objective": "reg:squarederror", "max_depth": 2, "eta": 1.0}
    b_cat = xgb.train(p, d_cat, 3, verbose_eval=False)
    b_num = xgb.train(p, d_num, 3, verbose_eval=False)
    rmse_cat = np.sqrt(np.mean((b_cat.predict(d_cat) - y) ** 2))
    rmse_num = np.sqrt(np.mean((b_num.predict(d_num) - y) ** 2))
    assert rmse_cat < rmse_num


def test_categorical_missing_default_direction():
    X, y = _cat_data()
    X[::5, 0] = np.nan
    d = xgb.DMatrix(X, label=y, feature_types=["c", "q"])
    bst = xgb.train({"objective": "reg:squarederror", "max_depth": 3},
                    d, num_boost_round=4, verbose_eval=False)
    p = bst.predict(xgb.DMatrix(X, feature_types=["c", "q"]))
    assert np.all(np.isfinite(p))


def test_categorical_json_round_trip():
    X, y = _cat_data()
    d = xgb.DMatrix(X, label=y, feature_types=["c", "q"])
    bst = xgb.train({"objective": "reg:squarederror", "max_depth": 3},
                    d, num_boost_round=3, verbose_eval=False)
    j = bst.save_json()
    tree0 = j["learner"]["gradient_booster"]["model"]["trees"][0]
    assert 1 in tree0["split_type"]
    assert len(tree0["categories_nodes"]) == sum(
        1 for s, l in zip(tree0["split_type"], tree0["left_children"]) if s == 1 and l != -1
    )
    import json

    bst2 = xgb.Booster()
    bst2.load_json(json.loads(json.dumps(j)))
    p1 = bst.predict(d)
    p2 = bst2.predict(xgb.DMatrix(X, feature_types=["c", "q"]))
    np.testing.assert_allclose(p1, p2, rtol=1e-5)


def test_pandas_categorical_dtype():
    pd = pytest.importorskip("pandas")
    rng = np.random.RandomState(2)
    codes = rng.randint(0, 4, size=500)
    df = pd.DataFrame({
        "c": pd.Categorical.from_codes(codes, categories=["a", "b", "x", "y"]),
        "v": rng.randn(500),
    })
    y = (codes == 2).astype(np.float32) * 2.0
    d = xgb.DMatrix(df, label=y, enable_categorical=True)
    assert d.feature_types == ["c", "q"]
    bst = xgb.train({"objective": "reg:squarederror", "max_depth": 2, "eta": 1.0},
                    d, num_boost_round=3, verbose_eval=False)
    pred = bst.predict(d)
    assert abs(pred[codes == 2].mean() - 2.0) < 0.3


def _multiset_data(n=4000, n_cats=24, seed=7, hot=(2, 5, 9, 11, 17, 20, 23)):
    """High-cardinality categorical where the signal set is scattered across
    codes: a single optimal-partition split can isolate it, one-hot cannot."""
    rng = np.random.RandomState(seed)
    cats = rng.randint(0, n_cats, size=n).astype(np.float32)
    y = (np.isin(cats, list(hot)).astype(np.float32) * 4.0
         + 0.05 * rng.randn(n).astype(np.float32))
    return cats.reshape(-1, 1), y


def test_partition_split_beats_onehot():
    """Optimal-partition categorical splits (evaluate_splits.h:61-203 sorted
    gradient scan) at shallow depth beat the one-hot regime."""
    X, y = _multiset_data()
    p_base = {"objective": "reg:squarederror", "max_depth": 2, "eta": 1.0}
    d = xgb.DMatrix(X, label=y, feature_types=["c"])
    # partition regime (24 cats >= max_cat_to_onehot default 4)
    b_part = xgb.train(p_base, d, 2, verbose_eval=False)
    # forced one-hot regime via a huge max_cat_to_onehot threshold
    b_oh = xgb.train({**p_base, "max_cat_to_onehot": 1000}, d, 2, verbose_eval=False)
    rmse_part = np.sqrt(np.mean((b_part.predict(d) - y) ** 2))
    rmse_oh = np.sqrt(np.mean((b_oh.predict(d) - y) ** 2))
    assert rmse_part < rmse_oh * 0.5, (rmse_part, rmse_oh)
    # root must carry a multi-category set
    t = b_part._gbm.model.trees[0]
    assert t.split_type[0] == 1 and len(t.categories[0]) > 1


def test_partition_json_round_trip_and_predictor_parity():
    X, y = _multiset_data(seed=9)
    d = xgb.DMatrix(X, label=y, feature_types=["c"])
    bst = xgb.train({"objective": "reg:squarederror", "max_depth": 3, "eta": 0.7},
                    d, 3, verbose_eval=False)
    # multi-category sets survive the JSON round trip (tiny tolerance: the
    # trained booster predicts through its incremental cache, summation
    # order differs from the fresh pass)
    import json
    bst2 = xgb.Booster()
    bst2.load_json(json.loads(json.dumps(bst.save_json())))
    np.testing.assert_allclose(
        bst.predict(d), bst2.predict(xgb.DMatrix(X, feature_types=["c"])),
        rtol=1e-5, atol=1e-6,
    )
    # and the two hosts' tree structures are bit-identical
    for t1, t2 in zip(bst._gbm.model.trees, bst2._gbm.model.trees):
        np.testing.assert_array_equal(t1.split_conditions, t2.split_conditions)
        assert all(
            np.array_equal(a, b) for a, b in zip(t1.categories or [], t2.categories or [])
        )
    # XLA predictor parity with the host RegTree walk (predict_fn.h oracle)
    preds = bst.predict(d, output_margin=True)
    base = 0.5
    for i in range(0, len(X), 371):
        host = base + sum(t.predict_one(X[i]) for t in bst._gbm.model.trees)
        np.testing.assert_allclose(preds[i], host, rtol=1e-5)


def test_partition_lossguide():
    X, y = _multiset_data(seed=11)
    d = xgb.DMatrix(X, label=y, feature_types=["c"])
    bst = xgb.train({"objective": "reg:squarederror", "grow_policy": "lossguide",
                     "max_leaves": 8, "max_depth": 0, "eta": 1.0},
                    d, 2, verbose_eval=False)
    rmse = np.sqrt(np.mean((bst.predict(d) - y) ** 2))
    assert rmse < 0.5
    t = bst._gbm.model.trees[0]
    internal = t.left_children != -1
    assert (t.split_type[internal] == 1).any()
    assert any(len(t.categories[i]) > 1 for i in np.nonzero(internal)[0])


def test_categorical_trains_through_fused_device_path():
    """Categorical depthwise training must run the FUSED grower (device-
    resident pending trees with cat metadata), not the legacy host-prune
    path (review r3 weak #7), and must match the legacy grower's quality."""
    rng = np.random.RandomState(8)
    n = 3000
    codes = rng.randint(0, 12, n).astype(np.float32)  # one-hot regime
    codes2 = rng.randint(0, 40, n).astype(np.float32)  # partition regime
    num = rng.randn(n).astype(np.float32)
    y = ((codes % 3 == 0) | ((codes2 > 25) & (num > 0))).astype(np.float32)
    X = np.column_stack([codes, num, codes2]).astype(np.float32)
    d = xgb.DMatrix(X, label=y, feature_types=["c", "q", "c"])
    bst = xgb.Booster({"objective": "binary:logistic", "max_depth": 5,
                       "max_cat_to_onehot": 16}, [d])
    for i in range(8):
        bst.update(d, i)
    from xgboost_tpu.gbm.gbtree import _PendingTree

    ents = bst._gbm.model._entries
    assert all(isinstance(e, _PendingTree) for e in ents)
    assert all(e.cat_mask is not None and e.cat_set is not None
               for e in ents)
    # quality: the fused categorical grower must learn the categorical rule
    from xgboost_tpu.metric import create_metric

    auc = float(create_metric("auc").evaluate(bst.predict(d), y))
    assert auc > 0.97, auc
    # save -> load -> predict parity (bitsets survive IO)
    import tempfile, os

    with tempfile.TemporaryDirectory() as td:
        fp = os.path.join(td, "m.json")
        bst.save_model(fp)
        b2 = xgb.Booster(model_file=fp)
        np.testing.assert_allclose(b2.predict(d), bst.predict(d),
                                   rtol=1e-5, atol=1e-6)
