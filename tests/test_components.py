"""Component tests: CLI, DataIter, SHAP, gblinear, DART, sampling
(reference analogs: test_cli.py, test_data_iterator.py, test_shap.py,
test_linear.py, test_updaters dart/sampling cases)."""

import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import xgboost_tpu as xgb


def _data(n=1200, f=5, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f).astype(np.float32)
    y = (X[:, 0] * 2 - X[:, 1] + 0.3 * rng.randn(n) > 0).astype(np.float32)
    return X, y


# ---------------------------------------------------------------- CLI
def test_cli_train_pred_dump(tmp_path):
    from xgboost_tpu.cli import cli_main

    X, y = _data(400, 4)
    train_csv = tmp_path / "train.csv"
    np.savetxt(train_csv, np.column_stack([y, X]), delimiter=",", fmt="%.6g")
    conf = tmp_path / "train.conf"
    conf.write_text(
        f"""# comment line
task = train
data = {train_csv}
num_round = 3
objective = binary:logistic
max_depth = 3
model_out = {tmp_path}/m.json
silent = 1
"""
    )
    assert cli_main([str(conf)]) == 0
    assert (tmp_path / "m.json").exists()

    pconf = tmp_path / "pred.conf"
    pconf.write_text(
        f"task=pred\nmodel_in={tmp_path}/m.json\ntest:data={train_csv}\nname_pred={tmp_path}/pred.txt\n"
    )
    assert cli_main([str(pconf)]) == 0
    preds = np.loadtxt(tmp_path / "pred.txt")
    assert preds.shape == (400,)
    assert np.all((preds >= 0) & (preds <= 1))

    dconf = tmp_path / "dump.conf"
    dconf.write_text(
        f"task=dump\nmodel_in={tmp_path}/m.json\nname_dump={tmp_path}/dump.txt\nwith_stats=1\n"
    )
    assert cli_main([str(dconf), f"name_dump={tmp_path}/dump.txt"]) == 0
    text = (tmp_path / "dump.txt").read_text()
    assert "booster[0]" in text and "leaf=" in text


# ---------------------------------------------------------------- DataIter
def test_streaming_quantile_dmatrix_matches_batch():
    from xgboost_tpu.data.iterator import DataIter, StreamingQuantileDMatrix

    X, y = _data(1000, 4)

    class It(DataIter):
        def __init__(self):
            super().__init__()
            self.i = 0

        def reset(self):
            self.i = 0

        def next(self, input_data):
            if self.i >= 4:
                return 0
            sl = slice(self.i * 250, (self.i + 1) * 250)
            input_data(data=X[sl], label=y[sl])
            self.i += 1
            return 1

    dstream = StreamingQuantileDMatrix(It(), max_bin=32)
    dbatch = xgb.DMatrix(X, label=y)
    p = {"objective": "binary:logistic", "max_depth": 3, "max_bin": 32}
    b1 = xgb.train(p, dstream, 5, verbose_eval=False)
    b2 = xgb.train(p, dbatch, 5, verbose_eval=False)
    p1 = b1.predict(dbatch)
    p2 = b2.predict(dbatch)
    # streamed sketch is approximate: models agree closely but not exactly
    assert np.corrcoef(p1, p2)[0, 1] > 0.99


# ---------------------------------------------------------------- SHAP
def test_shap_additivity():
    X, y = _data(60, 4)
    d = xgb.DMatrix(X, label=y)
    bst = xgb.train({"objective": "binary:logistic", "max_depth": 3}, d, 3, verbose_eval=False)
    contribs = bst.predict(d, pred_contribs=True)
    assert contribs.shape == (60, 5)
    margin = bst.predict(d, output_margin=True)
    np.testing.assert_allclose(contribs.sum(axis=1), margin, rtol=1e-3, atol=1e-3)


def test_shap_approx_additivity():
    X, y = _data(40, 3)
    d = xgb.DMatrix(X, label=y)
    bst = xgb.train({"objective": "binary:logistic", "max_depth": 3}, d, 2, verbose_eval=False)
    contribs = bst.predict(d, pred_contribs=True, approx_contribs=True)
    margin = bst.predict(d, output_margin=True)
    np.testing.assert_allclose(contribs.sum(axis=1), margin, rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------- gblinear
def test_gblinear_recovers_linear_model():
    rng = np.random.RandomState(0)
    X = rng.randn(2000, 3).astype(np.float32)
    y = (1.5 * X[:, 0] - 2.0 * X[:, 1] + 0.5).astype(np.float32)
    d = xgb.DMatrix(X, label=y)
    bst = xgb.train(
        {"booster": "gblinear", "objective": "reg:squarederror", "eta": 0.5,
         "lambda": 0.0},
        d, num_boost_round=50, verbose_eval=False,
    )
    pred = bst.predict(d)
    rmse = float(np.sqrt(np.mean((pred - y) ** 2)))
    assert rmse < 0.1, rmse


# ---------------------------------------------------------------- DART
def test_dart_trains_and_differs_from_gbtree():
    X, y = _data()
    d = xgb.DMatrix(X, label=y)
    res = {}
    bst = xgb.train(
        {"booster": "dart", "objective": "binary:logistic", "max_depth": 3,
         "rate_drop": 0.5, "eval_metric": "logloss", "seed": 1},
        d, num_boost_round=10, evals=[(d, "train")], evals_result=res, verbose_eval=False,
    )
    assert res["train"]["logloss"][-1] < res["train"]["logloss"][0]
    assert len(bst._gbm.weight_drop) == 10
    assert any(w != 1.0 for w in bst._gbm.weight_drop)


# ---------------------------------------------------------------- sampling
def test_subsample_and_colsample_still_learn():
    X, y = _data(3000, 8)
    d = xgb.DMatrix(X, label=y)
    res = {}
    xgb.train(
        {"objective": "binary:logistic", "max_depth": 4, "subsample": 0.5,
         "colsample_bytree": 0.5, "colsample_bylevel": 0.7,
         "colsample_bynode": 0.7, "eval_metric": "auc", "seed": 3},
        d, num_boost_round=15, evals=[(d, "train")], evals_result=res, verbose_eval=False,
    )
    assert res["train"]["auc"][-1] > 0.9


def test_colsample_bytree_restricts_features():
    X, y = _data(800, 10)
    d = xgb.DMatrix(X, label=y)
    bst = xgb.train(
        {"objective": "binary:logistic", "max_depth": 3, "colsample_bytree": 0.3,
         "seed": 7},
        d, num_boost_round=1, verbose_eval=False,
    )
    t = bst._gbm.model.trees[0]
    used = set(t.split_indices[t.left_children != -1].tolist())
    assert len(used) <= 3


# ---------------------------------------------------------------- misc API
def test_training_continuation():
    X, y = _data()
    d = xgb.DMatrix(X, label=y)
    b1 = xgb.train({"objective": "binary:logistic", "max_depth": 3}, d, 5, verbose_eval=False)
    b2 = xgb.train({"objective": "binary:logistic", "max_depth": 3}, d, 5,
                   xgb_model=b1, verbose_eval=False)
    assert b2.num_boosted_rounds() == 10
    b3 = xgb.train({"objective": "binary:logistic", "max_depth": 3}, d, 10, verbose_eval=False)
    # continued model should behave comparably to one trained in one go
    p2, p3 = b2.predict(d), b3.predict(d)
    assert np.corrcoef(p2, p3)[0, 1] > 0.999


def test_booster_slicing():
    X, y = _data()
    d = xgb.DMatrix(X, label=y)
    bst = xgb.train({"objective": "binary:logistic", "max_depth": 3}, d, 6, verbose_eval=False)
    head = bst[:3]
    assert head.num_boosted_rounds() == 3
    np.testing.assert_allclose(
        head.predict(d, output_margin=True),
        bst.predict(d, output_margin=True, iteration_range=(0, 3)),
        rtol=1e-5,
    )


def test_cv_runs():
    X, y = _data(600, 4)
    d = xgb.DMatrix(X, label=y)
    hist = xgb.cv({"objective": "binary:logistic", "max_depth": 2}, d,
                  num_boost_round=3, nfold=3, as_pandas=False)
    assert "test-logloss-mean" in hist
    assert len(hist["test-logloss-mean"]) == 3


def test_exact_k_nested_column_sampling():
    """Hierarchical colsample draws EXACT-k nested subsets (random.h:120):
    every node sees exactly round(bynode*round(bylevel*round(bytree*F)))
    features, never zero (review r2 weak #8)."""
    import jax
    import jax.numpy as jnp
    from xgboost_tpu.tree.grow import exact_k_subset

    key = jax.random.PRNGKey(0)
    F = 10
    parent = jnp.zeros(F, bool).at[jnp.arange(6)].set(True)  # 6-feature set
    for k in (1, 3, 6):
        sub = exact_k_subset(key, parent, k)
        assert int(sub.sum()) == k
        assert bool((sub & ~parent).sum() == 0), "subset must nest in parent"
    # batched per-node draws differ across nodes but keep exact k
    batch = jnp.broadcast_to(parent[None, :], (8, F))
    keys = key
    sub = exact_k_subset(keys, batch, 2)
    assert sub.sum(axis=1).min() == 2 and sub.sum(axis=1).max() == 2


def test_small_F_colsample_never_empty():
    rng = np.random.RandomState(0)
    X = rng.randn(2000, 3).astype(np.float32)
    y = (X.sum(1) > 0).astype(np.float32)
    d = xgb.DMatrix(X, label=y)
    # bernoulli at 0.4 on 3 features would often draw zero; exact-k cannot
    bst = xgb.train({"objective": "binary:logistic", "max_depth": 4,
                     "colsample_bylevel": 0.4, "colsample_bynode": 0.4},
                    d, 5, verbose_eval=False)
    from xgboost_tpu.metric import create_metric
    auc = float(create_metric("auc").evaluate(bst.predict(d), y))
    assert auc > 0.7


def test_segmented_rank_metrics_match_per_group_oracle():
    """Vectorized ndcg@/map@/pre@/grouped-AUC must equal a straightforward
    per-group implementation."""
    from xgboost_tpu.metric import create_metric

    rng = np.random.RandomState(5)
    sizes = rng.randint(1, 40, 60)
    gptr = np.concatenate([[0], np.cumsum(sizes)])
    n = int(gptr[-1])
    p = rng.randn(n).astype(np.float32)
    y = rng.randint(0, 4, n).astype(np.float32)

    def oracle_ndcg(k):
        vals = []
        for g in range(len(sizes)):
            lo, hi = gptr[g], gptr[g + 1]
            o = np.argsort(-p[lo:hi], kind="stable")
            r = y[lo:hi][o][:k]
            dcg = ((2.0 ** r - 1) / np.log2(np.arange(len(r)) + 2)).sum()
            i = np.sort(y[lo:hi])[::-1][:k]
            idcg = ((2.0 ** i - 1) / np.log2(np.arange(len(i)) + 2)).sum()
            vals.append(dcg / idcg if idcg > 0 else 1.0)
        return np.mean(vals)

    def oracle_map(k):
        # reference semantics (rank_metric.cc:321-330): nhits counts hits
        # over the WHOLE group; only the sumap terms are top-k-gated; the
        # final division is by the group's total hit count
        vals = []
        for g in range(len(sizes)):
            lo, hi = gptr[g], gptr[g + 1]
            o = np.argsort(-p[lo:hi], kind="stable")
            rel = (y[lo:hi][o] > 0).astype(float)
            if rel.sum() == 0:
                vals.append(1.0)
                continue
            prec = np.cumsum(rel) / (np.arange(len(rel)) + 1)
            vals.append((prec * rel)[:k].sum() / rel.sum())
        return np.mean(vals)

    for k in (5, 10):
        m = create_metric(f"ndcg@{k}")
        got = float(m.evaluate(jnp.asarray(p), jnp.asarray(y), group_ptr=gptr))
        assert abs(got - oracle_ndcg(k)) < 1e-9, (got, oracle_ndcg(k))
        m2 = create_metric(f"map@{k}")
        got2 = float(m2.evaluate(jnp.asarray(p), jnp.asarray(y), group_ptr=gptr))
        assert abs(got2 - oracle_map(k)) < 1e-9

    # grouped AUC vs per-group binary AUC
    from xgboost_tpu.metric.auc import _binary_auc
    yb = (y > 1).astype(np.float32)
    m3 = create_metric("auc")
    got3 = float(m3.evaluate(jnp.asarray(p), jnp.asarray(yb), group_ptr=gptr))
    vals = []
    for g in range(len(sizes)):
        lo, hi = gptr[g], gptr[g + 1]
        ylg = yb[lo:hi]
        if hi <= lo or ylg.min(initial=1) == ylg.max(initial=0):
            continue
        vals.append(float(_binary_auc(jnp.asarray(p[lo:hi]), jnp.asarray(ylg),
                                      jnp.ones(hi - lo, np.float32))))
    assert abs(got3 - np.mean(vals)) < 1e-6


def test_arrow_table_adapter():
    pa = pytest.importorskip("pyarrow")
    rng = np.random.RandomState(0)
    df_np = rng.randn(200, 3).astype(np.float32)
    table = pa.table({f"f{i}": df_np[:, i] for i in range(3)})
    d = xgb.DMatrix(table, label=(df_np.sum(1) > 0).astype(np.float32))
    assert d.num_row() == 200 and d.num_col() == 3
    np.testing.assert_allclose(np.asarray(d.data), df_np, rtol=1e-6)


def test_load_row_split_partitions_disjoint():
    import tempfile, os
    rows = ["1 0:1.5 1:2.0", "0 0:0.5", "1 1:3.0", "0 0:2.5 1:1.0", "1 0:9.0"]
    with tempfile.NamedTemporaryFile("w", suffix=".libsvm", delete=False) as f:
        f.write("\n".join(rows) + "\n")
        path = f.name
    try:
        parts = [xgb.load_row_split(path, r, 2) for r in range(2)]
        assert parts[0].num_row() + parts[1].num_row() == 5
        y0 = parts[0].info.label
        y1 = parts[1].info.label
        full = xgb.DMatrix(path).info.label
        assert sorted(np.concatenate([y0, y1]).tolist()) == sorted(full.tolist())
    finally:
        os.unlink(path)


def test_checkpoint_crash_resume_equivalence():
    """Fault-tolerance story (reference: rabit checkpoint API + mock-based
    kill tests, allreduce_mock.h; production recovery = restart from the
    saved model): training interrupted at round 5 and resumed from the
    checkpoint must reproduce the uninterrupted 10-round model."""
    rng = np.random.RandomState(0)
    X = rng.randn(3000, 8).astype(np.float32)
    y = (np.nan_to_num(X).sum(1) > 0).astype(np.float32)
    params = {"objective": "binary:logistic", "max_depth": 4, "eta": 0.3}

    d = xgb.DMatrix(X, label=y)
    full = xgb.train(params, d, 10, verbose_eval=False)

    first = xgb.train(params, d, 5, verbose_eval=False)
    blob = first.save_raw()  # "crash": only the serialized model survives
    del first, d

    d2 = xgb.DMatrix(X, label=y)  # fresh process analog
    restored = xgb.Booster(params)
    restored.load_model(blob)
    resumed = xgb.train(params, d2, 5, verbose_eval=False, xgb_model=restored)

    assert resumed.num_boosted_rounds() == 10
    np.testing.assert_allclose(
        resumed.predict(d2), full.predict(d2), rtol=1e-4, atol=1e-5
    )


def test_inplace_predict_matches_dmatrix_predict():
    rng = np.random.RandomState(0)
    X = rng.randn(1000, 6).astype(np.float32)
    X[rng.rand(1000, 6) < 0.1] = np.nan
    y = (np.nan_to_num(X).sum(1) > 0).astype(np.float32)
    d = xgb.DMatrix(X, label=y)
    bst = xgb.train({"objective": "binary:logistic", "max_depth": 4}, d, 5,
                    verbose_eval=False)
    # the serving path's native walker accumulates in double, so parity
    # with the XLA segment_sum is float32 round-off — the contract is
    # |diff| < 1e-5 on margins (docs/serving.md), not bit identity
    p1 = bst.predict(xgb.DMatrix(X))
    p2 = bst.inplace_predict(X)
    np.testing.assert_allclose(p1, p2, rtol=1e-6, atol=1e-6)
    m = bst.inplace_predict(X, predict_type="margin")
    np.testing.assert_allclose(
        m, bst.predict(xgb.DMatrix(X), output_margin=True), atol=1e-5)
    # missing sentinel handling on the fast path
    Xs = np.nan_to_num(X, nan=-999.0)
    p3 = bst.inplace_predict(Xs, missing=-999.0)
    np.testing.assert_allclose(p1, p3, rtol=1e-6, atol=1e-6)


def test_approx_resketeches_per_iteration():
    """tree_method='approx' rebuilds hessian-weighted cuts every round
    (updater_histmaker.cc per-iteration proposal) and still learns; its
    trees differ from hist's once hessians become non-uniform."""
    rng = np.random.RandomState(0)
    X = rng.randn(4000, 8).astype(np.float32)
    y = (np.nan_to_num(X).sum(1) > 0).astype(np.float32)
    d = xgb.DMatrix(X, label=y)
    b_approx = xgb.train({"objective": "binary:logistic", "max_depth": 4,
                          "tree_method": "approx", "max_bin": 32}, d, 6,
                         verbose_eval=False)
    from xgboost_tpu.metric import create_metric
    auc = float(create_metric("auc").evaluate(b_approx.predict(d), y))
    assert auc > 0.9
    d2 = xgb.DMatrix(X, label=y)
    b_hist = xgb.train({"objective": "binary:logistic", "max_depth": 4,
                        "tree_method": "tpu_hist", "max_bin": 32}, d2, 6,
                       verbose_eval=False)
    # round-0 hessians are uniform (logistic at base 0.5): identical cuts;
    # later rounds weight by hessian -> different cuts -> different trees
    t_a = b_approx._gbm.model.trees[-1]
    t_h = b_hist._gbm.model.trees[-1]
    assert (t_a.num_nodes != t_h.num_nodes
            or not np.allclose(t_a.split_conditions, t_h.split_conditions))


def test_fault_injection_mock_recovery(tmp_path):
    """The rabit allreduce_mock analog (rabit/src/allreduce_mock.h: kill a
    worker at a scripted (version, seqno) ntrial times; recovery = restart
    from the last checkpoint). Scripts a fault at round 6 that fires twice;
    a restart loop resuming from TrainingCheckPoint files must converge to
    the exact uninterrupted model."""
    from xgboost_tpu.utils.fault import InjectedFault, fault_injection

    rng = np.random.RandomState(1)
    X = rng.randn(2000, 6).astype(np.float32)
    y = (np.nan_to_num(X).sum(1) > 0).astype(np.float32)
    params = {"objective": "binary:logistic", "max_depth": 3, "eta": 0.3}
    rounds = 10

    d = xgb.DMatrix(X, label=y)
    full = xgb.train(params, d, rounds, verbose_eval=False)

    def latest_checkpoint():
        cks = sorted(tmp_path.glob("ck_*.json"),
                     key=lambda p: int(p.stem.split("_")[1]))
        return cks[-1] if cks else None

    # fault at version 6, seqno 1 (the "grow" site), two trials: the first
    # restart hits it again before it exhausts — the mock's ntrial semantics
    with fault_injection({(6, 1): 2}) as spec:
        attempts = 0
        bst = None
        while attempts < 5:
            attempts += 1
            prev = latest_checkpoint()
            model = None
            done = 0
            if prev is not None:
                model = xgb.Booster(params)
                model.load_model(str(prev))
                done = model.num_boosted_rounds()
            try:
                bst = xgb.train(
                    params, xgb.DMatrix(X, label=y), rounds - done,
                    xgb_model=model, verbose_eval=False,
                    callbacks=[xgb.callback.TrainingCheckPoint(
                        str(tmp_path), name="ck", interval=2)],
                )
                break
            except InjectedFault:
                continue
        assert bst is not None and attempts == 3  # 2 kills + 1 clean run
        assert [f[0] for f in spec.fired] == ["grow", "grow"]

    assert bst.num_boosted_rounds() == rounds
    np.testing.assert_allclose(bst.predict(d), full.predict(d),
                               rtol=1e-4, atol=1e-5)


def test_fault_injection_inactive_is_noop():
    from xgboost_tpu.utils import fault

    fault.begin_version(3)  # no spec armed: must be a no-op
    fault.inject("gradient")
    with fault.fault_injection({(0, 0): 1}) as spec:
        fault.begin_version(0)
        try:
            fault.inject("gradient")
            raise AssertionError("fault did not fire")
        except fault.InjectedFault as e:
            assert (e.version, e.seqno, e.site) == (0, 0, "gradient")
        # trigger exhausted: same site next round is clean
        fault.begin_version(1)
        fault.inject("gradient")
        assert spec.fired == [("gradient", 0, 0)]


def test_tree_method_exact_recovers_exact_threshold():
    """tree_method='exact' = exact binning (one bin per distinct value, the
    colmaker candidate set, updater_colmaker.cc:367): a split threshold
    invisible to coarse quantile cuts must be found exactly."""
    rng = np.random.RandomState(0)
    # 997 distinct values; label flips at an arbitrary one of them
    vals = np.sort(rng.randn(997).astype(np.float32))
    x = vals[rng.randint(0, 997, size=4000)]
    cut = vals[700]
    y = (x >= cut).astype(np.float32)
    d = xgb.DMatrix(x[:, None], label=y)
    hist = xgb.train({"objective": "binary:logistic", "max_depth": 1,
                      "max_bin": 8, "eta": 1.0}, d, 1, verbose_eval=False)
    d2 = xgb.DMatrix(x[:, None], label=y)
    exact = xgb.train({"objective": "binary:logistic", "max_depth": 1,
                       "tree_method": "exact", "eta": 1.0}, d2, 1,
                      verbose_eval=False)
    # the exact tree's root condition IS the flip value; 8 quantile bins
    # cannot represent it
    t = exact._gbm.model.trees[0]
    assert t.num_nodes == 3
    assert np.isclose(t.split_conditions[0], cut)
    err_exact = ((exact.predict(d2) > 0.5) != y).mean()
    err_hist = ((hist.predict(d) > 0.5) != y).mean()
    assert err_exact == 0.0
    assert err_hist > 0.0
    assert not np.isclose(hist._gbm.model.trees[0].split_conditions[0], cut)


def test_tree_method_exact_cap_and_colmaker_alias():
    from xgboost_tpu.data.quantile import compute_exact_cuts

    rng = np.random.RandomState(1)
    X = rng.randn(300, 2).astype(np.float32)  # ~300 distinct per feature
    with pytest.raises(ValueError, match="distinct"):
        compute_exact_cuts(X, cap=100)

    y = (X[:, 0] > 0).astype(np.float32)
    d = xgb.DMatrix(X, label=y)
    bst = xgb.train({"objective": "binary:logistic", "max_depth": 2,
                     "updater": "grow_colmaker"}, d, 2, verbose_eval=False)
    # exact binning was used: the binned cache carries the "exact" key
    assert "exact" in d._binned
    assert np.isfinite(bst.predict(d)).all()


def test_tree_method_exact_sparse_categorical_codes():
    """Exact cuts must size the bin width from the max category code, not
    the distinct-value count: sparse codes (e.g. {0, 100}) would otherwise
    be rejected by the identity-cut validation."""
    import pandas as pd

    rng = np.random.RandomState(2)
    codes = rng.choice([0, 100], size=500)
    x2 = rng.randn(500).astype(np.float32)
    df = pd.DataFrame({
        "c": pd.Categorical.from_codes(
            codes, categories=[str(i) for i in range(101)]),
        "q": x2,
    })
    y = (codes == 100).astype(np.float32)
    d = xgb.DMatrix(df, label=y, enable_categorical=True)
    bst = xgb.train({"objective": "binary:logistic", "max_depth": 2,
                     "tree_method": "exact", "eta": 1.0}, d, 1,
                    verbose_eval=False)
    assert ((bst.predict(d) > 0.5) == y.astype(bool)).all()


def test_update_many_scan_matches_per_round_updates():
    """update_many = one lax.scan dispatch per chunk; same RNG keys as the
    per-round path, so the trees match (float-fusion noise only)."""
    rng = np.random.RandomState(0)
    X = rng.randn(3000, 8).astype(np.float32)
    X[rng.rand(3000, 8) < 0.05] = np.nan
    y = (np.nan_to_num(X).sum(1) > 0).astype(np.float32)
    params = {"objective": "binary:logistic", "max_depth": 4, "eta": 0.3,
              "subsample": 0.8, "colsample_bytree": 0.7, "seed": 9}

    d1 = xgb.DMatrix(X, label=y)
    b1 = xgb.Booster(params, [d1])
    for i in range(8):
        b1.update(d1, i)
    d2 = xgb.DMatrix(X, label=y)
    b2 = xgb.Booster(params, [d2])
    b2.update_many(d2, 0, 8, chunk=3)  # uneven chunks: 3+3+2
    np.testing.assert_allclose(b1.predict(d1), b2.predict(d2),
                               rtol=1e-5, atol=1e-6)
    assert b2.num_boosted_rounds() == 8

    # multiclass: one tree per group per round inside the scan
    ym = (y + (np.nan_to_num(X)[:, 0] > 1)).clip(0, 2)
    d3 = xgb.DMatrix(X, label=ym)
    b3 = xgb.Booster({"objective": "multi:softprob", "num_class": 3,
                      "max_depth": 3, "seed": 4}, [d3])
    for i in range(3):
        b3.update(d3, i)
    d4 = xgb.DMatrix(X, label=ym)
    b4 = xgb.Booster({"objective": "multi:softprob", "num_class": 3,
                      "max_depth": 3, "seed": 4}, [d4])
    b4.update_many(d4, 0, 3)
    np.testing.assert_allclose(b3.predict(d3), b4.predict(d4),
                               rtol=1e-5, atol=1e-6)

    # ineligible configs (DART here) fall back per-round transparently
    db = xgb.DMatrix(X, label=y)
    bb = xgb.Booster({"booster": "dart", "objective": "binary:logistic",
                      "max_depth": 3}, [db])
    bb.update_many(db, 0, 3)
    assert bb.num_boosted_rounds() == 3


def test_get_split_value_histogram():
    rng = np.random.RandomState(0)
    X = rng.randn(2000, 4).astype(np.float32)
    y = (X[:, 1] > 0.3).astype(np.float32)
    d = xgb.DMatrix(X, label=y, feature_names=["a", "b", "c", "dd"])
    bst = xgb.train({"objective": "binary:logistic", "max_depth": 3}, d, 5,
                    verbose_eval=False)
    h = bst.get_split_value_histogram("b", as_pandas=False)
    assert h.shape[1] == 2 and h[:, 1].sum() > 0
    # splits concentrate near the true threshold 0.3
    top = h[np.argmax(h[:, 1]), 0]
    assert abs(top - 0.3) < 0.5
    with pytest.raises(ValueError, match="unknown feature"):
        bst.get_split_value_histogram("nope")


def test_chunk_backed_model_paths():
    """update_many stores whole scan chunks (_PendingChunk) instead of
    per-tree device slices; every consumer — eval-cache catch-up through
    stacked_slice over _ChunkRefs, mixed chunk+per-round entries, predict
    on fresh data, JSON save/load — must behave identically."""
    rng = np.random.RandomState(0)
    X = rng.randn(800, 6).astype(np.float32)
    y = (X[:, 0] - X[:, 1] > 0).astype(np.float32)
    dtrain = xgb.DMatrix(X[:600], label=y[:600])
    dval = xgb.DMatrix(X[600:], label=y[600:])
    bst = xgb.Booster({"objective": "binary:logistic", "max_depth": 3},
                      [dtrain, dval])
    bst.update_many(dtrain, 0, 7, chunk=3)
    from xgboost_tpu.gbm.gbtree import _ChunkRef

    model = bst._gbm.model
    assert any(isinstance(e, _ChunkRef) for e in model._entries)
    line = bst.eval(dval, "val", 6)  # catch-up walks chunk-backed forest
    assert "val-logloss" in line
    bst.update(dtrain, 7)  # mixed: per-round _PendingTree after chunks
    p = bst.predict(xgb.DMatrix(X))
    assert p.shape == (800,) and np.isfinite(p).all()
    import tempfile, os

    with tempfile.TemporaryDirectory() as td:
        fp = os.path.join(td, "m.json")
        bst.save_model(fp)
        b2 = xgb.Booster(model_file=fp)
        np.testing.assert_allclose(b2.predict(xgb.DMatrix(X)), p,
                                   rtol=1e-5, atol=1e-6)


def test_feature_names_from_any_cache_and_fmap(tmp_path):
    """Names must resolve from ANY cached matrix (not just the first
    registered) and an fmap file must actually be honored (ADVICE r3)."""
    rng = np.random.RandomState(0)
    X = rng.randn(300, 3).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    d_unnamed = xgb.DMatrix(X, label=y)  # registered FIRST, no names
    d_named = xgb.DMatrix(X, label=y, feature_names=["aa", "bb", "cc"])
    bst = xgb.Booster({"objective": "binary:logistic", "max_depth": 2},
                      [d_unnamed, d_named])
    for i in range(3):
        bst.update(d_named, i)
    assert set(bst.get_score()) <= {"aa", "bb", "cc"}
    mj = bst.save_json()
    assert mj["learner"]["feature_names"] == ["aa", "bb", "cc"]
    # fmap file overrides
    fmap = tmp_path / "feat.map"
    fmap.write_text("0 alpha q\n1 beta q\n2 gamma q\n")
    assert set(bst.get_score(fmap=str(fmap))) <= {"alpha", "beta", "gamma"}
    h = bst.get_split_value_histogram("beta", fmap=str(fmap),
                                      as_pandas=False)
    assert h.shape[1] == 2


@pytest.mark.slow  # ~12s of tier-1 budget (1-core box); the main
# scan-vs-per-round parity pin above stays in tier-1
def test_update_many_scan_with_num_parallel_tree():
    """The whole-chunk scan now handles num_parallel_tree > 1 (boosted
    random forests): predictions must match per-round updates exactly and
    slicing semantics must see num_parallel_tree trees per round."""
    X, y = _data(1500, 5, seed=12)
    params = {"objective": "binary:logistic", "max_depth": 3,
              "num_parallel_tree": 3, "subsample": 0.6, "seed": 9}
    d1 = xgb.DMatrix(X, label=y)
    b1 = xgb.Booster(params, [d1])
    for i in range(4):
        b1.update(d1, i)
    d2 = xgb.DMatrix(X, label=y)
    b2 = xgb.Booster(params, [d2])
    b2.update_many(d2, 0, 4, chunk=2)
    assert b2._gbm.model.num_trees == 12
    assert b2._gbm.model.tree_info == b1._gbm.model.tree_info
    np.testing.assert_allclose(b1.predict(d1), b2.predict(d2),
                               rtol=1e-5, atol=1e-6)


def test_booster_feature_properties_and_config_io():
    """Booster.feature_names/feature_types properties and
    save_config/load_config (reference core.py properties +
    XGBoosterSaveJsonConfig)."""
    X, y = _data(300, 3)
    d = xgb.DMatrix(X, label=y, feature_names=["a", "b", "c"])
    bst = xgb.train({"objective": "binary:logistic", "max_depth": 2}, d, 2,
                    verbose_eval=False)
    assert bst.feature_names == ["a", "b", "c"]
    bst.feature_names = ["x", "y", "z"]
    assert bst.feature_names == ["x", "y", "z"]
    assert set(bst.get_score()) <= {"x", "y", "z"}
    cfg = bst.save_config()
    j = json.loads(cfg)
    assert j["learner"]["objective"]["name"] == "binary:logistic"
    assert j["learner"]["gradient_booster"]["name"] == "gbtree"
    b2 = xgb.Booster()
    b2.load_config(cfg)
    assert b2.lparam.objective == "binary:logistic"


def test_sklearn_linear_coef_intercept_evals_result():
    """coef_/intercept_ for gblinear (reference sklearn.py properties),
    AttributeError for tree boosters, evals_result() accessor."""
    rng = np.random.RandomState(0)
    X = rng.randn(500, 3).astype(np.float32)
    y = (1.5 * X[:, 0] - 2.0 * X[:, 1] + 0.5).astype(np.float32)
    from xgboost_tpu.sklearn import XGBClassifier, XGBRegressor

    m = XGBRegressor(booster="gblinear", n_estimators=40, learning_rate=0.5,
                     reg_lambda=0.0, base_score=0.5)
    m.fit(X, y)
    np.testing.assert_allclose(m.coef_, [1.5, -2.0, 0.0], atol=0.1)
    # base_score absorbs the constant: the bias weight itself is ~0
    assert abs(float(m.intercept_[0]) + 0.5 - 0.5) < 0.1
    assert m.get_num_boosting_rounds() == 40

    c = XGBClassifier(n_estimators=3, max_depth=2)
    yb = (y > 0).astype(np.float32)
    c.fit(X, yb, eval_set=[(X, yb)], verbose=False)
    assert "validation_0" in c.evals_result()
    with pytest.raises(AttributeError):
        c.coef_


def test_dmatrix_surface_completions(tmp_path):
    """set_info / get_uint_info / get_group / get_data / save_binary
    round-trip (reference core.py DMatrix surface)."""
    import scipy.sparse as sp

    X, y = _data(120, 4)
    d = xgb.DMatrix(X)
    d.set_info(label=y, weight=np.ones(120, np.float32), group=[60, 60],
               feature_names=["a", "b", "c", "dd"])
    assert d.get_label().shape == (120,)
    np.testing.assert_array_equal(d.get_group(), [60, 60])
    assert d.get_uint_info("group_ptr").tolist() == [0, 60, 120]
    csr = d.get_data()
    assert sp.issparse(csr) and csr.shape == (120, 4)
    np.testing.assert_allclose(csr.toarray(), np.nan_to_num(X), atol=1e-6)
    fp = str(tmp_path / "m.buffer.npz")
    d.save_binary(fp)
    d2 = xgb.DMatrix(fp)
    assert d2.num_row() == 120 and d2.feature_names == ["a", "b", "c", "dd"]
    np.testing.assert_allclose(d2.get_label(), y)


def test_save_binary_exact_fname_and_full_metadata(tmp_path):
    """The reference-canonical save_binary('train.buffer') must write
    exactly that file (np.savez on a path appends '.npz' — ADVICE r4) and
    persist weight/group/base_margin/feature_types, not just data+label."""
    import os

    X, y = _data(90, 3)
    w = np.linspace(0.5, 1.5, 90).astype(np.float32)
    bm = (y * 0.1).astype(np.float32)
    d = xgb.DMatrix(X, label=y, weight=w, base_margin=bm,
                    feature_names=["f0", "f1", "f2"],
                    feature_types=["q", "q", "q"], group=[45, 45])
    fp = str(tmp_path / "train.buffer")
    d.save_binary(fp)
    assert os.path.exists(fp), "save_binary must write exactly fname"
    assert not os.path.exists(fp + ".npz")
    d2 = xgb.DMatrix(fp)
    np.testing.assert_allclose(d2.get_label(), y)
    np.testing.assert_allclose(d2.get_weight(), w)
    np.testing.assert_allclose(d2.get_base_margin(), bm)
    np.testing.assert_array_equal(d2.get_group(), [45, 45])
    assert d2.feature_names == ["f0", "f1", "f2"]
    assert d2.feature_types == ["q", "q", "q"]
    # training on the reloaded matrix sees identical data
    b1 = xgb.train({"max_depth": 3, "seed": 0}, d, num_boost_round=3)
    b2 = xgb.train({"max_depth": 3, "seed": 0}, d2, num_boost_round=3)
    np.testing.assert_allclose(b1.predict(d), b2.predict(d2), rtol=1e-6)
    # pathlib input takes the same full-metadata path as str
    d3 = xgb.DMatrix(tmp_path / "train.buffer")
    np.testing.assert_allclose(d3.get_weight(), w)
    # unlabeled matrix round-trips to an unlabeled matrix (no empty-array
    # label sneaking in)
    d4 = xgb.DMatrix(X)
    fp2 = str(tmp_path / "nolabel.buffer")
    d4.save_binary(fp2)
    d5 = xgb.DMatrix(fp2)
    assert d5.info.label is None and d5.num_row() == 90
