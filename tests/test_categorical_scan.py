"""Categorical splits on the chip's training path: the scan chunk, the
Mosaic level kernels (interpreted here) and the fused split evaluation,
held to the package's plain grower (``tree/grow.py``) and to the
benchmark's numpy categorical grower (``benchmark/reference/cat_grower.py``).

Count-valued gradients (whole numbers, exact in the kernels' two bf16
terms) make the fused tree the plain grower's node for node; a Booster's
own logistic gradients are held to the numpy replay's limits. The replay
must also refuse a forest grown with every category one-vs-rest and one
grown on the codes as numbers."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import xgboost_tpu as xgb
from xgboost_tpu.tree import hist_kernel as hk

_REF = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmark", "reference", "cat_grower.py")


def _cat_grower():
    spec = importlib.util.spec_from_file_location("cat_grower", _REF)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


cat_grower = _cat_grower()

# (cards of the coded columns, numerical columns, share of one coded
# column missing)
CASES = {
    "onehot": ((2, 3, 2, 3), 0, 0.0),
    "partition": ((9, 24, 5, 12), 0, 0.0),
    "mixed": ((2, 9, 3, 18, 5), 1, 0.0),
    "missing": ((3, 12, 2, 7), 1, 0.08),
}
PARAMS = {"objective": "binary:logistic", "max_depth": 4, "eta": 0.3,
          "max_bin": 32, "max_cat_to_onehot": 4, "seed": 3}


def _data(case, n=3072, seed=0):
    cards, n_num, miss = CASES[case]
    rng = np.random.default_rng(seed)
    cols = [rng.integers(0, c, n) for c in cards]
    logit = sum(rng.normal(0, 1.0, c)[x] for c, x in zip(cards, cols))
    nums = [rng.normal(size=n) for _ in range(n_num)]
    logit = logit + sum(0.7 * v for v in nums)
    y = (rng.random(n) < 1 / (1 + np.exp(-logit))).astype(np.float32)
    X = np.column_stack(cols + nums).astype(np.float32)
    if miss:
        X[rng.random(n) < miss, 1] = np.nan
    types = ["c"] * len(cards) + ["q"] * n_num
    return X, y, types


@pytest.fixture()
def mosaic(monkeypatch):
    """The chip's routes: the Mosaic kernels, their bodies interpreted."""
    monkeypatch.setattr(hk, "use_pallas", lambda: True)
    monkeypatch.setattr(hk, "_INTERPRET", True)
    monkeypatch.setenv("XGBTPU_HOIST_BUDGET_MB", "64")


def _counter(name, **labels):
    from xgboost_tpu.observability import REGISTRY

    fam = REGISTRY.get(name)
    if fam is None:
        return 0.0
    return sum(c.value for lab, c in fam.series()
               if all(lab.get(k) == v for k, v in labels.items()))


def _kinds(binned, params):
    return cat_grower.column_kinds(binned.n_features, binned.categorical,
                                   binned.cat_counts,
                                   params["max_cat_to_onehot"])


def _replay(bst, d, X, y, params, rounds):
    binned = d.get_binned(params["max_bin"])
    forest = cat_grower.CatForest.from_bytes(bytes(bst.save_raw("json")))
    ref, rep = cat_grower.replay_forest(
        X, y, np.asarray(binned.cuts.values), forest, _kinds(binned, params),
        objective=params["objective"], eta=params["eta"], rounds=rounds,
        max_depth=params["max_depth"])
    margin = np.asarray(bst.predict(d, output_margin=True))
    return rep, float(np.abs(margin.reshape(ref.shape) - ref).max())


@pytest.mark.parametrize("case", list(CASES))
def test_scan_chunk_on_the_mosaic_kernels_passes_the_numpy_replay(mosaic,
                                                                  case):
    """A categorical Booster's chunk through ``update_many`` (the scan
    program, the level kernels with a ``[Kp, 5 + B]`` table, interpreted):
    every split the numpy grower's best or a tie, no leaf it would have
    split, leaves in the bf16 hi/lo class, the margin to 1e-3; its
    counters say which splits were categorical, and the saved JSON loads
    back to the same margins."""
    X, y, types = _data(case)
    d = xgb.DMatrix(X, label=y, feature_types=types)
    bst = xgb.Booster(PARAMS, [d])
    splits0 = _counter("splits_total")
    kinds0 = {k: _counter("cat_splits_total", kind=k)
              for k in ("onehot", "partition")}
    bst.update_many(d, 0, 3, chunk=3)
    from xgboost_tpu.gbm.gbtree import _ChunkRef

    assert all(isinstance(e, _ChunkRef) for e in bst._gbm.model._entries)
    rep, margin_err = _replay(bst, d, X, y, PARAMS, 3)
    assert rep["nodes"] > 0 and rep["cat_splits"] > 0
    assert not rep["mismatch"] and not rep["ungrown"], rep["mismatch"][:3]
    assert not rep["leaf_tol_exceeded"]
    assert margin_err <= 1e-3
    splits = _counter("splits_total") - splits0
    kinds = {k: _counter("cat_splits_total", kind=k) - v
             for k, v in kinds0.items()}
    assert splits == rep["nodes"]
    assert kinds["onehot"] + kinds["partition"] == rep["cat_splits"]
    if case == "onehot":
        assert kinds["partition"] == 0 and kinds["onehot"] > 0
    if case == "partition":
        assert kinds["onehot"] == 0 and kinds["partition"] > 0
    # the saved model's category sets, read back by the package
    loaded = xgb.Booster(model_file=bytearray(bst.save_raw("json")))
    fresh = xgb.DMatrix(X, feature_types=types)
    np.testing.assert_allclose(
        np.asarray(loaded.predict(fresh, output_margin=True)),
        np.asarray(bst.predict(d, output_margin=True)), rtol=0, atol=1e-5)


@pytest.mark.parametrize("case", ["partition", "missing"])
def test_fused_tree_is_the_plain_growers_on_count_gradients(mosaic, case):
    """The fused grower (interpreted Mosaic level kernels, routing through
    the categorical table) against ``tree/grow.py`` on count-valued
    gradients: the same heap, split for split and set for set."""
    from xgboost_tpu.gbm.gbtree import _cat_cfg
    from xgboost_tpu.tree.grow import GrowParams, grow_tree
    from xgboost_tpu.tree.grow_fused import grow_tree_fused
    from xgboost_tpu.params import TrainParam

    X, y, types = _data(case, n=2048, seed=1)
    d = xgb.DMatrix(X, label=y, feature_types=types)
    binned = d.get_binned(32)
    tp = TrainParam()
    tp.update({"max_cat_to_onehot": 4})
    cfg, _ = _cat_cfg(GrowParams(max_depth=4), binned, tp)
    assert cfg.categorical or cfg.cat_partition
    rng = np.random.default_rng(2)
    n = binned.n_rows
    g = jnp.asarray(rng.integers(-4, 5, n).astype(np.float32)
                    + 3.0 * (y - 0.5))
    h = jnp.asarray(rng.integers(1, 4, n).astype(np.float32))
    cuts = jnp.asarray(binned.cuts.values)
    key = jax.random.PRNGKey(0)
    plain = grow_tree(binned.bins, g, h, cuts, key, cfg)
    binsf, n_pad = binned.fused_bins()
    pad = jnp.zeros((n_pad - n,), jnp.float32)
    fused = grow_tree_fused(binsf, jnp.concatenate([g, pad]),
                            jnp.concatenate([h, pad]), cuts, key, 0.3, 0.0,
                            cfg)
    split = np.asarray(plain.is_split)
    assert split.sum() > 3
    np.testing.assert_array_equal(np.asarray(fused.keep), split)
    for name in ("feature", "split_bin", "default_left"):
        np.testing.assert_array_equal(
            np.where(split, np.asarray(getattr(fused, name)), 0),
            np.where(split, np.asarray(getattr(plain, name)), 0), name)
    np.testing.assert_array_equal(
        np.asarray(fused.cat_set)[split], np.asarray(plain.cat_set)[split])
    np.testing.assert_array_equal(np.asarray(fused.node_g),
                                  np.asarray(plain.node_g))


@pytest.mark.parametrize("control", ["all_onehot", "codes_as_numbers"])
def test_the_replay_refuses_a_forest_that_is_not_the_configurations(
        control):
    """The controls: a forest grown with every category one-vs-rest
    (``max_cat_to_onehot`` 256), and one grown on the codes as numerical
    thresholds, replayed as the configuration's (partition at four
    categories and more) must come out with mismatches."""
    X, y, types = _data("partition", n=4096, seed=4)
    d_cfg = xgb.DMatrix(X, label=y, feature_types=types)
    params = dict(PARAMS)
    if control == "all_onehot":
        grown = dict(params, max_cat_to_onehot=256)
        d_grow = d_cfg
    else:
        grown = params
        d_grow = xgb.DMatrix(X, label=y)
    bst = xgb.Booster(grown, [d_grow])
    bst.update_many(d_grow, 0, 2, chunk=2)
    binned = d_cfg.get_binned(params["max_bin"])
    forest = cat_grower.CatForest.from_bytes(bytes(bst.save_raw("json")))
    cuts = np.asarray(d_grow.get_binned(params["max_bin"]).cuts.values)
    _, rep = cat_grower.replay_forest(
        X, y, cuts, forest, _kinds(binned, params),
        objective=params["objective"], eta=params["eta"], rounds=2,
        max_depth=params["max_depth"])
    assert len(rep["mismatch"]) > rep["nodes"] // 4, rep["mismatch"][:3]


def test_the_scan_gate_admits_categorical_depthwise_on_one_device():
    """A categorical matrix takes the scan chunk depth-wise on one device;
    lossguide keeps the per-round path."""
    import types as _types

    X, y, types = _data("mixed", n=256)
    d = xgb.DMatrix(X, label=y, feature_types=types)
    probe = _types.SimpleNamespace(categorical=(0,), is_paged=False)
    for policy, want in (("depthwise", True), ("lossguide", False)):
        bst = xgb.Booster(dict(PARAMS, grow_policy=policy), [d])
        bst._configure()
        assert bst._gbm.scan_rounds_supported(probe, bst._obj, 1) is want


def test_the_json_holds_xgboost_category_arrays():
    """``save_raw('json')`` of a scanned categorical chunk carries XGBoost
    1.6's ``split_type`` and ``categories_nodes`` / ``_segments`` /
    ``_sizes`` / ``categories``, one set a categorical split."""
    import json

    X, y, types = _data("mixed", n=1024)
    d = xgb.DMatrix(X, label=y, feature_types=types)
    bst = xgb.Booster(PARAMS, [d])
    bst.update_many(d, 0, 2, chunk=2)
    trees = json.loads(bst.save_raw("json"))["learner"]["gradient_booster"][
        "model"]["trees"]
    for t in trees:
        cat_nodes = [i for i, (s, lc) in enumerate(
            zip(t["split_type"], t["left_children"])) if s == 1 and lc >= 0]
        assert t["categories_nodes"] == cat_nodes
        assert len(t["categories_segments"]) == len(cat_nodes)
        assert sum(t["categories_sizes"]) == len(t["categories"])
    assert any(t["categories_nodes"] for t in trees)


def _scan_op_names(d, params):
    """The scope paths of the scan program ``update_many`` calls."""
    import re

    from xgboost_tpu.gbm import gbtree

    orig = gbtree._scan_rounds_impl
    jitted = getattr(orig, "_guarded_jit", orig)
    texts = []

    def capturing(*args, **kwargs):
        if not texts:
            texts.append(jitted.lower(*args, **kwargs).as_text(
                debug_info=True))
        return orig(*args, **kwargs)

    gbtree._scan_rounds_impl = capturing
    try:
        xgb.Booster(params, [d]).update_many(d, 0, 1, chunk=1)
    finally:
        gbtree._scan_rounds_impl = orig
    return set(re.findall(r'"([^"]*xgb\.[^"]*)"', texts[0]))


def test_the_categorical_candidates_carry_their_scope(mosaic):
    """``xgb.cat_split`` sits inside ``xgb.split_eval`` in a categorical
    job's scan program and nowhere in a numerical one's."""
    X, y, types = _data("mixed", n=1024)
    cat = _scan_op_names(xgb.DMatrix(X, label=y, feature_types=types),
                         PARAMS)
    assert any("xgb.split_eval/xgb.cat_split" in p for p in cat)
    assert not any("xgb.cat_split" in p and "xgb.split_eval" not in p
                   for p in cat)
    num = _scan_op_names(xgb.DMatrix(X, label=y), PARAMS)
    assert num and not any("xgb.cat_split" in p for p in num)
