"""The Cover Type deployment against the plain reference at a small size
(ISSUE 32, CPU): eight class trees a round with class 0 empty, 54 columns of
the generator's three kinds, depth 6, ``max_bin`` 256, three rounds through
``Booster.update_many`` on 4,096 rows; ``reference/grower.py`` replays every
tree on the system's cuts with its float64 softmax gradient."""

import numpy as np
import pytest

from bench_paths import load

grower = load("reference/grower.py")
walk = load("reference/walk.py")
train_window = load("traffic/train_window.py")
generate = load("generators/covtype_like.py").generate

ROWS, ROUNDS, CLASSES = 4096, 3, 8


@pytest.fixture(scope="module",
                params=[("multi:softmax", 3200000041),
                        ("multi:softprob", 3200000042)],
                ids=["softmax", "softprob"])
def trained(request):
    import xgboost_tpu as xgb

    objective, seed = request.param
    X, y = generate(rows=ROWS, cols=54, seed=seed, law_seed=0)
    params = {"objective": objective, "num_class": CLASSES,
              "tree_method": "tpu_hist", "max_depth": 6, "eta": 0.3,
              "max_bin": 256, "seed": seed}
    d = xgb.DMatrix(X, label=y)
    bst = xgb.Booster(params, [d])
    bst.update_many(d, 0, ROUNDS, chunk=ROUNDS)
    margin = np.asarray(bst.predict(d, output_margin=True))
    forest = walk.Forest.from_bytes(bytes(bst.save_raw("json")))
    cuts = np.asarray(d.get_binned(256).cuts.values)
    ref_margin, rep = grower.replay_forest(
        X, y, cuts, forest, objective=objective, eta=0.3, rounds=ROUNDS,
        max_depth=6)
    return X, y, margin, forest, ref_margin, rep


def test_label_zero_never_occurs_and_its_tree_is_grown(trained):
    X, y, margin, forest, _, _ = trained
    assert y.min() >= 1 and forest.num_class == CLASSES
    assert len(forest.trees) == ROUNDS * CLASSES
    assert list(forest.tree_group) == list(range(CLASSES)) * ROUNDS
    for t in range(0, ROUNDS * CLASSES, CLASSES):
        # g = p0 > 0 on every row and g / h = 1 / (2 (1 - p0)) hardly moves
        # from row to row, so no split repays the second child's lambda
        # (the reference's best gain at the root is negative): the tree the
        # program grows for class 0 is one leaf, below zero, every round
        tree = forest.trees[t]
        assert list(tree["left_children"]) == [-1]
        assert tree["split_conditions"][0] < -0.05
    # class 0's margin falls on every row
    assert np.all(margin[:, 0] < forest.base_margin())


def test_every_split_is_the_references_best_or_a_tie(trained):
    *_, rep = trained
    assert rep["nodes"] > CLASSES * ROUNDS
    assert not rep["mismatch"], rep["mismatch"][:3]
    assert not rep["ungrown"], rep["ungrown"][:3]
    assert rep["same"] + rep["tie"] == rep["nodes"]
    assert rep["tie"] <= train_window.TIE_SHARE_LIMIT * rep["nodes"]
    assert rep["mcw_short"] <= grower.MCW_RTOL


def test_leaves_are_in_the_bf16_hi_lo_class(trained):
    *_, rep = trained
    assert not rep["leaf_tol_exceeded"], rep["leaf_tol_exceeded"][:3]


def test_margins_agree_on_all_eight_columns(trained):
    X, y, margin, forest, ref_margin, _ = trained
    assert margin.shape == ref_margin.shape == (ROWS, CLASSES)
    assert np.abs(margin - ref_margin).max() <= train_window.MARGIN_LIMIT
    # and the saved model, walked in numpy, is the same forest
    np.testing.assert_allclose(forest.margin(X), margin, atol=1e-5)


def test_the_two_valued_columns_take_one_cut(trained):
    """44 of the 54 columns hold two values: one finite cut each, where a
    quantitative column fills its bins."""
    import xgboost_tpu as xgb

    X, y, *_ = trained
    cuts = np.asarray(xgb.DMatrix(X, label=y).get_binned(256).cuts.values)
    bins = grower.bin_rows(X, cuts)
    assert all(len(np.unique(bins[:, f])) == 2 for f in range(10, 54))
    assert len(np.unique(bins[:, 0])) > 200
