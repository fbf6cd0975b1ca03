"""The plain numpy references agree with the package at a tiny size (CPU)."""

import numpy as np
import pytest

from bench_paths import load

grower = load("reference/grower.py")
walk = load("reference/walk.py")
quality = load("reference/quality.py")
serve = load("traffic/serve_open_loop.py")

CASES = {
    "binary": ({}, {"objective": "binary:logistic", "max_depth": 4}),
    "multiclass": ({"num_class": 3},
                   {"objective": "multi:softprob", "num_class": 3,
                    "max_depth": 3}),
}
generate = load("generators/linear_logit.py").generate


@pytest.fixture(scope="module", params=sorted(CASES))
def trained(request):
    import xgboost_tpu as xgb

    gen_kw, params = CASES[request.param]
    X, y = generate(rows=1536, cols=7, seed=5, **gen_kw)
    params = dict(params, tree_method="tpu_hist", eta=0.3, max_bin=32,
                  seed=5)
    d = xgb.DMatrix(X, label=y)
    bst = xgb.Booster(params, [d])
    bst.update_many(d, 0, 3, chunk=3)
    forest = walk.Forest.from_bytes(bytes(bst.save_raw("json")))
    return xgb, X, y, params, d, bst, forest


def test_numpy_walk_equals_the_package_margin(trained):
    xgb, X, y, params, d, bst, forest = trained
    want = np.asarray(bst.predict(xgb.DMatrix(X), output_margin=True))
    got = forest.margin(X)
    np.testing.assert_allclose(got.reshape(want.shape), want, atol=1e-5)
    two = np.asarray(bst.predict(xgb.DMatrix(X), output_margin=True,
                                 iteration_range=(0, 2)))
    got2 = forest.margin(X, trees=2 * forest.num_class)
    np.testing.assert_allclose(got2.reshape(two.shape), two, atol=1e-5)


def test_numpy_grower_finds_the_package_splits(trained):
    xgb, X, y, params, d, bst, forest = trained
    binned = d.get_binned(params["max_bin"])
    cuts = np.asarray(binned.cuts.values)
    assert np.array_equal(np.asarray(binned.bins)[:len(X)],
                          grower.bin_rows(X, cuts))
    margin, rep = grower.replay_forest(
        X, y, cuts, forest, objective=params["objective"], eta=0.3, rounds=3,
        max_depth=params["max_depth"])
    assert rep["nodes"] > 0 and not rep["mismatch"], rep["mismatch"][:3]
    assert not rep["ungrown"], rep["ungrown"][:3]
    assert not rep["leaf_tol_exceeded"], rep["leaf_tol_exceeded"][:3]
    assert rep["same"] + rep["tie"] == rep["nodes"]
    want = np.asarray(bst.predict(xgb.DMatrix(X), output_margin=True))
    np.testing.assert_allclose(margin.reshape(want.shape), want, atol=1e-4)


def test_grower_reports_a_wrong_split_and_a_wrong_leaf(trained):
    xgb, X, y, params, d, bst, forest = trained
    cuts = np.asarray(d.get_binned(params["max_bin"]).cuts.values)
    tree = {k: v.copy() for k, v in forest.trees[0].items()}
    g, h = grower.gradients(params["objective"], np.full(
        (len(X), forest.num_class), forest.base_margin()), y,
        forest.num_class)
    bins = grower.bin_rows(X, cuts)
    # move the root to another feature's median cut: no longer the best
    f = (int(tree["split_indices"][0]) + 1) % X.shape[1]
    tree["split_indices"][0] = f
    tree["split_conditions"][0] = cuts[f][cuts.shape[1] // 2]
    depth = params["max_depth"]
    _, rep = grower.replay_tree(bins, cuts, g[:, 0], h[:, 0], tree, eta=0.3,
                                max_depth=depth)
    assert rep["mismatch"]
    tree = {k: v.copy() for k, v in forest.trees[0].items()}
    leaf = int(np.flatnonzero(tree["left_children"] < 0)[0])
    tree["split_conditions"][leaf] += 0.01
    _, rep = grower.replay_tree(bins, cuts, g[:, 0], h[:, 0], tree, eta=0.3,
                                max_depth=depth)
    assert rep["leaf_tol_exceeded"]


def _grown(max_depth, **extra):
    """Three rounds of the package on one seeded table, and their replay's
    inputs."""
    import xgboost_tpu as xgb

    X, y = generate(rows=2048, cols=6, seed=9)
    params = dict({"objective": "binary:logistic", "tree_method": "tpu_hist",
                   "eta": 0.3, "max_bin": 32, "seed": 9,
                   "max_depth": max_depth}, **extra)
    d = xgb.DMatrix(X, label=y)
    bst = xgb.Booster(params, [d])
    bst.update_many(d, 0, 3, chunk=3)
    forest = walk.Forest.from_bytes(bytes(bst.save_raw("json")))
    cuts = np.asarray(d.get_binned(32).cuts.values)
    return X, y, cuts, forest


@pytest.mark.parametrize("grown,configured", [(3, 5), (1, 5), (0, 2)])
def test_grower_reports_a_forest_cut_short(grown, configured):
    """A forest grown to a lesser depth than the configuration's (or one of
    stumps, or of bare leaves) replays without one wrong split, and is
    still reported: its leaves sit where the reference would split."""
    X, y, cuts, forest = _grown(grown)
    _, rep = grower.replay_forest(X, y, cuts, forest,
                                  objective="binary:logistic", eta=0.3,
                                  rounds=3, max_depth=configured)
    assert not rep["mismatch"] and not rep["leaf_tol_exceeded"]
    assert rep["leaves_checked"] >= 3 * 2 ** grown and rep["ungrown"]
    _, rep = grower.replay_forest(X, y, cuts, forest,
                                  objective="binary:logistic", eta=0.3,
                                  rounds=3, max_depth=grown)
    assert not rep["ungrown"] and rep["leaves_checked"] == 0


def test_grower_reports_a_forest_grown_too_deep():
    X, y, cuts, forest = _grown(4)
    _, rep = grower.replay_forest(X, y, cuts, forest,
                                  objective="binary:logistic", eta=0.3,
                                  rounds=3, max_depth=3)
    assert any("max_depth 3" in m[-1] for m in rep["mismatch"])


def test_grower_passes_leaves_the_parameters_stop():
    """Leaves above max_depth that min_child_weight or gamma forbids to
    split are checked and pass."""
    X, y, cuts, forest = _grown(6, min_child_weight=24.0)
    _, rep = grower.replay_forest(X, y, cuts, forest,
                                  objective="binary:logistic", eta=0.3,
                                  rounds=3, max_depth=6,
                                  min_child_weight=24.0)
    assert rep["leaves_checked"] > 10
    assert not rep["ungrown"] and not rep["mismatch"], rep["ungrown"][:3]
    # the same forest held to a looser parameter is cut short
    _, rep = grower.replay_forest(X, y, cuts, forest,
                                  objective="binary:logistic", eta=0.3,
                                  rounds=3, max_depth=6, min_child_weight=1.0)
    assert rep["ungrown"]
    X, y, cuts, forest = _grown(5, gamma=4.0)
    _, rep = grower.replay_forest(X, y, cuts, forest,
                                  objective="binary:logistic", eta=0.3,
                                  rounds=3, max_depth=5, gamma=4.0)
    assert rep["leaves_checked"] > 0 and not rep["ungrown"], rep["ungrown"][:3]


def _lightest_split(bins, cuts, h, tree):
    """(node, float64 hessian sum of its lighter child) of the split of
    ``tree`` whose lighter child is the lightest."""
    found, stack = (None, np.inf), [(0, np.arange(len(bins)))]
    while stack:
        node, rows = stack.pop()
        if int(tree["left_children"][node]) < 0:
            continue
        f = int(tree["split_indices"][node])
        b = int(np.flatnonzero(
            cuts[f] == np.float32(tree["split_conditions"][node]))[0])
        left = bins[rows, f] <= b
        light = min(h[rows[left]].sum(), h[rows[~left]].sum())
        if light < found[1]:
            found = (node, light)
        stack.append((int(tree["left_children"][node]), rows[left]))
        stack.append((int(tree["right_children"][node]), rows[~left]))
    return found


@pytest.mark.parametrize("short,passes", [
    (0.0, True),        # on the threshold: the reference's own rule
    (1.5e-6, True),     # the chip's reading (PERF.md section 6, PR 30)
    (0.5 * 1e-3, True),
    (2.0 * 1e-3, False),
    (0.2, False)])      # a whole row short: what a dropped threshold reads
def test_grower_holds_min_child_weight_within_its_slack(short, passes):
    """A child of a system split may fall short of ``min_child_weight`` by
    ``MCW_RTOL`` of it and no more: the replay of one tree under a threshold
    set that far above its lightest child."""
    assert grower.MCW_RTOL == 1e-3
    X, y, cuts, forest = _grown(4)
    tree = forest.trees[0]
    bins = grower.bin_rows(X, cuts)
    g, h = grower.gradients("binary:logistic", np.full(
        (len(X), 1), forest.base_margin()), y, 1)
    node, light = _lightest_split(bins, cuts, h[:, 0], tree)
    mcw = light / (1.0 - short)
    _, rep = grower.replay_tree(bins, cuts, g[:, 0], h[:, 0], tree, eta=0.3,
                                max_depth=4, min_child_weight=mcw)
    assert rep["mcw_short"] == pytest.approx(short, rel=1e-6, abs=1e-12)
    if passes:
        assert not rep["mismatch"], rep["mismatch"][:3]
        assert rep["same"] + rep["tie"] == rep["nodes"]
        # only a shortfall makes a tie that the slack alone allows
        assert rep["mcw_decided"] == (1 if short > 0 else 0)
    else:
        assert [m[0] for m in rep["mismatch"]] == [node]
        assert "under min_child_weight" in rep["mismatch"][0][1]


def test_grower_does_not_ask_for_a_split_a_rounding_clear_of_the_threshold():
    """The other side of the threshold: where the reference's best split
    clears ``min_child_weight`` by less than the slack, a system that took
    the next best (it read the child a rounding short) is a tie, and one
    that took a worse split than that is still a mismatch."""
    X, y, cuts, forest = _grown(1)
    tree = {k: v.copy() for k, v in forest.trees[0].items()}
    bins = grower.bin_rows(X, cuts)
    g, h = grower.gradients("binary:logistic", np.full(
        (len(X), 1), forest.base_margin()), y, 1)
    g, h = g[:, 0], h[:, 0]
    rows = np.arange(len(X))
    gain, G, H, GL, HL = grower._split_gains(bins, rows, g, h,
                                             cuts.shape[1], 1.0)
    f0 = int(tree["split_indices"][0])
    b0 = int(np.flatnonzero(
        cuts[f0] == np.float32(tree["split_conditions"][0]))[0])
    light = min(HL[f0, b0], H - HL[f0, b0])
    # the threshold a rounding under the best split's lighter child: the
    # reference takes that split, a system a rounding off may not see it
    mcw = light * (1.0 - 1e-6)
    lighter = np.minimum(HL, H - HL)
    second = np.where(lighter < mcw * (1 + grower.MCW_RTOL), -np.inf, gain)
    f2, b2 = np.unravel_index(int(second.argmax()), second.shape)
    assert (f2, b2) != (f0, b0) and second[f2, b2] < gain[f0, b0] * 0.999
    for f, b, verdict in ((f0, b0, "same"), (f2, b2, "tie")):
        t = {k: v.copy() for k, v in tree.items()}
        t["split_indices"][0], t["split_conditions"][0] = f, cuts[f][b]
        _, rep = grower.replay_tree(bins, cuts, g, h, t, eta=0.3,
                                    max_depth=1, min_child_weight=mcw)
        assert not [m for m in rep["mismatch"] if m[0] == 0], rep["mismatch"]
        assert rep[verdict] >= 1
    third = second.copy()
    third[second > second[f2, b2] * 0.99] = -np.inf
    f3, b3 = np.unravel_index(int(third.argmax()), third.shape)
    t = {k: v.copy() for k, v in tree.items()}
    t["split_indices"][0], t["split_conditions"][0] = f3, cuts[f3][b3]
    _, rep = grower.replay_tree(bins, cuts, g, h, t, eta=0.3, max_depth=1,
                                min_child_weight=mcw)
    assert [m for m in rep["mismatch"] if m[0] == 0]


def test_seed_draws_the_rows_and_law_seed_the_task():
    Xa, ya = generate(rows=4000, cols=5, seed=1)
    Xb, yb = generate(rows=4000, cols=5, seed=2)
    assert not np.array_equal(Xa, Xb)
    # the same labelling function in both: one linear score separates both
    w = np.random.default_rng(0).standard_normal((5, 1), dtype=np.float32)
    for X, y in ((Xa, ya), (Xb, yb)):
        assert quality.auc(X @ w[:, 0], y) > 0.8
    _, yc = generate(rows=4000, cols=5, seed=1, law_seed=3)
    assert (ya != yc).mean() > 0.2
    _, ym = generate(rows=4000, cols=5, seed=1, num_class=4)
    assert set(np.unique(ym)) == {0.0, 1.0, 2.0, 3.0}


def test_quality_metrics_agree_with_the_package():
    from xgboost_tpu.metric import create_metric

    rng = np.random.default_rng(0)
    y = (rng.random(500) < 0.4).astype(np.float32)
    score = np.round(rng.normal(size=500) + y, 1)  # ties on purpose
    assert quality.auc(score, y) == pytest.approx(
        float(create_metric("auc").evaluate(score.astype(np.float32), y)),
        abs=1e-6)
    m = rng.normal(size=(200, 3))
    yy = rng.integers(0, 3, 200).astype(np.float32)
    p = np.exp(m) / np.exp(m).sum(1, keepdims=True)
    assert quality.mlogloss_from_margin(m, yy) == pytest.approx(float(
        create_metric("mlogloss").evaluate(p.astype(np.float32), yy)),
        abs=1e-5)
    mm = rng.normal(size=300)
    y3 = (rng.random(300) < 0.5).astype(np.float32)
    assert quality.logloss_from_margin(mm, y3) == pytest.approx(float(
        create_metric("logloss").evaluate(
            (1 / (1 + np.exp(-mm))).astype(np.float32), y3)), abs=1e-5)


def test_schedule_is_a_fixed_amount_of_work_drawn_from_the_seed():
    mix = {"rate_rps": 40, "rows_median": 32, "rows_sigma": 1.5,
           "rows_min": 1, "rows_max": 4096, "schedule_seed": 11}
    a = serve.schedule(mix, 30.0, seed=1, pool_rows=250_000)
    b = serve.schedule(mix, 30.0, seed=1, pool_rows=250_000)
    c = serve.schedule(mix, 30.0, seed=2, pool_rows=250_000)
    d = serve.schedule(dict(mix, schedule_seed=12), 30.0, seed=1,
                       pool_rows=250_000)
    for x, y in zip(a, b):
        assert np.array_equal(x, y)
    due, rows, offset = a
    assert len(due) == 1200 and (np.diff(due) >= 0).all() and due[-1] < 30
    # --seed moves the rows a request carries, never when it comes or its size
    assert np.array_equal(due, c[0]) and np.array_equal(rows, c[1])
    assert not np.array_equal(offset, c[2])
    # another schedule seed: the same requests in another order at other times
    assert not np.array_equal(rows, d[1])
    assert np.array_equal(np.sort(rows), np.sort(d[1]))
    assert rows.min() >= 1 and rows.max() == 4096
    assert np.median(rows) == pytest.approx(32, abs=1)
    assert 90 < rows.mean() < 110          # ISSUE 22's "mean about 98"
    assert 0.005 < (rows > 1024).mean() < 0.02   # "one in a hundred"
    assert (offset + rows <= 250_000).all()
