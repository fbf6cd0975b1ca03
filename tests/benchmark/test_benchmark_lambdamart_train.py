"""``lambdamart.train_rounds`` (ISSUE 26's review): the plain reference grown
alone, following no tree of the program's, is what the holdout's band is
confirmed with. On a small seeded task it grows the program's trees."""

import numpy as np
import pytest

from bench_paths import load

gen = load("generators/ltr_queries.py")
lambdamart = load("reference/lambdamart.py")
walk = load("reference/walk.py")

PARAMS = {"objective": "rank:ndcg", "tree_method": "tpu_hist", "max_depth": 3,
          "eta": 0.3, "min_child_weight": 0.1, "max_bin": 32,
          "lambdarank_num_pair_per_sample": 1, "seed": 11}
ROUNDS = 3


def _splits(tree):
    if "value" in tree:
        return []
    return ([(tree["feature"], tree["bin"])] + _splits(tree["left"])
            + _splits(tree["right"]))


@pytest.mark.parametrize("gamma", [0.0, 0.02])
def test_the_reference_alone_grows_the_programs_trees(gamma):
    import xgboost_tpu as xgb

    X, y, qid = gen.generate(rows=40_000, cols=9, seed=5, queries=32)
    gptr = lambdamart.group_ptr_of(qid)
    assert 32 * int(np.diff(gptr).max()) ** 2 > 1 << 25  # pairs are sampled
    d = xgb.DMatrix(X, label=y, qid=qid)
    bst = xgb.Booster(dict(PARAMS, gamma=gamma), [d])
    for t in range(ROUNDS):
        bst.update(d, t)
    forest = walk.Forest.from_bytes(bytes(bst.save_raw("json")))
    cuts = np.asarray(d.get_binned(PARAMS["max_bin"]).cuts.values)
    bins = lambdamart.grower.bin_rows(X, cuts)
    grow = dict(objective=PARAMS["objective"], seed=PARAMS["seed"], n_pair=1,
                B=cuts.shape[1], eta=PARAMS["eta"],
                max_depth=PARAMS["max_depth"], lam=1.0,
                min_child_weight=PARAMS["min_child_weight"])
    trees, margin = lambdamart.train_rounds(bins, y, gptr, rounds=ROUNDS,
                                            gamma=gamma, **grow)
    for mine, theirs in zip(trees, forest.trees):
        inner = np.flatnonzero(np.asarray(theirs["left_children"]) >= 0)
        want = sorted(
            (int(theirs["split_indices"][i]), int(np.flatnonzero(
                cuts[theirs["split_indices"][i]]
                == np.float32(theirs["split_conditions"][i]))[0]))
            for i in inner)
        assert sorted(_splits(mine)) == want
    if gamma:  # and the pruning is live: the first tree loses nodes to it
        unpruned, _ = lambdamart.train_rounds(bins, y, gptr, rounds=1,
                                              gamma=0.0, **grow)
        assert len(_splits(trees[0])) < len(_splits(unpruned[0]))
    got = bst.predict(d, output_margin=True).reshape(-1)
    assert np.abs(got - got[0] - (margin - margin[0])).max() < 1e-4
    out = np.zeros(len(y))
    for tree in trees:
        lambdamart.apply_tree(tree, bins, np.arange(len(y)), out)
    assert np.array_equal(out, margin)
