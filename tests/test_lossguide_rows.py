"""The leaf-wise chip path against the plain reference.

``grow_tree_lossguide_rows`` (rows grouped by leaf, the smaller child's
histogram in the Mosaic node kernel, its body interpreted here) must grow
the tree ``grow_tree_lossguide`` grows: the same nodes in the same order
(one pop a step, equal gains to the lower node id), every row in the same
leaf. With gradients that two bf16 terms carry exactly, the sums agree to
float32 rounding and the margins to 1e-5; with raw float32 gradients the
leaf values stay inside the hi/lo error class.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import xgboost_tpu as xgb
from xgboost_tpu.tree import hist_kernel as hk
from xgboost_tpu.tree import node_hist as nh
from xgboost_tpu.tree.grow import GrowParams
from xgboost_tpu.tree.grow_lossguide import (
    chip_bins,
    finalize_alloc,
    grow_tree_lossguide,
    grow_tree_lossguide_rows,
    node_work,
)
from xgboost_tpu.tree.param import SplitParams

U = 2.0 ** -15  # two bf16 terms: the class of a sum's error, of sum |g|


@pytest.fixture
def mosaic_route(monkeypatch):
    """The route the chip takes: the Pallas kernels, their bodies
    interpreted."""
    monkeypatch.setattr(hk, "use_pallas", lambda: True)
    monkeypatch.setattr(hk, "_INTERPRET", True)


def _exact(x):
    """``x`` rounded to 12 fractional bits: two bf16 terms carry it, and
    float32 sums of a few thousand stay exact."""
    return (np.round(np.asarray(x, np.float64) * 4096) / 4096).astype(
        np.float32)


def _data(case, n=3000, F=6, B=32, seed=0):
    """(bins uint8 [n, F] with missing == B, g, h, cuts, params) of a case."""
    rng = np.random.RandomState(seed)
    bins = rng.randint(0, B, size=(n, F)).astype(np.uint8)
    g = rng.randn(n)
    h = rng.rand(n) + 0.1
    kw = dict(max_leaves=16, max_depth=0, mcw=1.0, gamma=0.0, lam=1.0)
    if case == "chain":
        # the gradient doubles with every bin of feature 0: the best split
        # peels the top bin off, again and again
        g = -(2.0 ** (bins[:, 0].astype(np.float64) - B + 3)) \
            + 0.001 * rng.randn(n)
        h = np.ones(n)
    elif case == "one_row":
        # one row far from every other: a leaf of that row alone
        g[17] = 400.0
        bins[17, 2] = B - 1
        bins[bins[:, 2] == B - 1, 2] = B - 2
        bins[17, 2] = B - 1
        kw.update(mcw=0.0, lam=0.01, max_leaves=8)
    elif case == "max_depth":
        kw.update(max_depth=3, max_leaves=32)
    elif case == "mcw_stop":
        kw.update(mcw=150.0, max_leaves=255)
    elif case == "gamma":
        kw.update(gamma=9.0, max_leaves=32)
    elif case in ("missing_left", "missing_right"):
        miss = rng.rand(n, F) < 0.15
        bins[miss] = B
        # missing rows of feature 1 lean with the low bins or the high
        low = bins[:, 1] < B // 2
        side = low if case == "missing_left" else ~low
        g = np.where(miss[:, 1] | side & ~miss[:, 1], -1.0, 1.0) \
            + 0.3 * rng.randn(n)
    elif case.startswith("leaves_"):
        kw.update(max_leaves=int(case.split("_")[1]), mcw=0.1)
    elif case == "monotone":
        kw.update(extra=dict(monotone=(1, -1, 0, 0, 0, 0)), max_leaves=32)
    elif case == "colsample":
        kw.update(extra=dict(colsample_bytree=0.8, colsample_bylevel=0.8,
                             colsample_bynode=0.5), max_leaves=32)
    cuts = np.sort(rng.rand(F, B).astype(np.float32), axis=1)
    return bins, _exact(g), _exact(h), cuts, kw


def _grow(bins, g, h, cuts, kw, rows_path):
    cfg = GrowParams(max_depth=kw["max_depth"], split=SplitParams(
        min_child_weight=kw["mcw"], reg_lambda=kw["lam"],
        min_split_loss=kw["gamma"]), **kw.get("extra", {}))
    key = jax.random.PRNGKey(3)
    args = (jnp.asarray(g), jnp.asarray(h), jnp.asarray(cuts), key, cfg,
            kw["max_leaves"])
    if rows_path:
        binsT, packed = chip_bins(jnp.asarray(bins), cuts.shape[1])
        tree, rows = grow_tree_lossguide_rows(binsT, packed, *args,
                                              n=len(g))
        work = node_work(tree.left, tree.right, rows)
    else:
        tree, work = grow_tree_lossguide(jnp.asarray(bins), *args), None
    keep, lv, delta = finalize_alloc(tree, jnp.float32(0.3),
                                     jnp.float32(kw["gamma"]))
    return tree, work, np.asarray(keep), np.asarray(lv), np.asarray(delta)


def _same_tree(a, b):
    m = int(a.n_nodes)
    assert int(b.n_nodes) == m
    for k in ("left", "right", "feature", "split_bin", "default_left",
              "depth"):
        np.testing.assert_array_equal(np.asarray(getattr(a, k))[:m],
                                      np.asarray(getattr(b, k))[:m], k)
    np.testing.assert_array_equal(np.asarray(a.positions),
                                  np.asarray(b.positions))


CASES = ["chain", "one_row", "max_depth", "mcw_stop", "gamma",
         "missing_left", "missing_right", "leaves_2", "leaves_16",
         "leaves_255", "monotone", "colsample"]


@pytest.mark.parametrize("case", CASES)
def test_chip_path_grows_the_references_tree(mosaic_route, case):
    bins, g, h, cuts, kw = _data(case)
    ref, _, keep_r, lv_r, delta_r = _grow(bins, g, h, cuts, kw, False)
    got, work, keep_c, lv_c, delta_c = _grow(bins, g, h, cuts, kw, True)
    _same_tree(ref, got)
    np.testing.assert_array_equal(keep_r, keep_c)
    np.testing.assert_allclose(delta_c, delta_r, atol=1e-5)
    splits = int(np.sum(np.asarray(ref.left) >= 0))
    leaves = splits + 1
    if case.startswith("leaves_"):
        assert leaves == kw["max_leaves"], leaves
    if case == "mcw_stop":
        assert 1 < leaves < kw["max_leaves"]
    if case == "max_depth":
        assert int(np.asarray(ref.depth)[:int(ref.n_nodes)].max()) <= 3
        assert leaves < kw["max_leaves"]
    if case == "gamma":
        assert keep_r.sum() < splits  # some splits pruned on device
    if case == "one_row":
        assert 1 in np.bincount(np.asarray(ref.positions))
    if case.startswith("missing_"):
        dl = np.asarray(ref.default_left)[np.asarray(ref.left) >= 0]
        assert dl.any() and not dl.all()
    # the rows the partitions streamed, the split nodes' own, and the rows
    # the node histograms built, the smaller child's a split
    pos = np.asarray(ref.positions)
    left = np.asarray(ref.left)
    right = np.asarray(ref.right)
    size = np.zeros(len(left), np.int64)
    for node in range(int(ref.n_nodes) - 1, -1, -1):
        size[node] = (np.sum(pos == node) if left[node] < 0
                      else size[left[node]] + size[right[node]])
    split = np.flatnonzero(left >= 0)
    want = sum(min(size[left[k]], size[right[k]]) for k in split)
    assert work == (int(size[split].sum()), want)


def test_chain_is_deep(mosaic_route):
    """The unbalanced case is a chain: one leaf peeled off a step."""
    bins, g, h, cuts, kw = _data("chain")
    ref, *_ = _grow(bins, g, h, cuts, kw, False)
    depth = np.asarray(ref.depth)[:int(ref.n_nodes)]
    assert depth.max() >= kw["max_leaves"] // 2


def test_raw_gradients_stay_in_the_hilo_class(mosaic_route):
    """float32 gradients no two bf16 terms carry: the same tree, and every
    leaf value within the hi/lo error class of the sums it is made of."""
    rng = np.random.RandomState(5)
    n, F, B = 3000, 6, 32
    bins = rng.randint(0, B, size=(n, F)).astype(np.uint8)
    g = rng.randn(n).astype(np.float32)
    h = (rng.rand(n) + 0.1).astype(np.float32)
    cuts = np.sort(rng.rand(F, B).astype(np.float32), axis=1)
    kw = dict(max_leaves=64, max_depth=0, mcw=1.0, gamma=0.0, lam=1.0)
    ref, _, _, lv_r, _ = _grow(bins, g, h, cuts, kw, False)
    got, _, _, lv_c, _ = _grow(bins, g, h, cuts, kw, True)
    _same_tree(ref, got)
    pos = np.asarray(ref.positions)
    for leaf in np.unique(pos):
        rows = pos == leaf
        H = h[rows].sum()
        tol = 0.3 * (U * np.abs(g).sum() / (H + 1.0)
                     + abs(g[rows].sum()) * U * h.sum() / (H + 1.0) ** 2)
        assert abs(lv_c[leaf] - lv_r[leaf]) <= tol + 1e-6, leaf


def test_equal_gains_pop_the_lower_node_id():
    """Two open leaves of exactly equal gain: the one pushed first (the
    lower id) is split first, on every platform."""
    n, F, B = 64, 2, 4
    # rows in two mirrored halves: the root splits them, and each child
    # then has the same best split, of the same gain
    bins = np.zeros((n, F), np.uint8)
    bins[:, 0] = np.repeat([0, 3], n // 2)
    bins[:, 1] = np.tile(np.repeat([0, 3], n // 4), 2)
    g = np.where(bins[:, 1] == 0, -1.0, 1.0).astype(np.float32) \
        + np.where(bins[:, 0] == 0, -4.0, 4.0).astype(np.float32)
    h = np.ones(n, np.float32)
    cuts = np.tile(np.arange(1, B + 1, dtype=np.float32), (F, 1))
    kw = dict(max_leaves=3, max_depth=0, mcw=0.0, gamma=0.0, lam=1.0)
    tree, *_ = _grow(bins, g, h, cuts, kw, False)
    left = np.asarray(tree.left)
    assert left[0] == 1 and left[1] == 3 and left[2] == -1


def _arena(bins, g, h, B, perm=None):
    """A job's arena as the chip path builds it (half 0 in row order, or
    its rows in the order ``perm``), and the padded row count."""
    binsT, packed = chip_bins(jnp.asarray(bins), B)
    n_pad = binsT.shape[1]
    arena = nh.build_arena(packed, jnp.asarray(g), jnp.asarray(h),
                           n_pad + nh.chunk_rows(n_pad))
    if perm is not None:
        a = np.array(arena)
        a[0, :, :len(perm)] = a[0][:, perm]
        arena = jnp.asarray(a)
    return arena, binsT


def test_node_kernel_counts_and_sums(mosaic_route):
    """The node kernel over chunks of an arena range: g and h within the
    hi/lo class, the row count of every (feature, bin) exact, rows past the
    count left out."""
    rng = np.random.RandomState(2)
    n, F, B = 5000, 7, 16
    bins = rng.randint(0, B + 1, size=(n, F)).astype(np.uint8)
    g = rng.randn(n).astype(np.float32)
    h = rng.rand(n).astype(np.float32)
    arena, binsT = _arena(bins, g, h, B, rng.permutation(n))
    ch = 2 * nh.NODE_TR  # a count over one chunk: the loop and its mask
    start, count = 1234, 2777
    hist = np.asarray(nh.node_hist(arena, jnp.int32(0), jnp.int32(start),
                                   jnp.int32(count), F=F, B=B,
                                   Fp=binsT.shape[0], ch=ch))
    ids = np.asarray(arena)[0, -(-F // 3) + 2, start:start + count]  # id
    tol_g, tol_h = U * np.abs(g[ids]).sum(), U * h[ids].sum()
    for f in range(F):
        b = bins[ids, f]
        keep = b < B
        np.testing.assert_allclose(
            hist[f, 0], np.bincount(b[keep], g[ids][keep], B), atol=tol_g)
        np.testing.assert_allclose(
            hist[f, 1], np.bincount(b[keep], h[ids][keep], B), atol=tol_h)
        np.testing.assert_array_equal(hist[f, 2], np.bincount(b[keep],
                                                              minlength=B))


def _partitioned(arena, half, start, count, f, b, dl, B):
    """numpy's stable partition of the range into the other half."""
    a = np.asarray(arena).copy()
    seg = a[half, :, start:start + count]
    field = (seg[f // 3] >> (10 * (f % 3))) & 1023
    go = np.where(field >= B, dl, field <= b)
    a[1 - half, :, start:start + count] = np.concatenate(
        [seg[:, go], seg[:, ~go]], axis=1)
    return a, int(go.sum())


def _junk_arena(rng, n, F, B):
    """An arena of ``n`` rows of random bins, and random words everywhere
    else (the other half's neighbours of a range among them)."""
    bins = rng.randint(0, B + 1, size=(n, F)).astype(np.uint8)
    arena, _ = _arena(bins, rng.randn(n), rng.rand(n), B,
                      rng.permutation(n))
    a = np.array(arena)
    a[1] = rng.randint(-2 ** 31, 2 ** 31 - 1, size=a[1].shape,
                       dtype=np.int64)
    return jnp.asarray(a)


@pytest.mark.parametrize("half", [0, 1])
def test_partition_is_stable_and_in_place(mosaic_route, half):
    """A leaf's range moves into the arena's other half, its left rows then
    its right rows, each in their order, over several of the kernel's tiles
    (and of its count's), from and to a mid-tile lane, either default
    direction and either half; the rows outside the range do not move in
    either half."""
    rng = np.random.RandomState(4)
    n, F, B = 6000, 5, 16
    arena = _junk_arena(rng, n, F, B)
    a = np.array(arena)
    if half == 1:
        a[[0, 1]] = a[[1, 0]]
        arena = jnp.asarray(a)
    # 4,900 rows: 20 move tiles, 3 count tiles, both ends mid-tile
    for start, count, f, b, dl in ((300, 4900, 3, 7, True),
                                   (300, 4900, 3, 7, False),
                                   (2049, 130, 4, 2, True),
                                   (5, 3, 1, 4, False)):
        want, n_left = _partitioned(arena, half, start, count, f, b, dl, B)
        got, got_left = nh.partition_node(
            arena, jnp.int32(half), jnp.int32(start), jnp.int32(count),
            jnp.int32(f), jnp.int32(b), jnp.bool_(dl), B=B)
        assert 0 < n_left < count and int(got_left) == n_left
        np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("rows", [1, nh.PART_TR, 2048])
@pytest.mark.parametrize("side", ["left", "right"])
def test_partition_of_one_side_only(mosaic_route, rows, side):
    """Every row of the range goes one way (a range of one row, of one of
    the kernel's tiles and of one of its count's, each from a mid-tile
    lane): the one run is the range in its order, the other run empty."""
    rng = np.random.RandomState(rows)
    n, F, B = 3000, 4, 16
    arena = _junk_arena(rng, n, F, B)
    start = 77
    # feature 0's bins in the range all <= 8, or all over 8 (missing, bin
    # B, to the right)
    a = np.array(arena)
    lo, hi = (0, 9) if side == "left" else (9, B + 1)
    a[0, 0, start:start + rows] = (a[0, 0, start:start + rows] & ~1023) \
        | rng.randint(lo, hi, size=rows)
    arena = jnp.asarray(a)
    want, n_left = _partitioned(arena, 0, start, rows, 0, 8, False, B)
    assert n_left == (rows if side == "left" else 0)
    got, got_left = nh.partition_node(
        arena, jnp.int32(0), jnp.int32(start), jnp.int32(rows), jnp.int32(0),
        jnp.int32(8), jnp.bool_(False), B=B)
    assert int(got_left) == n_left
    np.testing.assert_array_equal(np.asarray(got), want)


def test_partition_counts_its_rows_exactly_past_float32(mosaic_route):
    """A range of 2**24 + 3 rows, 2**24 + 1 of them going left: a count
    float32 cannot hold. The partition's count of the left rows is int32,
    and exact."""
    n = (1 << 24) + 3
    right = np.array([7, n - 1])  # the two rows that go right
    words = np.zeros((1, 8, n + 4096), np.int32)  # one half, one sublane group
    words[0, 0, right] = 5
    sc = nh._split_scalars(0, 0, n, 0, 0, False)  # feature 0, bin 0
    n_left = nh._node_count_pallas(jnp.asarray(words), sc, B=8)[0]
    assert int(n_left) == n - 2 and float(np.float32(n - 2)) != n - 2


def _counted(name):
    from xgboost_tpu.observability import REGISTRY

    fam = REGISTRY.get(name)
    return sum(c.value for _, c in fam.series()) if fam else 0.0


def test_a_job_takes_the_chip_path_and_counts_its_rows(mosaic_route):
    """Through ``Booster.update_many``: the scan chunk grows on the chip
    path (the route says so), feeds ``node_hist_rows_total`` and
    ``node_partition_rows_total`` once a chunk (every split's smaller
    child's rows, and its own: the training rows walked through the
    forest), and its forest predicts what the reference's does."""
    from xgboost_tpu.dispatch import last_decisions

    rng = np.random.RandomState(0)
    X = rng.randn(3000, 6).astype(np.float32)
    X[rng.rand(*X.shape) < 0.05] = np.nan
    y = (np.nan_to_num(X[:, 0]) + np.nan_to_num(X[:, 1]) ** 2 > 0.7) \
        .astype(np.float32)
    params = {"objective": "binary:logistic", "grow_policy": "lossguide",
              "max_leaves": 31, "max_depth": 0, "eta": 0.3,
              "min_child_weight": 2}
    names = ("node_partition_rows_total", "node_hist_rows_total")
    before = [_counted(k) for k in names]
    d = xgb.DMatrix(X, label=y)
    bst = xgb.Booster(params, [d])
    bst.update_many(d, 0, 4, chunk=2)
    assert last_decisions()["lossguide_step"] == "pallas"
    counted = [_counted(k) - b for k, b in zip(names, before)]
    leaves = bst.predict(d, pred_leaf=True).astype(np.int64)
    want = [0, 0]
    for t, tree in enumerate(bst._gbm.model.trees):
        left, right = tree.left_children, tree.right_children
        size = np.bincount(leaves[:, t], minlength=len(left))
        for k in range(len(left) - 1, -1, -1):  # children after parents
            if left[k] >= 0:
                size[k] = size[left[k]] + size[right[k]]
                want[0] += size[k]
                want[1] += min(size[left[k]], size[right[k]])
    assert counted == want and 0 < want[1] < want[0]
    p_chip = bst.predict(d, output_margin=True)
    hk.use_pallas = lambda: False  # the plain reference, for comparison
    d2 = xgb.DMatrix(X, label=y)
    ref = xgb.Booster(params, [d2])
    ref.update_many(d2, 0, 4, chunk=2)
    assert last_decisions()["lossguide_step"] == "xla"
    # raw logistic gradients: a near-tie may fall the other way under the
    # hi/lo sums, so all but a few rows agree to the hi/lo class
    diff = np.abs(p_chip - ref.predict(d2, output_margin=True))
    assert np.quantile(diff, 0.99) < 2e-4, np.quantile(diff, 0.99)
    assert [t.num_leaves for t in bst._gbm.model.trees] == \
        [t.num_leaves for t in ref._gbm.model.trees]


def test_interaction_constraints_stay_on_the_reference(mosaic_route,
                                                       monkeypatch):
    """A constrained job keeps the plain reference, and a TPU job says so
    once."""
    from xgboost_tpu.dispatch import last_decisions
    from xgboost_tpu.utils import console_logger

    said = []
    monkeypatch.setattr(console_logger, "warning",
                        lambda *a: said.append(" ".join(map(str, a))))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    from xgboost_tpu.gbm.gbtree import _lossguide_on_chip

    cfg = GrowParams(interaction=((0, 1), (2, 3)))
    assert not _lossguide_on_chip(cfg, 4, 32)
    assert last_decisions()["lossguide_step"] == "xla"
    assert _lossguide_on_chip(GrowParams(), 4, 32)
    assert len([s for s in said if "lossguide" in s]) == 1
