"""A round's class trees in one pass over the rows (ISSUE 34).

Where a scanned round grows more than one tree (``num_class`` x
``num_parallel_tree``) and the Mosaic level kernels run, the trees are grown
level by level together and a level kernel call carries several trees'
gradient channels over one read of the rows. Everything here runs on the
CPU with the kernel bodies interpreted, at tiny sizes:

(a) a level call of T trees against T calls of one tree, bit for bit;
(b) a forest through ``Booster.update_many``, one pass against the class
    loop, bit for bit;
(c) a job that grows one tree a round never reaches the new entry;
(d) the trees a level call carries at Cover Type's shape, and that shape's
    hoist plan."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import xgboost_tpu as xgb
from xgboost_tpu.gbm import gbtree
from xgboost_tpu.observability import REGISTRY
from xgboost_tpu.parallel import make_mesh, mesh_context
from xgboost_tpu.tree import grow_fused
from xgboost_tpu.tree import hist_kernel as hk

N, F, B = 512, 6, 16
TR = 256  # two grid steps: the accumulator carries over


@pytest.fixture
def mosaic_route(monkeypatch):
    """The route the chip takes: the Pallas kernels, their bodies
    interpreted."""
    monkeypatch.setattr(hk, "use_pallas", lambda: True)
    monkeypatch.setattr(hk, "_INTERPRET", True)
    monkeypatch.setenv("XGBTPU_HOIST_BUDGET_MB", "64")


# ---------------------------------------------------------------------------
# (a) the kernel
# ---------------------------------------------------------------------------


def _level_inputs(T, d, seed=0, N=N):
    """Bins with missing values, T trees' positions at level ``d - 1`` (some
    rows stayed above it), gradients and decision tables whose first column
    marks no split, the left or the right child."""
    rng = np.random.RandomState(seed + 7 * d + 31 * T)
    bins = rng.randint(0, B + 1, (N, F)).astype(np.int32)
    Kp = (1 << d) >> 1
    if d == 0:
        pos = np.zeros((T, N), np.int32)
        ptab = np.zeros((T, 1, 4), np.float32)
    else:
        prev = (1 << (d - 1)) - 1
        pos = rng.randint(max(prev - 1, 0), prev + Kp, (T, N)).astype(np.int32)
        ptab = np.stack([np.stack([
            rng.randint(0, 3, Kp), rng.randint(0, F, Kp),
            rng.randint(0, B, Kp), rng.randint(0, 2, Kp)], 1)
            for _ in range(T)]).astype(np.float32)
    gh = rng.randn(2 * T, N).astype(np.float32)
    gh[1::2] = np.abs(gh[1::2])
    return (jnp.asarray(bins), jnp.asarray(pos), jnp.asarray(gh),
            jnp.asarray(ptab))


LEVELS = [(0, False)] + [(d, sub) for d in (1, 2, 3) for sub in (False, True)]


@pytest.mark.parametrize("hoist", ["full", "partial", "construct"])
@pytest.mark.parametrize("d,sub", LEVELS)
@pytest.mark.parametrize("T", [2, 3, 8])
def test_level_call_of_T_trees_equals_T_calls(mosaic_route, T, d, sub, hoist):
    bins, pos, gh, ptab = _level_inputs(T, d)
    K = 1 << d
    kw = dict(F=F, K=K, Kp=K >> 1, B=B, d=d, tr=TR, sub=sub)
    Fh = {"full": F, "partial": 4, "construct": 0}[hoist]
    binsT = hk._feature_major(bins, hk._SUBLANES, B)
    if Fh:
        onehot = hk._build_onehot_xla(bins[:, :Fh].astype(jnp.uint8), B=B)

        def call(*a):
            return hk._hoisted_level_pallas(binsT, onehot, *a, **kw)
    else:
        def call(*a):
            return hk._fused_level_pallas(binsT, *a, **kw)

    pos_T, hist_T = call(pos, gh, ptab)
    ones = [call(pos[t:t + 1], gh[2 * t:2 * t + 2], ptab[t])
            for t in range(T)]
    Kc = K >> 1 if sub else K
    assert pos_T.shape == (T, N) and hist_T.shape == (T, F, 2 * Kc, B)
    np.testing.assert_array_equal(
        np.asarray(pos_T), np.concatenate([np.asarray(p) for p, _ in ones]))
    np.testing.assert_array_equal(
        np.asarray(hist_T), np.stack([np.asarray(h) for _, h in ones]))
    assert float(jnp.abs(hist_T).sum()) > 0.0
    if d:  # the tables route: some row moved
        assert bool((pos_T != pos).any())


def test_dispatcher_carries_what_the_wrapper_returns(mosaic_route):
    """``fused_level_trees`` picks the tile for ``T x Kc`` nodes and is the
    streaming kernel's result; with no resident one-hot, the construct-only
    kernel's."""
    bins, pos, gh, ptab = _level_inputs(4, 2, N=hk.TR)  # a construct tile
    kw = dict(K=4, Kp=2, B=B, d=2)
    onehot = hk._build_onehot_xla(bins.astype(jnp.uint8), B=B)
    for oh in (onehot, None):
        got = hk.fused_level_trees(bins, pos, gh, ptab, onehot=oh,
                                   sibling_sub=True, **kw)
        for t in range(4):
            p, h = hk.fused_level(bins, pos[t:t + 1], gh[2 * t:2 * t + 2],
                                  ptab[t], pallas=True, onehot=oh,
                                  sibling_sub=True, **kw)
            np.testing.assert_array_equal(np.asarray(got[0][t:t + 1]),
                                          np.asarray(p))
            np.testing.assert_array_equal(np.asarray(got[1][t]),
                                          np.asarray(h))


# ---------------------------------------------------------------------------
# (b) the forest
# ---------------------------------------------------------------------------


def _multiclass(n_class, rows=N, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.rand(rows, 7).astype(np.float32)
    X[rng.rand(rows, 7) < 0.05] = np.nan
    y = rng.randint(0, n_class, rows).astype(np.float32)
    return X, y


def _level_trees_counts():
    fam = REGISTRY.get("dispatch_decisions_total")
    out = {}
    if fam is not None:
        for labels, child in fam.series():
            if labels["op"] == "level_trees":
                out[int(labels["impl"])] = out.get(int(labels["impl"]), 0) \
                    + int(child.value)
    return out


def _forest(monkeypatch, params, one_pass, rounds=3):
    """Three rounds in one scan chunk; the class loop through the same
    entry, ``_scan_rounds_impl``, whose choice is patched and nothing
    else."""
    X, y = _multiclass(params.get("num_class", 2))
    if not one_pass:
        monkeypatch.setattr(gbtree, "_round_in_one_pass",
                            lambda cfg, trees: False)
    d = xgb.DMatrix(X, label=y)
    bst = xgb.Booster(params, [d])
    before = _level_trees_counts()
    bst.update_many(d, 0, rounds, chunk=rounds)
    after = _level_trees_counts()
    calls = {T: c - before.get(T, 0) for T, c in after.items()
             if c - before.get(T, 0)}
    margin = np.asarray(bst.predict(d, output_margin=True))
    return bytes(bst.save_raw()), margin, calls, bst


def _params(n_class, npt, depth=3):
    """``n_class`` 1: a binary forest of ``npt`` parallel trees."""
    objective = ({"objective": "multi:softprob", "num_class": n_class}
                 if n_class > 1 else {"objective": "binary:logistic"})
    return dict(objective, num_parallel_tree=npt, max_depth=depth, max_bin=B,
                eta=0.5, subsample=0.7, colsample_bytree=0.6, seed=3)


@pytest.mark.parametrize("n_class,npt", [(3, 1), (3, 2), (8, 1), (8, 2),
                                         (1, 2)])
def test_forest_one_pass_equals_class_loop(mosaic_route, monkeypatch,
                                           n_class, npt):
    params = _params(n_class, npt)
    model, margin, calls, bst = _forest(monkeypatch, params, one_pass=True)
    # at this size every level call carries the round's trees: one traced
    # program, one call a level
    assert calls == {n_class * npt: params["max_depth"]}
    assert len(bst.get_dump()) == 3 * n_class * npt
    loop_model, loop_margin, loop_calls, _ = _forest(monkeypatch, params,
                                                     one_pass=False)
    assert loop_calls == {}
    assert model == loop_model
    np.testing.assert_array_equal(margin, loop_margin)
    assert np.abs(margin).max() > 0.1


@pytest.mark.parametrize("n_class,carried,expect_calls", [
    (8, [8, 8, 8, 4, 2], {8: 3, 4: 2, 2: 4}),
    (3, [3, 3, 3, 3, 1], {3: 4, 1: 3})])
def test_forest_when_the_deep_levels_carry_fewer_trees(
        mosaic_route, monkeypatch, n_class, carried, expect_calls):
    """A VMEM budget under which T falls with the depth: positions
    regrouped between levels, and a call a tree through the one-tree
    dispatcher where not even two fit (three trees: T divides them). Still
    the class loop's forest, bit for bit."""
    Qh, rows = 7 * B, grow_fused.pad_rows(N)
    # the streaming step at tr 128 with the deepest level's 16 nodes: the
    # least under which the plan still hoists every feature
    monkeypatch.setattr(hk, "_VMEM_HOIST_BUDGET",
                        hk._hoist_vmem_bytes(128, Qh, 16, 7, B))
    assert hk.hoist_plan(rows, 7, B, 5) == 7
    assert [hk.level_trees(rows, 7, Kc, B, n_class, Qh)
            for Kc in (1, 1, 2, 4, 8)] == carried
    params = _params(n_class, 1, depth=5)
    model, margin, calls, _ = _forest(monkeypatch, params, one_pass=True,
                                      rounds=2)
    assert calls == expect_calls
    loop_model, loop_margin, _, _ = _forest(monkeypatch, params,
                                            one_pass=False, rounds=2)
    assert model == loop_model
    np.testing.assert_array_equal(margin, loop_margin)


# ---------------------------------------------------------------------------
# (c) the bypass
# ---------------------------------------------------------------------------


def _refuse_the_entry(monkeypatch):
    def refuse(*a, **k):
        raise AssertionError("a one-tree job reached the multi-tree entry")

    monkeypatch.setattr(gbtree, "grow_trees_one_pass", refuse)
    monkeypatch.setattr(grow_fused, "grow_trees_one_pass", refuse)
    monkeypatch.setattr(grow_fused, "fused_level_trees", refuse)
    monkeypatch.setattr(hk, "fused_level_trees", refuse)


BINARY = {"objective": "binary:logistic", "max_depth": 3, "max_bin": B,
          "eta": 0.5}


@pytest.mark.parametrize("path", ["scan", "mesh_scan", "per_round"])
def test_one_tree_job_never_reaches_the_entry(mosaic_route, monkeypatch,
                                              path):
    _refuse_the_entry(monkeypatch)
    X, y = _multiclass(2)
    before = _level_trees_counts()

    def run():
        d = xgb.DMatrix(X, label=y)
        bst = xgb.Booster(BINARY, [d])
        if path == "per_round":
            for i in range(2):
                bst.update(d, i)
        else:
            bst.update_many(d, 0, 2, chunk=2)
        return bst

    if path == "mesh_scan":
        # the interpreter cannot replay a kernel inside shard_map: the mesh
        # takes the XLA level route here, as the rehearsals do
        monkeypatch.setattr(hk, "use_pallas", lambda: False)
        with mesh_context(make_mesh(4)):
            bst = run()
    else:
        bst = run()
    assert len(bst.get_dump()) == 2
    assert _level_trees_counts() == before


def test_the_refusal_is_reached_by_a_multiclass_job(mosaic_route,
                                                    monkeypatch):
    """The control of the test above: three class trees a round do come to
    the entry it patches."""
    _refuse_the_entry(monkeypatch)
    X, y = _multiclass(3)
    d = xgb.DMatrix(X, label=y)
    with pytest.raises(AssertionError, match="multi-tree entry"):
        xgb.Booster(_params(3, 1), [d]).update_many(d, 0, 2, chunk=2)


@pytest.mark.parametrize("trees,pallas,expect", [
    (1, True, False), (8, True, True), (8, False, False), (2, True, True)])
def test_the_choice_reads_the_tree_count_and_the_route(monkeypatch, trees,
                                                       pallas, expect):
    monkeypatch.setattr(hk, "use_pallas", lambda: pallas)
    X, y = _multiclass(2)
    bst = xgb.Booster(BINARY, [xgb.DMatrix(X, label=y)])
    bst._configure()
    cfg = bst._gbm._grow_params()
    assert gbtree._round_in_one_pass(cfg, trees) is expect


# ---------------------------------------------------------------------------
# (d) Cover Type's shape
# ---------------------------------------------------------------------------

COVTYPE = dict(rows=436224, F=54, B=256, Fh=33, depth=6, trees=8)


def test_trees_a_level_call_carries_at_cover_type():
    """T x Kc nodes against the streaming step's VMEM model at the plan's
    unchanged width: 8, 8, 8, 8, 4, 2 (Kc: one node at the root, one child
    of every split below it)."""
    c = COVTYPE
    built = [1] + [1 << (d - 1) for d in range(1, c["depth"])]
    T = [hk.level_trees(c["rows"], c["F"], Kc, c["B"], c["trees"],
                        c["Fh"] * c["B"]) for Kc in built]
    assert T == [8, 8, 8, 8, 4, 2]
    tiles = [hk._hoist_tr(c["Fh"] * c["B"], t * Kc, c["F"], c["B"])
             for t, Kc in zip(T, built)]
    assert tiles == [256, 256, 128, 128, 128, 128]
    # the accumulator never passes 64 rows: 128 bf16 channel rows a matmul
    assert max(2 * t * Kc for t, Kc in zip(T, built)) == 64
    # with no resident one-hot: the construct-only kernel's accumulator gate
    assert [hk.level_trees(c["rows"], c["F"], Kc, c["B"], c["trees"])
            for Kc in built] == [8, 8, 8, 8, 4, 2]
    # trees that T has to divide; rows no tile divides
    assert hk.level_trees(c["rows"], c["F"], 1, c["B"], 7,
                          c["Fh"] * c["B"]) == 7
    assert hk.level_trees(c["rows"], c["F"], 8, c["B"], 6,
                          c["Fh"] * c["B"]) == 3
    assert hk.level_trees(c["rows"] + 8, c["F"], 1, c["B"], 8,
                          c["Fh"] * c["B"]) == 1


def test_cover_type_hoist_plan_is_the_parents(monkeypatch):
    """33 of 54 features resident, as before this change (ledger, PR 32:
    ``hbm_peak_gb`` 4.17): the plan asks the VMEM model with one tree's
    deepest level, not with the trees a call may carry."""
    c = COVTYPE
    monkeypatch.setattr(hk, "use_pallas", lambda: True)
    monkeypatch.setenv("XGBTPU_HOIST_BUDGET_MB", "8192")
    assert hk.hoist_plan(c["rows"], c["F"], c["B"], c["depth"]) == c["Fh"]
    deepest = 1 << (c["depth"] - 1)
    assert hk._hoist_tr(c["Fh"] * c["B"], deepest, c["F"], c["B"]) == 128
    assert hk._hoist_tr((c["Fh"] + 1) * c["B"], deepest, c["F"],
                        c["B"]) == 0
    # the model's own arithmetic, unchanged
    assert hk._hoist_vmem_bytes(128, c["Fh"] * c["B"], deepest, c["F"],
                                c["B"]) == 12413952
