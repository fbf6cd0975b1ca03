"""The level kernels' accumulator tiled over features (ISSUE 35).

A matrix too wide for the untiled Mosaic level kernels (over 512 columns, or
an accumulator past their VMEM gates) runs a kernel whose accumulator covers a
tile of columns: a second, outer grid axis over feature tiles, the rows routed
once a level by the routing kernel. Everything here runs on the CPU with the
kernel bodies interpreted, at small sizes:

(a) one level and one whole tree at 520 and 2,000 columns through the tiled
    kernel against the plain reference (``fused_level_xla``, ``grow_tree``);
(b) the tiles add up: at 50 columns with a forced tile of 16 or 32 the tiled
    kernel's histogram is the untiled construction's bit for bit, with and
    without sibling subtraction, one tree and T, at two row tiles;
(c) the routing kernel at 2,000 columns against ``partition_apply_xla``;
(d) the VMEM model: every tile and row tile the plan returns is under the
    budget it states, a shape the untiled kernels take never reaches the
    tiles, the routing beside the tiles is asked at the table's own width,
    and the span and the printed routes say what a call swept;
(e) the feature-major bins (ISSUE 36): the tiled kernel and the routing
    beside it read ``[Fp, n]`` (a column's one-hot is ``[B, tr]``, rows on
    the lanes); a tiled tree asks for that array as one expression of its
    one widened matrix; since ISSUE 38 the four older cells' untiled
    programs read it too, padded to whole sublanes
    (``tests/test_feature_major.py`` holds those kernels to the parent's
    row-major layout bit for bit).
"""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import xgboost_tpu as xgb
from xgboost_tpu.observability import REGISTRY
from xgboost_tpu.tree import grow, grow_fused
from xgboost_tpu.tree import hist_kernel as hk

N = 1024
TR = 256  # four row tiles: a feature tile's accumulator carries over them


@pytest.fixture
def mosaic_route(monkeypatch):
    """The route the chip takes: the Pallas kernels, their bodies
    interpreted."""
    monkeypatch.setattr(hk, "use_pallas", lambda: True)
    monkeypatch.setattr(hk, "_INTERPRET", True)
    monkeypatch.setenv("XGBTPU_HOIST_BUDGET_MB", "0")


def _level_inputs(F, B, d, T=None, seed=0, n=N):
    """Bins with missing values, positions at level ``d - 1`` (some rows
    stayed above it), gradients, and decision tables whose first column
    marks no split, the left or the right child; ``T`` trees' of each where
    given."""
    rng = np.random.RandomState(seed + 7 * d + 31 * (T or 0) + F)
    R = T or 1
    bins = rng.randint(0, B + 1, (n, F)).astype(np.int32)
    Kp = (1 << d) >> 1
    if d == 0:
        pos = np.zeros((R, n), np.int32)
        ptab = np.zeros((R, 1, 4), np.float32)
    else:
        prev = (1 << (d - 1)) - 1
        pos = rng.randint(max(prev - 1, 0), prev + Kp, (R, n)).astype(np.int32)
        ptab = np.stack([np.stack([
            rng.randint(0, 3, Kp), rng.randint(0, F, Kp),
            rng.randint(0, B, Kp), rng.randint(0, 2, Kp)], 1)
            for _ in range(R)]).astype(np.float32)
    gh = rng.randn(2 * R, n).astype(np.float32)
    gh[1::2] = np.abs(gh[1::2])
    if T is None:
        ptab = ptab[0]
    return (jnp.asarray(bins), jnp.asarray(pos), jnp.asarray(gh),
            jnp.asarray(ptab))


# ---------------------------------------------------------------------------
# (b) the tiles add up
# ---------------------------------------------------------------------------

LEVELS = [(0, False)] + [(d, sub) for d in (1, 3) for sub in (False, True)]


@pytest.mark.parametrize("ft,tr", [(16, TR), (32, TR), (16, 512)])
@pytest.mark.parametrize("d,sub", LEVELS)
@pytest.mark.parametrize("T", [None, 3])
def test_tiles_add_up_to_the_untiled_kernel(mosaic_route, T, d, sub, ft, tr):
    """50 columns in tiles of 16 or 32 (the last padded with the missing
    bin; the ``(ft, tr)`` blocks of the feature-major bins) against the
    untiled construction (one ``(56, tr)`` block, whole sublanes) at the
    same row tile: the same positions, and every histogram cell the same
    bits."""
    F, B = 50, 16
    bins, pos, gh, ptab = _level_inputs(F, B, d, T)
    K = 1 << d
    kw = dict(K=K, Kp=K >> 1, B=B, d=d, sub=sub)
    pos_u, hist_u = hk._fused_level_pallas(
        hk._feature_major(bins, hk._SUBLANES, B), pos, gh, ptab, F=F, tr=tr,
        **kw)
    plan = hk.LevelPlan("tiled", tr, -(-F // ft), ft)
    pos_t, hist_t = hk._tiled_level(bins, pos, gh, ptab, plan=plan, vma=(),
                                    **kw)
    assert hist_t.shape == hist_u.shape
    np.testing.assert_array_equal(np.asarray(pos_t), np.asarray(pos_u))
    np.testing.assert_array_equal(np.asarray(hist_t), np.asarray(hist_u))
    assert float(jnp.abs(hist_t).sum()) > 0.0
    if d:
        assert bool((pos_t != pos).any())


def test_forced_tiles_through_the_dispatcher(mosaic_route, monkeypatch):
    """With the tile forced, ``fused_level`` and ``fused_level_trees`` send
    a narrow matrix through the tiled kernel (the printed routes count the
    tiles), and give what the untiled dispatch gives."""
    F, B = 50, 16
    bins, pos, gh, ptab = _level_inputs(F, B, 2, T=2)
    kw = dict(K=4, Kp=2, B=B, d=2, sibling_sub=True)
    want = hk.fused_level_trees(bins, pos, gh, ptab, **kw)
    one = hk.fused_level(bins, pos[:1], gh[:2], ptab[0], pallas=True, **kw)
    monkeypatch.setattr(hk, "_FORCE_TILE", 16)
    before = dispatch_routes().get(("feature_tiles", "4"), 0)
    got = hk.fused_level_trees(bins, pos, gh, ptab, **kw)
    one_t = hk.fused_level(bins, pos[:1], gh[:2], ptab[0], pallas=True, **kw)
    for a, b in zip(want + one, got + one_t):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # 50 columns in four tiles of 16, one count a call
    assert dispatch_routes()[("feature_tiles", "4")] - before == 2


# ---------------------------------------------------------------------------
# (a) against the plain reference, at widths no untiled kernel takes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("F,B", [(520, 128), (2000, 128), (520, 256)])
@pytest.mark.parametrize("d,sub", [(0, False), (3, True)])
def test_wide_level_equals_the_reference(mosaic_route, F, B, d, sub):
    """One level through the dispatcher, which finds the tiled kernel by
    itself at these widths, against ``fused_level_xla``'s segment sums of
    the same rows: positions equal; histograms within the hi/lo bf16
    class the kernel tests of this repository use (two bf16 terms carry
    16 significand bits, and the f32 accumulation orders differ), far under
    what moves a split."""
    n = 1024
    bins, pos, gh, ptab = _level_inputs(F, B, d, n=n)
    K = 1 << d
    plan = hk.level_plan(n, F, (K >> 1) if sub else K, B)
    assert plan.kernel == "tiled" and plan.ft == 128
    assert plan.tiles == -(-F // 128)
    pos_t, hist_t = hk.fused_level(bins, pos, gh, ptab, K=K, Kp=K >> 1, B=B,
                                   d=d, pallas=True, sibling_sub=sub)
    pos_x, hist_x = hk.fused_level_xla(bins, pos, gh, ptab, K=K, Kp=K >> 1,
                                       B=B, d=d)
    np.testing.assert_array_equal(np.asarray(pos_t), np.asarray(pos_x))
    hist_x = np.asarray(hist_x)  # [F, 2K, B], every node
    if sub:  # the kernel built the marked child of every parent alone
        mark = np.asarray(ptab[:, 0]).astype(int)
        Kp = K >> 1
        rows = [2 * p + mark[p] - 1 if mark[p] else None for p in range(Kp)]
        want = np.zeros((F, 2 * Kp, B), np.float32)
        for p, r in enumerate(rows):
            if r is not None:
                want[:, p] = hist_x[:, r]
                want[:, Kp + p] = hist_x[:, K + r]
        hist_x = want
    hist_t = np.asarray(hist_t)
    assert hist_t.shape == hist_x.shape
    scale = np.abs(np.asarray(gh)).sum()
    assert np.abs(hist_t - hist_x).max() <= 2.0 ** -15 * scale / 8
    np.testing.assert_allclose(hist_t, hist_x, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("F,B", [(520, 128), (2000, 256)])
def test_wide_tree_has_the_reference_splits(mosaic_route, F, B):
    """A whole depth-3 tree on a few thousand rows through
    ``grow_tree_fused`` (the tiled kernel at every level, the last routing in
    Mosaic at this width) against ``grow_tree`` over ``segment_sum``: the
    same splits, leaf values and margin delta to float noise."""
    n = 2048
    rng = np.random.RandomState(F)
    X = rng.randn(n, F).astype(np.float32)
    y = (X[:, 3] + 0.5 * X[:, F - 2] * (X[:, 17] > 0)
         + 0.1 * rng.randn(n) > 0).astype(np.float32)
    binned = xgb.data.quantile.BinnedMatrix.from_dense(X, max_bin=B)
    cut_vals = jnp.asarray(binned.cuts.values)
    g = jnp.asarray(0.5 - y)
    h = jnp.full((n,), 0.25, jnp.float32)
    cfg = grow.GrowParams(max_depth=3)
    key = jax.random.PRNGKey(0)
    before = dict(dispatch_routes())
    fused = grow_fused.grow_tree_fused(
        binned.bins, g, jnp.array(h), cut_vals, key, 0.3, 0.0, cfg)
    ref = grow.grow_tree(binned.bins, g, h, cut_vals, key, cfg)
    after = dispatch_routes()
    assert after.get(("level_hist", "pallas"), 0) \
        - before.get(("level_hist", "pallas"), 0) == 3
    assert after.get(("level_partition", "pallas"), 0) \
        > before.get(("level_partition", "pallas"), 0)
    assert after.get(("level_hist", "xla"), 0) \
        == before.get(("level_hist", "xla"), 0)
    keep = np.asarray(fused.keep)
    np.testing.assert_array_equal(keep, np.asarray(ref.is_split))
    assert keep.sum() >= 5
    np.testing.assert_array_equal(np.asarray(fused.feature)[keep],
                                  np.asarray(ref.feature)[keep])
    np.testing.assert_array_equal(np.asarray(fused.split_bin)[keep],
                                  np.asarray(ref.split_bin)[keep])
    np.testing.assert_allclose(np.asarray(fused.node_h), np.asarray(ref.node_h),
                               rtol=1e-4, atol=1e-4)


def dispatch_routes():
    fam = REGISTRY.get("dispatch_decisions_total")
    out = {}
    if fam is not None:
        for lab, c in fam.series():
            key = (lab["op"], lab["impl"])
            out[key] = out.get(key, 0) + int(c.value)
    return out


# ---------------------------------------------------------------------------
# (c) the routing kernel takes the width
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("F", [520, 2000])
def test_routing_kernel_at_the_width(mosaic_route, F):
    B, d, n = 128, 4, 2048
    bins, pos, _, ptab = _level_inputs(F, B, d, n=n)
    tr = hk._route_tr(n, F, 8, 4)
    assert tr == (512 if F == 520 else 256)
    assert hk._route_tr(n, 512, 8, 4) == hk.TR
    assert hk.pallas_route_fits(n, F, 8, 4)
    assert not hk.pallas_route_fits(n + 256, F, 8, 4)  # whole TR tiles
    want = hk.partition_apply_xla(bins, pos, ptab, Kp=8, B=B, d=d)
    got = hk.partition_apply(bins, pos, ptab, Kp=8, B=B, d=d, pallas=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert bool((got != pos).any())
    # what the dispatcher ran: the kernel on the tree's feature-major bins,
    # a ``(Fp, tr)`` block a step
    binsT = hk._feature_major(bins, 128, B)
    Fp = hk._up(F, 128)
    assert binsT.shape == (Fp, n) and bool((binsT[F:] == B).all())
    assert hk._route_tr(n, Fp, 8, 4) == tr
    got = hk._route_rows_pallas(binsT, pos, ptab, Kp=8, B=B, d=d, tr=tr)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# (d) the VMEM model
# ---------------------------------------------------------------------------

# rows, columns, bins, depth of the four accepted cells, and their plans
CELLS = [(750592, 50, 256, 6, 34), (2625536, 28, 256, 8, 7),
         (2271232, 136, 256, 6, 12), (436224, 54, 256, 6, 33)]


@pytest.mark.parametrize("F", [50, 136, 520, 968, 2000])
@pytest.mark.parametrize("depth", [6, 8])
@pytest.mark.parametrize("B", [128, 256])
def test_every_planned_tile_is_under_its_budget(monkeypatch, F, depth, B):
    """Every (tile, row tile) ``level_plan`` returns, at every level of the
    depth with and without sibling subtraction and with a resident prefix
    or none, is under the budget the model states; the tiles it counts
    cover the columns. A level of 64 built nodes at 128 bins has no tile
    (its accumulator alone is past the budget) and no plan."""
    monkeypatch.setattr(hk, "use_pallas", lambda: True)
    monkeypatch.setenv("XGBTPU_HOIST_BUDGET_MB", "8192")
    rows = 400384
    fh = hk.hoist_plan(rows, F, B, depth)
    for d in range(depth):
        for Kc in {1 << d, max(1 << d >> 1, 1)}:
            for width in {0, fh * B}:
                plan = hk.level_plan(rows, F, Kc, B, width)
                if plan is None:
                    continue
                assert rows % plan.tr == 0
                if plan.kernel == "hoisted":
                    assert hk._hoist_vmem_bytes(plan.tr, width, Kc, F, B) \
                        <= hk._VMEM_HOIST_BUDGET
                elif plan.kernel == "construct":
                    assert F <= hk._MAX_KERNEL_FEATURES
                    assert F * 2 * Kc * B * 4 <= hk._VMEM_ACC_BUDGET
                else:
                    assert hk._tile_vmem_bytes(plan.tr, plan.ft, Kc, B) \
                        <= hk._VMEM_HOIST_BUDGET
                    assert plan.ft == hk._FEATURE_TILE
                    assert F <= plan.tiles * plan.ft < F + plan.ft
                    assert hk._route_tr(rows, plan.tiles * plan.ft, Kc, 4)
    # past the untiled kernels' width every level under 64 built nodes at
    # 128 bins (32 at 256) has the tiled kernel, and none beyond has any
    if F > hk._MAX_KERNEL_FEATURES:
        assert hk.hoist_plan(rows, F, B, depth) == 0
        for d in range(depth):
            Kc = max(1 << d >> 1, 1)
            plan = hk.level_plan(rows, F, Kc, B)
            if Kc * B < 64 * 128:
                assert plan.kernel == "tiled"
            else:
                assert plan is None


@pytest.mark.parametrize("rows,F,B,depth,want", CELLS)
def test_the_cells_never_reach_the_tiles(monkeypatch, rows, F, B, depth, want):
    """The four accepted cells' shapes: the plan the parent gave, an
    untiled kernel at every level, one tile a call."""
    monkeypatch.setattr(hk, "use_pallas", lambda: True)
    monkeypatch.setenv("XGBTPU_HOIST_BUDGET_MB", "8192")
    assert hk.hoist_plan(rows, F, B, depth) == want
    for d in range(depth):
        plan = hk.level_plan(rows, F, max(1 << d >> 1, 1), B, want * B)
        assert plan.kernel == "hoisted" and plan.tiles == 1
    assert hk.feature_tile(F, B, depth) == 0
    assert hk._tile_at(F, B, 1) == 0
    assert hk._route_tr(rows, F, 1 << (depth - 1), 4) == hk.TR


def test_wide_plan_and_what_a_call_sweeps(monkeypatch):
    """400,384 x 2,000 at 128 bins and depth 6: nothing resident (the
    streaming kernel is untiled), sixteen tiles of 128 columns a call at a
    1,024-row tile at every level, every reader of the tree's bins on the
    padded array; a matrix of 200 columns at 256 bins tiles its deepest
    level alone."""
    monkeypatch.setattr(hk, "use_pallas", lambda: True)
    monkeypatch.setenv("XGBTPU_HOIST_BUDGET_MB", "8192")
    assert hk.hoist_plan(400384, 2000, 128, 6) == 0
    for Kc in (1, 1, 2, 4, 8, 16):
        assert hk.level_plan(400384, 2000, Kc, 128) \
            == hk.LevelPlan("tiled", 1024, 16, 128)
    assert hk.feature_tile(2000, 128, 6) == 128
    assert hk._tile_at(2000, 128, 1) == 128
    # the models restated for the feature-major blocks (ISSUE 36) count
    # the same bytes at whole tiles of 128: the routing's tile stays 256
    assert [hk._route_tr(400384, 2048, Kp, 4) for Kp in (1, 2, 4, 8, 16, 32)] \
        == [256] * 6
    assert hk.pallas_level_fits(500000, 2000, 1, 128)
    assert [hk.level_plan(8192, 200, Kc, 256).kernel
            for Kc in (1, 2, 4, 8, 16)] == ["construct"] * 4 + ["tiled"]
    assert hk.feature_tile(200, 256, 6) == 128 and hk._tile_at(200, 256, 1) == 0
    # a width whose whole bins row is past the routing kernel's tile has no
    # tiled level either: the tiles need the level's routing beside them
    assert hk.level_plan(8192, 4500, 16, 128).kernel == "tiled"
    assert hk._route_tr(8192, 6000, 16, 4) == 0
    assert hk.level_plan(8192, 6000, 16, 128) is None


@pytest.mark.parametrize("F,W,fits", [(2000, 4, True), (2000, 5 + 128, True),
                                      (5000, 4, True), (5000, 5 + 128, False)])
def test_the_tiles_ask_for_the_routing_at_the_tables_width(F, W, fits):
    """The tiled level needs the level's routing beside it, whose working
    set grows with the decision table's width (a categorical table is ``5 +
    B`` wide): the plan, the registry predicate and the tile the call asks
    ``_route_tr`` for agree, so a level the predicate admits never divides
    by a zero tile."""
    from xgboost_tpu.dispatch import Ctx, resolve

    rows, Kc, B = 8192, 16, 128
    plan = hk.level_plan(rows, F, Kc, B, 0, W)
    assert (plan is not None) == fits
    assert hk.pallas_level_fits(rows, F, Kc, B, 0, W) == fits
    assert bool(hk._route_tr(rows, -(-F // 128) * 128, Kc, W)) == fits
    dec = resolve("level_hist", Ctx(
        platform="tpu", pallas=True, interpret=False, rows=rows, features=F,
        nodes=Kc, bins=B, table_width=W, bins_dtype="uint8", sharded=False,
        onehot_width=0))
    assert (dec.impl == "pallas") == fits


# ---------------------------------------------------------------------------
# (e) the feature-major bins: one expression a tree, none in the older cells
# ---------------------------------------------------------------------------


def _feature_major_arrays(jaxpr, Fp, n):
    """The ``transpose`` equations of a flat jaxpr that make an int32
    ``[Fp, n]``, each with the variable its expression starts from (through
    the pad to whole tiles)."""
    made = {eqn.outvars[0]: eqn for eqn in jaxpr.eqns}
    out = []
    for eqn in jaxpr.eqns:
        aval = eqn.outvars[0].aval
        if eqn.primitive.name == "transpose" and aval.shape == (Fp, n) \
                and aval.dtype == jnp.int32:
            src = eqn.invars[0]
            while src in made and made[src].primitive.name in ("pad", "pjit"):
                src = made[src].invars[0]  # ``jnp.pad`` is a jitted call
            out.append(src)
    return out


@pytest.mark.parametrize("trees", [1, 3])
def test_a_tiled_tree_asks_for_one_feature_major_array(mosaic_route,
                                                       monkeypatch, trees):
    """A tiled tree's program (its jitted steps traced inline): the narrow
    bins are widened once, a tree or, where a round's trees are grown
    together, a round; every level's tiles, every routing below the root
    and the last routing read a feature-major ``[Fp, n]`` that is the SAME
    expression of that one widened array (what lets XLA keep one copy: the
    program compiled for the chip holds one, tests/test_device_phases.py);
    and no Mosaic call of the program reads the bins row-major."""
    monkeypatch.setattr(hk, "_FORCE_TILE", 16)
    n, F, B, depth = 1024, 50, 16, 3
    Fp = 64
    cfg = grow.GrowParams(max_depth=depth)
    S = jax.ShapeDtypeStruct
    eta, gamma = jnp.float32(0.3), jnp.float32(0.0)

    def one(bins, g, h, cuts, key):
        return grow_fused._grow_tree_fused_impl(bins, g[0], h[0], cuts,
                                                key[0], eta, gamma, cfg)

    def together(bins, g, h, cuts, key):
        return grow_fused.grow_trees_one_pass(
            bins, list(g), list(h), cuts, list(key), eta, gamma, cfg)

    with jax.disable_jit():
        jaxpr = jax.make_jaxpr(one if trees == 1 else together)(
            S((n, F), jnp.uint8), S((trees, n), jnp.float32),
            S((trees, n), jnp.float32), S((F, B), jnp.float32),
            S((trees, 2), jnp.uint32)).jaxpr
    widened = [e for e in jaxpr.eqns
               if e.primitive.name == "convert_element_type"
               and e.invars[0].aval.shape == (n, F)
               and e.invars[0].aval.dtype == jnp.uint8]
    assert len(widened) == 1
    calls = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    levels = [e for e in calls  # the histogram out; a routing: positions
              if e.outvars[0].aval.dtype == jnp.float32]
    assert len(levels) >= depth
    # routings: a tree's a level below the root, and its last
    assert len(calls) - len(levels) == trees * depth
    sources = _feature_major_arrays(jaxpr, Fp, n)
    # asked for by every tiled level (its tiles and routings) and by every
    # tree's last routing
    assert len(sources) == len(levels) + trees
    assert set(sources) == {widened[0].outvars[0]}
    for e in calls:
        assert e.invars[0].aval.shape == (Fp, n), e.invars[0].aval
        assert not any(v.aval.shape in ((n, F), (n, Fp)) for v in e.invars)


# columns, depth, hoist plan, objective and chips of the four older cells
OLDER_CELLS = {
    "anchor_train": (50, 6, 34, {"objective": "binary:logistic"}, 1),
    "higgs_train_x4": (28, 8, 7, {"objective": "binary:logistic"}, 4),
    "mslr_rank_train": (136, 6, 12, {"objective": "rank:ndcg",
                                     "lambdarank_num_pair_per_sample": 1}, 1),
    "covtype_train": (54, 6, 33, {"objective": "multi:softmax",
                                  "num_class": 8}, 1),
}


def _kernel_calls(jaxpr):
    """``(name, first operand's aval)`` of every jitted kernel wrapper
    (``_*_pallas``) in a jaxpr, its sub-jaxprs (scan, ``shard_map``, jitted
    steps) included, in program order."""
    out = []
    for eqn in jaxpr.eqns:
        name = eqn.params.get("name")
        if eqn.primitive.name in ("jit", "pjit") \
                and str(name).endswith("_pallas"):
            out.append((name, eqn.invars[0].aval))
            continue
        for v in eqn.params.values():
            for j in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(j, "jaxpr", j)
                if hasattr(inner, "eqns"):
                    out += _kernel_calls(inner)
    return out


@pytest.mark.parametrize("cell", OLDER_CELLS)
def test_the_older_cells_programs_read_feature_major_bins(
        mosaic_route, monkeypatch, cell):
    """The program each of the four older cells boosts with (the scan
    chunk's, on one chip and under a mesh of four; MSLR's objective is not
    scan-safe, so the per-round tree program), traced at the cell's
    columns, 256 bins, depth, objective and hoist plan on 4,096 rows a chip:
    every level call is the streaming kernel, none is tiled, and every
    level call and routing reads the widened bins FEATURE-MAJOR, ``s32[Fp,
    rows]`` padded to whole sublanes, never ``[rows, F]`` (ISSUE 38); the
    resident one-hot's builder alone reads them row-major."""
    from xgboost_tpu.gbm import gbtree
    from xgboost_tpu.parallel import grow as pgrow
    from xgboost_tpu.parallel import make_mesh, mesh_context

    F, depth, plan, objective, chips = OLDER_CELLS[cell]
    if len(jax.devices()) < chips:
        pytest.skip(f"needs {chips} devices")
    rows = 4096  # a feature's resident one-hot is 1 MiB: the plan in MiB
    monkeypatch.setenv("XGBTPU_HOIST_BUDGET_MB", str(plan))
    assert hk.hoist_plan(rows, F, 256, depth) == plan
    n = rows * chips
    rng = np.random.RandomState(1)
    X = rng.randn(n, F).astype(np.float32)
    y = rng.randint(0, 2, n).astype(np.float32)
    kw = {"qid": np.repeat(np.arange(n // 64), 64)} \
        if objective["objective"] == "rank:ndcg" else {}
    params = dict(objective, tree_method="tpu_hist", max_depth=depth,
                  max_bin=256, seed=1)
    module, attr = (pgrow, "_dist_scan_impl") if chips > 1 else (
        grow_fused, "_grow_tree_fused_impl") if kw else (
        gbtree, "_scan_rounds_impl")
    orig = getattr(module, attr)
    jitted = getattr(orig, "_guarded_jit", orig)
    traced = []

    class Traced(Exception):
        pass

    def tracing(*args, **kwargs):
        traced.append(jitted.trace(*args, **kwargs).jaxpr)
        raise Traced  # nothing of the program runs

    def boost():
        d = xgb.DMatrix(X, label=y, **kw)
        xgb.Booster(params, [d]).update_many(d, 0, 2, chunk=2)

    monkeypatch.setattr(module, attr, tracing)
    with pytest.raises(Traced), (mesh_context(make_mesh(chips)) if chips > 1
                                 else contextlib.nullcontext()):
        boost()
    calls = _kernel_calls(traced[0].jaxpr)
    names = [name for name, _ in calls]
    levels = [name for name in names if "level" in name]
    assert set(levels) == {"_hoisted_level_pallas"}
    assert len(levels) >= depth
    assert "_route_rows_pallas" in names
    Fp = hk._up(F, hk._SUBLANES)
    for name, aval in calls:
        if name == "_build_onehot_pallas":  # the one-hot stays rows-major
            assert aval.shape[0] == rows, aval
            continue
        assert aval.shape == (Fp, rows) and aval.dtype == jnp.int32, (
            name, aval)


# ---------------------------------------------------------------------------
# what a job says about its tiles: the span, the printed routes, the warning
# ---------------------------------------------------------------------------


@pytest.fixture()
def chrome(tmp_path, monkeypatch):
    from xgboost_tpu.observability import trace

    out = tmp_path / "chrome.json"
    monkeypatch.setenv("XGBTPU_TRACE", str(out))
    trace.reset()
    yield out
    monkeypatch.delenv("XGBTPU_TRACE")
    trace.reset()


def _job(F, classes=0):
    rng = np.random.default_rng(5)
    X = rng.standard_normal((1024, F)).astype(np.float32)
    params = {"tree_method": "tpu_hist", "max_depth": 3, "max_bin": 16,
              "seed": 3}
    if classes:
        y = rng.integers(0, classes, 1024).astype(np.float32)
        params.update(objective="multi:softmax", num_class=classes)
    else:
        y = (X[:, 0] + X[:, 1] > 0).astype(np.float32)
        params.update(objective="binary:logistic")
    d = xgb.DMatrix(X, label=y)
    return d, xgb.Booster(params, [d])


def _scan_chunk_args(chrome):
    from xgboost_tpu.observability import trace

    trace.flush()
    return [e for e in trace.load_trace(str(chrome))
            if e.get("ph") == "X" and e["name"] == "scan_chunk"][-1]["args"]


def test_a_narrow_job_never_mentions_tiles(mosaic_route, chrome):
    """The untiled kernels: the span says the kernels are untiled and the
    routes never mention tiles, on either path."""
    d, bst = _job(12)
    routes0 = dispatch_routes()
    bst.update_many(d, 0, 2, chunk=2)
    args = _scan_chunk_args(chrome)
    assert args["features"] == 12 and args["feature_tile"] == 0
    bst.update(d, 2)  # the per-round path
    assert not any(op == "feature_tiles" and n != routes0.get((op, i))
                   for (op, i), n in dispatch_routes().items())


@pytest.mark.parametrize("classes", [0, 3])
def test_a_tiled_job_says_its_tiles(mosaic_route, monkeypatch, chrome,
                                    classes):
    """Tiles of 16 forced on a 50-column matrix (four a call): the printed
    routes hold one note a level call of the traced program, for one
    depth-3 tree and for three class trees grown together (one call a
    level), and the same forest comes out as on the untiled kernels."""
    d, want = _job(50, classes)
    want.update_many(d, 0, 2, chunk=2)
    monkeypatch.setattr(hk, "_FORCE_TILE", 16)
    jax.clear_caches()  # the hook is no part of a traced program's key
    d, bst = _job(50, classes)
    routes0 = dispatch_routes()
    bst.update_many(d, 0, 2, chunk=2)
    args = _scan_chunk_args(chrome)
    assert args["features"] == 50 and args["feature_tile"] == 16
    swept = dispatch_routes().get(("feature_tiles", "4"), 0) \
        - routes0.get(("feature_tiles", "4"), 0)
    assert swept == 3  # one note a level call of the one traced program
    assert bytes(bst.save_raw("json")) == bytes(want.save_raw("json"))
    jax.clear_caches()  # nor may a later test find the forced programs


def test_a_level_that_leaves_mosaic_on_a_tpu_says_so(mosaic_route,
                                                     monkeypatch):
    """A level no kernel takes (512 nodes at 256 bins: a tile's accumulator
    is past the VMEM budget) resolves to XLA; on a TPU that is one
    warning a shape, and none where the user pinned the route."""
    from xgboost_tpu.dispatch import core
    from xgboost_tpu.utils import console_logger

    B, d, n = 256, 9, 1024
    assert hk.level_plan(n, 12, 1 << d, B) is None
    bins, pos, gh, ptab = _level_inputs(12, B, d, n=n)
    said = []
    monkeypatch.setattr(console_logger, "warning",
                        lambda *a: said.append(" ".join(map(str, a))))
    kw = dict(K=1 << d, Kp=1 << d >> 1, B=B, d=d, pallas=True)
    hk.fused_level(bins, pos, gh, ptab, **kw)
    assert said == []  # the CPU suite's platform is no TPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(core._STATE, "warned", {})
    want = hk.fused_level(bins, pos, gh, ptab, **kw)
    hk.fused_level(bins, pos, gh, ptab, **kw)
    assert len(said) == 1 and "fits no Mosaic kernel" in said[0] \
        and "level 9" in said[0] and "1024 x 12" in said[0]
    # the last routing, through its own resolve: XLA on the CPU suite by
    # preference, so nothing fell and nothing is said
    monkeypatch.setenv("XGBTPU_DISPATCH", "level_hist=xla")
    monkeypatch.setattr(core._STATE, "warned", {})
    got = hk.fused_level(bins, pos, gh, ptab, **kw)
    assert len(said) == 1  # pinned: a choice, not a fall-back
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
