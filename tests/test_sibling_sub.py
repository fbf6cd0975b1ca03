"""Sibling subtraction on the Pallas level route (ISSUE 27): below the root
a level kernel builds one child of every split, the one its parent marked in
the decision table, and ``derive_siblings`` takes the other as parent - built.

Kernel bodies run in interpret mode on the CPU, against the segment-sum
oracle ``fused_level_xla`` (the direct ``[F, 2K, B]``) to the 2e-4 of
``tests/test_hoisted.py``. Reference analog: ``SubtractionTrick``
(``updater_gpu_hist.cu``) and ``test_histogram.cu``'s subtraction cases.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xgboost_tpu.tree import hist_kernel as hk
from xgboost_tpu.tree.grow import GrowParams
from xgboost_tpu.tree.grow_fused import GrownTree, grow_tree_fused

# the three cells' padded rows, features, depth and resident features
# (PERF.md section 4; every run of the benchmark prints the plan)
CELLS = {"anchor_train": (750_592, 50, 6, 34),
         "higgs_train_x4": (2_625_536, 28, 8, 7),
         "mslr_rank_train": (2_271_232, 136, 6, 12)}
BINS = 256


@pytest.fixture()
def interpreted(monkeypatch):
    """The Pallas dispatch, its kernel bodies interpreted."""
    monkeypatch.setattr(hk, "use_pallas", lambda: True)
    monkeypatch.setattr(hk, "_INTERPRET", True)


def _level_case(d, cat, seed):
    """Rows at level d - 1 with a decision table that splits every parent
    but the last (at d = 1: the only one, in the odd seed), and marks a
    child of each; a tenth of the cells missing, a shallower leaf's rows
    (outside the level) and zero-gradient padding rows in the missing bin."""
    rng = np.random.RandomState(seed)
    n, F, B = 1024, 4, 16
    Kp = 1 << (d - 1)
    prev_off = Kp - 1
    bins = rng.randint(0, B, size=(n, F)).astype(np.int32)
    bins[rng.rand(n, F) < 0.1] = B
    gh = rng.randn(2, n).astype(np.float32)  # rows on the lanes (ISSUE 31)
    gh[1] = np.abs(gh[1])
    pos = rng.randint(prev_off, prev_off + Kp, size=(1, n)).astype(np.int32)
    if d >= 2:
        pos[0, rng.rand(n) < 0.1] = 0  # a leaf above the level
    bins[-64:], gh[:, -64:] = B, 0.0  # padding rows
    ptab = np.zeros((Kp, 5 + B if cat else 4), np.float32)
    ptab[:, 0] = rng.randint(1, 3, Kp)  # split; 2: the right child is built
    ptab[:, 1] = rng.randint(0, F, Kp)
    ptab[:, 2] = rng.randint(0, B, Kp)
    ptab[:, 3] = rng.randint(0, 2, Kp)
    unsplit = Kp - 1 if (d >= 2 or seed % 2) else None
    if unsplit is not None:
        ptab[unsplit, 0] = 0.0
    if cat:
        ptab[0, 4] = 1.0  # parent 0 splits on a category set
        ptab[0, 5:] = rng.rand(B) < 0.4
    return (jnp.asarray(bins), jnp.asarray(pos), jnp.asarray(gh),
            jnp.asarray(ptab), unsplit)


@pytest.mark.parametrize("kernel", ["full_hoist", "partial_hoist",
                                    "construct"])
@pytest.mark.parametrize("d,cat,seed", [(1, False, 0), (1, False, 1),
                                        (2, False, 2), (2, True, 3),
                                        (3, False, 4), (3, True, 5)])
def test_built_half_and_derivation_match_the_direct_build(interpreted, kernel,
                                                          d, cat, seed):
    bins, pos, gh, ptab, unsplit = _level_case(d, cat, seed)
    n, F = bins.shape
    B, K, Kp = 16, 1 << d, 1 << (d - 1)
    _, parent = hk.fused_level_xla(bins, pos, gh, ptab, K=Kp, Kp=0, B=B,
                                   d=d - 1)
    want_pos, want = hk.fused_level_xla(bins, pos, gh, ptab, K=K, Kp=Kp, B=B,
                                        d=d)
    binsT = hk._feature_major(bins, hk._SUBLANES, B)
    kw = dict(F=F, K=K, Kp=Kp, B=B, d=d, tr=256, sub=True)
    if kernel == "construct":
        got_pos, built = hk._fused_level_pallas(binsT, pos, gh, ptab, **kw)
    else:
        Fh = F if kernel == "full_hoist" else 2
        got_pos, built = hk._hoisted_level_pallas(
            binsT, hk.build_onehot(bins[:, :Fh], B=B), pos, gh, ptab, **kw)
    assert built.shape == (F, 2 * Kp, B)
    np.testing.assert_array_equal(np.asarray(got_pos), np.asarray(want_pos))
    got = np.asarray(hk.derive_siblings(parent, built, ptab))
    assert got.shape == (F, 2 * K, B)
    np.testing.assert_allclose(got, np.asarray(want), rtol=2e-4, atol=2e-4)
    if unsplit is not None:
        rows = [2 * unsplit, 2 * unsplit + 1, K + 2 * unsplit,
                K + 2 * unsplit + 1]
        assert not np.asarray(built)[:, [unsplit, Kp + unsplit]].any()
        assert not got[:, rows].any(), "an unsplit parent has no children"


def _tree_inputs(n_pad=2048, F=5, B=16, seed=0):
    rng = np.random.RandomState(seed)
    bins = rng.randint(0, B, size=(n_pad, F)).astype(np.int32)
    bins[rng.rand(n_pad, F) < 0.05] = B
    score = (bins[:, 0] > 7) * 1.0 + (bins[:, 1] % 3 == 0) * 0.7 \
        - (bins[:, 2] > 11) * 0.5
    y = (score + 0.3 * rng.randn(n_pad) > 0.6).astype(np.float32)
    g = (0.5 - y).astype(np.float32)
    h = np.full(n_pad, 0.25, np.float32)
    bins[-100:], g[-100:], h[-100:] = B, 0.0, 0.0  # padding rows
    cuts = np.sort(rng.randn(F, B).astype(np.float32), axis=1)
    return bins, g, h, cuts


def _grow(cfg, hoist_features, pin, monkeypatch, mesh=None):
    """One tree through the level loop with ``sibling_sub`` left alone or
    pinned; the route is taken when the program is traced, so the traces of
    the other pin are dropped first."""
    if pin:
        monkeypatch.setenv("XGBTPU_DISPATCH", pin)
    else:
        monkeypatch.delenv("XGBTPU_DISPATCH", raising=False)
    jax.clear_caches()
    bins, g, h, cuts = _tree_inputs()
    B = cuts.shape[1]

    def grower(bins_s, g_s, h_s, cuts_s, key_s):
        onehot = (hk.build_onehot(bins_s[:, :hoist_features], B=B)
                  if hoist_features else None)
        return grow_tree_fused(bins_s, g_s, h_s, cuts_s, key_s,
                               jnp.float32(0.3), jnp.float32(0.0), cfg=cfg,
                               onehot=onehot)

    args = [jnp.asarray(a) for a in (bins, g, h, cuts)]
    args.append(jax.random.PRNGKey(0))
    if mesh is None:
        return grower(*args), None
    from jax.sharding import PartitionSpec as P

    from xgboost_tpu.parallel import shard_rows
    from xgboost_tpu.parallel.mesh import ROW_AXIS

    fn = jax.jit(jax.shard_map(
        grower, mesh=mesh,
        in_specs=(P(ROW_AXIS, None), P(ROW_AXIS), P(ROW_AXIS), P(None, None),
                  P()),
        out_specs=GrownTree(**{f: (P(ROW_AXIS) if f == "delta" else P())
                               for f in GrownTree._fields}),
        check_vma=False))
    args[:3] = [shard_rows(a, mesh) for a in args[:3]]
    return fn(*args), fn.lower(*args).as_text()


def _assert_same_tree(on, off):
    for f in ("keep", "feature", "split_bin"):
        np.testing.assert_array_equal(np.asarray(getattr(on, f)),
                                      np.asarray(getattr(off, f)), err_msg=f)
    for f in ("leaf_value", "delta"):
        np.testing.assert_allclose(np.asarray(getattr(on, f)),
                                   np.asarray(getattr(off, f)), rtol=0,
                                   atol=1e-5, err_msg=f)


def _resolved(impl, reason):
    """``dispatch_decisions_total`` of the ``sibling_sub`` row."""
    from xgboost_tpu.observability import REGISTRY

    m = re.search(r'dispatch_decisions_total\{impl="%s",op="sibling_sub",'
                  r'reason="%s"\} (\d+)' % (impl, reason),
                  REGISTRY.exposition())
    return int(m.group(1)) if m else 0


@pytest.mark.parametrize("case", ["partial_hoist", "construct",
                                  "categorical", "constrained"])
def test_tree_with_sibling_sub_is_the_direct_builds(interpreted, monkeypatch,
                                                    case):
    """``sibling_sub`` on against pinned off, through everything
    ``_level_update`` does with the full ``[F, 2K, B]``: categorical tables
    (one-hot and partition), a monotone constraint, interaction groups and
    per-level and per-node column sampling."""
    from xgboost_tpu import dispatch

    cfg = GrowParams(max_depth=4)
    if case == "categorical":
        cfg = dataclasses.replace(cfg, categorical=(1,), cat_partition=(3,))
    elif case == "constrained":
        cfg = dataclasses.replace(
            cfg, monotone=(1, 0, -1, 0, 0), interaction=((0, 1, 2), (2, 3, 4)),
            colsample_bylevel=0.8, colsample_bynode=0.8)
    hoist = 3 if case == "partial_hoist" else 0
    before = _resolved("on", "preferred")
    on, _ = _grow(cfg, hoist, None, monkeypatch)
    assert dispatch.table_snapshot()["sibling_sub"] == {
        "impl": "on", "reason": "preferred"}
    engaged = _resolved("on", "preferred")
    off, _ = _grow(cfg, hoist, "sibling_sub=off", monkeypatch)
    assert dispatch.table_snapshot()["sibling_sub"] == {
        "impl": "off", "reason": "pinned"}
    # how often it engages is counted where the routes are: once a level
    # below the root, when the tree's program is traced
    assert engaged - before == cfg.max_depth - 1
    assert np.asarray(on.keep).sum() >= 6, "a tree too small to tell"
    _assert_same_tree(on, off)


def test_a_level_off_the_pallas_impl_is_built_directly(interpreted,
                                                       monkeypatch):
    """Where ``level_hist`` does not resolve to ``pallas`` (here: pinned to
    ``xla``, as the chip smoke's reference stage does) the loop gets the
    direct ``[F, 2K, B]`` back and subtracts nothing: the marks in the
    decision table route as a plain split does, byte for byte."""
    cfg = GrowParams(max_depth=4)
    on, _ = _grow(cfg, 0, "level_hist=xla", monkeypatch)
    off, _ = _grow(cfg, 0, "level_hist=xla,sibling_sub=off", monkeypatch)
    for f in GrownTree._fields:
        np.testing.assert_array_equal(np.asarray(getattr(on, f)),
                                      np.asarray(getattr(off, f)), err_msg=f)


def _all_reduce_shapes(stablehlo):
    """The rank-3 operands of a lowered program's all-reduces."""
    shapes = []
    for m in re.finditer(r'"stablehlo\.all_reduce"\(.*?\)\s*->\s*'
                         r'tensor<([0-9x]+)xf32>', stablehlo, re.S):
        dims = [int(x) for x in m.group(1).split("x")]
        if len(dims) == 3:
            shapes.append(dims)
    return shapes


@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs two devices")
def test_mesh_psum_carries_the_built_half(interpreted, monkeypatch):
    """Two row shards: the per-level psum's operand is ``[F, K, B]`` at
    level d >= 1 (the built children's g and h), not ``[F, 2K, B]``, and
    the tree is the direct build's."""
    from xgboost_tpu.parallel import make_mesh
    from xgboost_tpu.parallel.mesh import ROW_AXIS

    depth, F, B = 4, 5, 16
    cfg = dataclasses.replace(GrowParams(max_depth=depth), axis_name=ROW_AXIS)
    mesh = make_mesh(2)
    on, text_on = _grow(cfg, 3, None, monkeypatch, mesh=mesh)
    off, text_off = _grow(cfg, 3, "sibling_sub=off", monkeypatch, mesh=mesh)
    assert _all_reduce_shapes(text_on) == (
        [[F, 2, B]] + [[F, 1 << d, B] for d in range(1, depth)])
    assert _all_reduce_shapes(text_off) == [[F, 2 << d, B]
                                            for d in range(depth)]
    _assert_same_tree(on, off)


# ---------------------------------------------------------------------------
# the gates: the plan stays, the tile follows the built width
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cell,plan", [("anchor_train", 34),
                                       ("higgs_train_x4", 7),
                                       ("mslr_rank_train", 12)])
def test_hoist_plan_of_the_cells_is_the_parents(monkeypatch, cell, plan):
    """``hoist_plan`` still sizes the resident one-hot for the DIRECT
    deepest level (given the built width its VMEM gate would open to 50, 21
    and 40 features: another lever, another issue)."""
    monkeypatch.setattr(hk, "use_pallas", lambda: True)
    monkeypatch.setenv("XGBTPU_HOIST_BUDGET_MB", "8192")
    n, F, depth, _ = CELLS[cell]
    assert hk.hoist_plan(n, F, BINS, depth) == plan


@pytest.mark.parametrize("cell,tiles", [
    ("anchor_train", [256, 256, 256, 256, 256, 128]),
    ("higgs_train_x4", [512] * 8),
    ("mslr_rank_train", [512] * 6)])
def test_fused_level_dispatches_the_built_widths_tile(monkeypatch, cell,
                                                      tiles):
    """Below the root ``fused_level`` asks the VMEM model for the tile of
    the nodes it builds, 2^(d-1): the deep levels get the tile the level
    above has (direct build: the anchor's level 4 and HIGGS's level 7 and
    MSLR's level 5 ran 128 rows)."""
    n, F, depth, Fh = CELLS[cell]
    calls = []

    def record(binsT, onehot, pos, gh, ptab, *, F, K, Kp, B, d, tr, vma,
               sub):
        calls.append((d, tr, sub))
        Kc = Kp if sub else K
        return pos, jnp.zeros((F, 2 * Kc, B), jnp.float32)

    monkeypatch.setattr(hk, "_hoisted_level_pallas", record)
    S = jax.ShapeDtypeStruct
    for d in range(depth):
        K, Kp = 1 << d, (1 << d) >> 1
        _, hist = jax.eval_shape(
            lambda *a: hk.fused_level(*a[:4], K=K, Kp=Kp, B=BINS, d=d,
                                      pallas=True, onehot=a[4],
                                      sibling_sub=d >= 1),
            S((n, F), jnp.int32), S((1, n), jnp.int32),
            S((2, n), jnp.float32), S((max(Kp, 1), 4), jnp.float32),
            S((n, Fh * BINS), jnp.int8))
        assert hist.shape == (F, 2 * max(Kp, 1), BINS)
        assert calls[-1] == (d, hk._hoist_tr(Fh * BINS, max(Kp, 1), F, BINS),
                             d >= 1)
        assert n % calls[-1][1] == 0
    assert [tr for _, tr, _ in calls] == tiles


def test_level_update_marks_the_smaller_hessian_child():
    """``mark_built``: column 0 of the next decision table reads 1 where
    the left child has the smaller (or equal) hessian sum, 2 where the
    right has, 0 where the node does not split; without it, 0 / 1."""
    from xgboost_tpu.tree.grow_fused import _init_state, _level_update

    F, B = 2, 4
    # root: feature 0 splits 1 | 3 rows (left smaller), so does feature 1
    # mirrored; pick by gradient so that the winner is known
    hg = np.zeros((F, 1, B), np.float32)
    hh = np.zeros((F, 1, B), np.float32)
    hg[0, 0] = [-3.0, 1.0, 1.0, 1.0]
    hh[0, 0] = [1.0, 1.0, 1.0, 1.0]
    hg[1, 0] = [0.0, 0.0, 0.0, 0.0]
    hh[1, 0] = [1.0, 1.0, 1.0, 1.0]
    hist = jnp.asarray(np.concatenate([hg, hh], axis=1))
    cfg = GrowParams(max_depth=2)
    cuts = jnp.asarray(np.tile(np.arange(B, dtype=np.float32), (F, 1)))
    st0 = _init_state(cfg, F, jnp.float32(0.0), jnp.float32(4.0), B)
    args = (hist, cuts, jnp.ones((F,), bool), jax.random.PRNGKey(0), cfg, 0)
    marked = _level_update(st0, *args, mark_built=True)
    plain = _level_update(st0, *args)
    assert float(plain.ptab[0, 0]) == 1.0
    assert float(marked.ptab[0, 0]) == 1.0  # HL 1 <= HR 3: the left
    np.testing.assert_array_equal(np.asarray(marked.ptab[:, 1:]),
                                  np.asarray(plain.ptab[:, 1:]))
    # the mirrored histogram puts the small child on the right
    hist_r = jnp.asarray(np.concatenate([-hg[:, :, ::-1], hh], axis=1))
    marked_r = _level_update(st0, hist_r, *args[1:], mark_built=True)
    assert float(marked_r.ptab[0, 0]) == 2.0
    assert float(marked_r.ptab[0, 2]) == 2.0  # bins 0-2 left, bin 3 right
    # a node that cannot split stays 0
    flat = jnp.asarray(np.concatenate([np.zeros_like(hg), hh], axis=1))
    assert float(_level_update(st0, flat, *args[1:],
                               mark_built=True).ptab[0, 0]) == 0.0
