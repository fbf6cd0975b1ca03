"""Rows on the lanes (ISSUE 31): the per-row arrays of a tree (positions
``[1, n]`` int32, gradients ``[2, n]`` float32) have the rows on the lane axis
from the grower's root to ``leaf_delta``. On the TPU a ``[n, 1]`` or ``[n, 2]``
array is padded to 128 lanes (512 bytes a row where 4 or 8 are used), in HBM
and in VMEM, and every VPU op on it works one lane in 128.

(a) no such array is left in a tree's program on the Pallas route, one device
and under a four-device mesh; (b) the kernels in the new form give the XLA
route's positions, trees and margins; (c) the routing kernel's VMEM model
counts the new blocks. Kernel bodies run in interpret mode on the CPU; the
chip's compiler is held to the shapes in ``tests/test_device_phases.py``."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import xgboost_tpu as xgb
from xgboost_tpu import dispatch
from xgboost_tpu.parallel import make_mesh
from xgboost_tpu.parallel.mesh import ROW_AXIS
from xgboost_tpu.tree import hist_kernel as hk
from xgboost_tpu.tree.grow import GrowParams
from xgboost_tpu.tree.grow_fused import GrownTree, grow_tree_fused

LANES = 128


@pytest.fixture()
def interpreted(monkeypatch):
    """The Pallas dispatch, its kernel bodies interpreted."""
    monkeypatch.setattr(hk, "use_pallas", lambda: True)
    monkeypatch.setattr(hk, "_INTERPRET", True)
    jax.clear_caches()  # routes are resolved when a program is traced
    yield
    jax.clear_caches()


# ---------------------------------------------------------------------------
# (a) no lane-padded per-row array in a tree's program
# ---------------------------------------------------------------------------


def _avals(jaxpr):
    """Every array a jaxpr names, through every nested jaxpr (pjit,
    shard_map, pallas_call, scan, cond)."""
    for v in list(jaxpr.invars) + list(jaxpr.constvars):
        yield v.aval
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield v.aval
        for param in eqn.params.values():
            for sub in (param if isinstance(param, (list, tuple))
                        else [param]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _avals(sub)


def _padded_row_arrays(jaxpr, row_counts):
    return sorted({(tuple(a.shape), str(a.dtype)) for a in _avals(jaxpr)
                   if getattr(a, "ndim", 0) >= 2 and a.shape[0] in row_counts
                   and a.shape[-1] < LANES})


def _tree_program(mesh):
    """A depth-3 tree as the cells grow it: partial hoist, sibling
    subtraction below the root, the last routing and ``leaf_delta``. 128
    features and a 128-lane resident one-hot, so that the bins themselves
    are no exception to the rule."""
    n, F, B, Fh = 4 * hk.TR, LANES, 8, 16
    cfg = GrowParams(max_depth=3)
    if mesh is not None:
        cfg = dataclasses.replace(cfg, axis_name=ROW_AXIS)

    def grower(bins, g, h, cuts, key, onehot):
        return grow_tree_fused(bins, g, h, cuts, key, jnp.float32(0.3),
                               jnp.float32(0.0), cfg=cfg, onehot=onehot)

    S = jax.ShapeDtypeStruct
    args = (S((n, F), jnp.int32), S((n,), jnp.float32), S((n,), jnp.float32),
            S((F, B), jnp.float32), jax.random.PRNGKey(0),
            S((n, Fh * B), jnp.int8))
    if mesh is not None:
        grower = jax.shard_map(
            grower, mesh=mesh,
            in_specs=(P(ROW_AXIS, None), P(ROW_AXIS), P(ROW_AXIS),
                      P(None, None), P(), P(ROW_AXIS, None)),
            out_specs=GrownTree(**{f: (P(ROW_AXIS) if f == "delta" else P())
                                   for f in GrownTree._fields}),
            check_vma=True)
    return n, jax.make_jaxpr(grower)(*args)


@pytest.mark.parametrize("devices", [1, 4])
def test_tree_program_holds_no_lane_padded_row_array(interpreted, devices):
    if len(jax.devices()) < devices:
        pytest.skip(f"needs {devices} devices")
    n, closed = _tree_program(make_mesh(devices) if devices > 1 else None)
    routes = dispatch.last_decisions()
    assert (routes["level_hist"], routes["level_partition"],
            routes["leaf_delta"], routes["sibling_sub"]) == (
        "pallas", "pallas", "pallas", "on"), routes
    text = str(closed)
    assert text.count("pallas_call") >= 4  # three levels and the routing
    assert _padded_row_arrays(closed.jaxpr, {n, n // devices}) == []
    # the rule finds what it is for: the form this issue took away
    bad = jax.make_jaxpr(lambda g, h: jnp.stack([g, h], axis=-1))(
        jnp.zeros((n,)), jnp.zeros((n,)))
    assert ((n, 2), "float32") in _padded_row_arrays(bad.jaxpr, {n})


# ---------------------------------------------------------------------------
# (b) the XLA route's positions, trees and margins
# ---------------------------------------------------------------------------


def _level_case(d, cat, seed, n=4 * 256, F=5, B=16):
    """Rows at level ``d - 1`` over four 256-row tiles: a tenth of the
    cells missing, a leaf above the level, zero-gradient padding rows, one
    parent that does not split, a categorical parent where asked."""
    rng = np.random.RandomState(seed)
    Kp = 1 << (d - 1)
    prev = Kp - 1
    bins = rng.randint(0, B, size=(n, F)).astype(np.int32)
    bins[rng.rand(n, F) < 0.1] = B  # missing
    gh = rng.randn(2, n).astype(np.float32)
    gh[1] = np.abs(gh[1])
    pos = rng.randint(prev, prev + Kp, size=(1, n)).astype(np.int32)
    if d >= 2:
        pos[0, rng.rand(n) < 0.1] = 0
    bins[-40:], gh[:, -40:] = B, 0.0
    ptab = np.zeros((Kp, 5 + B if cat else 4), np.float32)
    ptab[:, 0] = rng.randint(1, 3, Kp)
    ptab[:, 1] = rng.randint(0, F, Kp)
    ptab[:, 2] = rng.randint(0, B, Kp)
    ptab[:, 3] = rng.randint(0, 2, Kp)
    if d >= 2:
        ptab[Kp - 1, 0] = 0.0
    if cat:
        ptab[0, 4] = 1.0
        ptab[0, 5:] = rng.rand(B) < 0.4
    return tuple(jnp.asarray(a) for a in (bins, pos, gh, ptab))


@pytest.mark.parametrize("sub", [False, True])
@pytest.mark.parametrize("kernel", ["hoisted", "construct"])
@pytest.mark.parametrize("d,cat,seed", [(1, False, 0), (2, True, 1),
                                        (3, False, 2), (4, True, 3)])
def test_level_kernels_equal_the_xla_level(interpreted, kernel, sub, d, cat,
                                           seed):
    bins, pos, gh, ptab = _level_case(d, cat, seed)
    n, F = bins.shape
    B, K, Kp = 16, 1 << d, 1 << (d - 1)
    assert pos.shape == (1, n) and gh.shape == (2, n)
    want_pos, want = hk.fused_level_xla(bins, pos, gh, ptab, K=K, Kp=Kp, B=B,
                                        d=d)
    kw = dict(F=F, K=K, Kp=Kp, B=B, d=d, tr=256, sub=sub)
    binsT = hk._feature_major(bins, hk._SUBLANES, B)
    if kernel == "hoisted":
        got_pos, hist = hk._hoisted_level_pallas(
            binsT, hk.build_onehot(bins[:, :2], B=B), pos, gh, ptab, **kw)
    else:
        got_pos, hist = hk._fused_level_pallas(binsT, pos, gh, ptab, **kw)
    assert got_pos.shape == (1, n) and got_pos.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got_pos), np.asarray(want_pos))
    if sub:
        _, parent = hk.fused_level_xla(bins, pos, gh, ptab, K=Kp, Kp=0, B=B,
                                       d=d - 1)
        assert hist.shape == (F, 2 * Kp, B)
        hist = hk.derive_siblings(parent, hist, ptab)
    np.testing.assert_allclose(np.asarray(hist), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    # the tree's last routing, through the dispatcher
    routed = hk.partition_apply(bins, pos, ptab, Kp=Kp, B=B, d=d, pallas=True)
    assert dispatch.last_decisions()["level_partition"] == (
        "pallas" if n % hk.TR == 0 else "xla")
    np.testing.assert_array_equal(np.asarray(routed), np.asarray(want_pos))


@pytest.mark.parametrize("nodes", [7, 63, 511])
def test_leaf_delta_three_row_dot_is_the_gather_exactly(nodes):
    """``tab^T [3, P] @ onehot [P, n]``: three bf16 terms carry a float32
    leaf value exactly, so the ``[3, n]`` form equals the gather bit for
    bit, whatever the values' spread."""
    rng = np.random.RandomState(nodes)
    n = 3 * 1024
    lv = (rng.randn(nodes) * 10.0 ** rng.randint(-6, 3, nodes)
          ).astype(np.float32)
    pos = jnp.asarray(rng.randint(0, nodes, size=(1, n)).astype(np.int32))
    pad = max(128, 1 << (nodes - 1).bit_length())
    got = hk.leaf_delta(pos, jnp.asarray(lv), pad, pallas=True)
    assert dispatch.last_decisions()["leaf_delta"] == "pallas"
    jaxpr = jax.make_jaxpr(
        lambda p, v: hk.leaf_delta(p, v, pad, pallas=True))(
        pos, jnp.asarray(lv))
    dots = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "dot_general"]
    assert [tuple(e.outvars[0].aval.shape) for e in dots] == [(3, n)]
    assert got.shape == (n,)
    np.testing.assert_array_equal(np.asarray(got), lv[np.asarray(pos)[0]])
    want = hk.leaf_delta(pos, jnp.asarray(lv), pad, pallas=False)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _tree_inputs(n_pad=2048, F=5, B=16, seed=0):
    rng = np.random.RandomState(seed)
    bins = rng.randint(0, B, size=(n_pad, F)).astype(np.int32)
    bins[rng.rand(n_pad, F) < 0.05] = B  # missing
    score = (bins[:, 0] > 7) * 1.0 + (bins[:, 1] % 3 == 0) * 0.7 \
        - (bins[:, 2] > 11) * 0.5
    y = (score + 0.3 * rng.randn(n_pad) > 0.6).astype(np.float32)
    g = (0.5 - y).astype(np.float32)
    h = np.full(n_pad, 0.25, np.float32)
    bins[-100:], g[-100:], h[-100:] = B, 0.0, 0.0  # padding rows
    cuts = np.sort(rng.randn(F, B).astype(np.float32), axis=1)
    return [jnp.asarray(a) for a in (bins, g, h, cuts)]


XLA_ROUTE = "level_hist=xla,level_partition=xla,leaf_delta=xla"


@pytest.mark.parametrize("case,pin", [("numerical", ""),
                                      ("numerical", "sibling_sub=off"),
                                      ("categorical", ""),
                                      ("categorical", "sibling_sub=off")])
def test_tree_on_the_pallas_route_is_the_xla_routes(interpreted, monkeypatch,
                                                    case, pin):
    """One tree over two ``TR`` tiles, missing values in it, through the
    interpreted kernels and through the XLA level, routing and gather:
    the same splits, the same leaf of every row to 1e-5 (the two bf16 terms
    a gradient against exact f32 sums)."""
    cfg = GrowParams(max_depth=4)
    if case == "categorical":
        cfg = dataclasses.replace(cfg, categorical=(1,), cat_partition=(3,))

    def grow(pins):
        monkeypatch.setenv("XGBTPU_DISPATCH", pins)
        jax.clear_caches()
        bins, g, h, cuts = _tree_inputs()
        onehot = hk.build_onehot(bins[:, :3], B=cuts.shape[1])
        tree = grow_tree_fused(bins, g, h, cuts, jax.random.PRNGKey(0),
                               jnp.float32(0.3), jnp.float32(0.0), cfg=cfg,
                               onehot=onehot)
        return tree, dispatch.last_decisions()

    got, routes = grow(pin)
    assert (routes["level_hist"], routes["level_partition"],
            routes["leaf_delta"]) == ("pallas", "pallas", "pallas")
    assert routes["sibling_sub"] == ("off" if pin else "on")
    want, routes = grow(XLA_ROUTE)
    assert (routes["level_hist"], routes["level_partition"],
            routes["leaf_delta"]) == ("xla", "xla", "xla")
    assert np.asarray(want.keep).sum() >= 6, "a tree too small to tell"
    for f in ("keep", "feature", "split_bin", "default_left", "cat_set"):
        np.testing.assert_array_equal(np.asarray(getattr(got, f)),
                                      np.asarray(getattr(want, f)), err_msg=f)
    for f in ("leaf_value", "delta", "node_g", "node_h"):
        np.testing.assert_allclose(np.asarray(getattr(got, f)),
                                   np.asarray(getattr(want, f)), rtol=0,
                                   atol=1e-5 if f in ("leaf_value", "delta")
                                   else 2e-3, err_msg=f)


def test_fit_margins_on_the_pallas_route_are_the_xla_routes(interpreted,
                                                            monkeypatch):
    """A whole fit with missing values through the public API: the training
    margins of the interpreted kernels against the XLA route's."""
    rng = np.random.RandomState(5)
    X = rng.randn(1500, 6).astype(np.float32)
    X[rng.rand(*X.shape) < 0.08] = np.nan
    y = (np.nan_to_num(X) @ rng.randn(6) > 0).astype(np.float32)
    params = {"objective": "binary:logistic", "max_depth": 3, "max_bin": 16,
              "eta": 0.3, "seed": 0}

    def fit(pins):
        monkeypatch.setenv("XGBTPU_DISPATCH", pins)
        jax.clear_caches()
        d = xgb.DMatrix(X, label=y)
        bst = xgb.train(params, d, num_boost_round=4)
        return (np.asarray(bst.predict(d, output_margin=True)),
                dispatch.last_decisions())

    got, routes = fit("")
    assert (routes["level_hist"], routes["level_partition"],
            routes["leaf_delta"]) == ("pallas", "pallas", "pallas")
    want, _ = fit(XLA_ROUTE)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# (c) the routing kernel's VMEM model
# ---------------------------------------------------------------------------

# padded rows, features, parents of the last level (PERF.md section 4)
CELLS = {"anchor_train": (750_592, 50, 32),
         "higgs_train_x4": (2_625_536, 28, 128),
         "mslr_rank_train": (2_271_232, 136, 32)}


def _route_step_bytes(F, Kp, W):
    """One ``_route_rows_pallas`` grid step by its blocks and values, as
    ``pallas_route_fits``'s docstring names them."""
    def up(x, m):
        return -(-x // m) * m

    TR = hk.TR
    bins_tile = TR * up(F, LANES) * 4
    pos_row = 8 * TR * 4  # a (1, TR) block: 8 sublanes, 32 bytes a row
    table = up(Kp, 8) * up(W, LANES) * 4
    blocks = 2 * (bins_tile + 2 * pos_row + table)  # double-buffered
    values = (bins_tile + bins_tile + bins_tile // 2  # loaded, f32, bf16
              + up(Kp, 8) * up(F, LANES) * (4 + 2)  # feature one-hot
              + 3 * up(Kp, 8) * TR * 4  # node one-hot, nodes' bins, product
              + up(W, 8) * TR * 4  # decisions
              + 16 * pos_row  # [1, TR] rows
              + (2 * up(W - 5, 8) * TR * 4 if W > 4 else 0))
    return blocks + values


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_pallas_route_fits_the_cells_by_the_new_blocks(monkeypatch, cell):
    n, F, Kp = CELLS[cell]
    assert hk.pallas_route_fits(n, F, Kp, 4)
    step = _route_step_bytes(F, Kp, 4)
    assert step < hk._VMEM_HOIST_BUDGET
    # the cells keep the whole ``TR`` tile (since PR 35 the tile is chosen
    # from the width, ``_route_tr``), and the model at that tile is that
    # arithmetic to the byte: a byte less and the tile halves ...
    assert hk._route_tr(n, F, Kp, 4) == hk.TR
    assert hk._route_vmem_bytes(hk.TR, F, Kp, 4) == step
    monkeypatch.setattr(hk, "_VMEM_HOIST_BUDGET", step)
    assert hk._route_tr(n, F, Kp, 4) == hk.TR
    monkeypatch.setattr(hk, "_VMEM_HOIST_BUDGET", step - 1)
    assert hk._route_tr(n, F, Kp, 4) == hk.TR // 2
    assert hk.pallas_route_fits(n, F, Kp, 4)
    # ... in which positions in and out are (1, tr) rows: 128 KiB a step
    # double-buffered, where the (tr, 1) columns took 2 MiB
    doc = " ".join(hk._route_vmem_bytes.__doc__.split())
    assert "``(1, tr)`` rows" in doc and "``(Kp, W)``" in doc
    assert "(tr, 1)" not in doc.replace("``(tr, 1)`` columns took", "")
    assert 2 * 2 * 8 * hk.TR * 4 == 128 * 1024
