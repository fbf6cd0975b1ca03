"""Distributed (8 virtual devices) vs single-device parity.

Reference analog: distributed==single-process tree parity asserted by
gpu_hist's debug_synchronize (updater_gpu_hist.cu:49) and the Dask
LocalCluster tests (test_with_dask.py). Here: same cuts + same data ->
the shard_map'd grower with psum'd histograms must reproduce the
single-device tree (up to float-sum reordering)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xgboost_tpu.data.quantile import BinnedMatrix, bin_matrix, compute_cuts
from xgboost_tpu.parallel import (
    distributed_compute_cuts,
    distributed_grow_tree,
    make_mesh,
    shard_rows,
)
from xgboost_tpu.tree.grow import GrowParams, grow_tree
from xgboost_tpu.tree.param import SplitParams

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 2, reason="needs multi-device (virtual CPU mesh)"
)


def _data(n=1024, f=6, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)
    margin = np.zeros(n, np.float32)
    p = 1 / (1 + np.exp(-margin))
    grad = (p - y).astype(np.float32)
    hess = (p * (1 - p)).astype(np.float32)
    return X, grad, hess


def test_distributed_tree_matches_single_device():
    X, grad, hess = _data()
    mesh = make_mesh()
    cuts = compute_cuts(X, max_bin=32)
    bins = bin_matrix(X, cuts)
    cfg = GrowParams(max_depth=4, split=SplitParams())
    key = jax.random.PRNGKey(7)

    single = grow_tree(bins, jnp.asarray(grad), jnp.asarray(hess),
                       jnp.asarray(cuts.values), key, cfg)
    dist = distributed_grow_tree(
        mesh,
        shard_rows(bins, mesh),
        shard_rows(jnp.asarray(grad), mesh),
        shard_rows(jnp.asarray(hess), mesh),
        jnp.asarray(cuts.values), key, cfg,
    )
    # identical split structure and near-identical stats
    np.testing.assert_array_equal(np.asarray(single.is_split), np.asarray(dist.is_split))
    np.testing.assert_array_equal(np.asarray(single.feature), np.asarray(dist.feature))
    np.testing.assert_array_equal(np.asarray(single.split_bin), np.asarray(dist.split_bin))
    np.testing.assert_allclose(
        np.asarray(single.node_weight), np.asarray(dist.node_weight), rtol=1e-4, atol=1e-5
    )
    np.testing.assert_array_equal(np.asarray(single.positions), np.asarray(dist.positions))


def test_distributed_sketch_close_to_exact():
    rng = np.random.RandomState(3)
    X = rng.randn(4096, 5).astype(np.float32)
    mesh = make_mesh()
    exact = compute_cuts(X, max_bin=16)
    approx = distributed_compute_cuts(mesh, shard_rows(jnp.asarray(X), mesh), max_bin=16)
    # interior cuts should deviate by at most a small quantile fraction
    for f in range(5):
        # compare achieved CDF positions rather than raw values
        pos_e = np.searchsorted(np.sort(X[:, f]), exact.values[f, :-1])
        pos_a = np.searchsorted(np.sort(X[:, f]), approx.values[f, :-1])
        np.testing.assert_allclose(pos_e, pos_a, atol=4096 * 0.02)


@pytest.mark.slow
def test_distributed_full_training_parity():
    """End-to-end: margins after 3 distributed rounds match single-device."""
    import xgboost_tpu as xgb
    from xgboost_tpu.tree.grow import leaf_value_map, prune_heap

    X, grad, hess = _data(512, 5, seed=9)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)
    mesh = make_mesh()
    cuts = compute_cuts(X, max_bin=16)
    bins = bin_matrix(X, cuts)
    cfg = GrowParams(max_depth=3, split=SplitParams())

    def run(distributed: bool):
        margin = jnp.zeros((512,), jnp.float32)
        b = shard_rows(bins, mesh) if distributed else bins
        for it in range(3):
            p = jax.nn.sigmoid(margin)
            g, h = p - y, p * (1 - p)
            if distributed:
                g, h = shard_rows(g, mesh), shard_rows(h, mesh)
                heap = distributed_grow_tree(mesh, b, g, h, jnp.asarray(cuts.values),
                                             jax.random.PRNGKey(it), cfg)
            else:
                heap = grow_tree(b, g, h, jnp.asarray(cuts.values),
                                 jax.random.PRNGKey(it), cfg)
            pruned = prune_heap(np.asarray(heap.is_split), np.asarray(heap.loss_chg), 0.0)
            lmap = jnp.asarray(leaf_value_map(pruned, np.asarray(heap.node_weight), 0.3))
            margin = margin + lmap[heap.positions]
        return np.asarray(margin)

    np.testing.assert_allclose(run(False), run(True), rtol=1e-4, atol=1e-5)


@pytest.mark.slow
def test_train_under_mesh_matches_single_device():
    """THE wiring test: xgb.train() inside mesh_context must reproduce the
    single-device model (reference oracle: distributed==single-process
    parity, gpu_hist debug_synchronize / test_with_dask.py)."""
    import xgboost_tpu as xgb
    from xgboost_tpu.parallel import mesh_context

    rng = np.random.RandomState(5)
    n = 1000  # deliberately NOT divisible by 8: exercises row padding
    X = rng.randn(n, 6).astype(np.float32)
    X[rng.rand(n, 6) < 0.05] = np.nan
    y = (np.nan_to_num(X[:, 0]) + 0.5 * np.nan_to_num(X[:, 1]) > 0).astype(np.float32)
    params = {"objective": "binary:logistic", "max_depth": 4, "eta": 0.5,
              "max_bin": 32}

    def run(distributed, share_cuts=True):
        d = xgb.DMatrix(X, label=y)
        if share_cuts:
            d.get_binned(params["max_bin"])  # pre-bin: exact cuts cached
        if distributed:
            with mesh_context(make_mesh()):
                return xgb.train(params, d, 5, verbose_eval=False)
        return xgb.train(params, d, 5, verbose_eval=False)

    b_single, b_mesh = run(False), run(True)
    d_eval = xgb.DMatrix(X)
    # same cuts -> identical tree structures (splits on psum'd histograms)
    for t1, t2 in zip(b_single._gbm.model.trees, b_mesh._gbm.model.trees):
        np.testing.assert_array_equal(t1.split_indices, t2.split_indices)
        np.testing.assert_array_equal(t1.left_children, t2.left_children)
        np.testing.assert_allclose(t1.split_conditions, t2.split_conditions,
                                   rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(
        b_single.predict(d_eval), b_mesh.predict(d_eval), rtol=1e-4, atol=1e-5
    )
    # distributed SKETCH path (quantile.cc:270 analog): cuts are approximate,
    # so assert metric parity rather than structure
    from xgboost_tpu.metric import create_metric

    b_sketch = run(True, share_cuts=False)
    auc = create_metric("auc")
    a1 = float(auc.evaluate(b_single.predict(d_eval), y))
    a2 = float(auc.evaluate(b_sketch.predict(d_eval), y))
    assert abs(a1 - a2) < 0.01, (a1, a2)


@pytest.mark.slow
def test_train_under_mesh_lossguide():
    import xgboost_tpu as xgb
    from xgboost_tpu.parallel import mesh_context

    rng = np.random.RandomState(6)
    X = rng.randn(512, 5).astype(np.float32)
    y = (X[:, 0] * X[:, 1] > 0).astype(np.float32)
    params = {"objective": "binary:logistic", "grow_policy": "lossguide",
              "max_leaves": 16, "max_depth": 0, "eta": 0.5, "max_bin": 32}
    d = xgb.DMatrix(X, label=y)
    b1 = xgb.train(params, d, 3, verbose_eval=False)
    d2 = xgb.DMatrix(X, label=y)
    d2.get_binned(params["max_bin"])  # share exact cuts
    with mesh_context(make_mesh()):
        b2 = xgb.train(params, d2, 3, verbose_eval=False)
    np.testing.assert_allclose(
        b1.predict(d), b2.predict(d), rtol=1e-4, atol=1e-5
    )


@pytest.mark.slow
def test_mesh_update_many_scan_matches_per_round():
    """The whole-chunk shard_map scan (distributed_boost_rounds_scan) must
    reproduce mesh per-round training on shared cuts."""
    import xgboost_tpu as xgb
    from xgboost_tpu.parallel import mesh_context

    rng = np.random.RandomState(4)
    X = rng.randn(2051, 6).astype(np.float32)  # not divisible: padding path
    y = (X.sum(1) > 0).astype(np.float32)
    params = {"objective": "binary:logistic", "max_depth": 3, "eta": 0.4,
              "subsample": 0.9, "seed": 3}
    mesh = make_mesh(8)
    with mesh_context(mesh):
        d1 = xgb.DMatrix(X, label=y)
        d1.get_binned(256)
        b1 = xgb.Booster(params, [d1])
        b1.update_many(d1, 0, 6, chunk=4)
        p1 = b1.predict(d1)

        d2 = xgb.DMatrix(X, label=y)
        d2._binned = d1._binned  # identical distributed-sketch cuts
        b2 = xgb.Booster(params, [d2])
        for i in range(6):
            b2.update(d2, i)
        p2 = b2.predict(d2)
    assert b1.num_boosted_rounds() == 6
    np.testing.assert_allclose(p1, p2, rtol=1e-4, atol=1e-5)


@pytest.mark.slow
def test_mosaic_kernels_under_shard_map_interpret():
    """The REAL pallas level-kernel bodies (construct AND hoisted) execute
    under shard_map via interpret mode and grow trees matching the XLA
    fallback — pinning the mesh+pallas composition round 3 had gated off
    (review weak #6). The interpreted replay cannot run under the VMA
    checker (it re-evaluates the kernel jaxpr op-by-op, which real Mosaic
    lowering never does), so this test drives its own check_vma=False
    shard_map; the boundary proof itself is exercised with check_vma=True
    by every other mesh test through the library path."""
    import dataclasses

    import numpy as np
    from jax.sharding import PartitionSpec as P

    from xgboost_tpu.parallel.mesh import ROW_AXIS, make_mesh, shard_rows
    from xgboost_tpu.tree import hist_kernel as hk
    from xgboost_tpu.tree.grow import GrowParams
    from xgboost_tpu.tree.grow_fused import GrownTree, grow_tree_fused
    from xgboost_tpu.tree.hist_kernel import build_onehot

    rng = np.random.RandomState(0)
    n_pad, F, B = 4096, 4, 16  # multiple of both row tiles
    bins = rng.randint(0, B, size=(n_pad, F)).astype(np.int32)
    g = rng.randn(n_pad).astype(np.float32)
    h = np.abs(rng.randn(n_pad)).astype(np.float32) + 0.1
    cut_vals = np.sort(rng.randn(F, B).astype(np.float32), axis=1)
    cfg = dataclasses.replace(GrowParams(max_depth=3), axis_name=ROW_AXIS)
    mesh = make_mesh(4)
    out_specs = GrownTree(**{f: (P(ROW_AXIS) if f == "delta" else P())
                             for f in GrownTree._fields})

    def run(hoist: bool):
        def grower(bins_s, g_s, h_s, cuts_s, key_s):
            onehot = build_onehot(bins_s, B=B) if hoist else None
            return grow_tree_fused(bins_s, g_s, h_s, cuts_s, key_s,
                                   jnp.float32(0.3), jnp.float32(0.0),
                                   cfg=cfg, onehot=onehot)

        fn = jax.shard_map(
            grower, mesh=mesh,
            in_specs=(P(ROW_AXIS, None), P(ROW_AXIS), P(ROW_AXIS),
                      P(None, None), P()),
            out_specs=out_specs, check_vma=False)
        t = fn(shard_rows(jnp.asarray(bins), mesh),
               shard_rows(jnp.asarray(g), mesh),
               shard_rows(jnp.asarray(h), mesh),
               jnp.asarray(cut_vals), jax.random.PRNGKey(0))
        return {f: np.asarray(getattr(t, f))
                for f in ("keep", "feature", "split_bin", "leaf_value")}

    ref = run(False)  # XLA fallback (use_pallas False on CPU)
    orig_up, orig_int = hk.use_pallas, hk._INTERPRET
    try:
        hk._INTERPRET = True
        hk.use_pallas = lambda: True  # force the pallas dispatch path
        got_construct = run(False)
        got_hoisted = run(True)
    finally:
        hk._INTERPRET = orig_int
        hk.use_pallas = orig_up
    for name, got in (("construct", got_construct),
                      ("hoisted", got_hoisted)):
        for f in ref:
            np.testing.assert_allclose(got[f], ref[f], rtol=2e-4,
                                       atol=2e-4, err_msg=f"{name}:{f}")
