"""The serving fast path (ISSUE 2 tentpole): zero-copy inplace predict
parity against the DMatrix path, shape-bucketed program-cache reuse
(verified through the registry counters), the forest snapshot cache, the
native CPU walker, and the pallas-blacklist retry escape."""

import numpy as np
import pytest

import xgboost_tpu as xgb
from xgboost_tpu.observability import REGISTRY
from xgboost_tpu.predictor import serving


def _counter(name: str) -> float:
    fam = REGISTRY.get(name)
    return 0.0 if fam is None else fam.value


def _data(n=1200, F=8, seed=0, nan_frac=0.15):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, F).astype(np.float32)
    if nan_frac:
        X[rng.rand(n, F) < nan_frac] = np.nan
    y = (np.nan_to_num(X).sum(1) > 0).astype(np.float32)
    return X, y


def _train(X, y, extra=None, rounds=6):
    params = {"objective": "binary:logistic", "max_depth": 4,
              "verbosity": 0, "seed": 3}
    params.update(extra or {})
    return xgb.train(params, xgb.DMatrix(X, label=y), rounds,
                     verbose_eval=False)


def test_inplace_margin_parity_dense_nan():
    """Acceptance: margin parity |diff| < 1e-5 vs the DMatrix path, with
    NaN missing routed through default children."""
    X, y = _data()
    bst = _train(X, y)
    m_d = np.asarray(bst.predict(xgb.DMatrix(X), output_margin=True))
    m_i = np.asarray(bst.inplace_predict(X, predict_type="margin"))
    assert np.max(np.abs(m_d - m_i)) < 1e-5
    p_d = np.asarray(bst.predict(xgb.DMatrix(X)))
    p_i = np.asarray(bst.inplace_predict(X))
    assert np.max(np.abs(p_d - p_i)) < 1e-5


def test_inplace_parity_csr_and_missing_sentinel():
    import scipy.sparse as sp

    X, y = _data(nan_frac=0.0)
    bst = _train(X, y)
    Xs = sp.csr_matrix(X)
    np.testing.assert_allclose(
        bst.inplace_predict(Xs), bst.predict(xgb.DMatrix(Xs)), atol=1e-5)
    # sentinel: -999 stored values must act like NaN on both paths
    Xm = X.copy()
    Xm[::5, 0] = -999.0
    np.testing.assert_allclose(
        bst.inplace_predict(Xm, missing=-999.0),
        bst.predict(xgb.DMatrix(Xm, missing=-999.0)), atol=1e-5)
    # CSR with sentinel among STORED values
    Xsm = sp.csr_matrix(Xm)
    np.testing.assert_allclose(
        bst.inplace_predict(Xsm, missing=-999.0),
        bst.predict(xgb.DMatrix(Xm, missing=-999.0)), atol=1e-5)


def test_inplace_iteration_range_and_multiclass():
    X, y = _data()
    bst = _train(X, y)
    np.testing.assert_allclose(
        bst.inplace_predict(X, iteration_range=(1, 4)),
        bst.predict(xgb.DMatrix(X), iteration_range=(1, 4)), atol=1e-5)
    # (0, 0) means all rounds, like the reference
    np.testing.assert_allclose(
        bst.inplace_predict(X, iteration_range=(0, 0)),
        bst.predict(xgb.DMatrix(X)), atol=1e-5)
    rng = np.random.RandomState(1)
    y3 = rng.randint(0, 3, len(X)).astype(np.float32)
    b3 = _train(X, y3, {"objective": "multi:softprob", "num_class": 3},
                rounds=4)
    np.testing.assert_allclose(
        b3.inplace_predict(X), b3.predict(xgb.DMatrix(X)), atol=1e-5)
    np.testing.assert_allclose(
        b3.inplace_predict(X, iteration_range=(0, 2)),
        b3.predict(xgb.DMatrix(X), iteration_range=(0, 2)), atol=1e-5)


def test_inplace_base_margin_and_strict_shape():
    X, y = _data(300)
    bst = _train(X, y, rounds=3)
    bm = np.linspace(-1, 1, len(X)).astype(np.float32)
    d = xgb.DMatrix(X)
    d.set_base_margin(bm)
    np.testing.assert_allclose(
        bst.inplace_predict(X, base_margin=bm, predict_type="margin"),
        bst.predict(d, output_margin=True), atol=1e-5)
    assert bst.inplace_predict(X[:7], strict_shape=True).shape == (7, 1)
    assert bst.inplace_predict(X[:7]).shape == (7,)
    with pytest.raises(ValueError):
        bst.inplace_predict(X[:, :4])  # feature-count mismatch


def test_bucket_schedule():
    assert serving.bucket_rows(1) == 16
    assert serving.bucket_rows(16) == 16
    assert serving.bucket_rows(17) == 32
    assert serving.bucket_rows(4096) == 4096
    assert serving.bucket_rows(8193) == 16384
    assert serving.bucket_rows(100_000) == 106_496  # multiple of 8192


def test_ragged_stream_bounded_compiles():
    """Acceptance: a ragged batch-size stream triggers a bounded number of
    compiles (program-cache misses), verified via the registry counters.
    Native walking is disabled so the stream exercises the bucketed
    XLA-program path."""
    X, y = _data(4096, 6, seed=7)
    bst = _train(X, y, rounds=4)
    rng = np.random.RandomState(0)
    import os

    os.environ["XGBTPU_NATIVE_SERVING"] = "0"
    try:
        bst.inplace_predict(X[:1])  # settle the forest snapshot
        h0, m0 = (_counter("predict_bucket_cache_hits_total"),
                  _counter("predict_bucket_cache_misses_total"))
        sizes = rng.randint(1, 4097, 1000)
        for n in sizes:
            bst.inplace_predict(X[:n])
        compiles = _counter("predict_bucket_cache_misses_total") - m0
        hits = _counter("predict_bucket_cache_hits_total") - h0
        # sizes in [1, 4096] touch at most buckets {16, 32, ..., 4096} = 9
        assert compiles <= 12, compiles
        assert hits == len(sizes) - compiles
    finally:
        os.environ.pop("XGBTPU_NATIVE_SERVING", None)


def test_serving_cache_lru_bound_and_evictions():
    cache = serving.ServingCache(maxsize=2)
    built = []

    def mk(tag):
        def build():
            built.append(tag)
            return lambda: tag
        return build

    e0 = _counter("predict_bucket_cache_evictions_total")
    assert cache.program(("a",), mk("a"))() == "a"
    assert cache.program(("b",), mk("b"))() == "b"
    assert cache.program(("a",), mk("a2"))() == "a"  # hit, no rebuild
    assert cache.program(("c",), mk("c"))() == "c"  # evicts b (LRU)
    assert len(cache) == 2
    assert cache.program(("b",), mk("b2"))() == "b2"  # rebuilt after evict
    assert built == ["a", "b", "c", "b2"]
    assert _counter("predict_bucket_cache_evictions_total") - e0 >= 2


def test_forest_snapshot_cache_reused():
    X, y = _data(500)
    bst = _train(X, y, rounds=3)
    bst.inplace_predict(X[:10])
    h0 = _counter("predict_forest_snapshot_hits_total")
    m0 = _counter("predict_forest_snapshot_misses_total")
    for _ in range(20):
        bst.inplace_predict(X[:10])
    assert _counter("predict_forest_snapshot_misses_total") == m0
    assert _counter("predict_forest_snapshot_hits_total") - h0 == 20
    # growing the model invalidates by key: one new stack, then cached
    bst.update(xgb.DMatrix(X, label=y), 3)
    bst.inplace_predict(X[:10])
    assert _counter("predict_forest_snapshot_misses_total") == m0 + 1


def test_native_walker_matches_xla_program():
    """The native CPU walker and the bucketed XLA program must agree to
    float32 round-off on the same forest."""
    from xgboost_tpu.native import get_serving_lib

    if get_serving_lib() is None:
        pytest.skip("native serving walker unavailable")
    import os

    X, y = _data(700, 10, seed=11)
    bst = _train(X, y, rounds=5)
    native = np.asarray(bst.inplace_predict(X, predict_type="margin"))
    n0 = _counter("predict_native_rows_total")
    bst.inplace_predict(X)
    assert _counter("predict_native_rows_total") - n0 == len(X)
    os.environ["XGBTPU_NATIVE_SERVING"] = "0"
    try:
        xla = np.asarray(bst.inplace_predict(X, predict_type="margin"))
    finally:
        os.environ.pop("XGBTPU_NATIVE_SERVING", None)
    assert np.max(np.abs(native - xla)) < 1e-5


def test_native_walker_safety_envelope():
    """Inputs the C walker cannot touch safely: out-of-range CSR indices
    are an input ERROR (scipy does not bounds-check caller-built arrays),
    and a too-narrow input with validate_features=False falls back to the
    clamping XLA path instead of reading raw memory."""
    import scipy.sparse as sp

    X, y = _data(200, 6, seed=4, nan_frac=0.0)
    bst = _train(X, y, rounds=3)
    bad = sp.csr_matrix(
        (np.ones(1, np.float32), np.array([99]), np.array([0, 1])),
        shape=(1, 6))
    with pytest.raises((ValueError, IndexError)):
        bst.inplace_predict(bad)
    # narrow input, validation off: must not crash; parity with the
    # DMatrix path's clamped walk
    narrow = X[:20, :2]
    out = bst.inplace_predict(narrow, validate_features=False)
    assert np.isfinite(out).all() and out.shape == (20,)
    with pytest.raises(ValueError):
        bst.inplace_predict(X, predict_type="leaf")  # unsupported type


def test_sklearn_predict_uses_inplace_path():
    from xgboost_tpu.sklearn import XGBClassifier

    X, y = _data(600, 5, seed=2, nan_frac=0.0)
    clf = XGBClassifier(n_estimators=4, max_depth=3, verbosity=0)
    clf.fit(X, y)
    r0 = _counter("inplace_predict_rows_total")
    proba = clf.predict_proba(X)
    assert _counter("inplace_predict_rows_total") - r0 == len(X)
    d = xgb.DMatrix(X)
    np.testing.assert_allclose(
        proba[:, 1], clf.get_booster().predict(d), atol=1e-5)


def test_pallas_blacklist_retry_escape():
    """ISSUE 2 satellite (review weak #7), now on the resilience layer:
    a degraded forest shape is skipped for N predicts, then retried
    instead of being poisoned for the life of the process — and the state
    is visible in the metrics exposition (ISSUE 5 tentpole)."""
    from xgboost_tpu.observability import REGISTRY
    from xgboost_tpu.predictor import _pallas_health
    from xgboost_tpu.resilience import DEGRADED, HEALTHY

    key = ("test", "shape", 1, 2, 3)
    assert _pallas_health.allowed(key)  # unknown: not blocked
    kind = _pallas_health.failure(
        RuntimeError("synthetic vmem overflow"), key=key, retry_after=3)
    assert kind == "permanent"
    assert _pallas_health.state(key) == DEGRADED
    assert 'degrade_state{capability="pallas_predict"} 1' in \
        REGISTRY.exposition()
    assert not _pallas_health.allowed(key)  # skip 1
    assert not _pallas_health.allowed(key)  # skip 2
    assert not _pallas_health.allowed(key)  # skip 3, countdown done
    assert _pallas_health.state(key) == HEALTHY
    assert _pallas_health.allowed(key)  # retry allowed
    _pallas_health.success(key)  # recovery clears the failure history
    assert _pallas_health.snapshot()["entries"] == {}




def test_pallas_walk_body_interpreted_matches_gather_walk(monkeypatch):
    """The pallas walk's kernel body, run through the interpret-mode test
    hook, against the gather walk on a heap-layout forest; and the table
    gate it shares with the ``predict_walk`` registry row. (Compiled on
    the chip by chip_smoke.py at T=512, N=128 — the width whose [T, N, 8]
    table outgrew the 16 MiB scoped-VMEM default; the table is [T, 8, N]
    since.)"""
    import jax.numpy as jnp

    from xgboost_tpu import dispatch, predictor

    X, y = _data(1200, 7, seed=5)
    # per-round trees stay device-resident (heap layout) until a save
    bst = xgb.train({"objective": "binary:logistic", "max_depth": 4,
                     "max_bin": 32, "verbosity": 0},
                    xgb.DMatrix(X, label=y), 6, verbose_eval=False)
    forest = bst._gbm.model.stacked()
    assert forest.heap_layout and forest.left.shape == (8, 32)
    base = jnp.zeros((len(X), 1), jnp.float32)
    want = np.asarray(predictor.predict_margin(forest, jnp.asarray(X), base))

    monkeypatch.setattr(predictor, "_INTERPRET", True)
    tab, _ = predictor._build_pred_tables(
        forest.left, forest.feature, forest.cond, forest.default_left,
        forest.tree_group, jnp.ones((8,), jnp.float32), 1)
    assert tab.shape == (8, 8, 32)  # [T, 8, N]: nodes on the lanes
    got = np.asarray(predictor.predict_margin(forest, jnp.asarray(X), base))
    np.testing.assert_allclose(got, want, atol=1e-6)
    assert predictor._pallas_health.snapshot()["worst"] == "healthy"

    assert predictor.pallas_walk_fits(512, 128)
    assert not predictor.pallas_walk_fits(4096, 128)
    ctx = dict(platform="tpu", has_cats=False, heap_layout=True)
    assert dispatch.resolve(
        "predict_walk", dispatch.Ctx(trees=512, nodes=128, **ctx)
    ).impl == "pallas"
    assert dispatch.resolve(
        "predict_walk", dispatch.Ctx(trees=4096, nodes=128, **ctx)
    ).impl == "xla"
