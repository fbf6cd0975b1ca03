"""External-memory (disk-paged) training (reference: SparsePageDMatrix /
sparse_page_source.h — cache on disk, pages re-streamed per iteration with
background prefetch)."""

import numpy as np
import pytest

import xgboost_tpu as xgb
from xgboost_tpu.data.iterator import DataIter
from xgboost_tpu.metric import create_metric


class _ArrayIter(DataIter):
    def __init__(self, parts, labels):
        super().__init__()
        self.parts, self.labels, self.i = parts, labels, 0

    def reset(self):
        self.i = 0

    def next(self, input_data):
        if self.i >= len(self.parts):
            return 0
        input_data(data=self.parts[self.i], label=self.labels[self.i])
        self.i += 1
        return 1


def _make(n_parts=4, rows=700, F=8, seed=0):
    rng = np.random.RandomState(seed)
    w = rng.randn(F)
    parts, labels = [], []
    for _ in range(n_parts):
        X = rng.randn(rows, F).astype(np.float32)
        parts.append(X)
        labels.append((X @ w + 0.4 * rng.randn(rows) > 0).astype(np.float32))
    return parts, labels, w


def test_external_memory_trains_matches_incore(tmp_path):
    parts, labels, w = _make()
    d_ext = xgb.ExternalMemoryQuantileDMatrix(
        _ArrayIter(parts, labels), cache_prefix=str(tmp_path / "cache"),
        max_bin=64, page_rows=1024)  # several pages, unaligned tail
    assert d_ext.num_row() == 2800
    params = {"objective": "binary:logistic", "max_depth": 4, "eta": 0.3,
              "max_bin": 64}
    bst = xgb.train(params, d_ext, 8, verbose_eval=False)

    # in-core reference on the same data: identical cuts pipeline -> the
    # paged grower must produce the same quality (trees may differ only
    # through sketch merge batching, which both paths share)
    X = np.concatenate(parts)
    y = np.concatenate(labels)
    d_in = xgb.DMatrix(X, label=y)
    bst_in = xgb.train(params, d_in, 8, verbose_eval=False)
    auc_ext = float(create_metric("auc").evaluate(bst.predict(d_in), y))
    auc_in = float(create_metric("auc").evaluate(bst_in.predict(d_in), y))
    assert auc_ext > 0.9
    assert abs(auc_ext - auc_in) < 0.03, (auc_ext, auc_in)


def test_external_memory_page_cache_roundtrip(tmp_path):
    parts, labels, _ = _make(n_parts=2, rows=300)
    d = xgb.ExternalMemoryQuantileDMatrix(
        _ArrayIter(parts, labels), cache_prefix=str(tmp_path / "c"),
        max_bin=32, page_rows=128)
    paged = d.get_binned(32, None)
    assert paged.n_pages == -(-600 // 128)
    total = 0
    for k in range(paged.n_pages):
        page = paged.read_page(k)
        assert page.shape[1] == 8
        assert (page <= 32).all()
        total += page.shape[0]
    assert total == 600
    paged.close()


def test_external_memory_raw_values_unavailable(tmp_path):
    parts, labels, _ = _make(n_parts=1, rows=200)
    d = xgb.ExternalMemoryQuantileDMatrix(
        _ArrayIter(parts, labels), cache_prefix=str(tmp_path / "c"),
        max_bin=32)
    with pytest.raises(NotImplementedError):
        _ = d.data


def test_native_pagecache_builds():
    from xgboost_tpu.native import get_pagecache_lib

    lib = get_pagecache_lib()
    assert lib is not None, "native page cache failed to build"


@pytest.mark.slow  # ~18s of tier-1 budget (1-core box); run with -m slow
def test_paged_training_equals_streaming_at_scale():
    """The paging machinery must be EXACT relative to the same streaming
    sketch: an external-memory matrix and a StreamingQuantileDMatrix built
    from the same iterator produce (near-)identical models — any
    divergence would mean page-boundary or accumulation bugs, not sketch
    approximation."""
    import xgboost_tpu as xgb
    from xgboost_tpu.data.external import ExternalMemoryQuantileDMatrix
    from xgboost_tpu.data.iterator import DataIter, StreamingQuantileDMatrix

    n, F, B = 100_000, 10, 5
    rng = np.random.RandomState(0)
    X = rng.randn(n, F).astype(np.float32)
    w = rng.randn(F).astype(np.float32)
    y = (X @ w + rng.randn(n) > 0).astype(np.float32)

    def make_it():
        class It(DataIter):
            def __init__(self):
                super().__init__()
                self.i = 0

            def reset(self):
                self.i = 0

            def next(self, input_data):
                if self.i >= B:
                    return 0
                sl = slice(self.i * (n // B), (self.i + 1) * (n // B))
                input_data(data=X[sl], label=y[sl])
                self.i += 1
                return 1
        return It()

    params = {"objective": "binary:logistic", "max_depth": 4, "max_bin": 32}
    bext = xgb.train(params, ExternalMemoryQuantileDMatrix(make_it(), max_bin=32),
                     5, verbose_eval=False)
    bstr = xgb.train(params, StreamingQuantileDMatrix(make_it(), max_bin=32),
                     5, verbose_eval=False)
    probe = xgb.DMatrix(X[:20000])
    np.testing.assert_allclose(bext.predict(probe), bstr.predict(probe),
                               rtol=1e-4, atol=1e-5)


def test_external_memory_predict_eval_early_stop(tmp_path):
    """Page-streamed predict/eval on the paged matrix itself (reference:
    cpu_predictor.cc:266 page-streamed prediction): predictions must be
    EXACT vs walking the same model over midpoint-densified pages, eval
    sets and early stopping must work out-of-core, and the margin-cache
    eval during training must agree with post-hoc predict."""
    parts, labels, w = _make(n_parts=4, rows=600, F=6, seed=3)
    d_ext = xgb.ExternalMemoryQuantileDMatrix(
        _ArrayIter(parts, labels), cache_prefix=str(tmp_path / "c1"),
        max_bin=32, page_rows=777)  # unaligned pages
    params = {"objective": "binary:logistic", "max_depth": 3, "eta": 0.3,
              "max_bin": 32, "eval_metric": "auc"}
    res = {}
    bst = xgb.train(params, d_ext, 12, evals=[(d_ext, "train")],
                    evals_result=res, verbose_eval=False)
    aucs = res["train"]["auc"]
    assert aucs[-1] > max(aucs[0], 0.85)

    # predict on the paged matrix == predict on its midpoint densification
    p_ext = bst.predict(d_ext)
    paged = d_ext._paged
    X_mid = np.concatenate([paged.float_page(k)
                            for k in range(paged.n_pages)])
    p_mid = bst.predict(xgb.DMatrix(X_mid))
    np.testing.assert_allclose(p_ext, p_mid, rtol=1e-6, atol=1e-7)

    # eval-set AUC line equals metric on streamed predictions
    y = np.concatenate(labels)
    auc = float(create_metric("auc").evaluate(p_ext, y))
    assert abs(auc - aucs[-1]) < 1e-4

    # early stopping entirely out-of-core: noisy labels stop early
    rng = np.random.RandomState(9)
    noisy = [rng.randint(0, 2, len(l)).astype(np.float32) for l in labels]
    d_noise = xgb.ExternalMemoryQuantileDMatrix(
        _ArrayIter(parts, noisy), cache_prefix=str(tmp_path / "c2"),
        max_bin=32, page_rows=777)
    bst2 = xgb.train(params, d_ext, 60, evals=[(d_noise, "val")],
                     early_stopping_rounds=5, verbose_eval=False)
    assert bst2.best_iteration < 59

    # pred_leaf streams pages too
    leaves = bst.predict(d_ext, pred_leaf=True)
    assert leaves.shape[0] == d_ext.num_row()


def test_pages_bit_packed_on_disk(tmp_path):
    """Disk pages store log2(bins+1) bits per entry (the reference's
    ELLPACK symbol compression, common/compressed_iterator.h), and the
    pack/unpack round trip is exact."""
    import os

    from xgboost_tpu.data.external import pack_symbols, unpack_symbols

    rng = np.random.RandomState(0)
    for bits, n in ((3, 1000), (6, 4096), (7, 333)):
        vals = rng.randint(0, 1 << bits, n).astype(np.uint8)
        rt = unpack_symbols(pack_symbols(vals, bits), bits, n, np.uint8)
        np.testing.assert_array_equal(rt, vals)

    parts, labels, _ = _make(n_parts=2, rows=500, F=8, seed=1)
    d = xgb.ExternalMemoryQuantileDMatrix(
        _ArrayIter(parts, labels), cache_prefix=str(tmp_path / "c"),
        max_bin=32, page_rows=400)
    paged = d._paged
    assert paged.packed and paged.bits == 6  # 33 symbols -> 6 bits
    # on-disk size ~6/8 of the raw byte layout
    raw = paged.rows_of(0) * paged.n_features
    assert os.path.getsize(paged.page_path(0)) == (raw * 6 + 7) // 8
    # and training still works on packed pages
    bst = xgb.train({"objective": "binary:logistic", "max_depth": 3,
                     "max_bin": 32}, d, 4, verbose_eval=False)
    p = bst.predict(d)
    assert np.isfinite(p).all()


def test_foreign_booster_on_paged_matrix_warns(tmp_path):
    """Walking a paged matrix with a booster trained elsewhere must warn:
    midpoint-reconstructed features are only exact for thresholds drawn
    from this matrix's own cuts (review r4 weak #7; reference
    cpu_predictor.cc:266 streams raw pages, no such approximation)."""
    import warnings

    import pytest

    parts, labels, w = _make()
    d_ext = xgb.ExternalMemoryQuantileDMatrix(
        _ArrayIter(parts, labels), cache_prefix=str(tmp_path / "cachefw"),
        max_bin=64, page_rows=1024)
    params = {"objective": "binary:logistic", "max_depth": 3, "eta": 0.3,
              "max_bin": 64}
    # self-trained booster: cuts match, NO warning
    bst_self = xgb.train(params, d_ext, 3, verbose_eval=False)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        bst_self.predict(d_ext)

    # foreign booster: trained on different data (different cuts)
    rng = np.random.RandomState(9)
    Xo = rng.randn(600, 8).astype(np.float32)
    yo = (Xo @ w > 0).astype(np.float32)
    bst_foreign = xgb.train(params, xgb.DMatrix(Xo, label=yo), 3,
                            verbose_eval=False)
    with pytest.warns(UserWarning, match="midpoint"):
        bst_foreign.predict(d_ext)


def test_local_histmaker_rejects_paged():
    """grow_local_histmaker re-sketches from raw values per node
    (tree/grow_local.py) and therefore needs in-memory data; an
    external-memory matrix is rejected with a clear error."""
    import pytest

    parts, labels, _ = _make()
    d_ext = xgb.ExternalMemoryQuantileDMatrix(
        _ArrayIter(parts, labels), max_bin=16, page_rows=1024)
    with pytest.raises(NotImplementedError, match="in-memory"):
        xgb.train({"updater": "grow_local_histmaker"},
                  d_ext, 2, verbose_eval=False)
