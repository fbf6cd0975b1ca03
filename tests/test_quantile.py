"""Cuts/binning unit tests (reference analog: tests/cpp/common/test_quantile.cc,
test_hist_util.cc)."""

import numpy as np

import xgboost_tpu as xgb
import pytest

from xgboost_tpu.data.quantile import BinnedMatrix, bin_matrix, compute_cuts


def test_cuts_monotone_and_cover_max():
    rng = np.random.RandomState(0)
    X = rng.randn(500, 4).astype(np.float32)
    cuts = compute_cuts(X, max_bin=16)
    assert cuts.values.shape == (4, 16)
    # each feature's cuts are non-decreasing and the sentinel exceeds max
    for f in range(4):
        assert np.all(np.diff(cuts.values[f]) >= 0)
        assert cuts.values[f, -1] > X[:, f].max()


def test_bin_semantics_match_searchsorted():
    rng = np.random.RandomState(1)
    X = rng.uniform(-5, 5, size=(300, 3)).astype(np.float32)
    cuts = compute_cuts(X, max_bin=8)
    bins = np.asarray(bin_matrix(X, cuts))
    for f in range(3):
        expect = np.searchsorted(cuts.values[f], X[:, f], side="right")
        expect = np.clip(expect, 0, 7)
        np.testing.assert_array_equal(bins[:, f], expect)


def test_missing_goes_to_overflow_bin():
    X = np.array([[1.0, np.nan], [2.0, 5.0], [np.nan, 6.0]], np.float32)
    bm = BinnedMatrix.from_dense(X, max_bin=4)
    bins = np.asarray(bm.bins)
    assert bins[2, 0] == 4  # missing bin == max_bin
    assert bins[0, 1] == 4


def test_quantile_balance():
    # uniform data should land roughly equally in all bins
    rng = np.random.RandomState(2)
    X = rng.uniform(size=(4096, 1)).astype(np.float32)
    bm = BinnedMatrix.from_dense(X, max_bin=8)
    counts = np.bincount(np.asarray(bm.bins)[:, 0], minlength=8)
    assert counts.min() > 4096 / 8 * 0.7


def test_weighted_cuts_shift():
    # all weight on large values pushes cut points right
    X = np.linspace(0, 1, 1000).astype(np.float32).reshape(-1, 1)
    w_hi = (X[:, 0] > 0.8).astype(np.float32) + 0.01
    cuts_u = compute_cuts(X, max_bin=4)
    cuts_w = compute_cuts(X, max_bin=4, weights=w_hi)
    assert cuts_w.values[0, 0] > cuts_u.values[0, 0]


def test_all_missing_feature():
    X = np.full((50, 2), np.nan, np.float32)
    X[:, 0] = np.arange(50)
    bm = BinnedMatrix.from_dense(X, max_bin=4)
    assert np.all(np.asarray(bm.bins)[:, 1] == 4)


def test_streaming_quantile_dmatrix_actually_streams():
    """Peak host memory for 2-pass ingest must be ~one batch + bins: after
    construction no full float copy exists until something asks for raw
    values (review r2 item 9; reference IterativeDeviceDMatrix property,
    iterative_device_dmatrix.h:81)."""
    from xgboost_tpu.data.iterator import DataIter, StreamingQuantileDMatrix

    rng = np.random.RandomState(0)
    parts = [rng.randn(500, 6).astype(np.float32) for _ in range(4)]
    labels = [(p.sum(1) > 0).astype(np.float32) for p in parts]

    class It(DataIter):
        def __init__(self):
            super().__init__()
            self.i = 0

        def reset(self):
            self.i = 0

        def next(self, input_data):
            if self.i >= len(parts):
                return 0
            input_data(data=parts[self.i], label=labels[self.i])
            self.i += 1
            return 1

    d = StreamingQuantileDMatrix(It(), max_bin=32)
    assert d._data is None, "raw floats must not be retained after ingest"
    assert d.num_row() == 2000 and d.num_col() == 6
    # training runs on bins only — _data stays None through a full train
    bst = xgb.train({"objective": "binary:logistic", "max_depth": 3,
                     "max_bin": 32}, d, 3, verbose_eval=False)
    assert d._data is None, "training must not materialize raw floats"
    # predict reconstructs representative values lazily and stays sane
    pred = bst.predict(d)
    assert np.isfinite(pred).all()
    from xgboost_tpu.metric import create_metric
    auc = float(create_metric("auc").evaluate(pred, np.concatenate(labels)))
    assert auc > 0.75, auc


def test_streaming_dmatrix_rebin_at_other_max_bin():
    """Training with a max_bin different from the constructor's must rebuild
    bins from lazily reconstructed values rather than crash on the absent
    raw-float copy."""
    from xgboost_tpu.data.iterator import DataIter, StreamingQuantileDMatrix

    rng = np.random.RandomState(1)
    parts = [rng.randn(400, 5).astype(np.float32) for _ in range(2)]
    labels = [(p.sum(1) > 0).astype(np.float32) for p in parts]

    class It(DataIter):
        def __init__(self):
            super().__init__(); self.i = 0
        def reset(self):
            self.i = 0
        def next(self, input_data):
            if self.i >= len(parts):
                return 0
            input_data(data=parts[self.i], label=labels[self.i]); self.i += 1
            return 1

    d = StreamingQuantileDMatrix(It(), max_bin=32)
    # default max_bin=256 misses the prebuilt cache -> rebin path
    bst = xgb.train({"objective": "binary:logistic", "max_depth": 3}, d, 2,
                    verbose_eval=False)
    assert np.isfinite(bst.predict(d)).all()


# ---------------------------------------------------------------------------
# column blocks (ISSUE 35): a matrix too large for the device to sketch at
# once goes a block of columns at a time; the result is the whole-matrix
# program's to the bit
# ---------------------------------------------------------------------------


def _awkward_matrix(n=3000, F=37, seed=0):
    """NaNs scattered and in a whole column, a constant column, a column of
    two values, duplicates, and weights."""
    rng = np.random.RandomState(seed)
    X = rng.randn(n, F).astype(np.float32)
    X[rng.rand(n, F) < 0.05] = np.nan
    X[:, 5] = 1.5
    X[:, 11] = np.nan
    X[:, 20] = (rng.rand(n) < 0.3).astype(np.float32)
    X[:, 21] = np.round(X[:, 21], 1)
    return X, rng.rand(n).astype(np.float32)


@pytest.mark.parametrize("route", ["auto", "xla"])
@pytest.mark.parametrize("weighted", [False, True])
@pytest.mark.parametrize("cols", [1, 8, 36])
def test_column_blocks_equal_the_whole_matrix(monkeypatch, cols, weighted,
                                              route):
    from xgboost_tpu.data import quantile
    from xgboost_tpu.observability import REGISTRY

    if route == "xla":
        monkeypatch.setenv("XGBTPU_DISPATCH",
                           "sketch_cuts=xla,bin_matrix=xla")
    X, w = _awkward_matrix()
    w = w if weighted else None
    whole = BinnedMatrix.from_dense(X, max_bin=64, weights=w)

    def blocks_taken():
        fam = REGISTRY.get("sketch_blocks_total")
        return 0 if fam is None else sum(c.value for _, c in fam.series())

    before = blocks_taken()
    monkeypatch.setattr(quantile, "_FORCE_BLOCK_COLS", cols)
    blocked = BinnedMatrix.from_dense(X, max_bin=64, weights=w)
    assert blocks_taken() - before == 2 * -(-37 // cols)  # cuts, then bins
    assert np.array_equal(whole.cuts.values, blocked.cuts.values)
    assert np.array_equal(whole.cuts.min_vals, blocked.cuts.min_vals)
    assert blocked.bins.dtype == whole.bins.dtype
    assert blocked.bins.shape == (3000, 37)
    assert np.array_equal(np.asarray(whole.bins), np.asarray(blocked.bins))
    # given cuts, the binning alone goes by blocks too
    again = BinnedMatrix.from_dense(X, max_bin=64, cuts=whole.cuts)
    assert np.array_equal(np.asarray(whole.bins), np.asarray(again.bins))


def test_block_width_comes_from_the_free_memory(monkeypatch):
    from xgboost_tpu.data import quantile
    from xgboost_tpu.tree import hist_kernel

    # no memory statistics (the CPU): the whole matrix, as ever
    assert quantile.sketch_block_cols(400_000, 2000) == 2000
    free = 15_700_000_000
    monkeypatch.setattr(hist_kernel, "device_free_bytes", lambda: free)
    # the four narrow deployments keep the whole-matrix program
    for n, F in ((750_000, 50), (10_500_000, 28), (2_270_296, 136),
                 (435_759, 54)):
        assert quantile.sketch_block_cols(n, F) == F
    # 400,000 x 2,000: 22.4 GB at 28 bytes a cell; four equal blocks
    cols = quantile.sketch_block_cols(400_000, 2000)
    assert cols == 500
    assert quantile._SKETCH_BYTES_PER_CELL * 400_000 * cols <= 0.5 * free
    # no near divisor: the blocks are as even as they come
    assert quantile.sketch_block_cols(400_000, 1999) == 667
    # a matrix of one very long column is still one column a block
    assert quantile.sketch_block_cols(2_000_000_000, 3) == 1
