"""ISSUE 15 — the data-plane fast path.

Pins the four tentpole contracts:
- native sketch + binning (dispatch ops ``sketch_cuts``/``bin_matrix``)
  BIT-IDENTICAL to the XLA route — the PR 5 canonical-cuts manifest
  contract depends on route-independent cuts;
- prefetch-overlapped paged rounds bit-identical to streaming, with the
  ``prefetch_wait``/``ingest`` flight split live;
- async checkpoint I/O: same bytes as the synchronous path, durable at
  ``train()`` return, SIGKILL mid-write resumes bit-identical, failures
  surface at the next sync point;
- eval routed through ``predict_walk`` without touching training numerics;
plus the batcher idle fast-path satellite.
"""

import glob
import os
import signal
import subprocess
import sys
import tempfile
import time

import numpy as np
import pytest

import xgboost_tpu as xgb
from conftest import require_native
from xgboost_tpu import dispatch
from xgboost_tpu.data.quantile import (
    BinnedMatrix, _ensure_sketch_ffi, bin_matrix, compute_cuts,
)
from xgboost_tpu.observability import flight
from xgboost_tpu.resilience import checkpoint as ckpt

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PARAMS = {"objective": "binary:logistic", "max_depth": 3, "max_bin": 16,
          "verbosity": 0}


def _data(n=2000, F=6, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, F).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    return X, y


# ---------------------------------------------------------------------------
# native sketch + binning (dispatch ops)
# ---------------------------------------------------------------------------


def _adversarial(n=3000, F=7, seed=0):
    """NaNs, heavy ties, an all-missing feature, spread weights — the
    shapes where a reassociated CDF or a tie-order slip would show."""
    rng = np.random.RandomState(seed)
    X = rng.randn(n, F).astype(np.float32)
    X[rng.rand(n, F) < 0.15] = np.nan
    X[:, 2] = np.round(X[:, 2] * 3) / 3  # duplicates
    X[:, 3] = np.nan  # all missing
    w = (rng.rand(n) * 10).astype(np.float32)
    return X, w


@pytest.mark.parametrize("max_bin", [16, 64, 300])
def test_native_sketch_and_bins_bit_identical_to_xla(monkeypatch, max_bin):
    require_native(_ensure_sketch_ffi(), "native sketch kernel")
    X, w = _adversarial()
    c_nat = compute_cuts(X, max_bin, weights=w)
    b_nat = np.asarray(bin_matrix(X, c_nat))
    assert dispatch.last_decisions().get("sketch_cuts") == "native"
    assert dispatch.last_decisions().get("bin_matrix") == "native"
    monkeypatch.setenv("XGBTPU_DISPATCH", "sketch_cuts=xla,bin_matrix=xla")
    c_xla = compute_cuts(X, max_bin, weights=w)
    b_xla = np.asarray(bin_matrix(X, c_nat))
    assert dispatch.last_decisions().get("sketch_cuts") == "xla"
    np.testing.assert_array_equal(c_nat.values, c_xla.values)
    np.testing.assert_array_equal(c_nat.min_vals, c_xla.min_vals)
    np.testing.assert_array_equal(b_nat, b_xla)
    # narrow storage written directly by the native kernel
    assert b_nat.dtype == (np.uint8 if max_bin + 1 <= 255 else np.uint16)


def test_sparse_blocked_ingest_matches_dense():
    """The CSR column-blocked sketch/quantize rides the same dispatch
    route and must agree with the dense path bit-for-bit."""
    sp = pytest.importorskip("scipy.sparse")

    from xgboost_tpu.data.sparse import CSRStorage

    X, _ = _data(1500, 9, seed=3)
    X[X < -1.2] = 0.0  # sparsify: CSR drops these as ABSENT (NaN-missing)
    Xd = np.where(X == 0.0, np.nan, X)  # the dense twin of that view
    bm_d = BinnedMatrix.from_dense(Xd, max_bin=32)
    bm_s = BinnedMatrix.from_sparse(CSRStorage(sp.csr_matrix(X)), max_bin=32)
    np.testing.assert_array_equal(bm_d.cuts.values, bm_s.cuts.values)
    np.testing.assert_array_equal(np.asarray(bm_d.bins), np.asarray(bm_s.bins))


def test_data_plane_ops_resolve_on_cpu():
    for op in ("sketch_cuts", "bin_matrix"):
        dec = dispatch.resolve(op)
        assert dec.impl in ("native", "xla"), dec
        if _ensure_sketch_ffi():
            assert dec.impl == "native", dec


def test_trained_model_identical_across_ingest_routes(monkeypatch):
    """End to end: a model trained on natively-ingested data is byte-equal
    to one trained on XLA-ingested data (cuts and bins are bit-identical,
    so everything downstream must be too)."""
    require_native(_ensure_sketch_ffi(), "native sketch kernel")
    X, y = _data()
    b1 = xgb.train(PARAMS, xgb.DMatrix(X, label=y), 3, verbose_eval=False)
    monkeypatch.setenv("XGBTPU_DISPATCH", "sketch_cuts=xla,bin_matrix=xla")
    b2 = xgb.train(PARAMS, xgb.DMatrix(X, label=y), 3, verbose_eval=False)
    assert b1.save_raw() == b2.save_raw()


# ---------------------------------------------------------------------------
# prefetch-overlapped paged rounds
# ---------------------------------------------------------------------------


def _paged_matrix(X, y, n_parts=3, max_bin=16):
    from xgboost_tpu.data.external import ExternalMemoryQuantileDMatrix
    from xgboost_tpu.data.iterator import DataIter

    step = -(-len(X) // n_parts)

    class _It(DataIter):
        def __init__(self):
            self.i = 0

        def reset(self):
            self.i = 0

        def next(self, input_data):
            if self.i >= n_parts:
                return 0
            lo = self.i * step
            input_data(data=X[lo:lo + step], label=y[lo:lo + step])
            self.i += 1
            return 1

    return ExternalMemoryQuantileDMatrix(_It(), max_bin=max_bin,
                                         page_rows=step)


def test_paged_prefetch_bit_identical_to_sync_reads(monkeypatch):
    """Paged training with the prefetch overlap admitted under a deep
    pipeline (depth 2) is bit-identical to the same run with
    XGBTPU_PAGE_PREFETCH=0 — and the prefetch_wait/ingest flight split is
    live while it runs."""
    X, y = _data(2100, 6)
    monkeypatch.setenv("XGBTPU_PIPELINE_DEPTH", "2")
    s0 = flight.stage_totals()
    d1 = _paged_matrix(X, y)  # 2-pass ingest charges the 'ingest' stage
    b1 = xgb.train(PARAMS, d1, 3, verbose_eval=False)
    delta = {k: flight.stage_totals().get(k, 0.0) - s0.get(k, 0.0)
             for k in ("prefetch_wait", "ingest")}
    assert delta["prefetch_wait"] > 0, delta  # overlap actually admitted
    assert delta["ingest"] > 0, delta  # the out-of-core construction sweep
    monkeypatch.setenv("XGBTPU_PAGE_PREFETCH", "0")
    d2 = _paged_matrix(X, y)
    b2 = xgb.train(PARAMS, d2, 3, verbose_eval=False)
    assert b1.save_raw() == b2.save_raw()


# ---------------------------------------------------------------------------
# async checkpoint I/O
# ---------------------------------------------------------------------------


def test_async_checkpoint_bit_identical_to_sync(monkeypatch, tmp_path):
    X, y = _data()
    d_async, d_sync = str(tmp_path / "a"), str(tmp_path / "s")
    b1 = xgb.train(PARAMS, xgb.DMatrix(X, label=y), 4, verbose_eval=False,
                   resume_from=d_async, checkpoint_interval=1)
    monkeypatch.setenv("XGBTPU_ASYNC_CKPT", "0")
    b2 = xgb.train(PARAMS, xgb.DMatrix(X, label=y), 4, verbose_eval=False,
                   resume_from=d_sync, checkpoint_interval=1)
    assert b1.save_raw() == b2.save_raw()
    fa = sorted(os.path.basename(p) for p in glob.glob(d_async + "/ckpt_*"))
    fs = sorted(os.path.basename(p) for p in glob.glob(d_sync + "/ckpt_*"))
    assert fa == fs and fa, (fa, fs)
    for name in fa:  # byte-for-byte: header, checksum, payload
        assert open(os.path.join(d_async, name), "rb").read() == \
            open(os.path.join(d_sync, name), "rb").read()
    # durable at train() return: the final round verifies on disk
    ok, detail, rounds = ckpt.verify_checkpoint(ckpt.checkpoint_path(
        d_async, 4))
    assert ok and rounds == 4, detail


def test_async_checkpoint_failure_surfaces_at_sync_point(tmp_path):
    """A write that exhausts its retry budget must fail training at the
    next checkpoint boundary, attributed to the round it was committing —
    not vanish on the writer thread."""
    from xgboost_tpu.resilience import chaos

    X, y = _data()
    with chaos.configure("checkpoint_write:permanent:2"):
        with pytest.raises(Exception) as exc:
            xgb.train(PARAMS, xgb.DMatrix(X, label=y), 5, verbose_eval=False,
                      resume_from=str(tmp_path), checkpoint_interval=1)
    assert getattr(exc.value, "checkpoint_rounds", None) is not None
    faults = [r for r in flight.RECORDER.records()
              if r.get("t") == "event" and r.get("name") == "checkpoint_fault"]
    assert faults, "checkpoint_fault flight event missing"


def test_async_checkpoint_sigkill_mid_write_resumes_bit_identical(tmp_path):
    """SIGKILL landing INSIDE an in-flight async checkpoint write (the
    writer is slowed so the kill provably interrupts it) leaves a verified
    previous checkpoint; resume completes bit-identical to an
    uninterrupted run — the PR 4 atomic contract survives the move to the
    writer thread."""
    ck = str(tmp_path / "ck")
    code = f"""
import numpy as np, os, sys
import xgboost_tpu as xgb
rng = np.random.RandomState(0)
X = rng.randn(2000, 6).astype(np.float32)
y = (X[:, 0] > 0).astype(np.float32)
print("START", flush=True)
xgb.train({PARAMS!r}, xgb.DMatrix(X, label=y), 6, verbose_eval=False,
          resume_from={ck!r}, checkpoint_interval=1)
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XGBTPU_TEST_CKPT_WRITE_DELAY="0.4")
    p = subprocess.Popen([sys.executable, "-c", code], env=env, cwd=REPO,
                         stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                         text=True)
    # wait until at least one checkpoint landed, then kill while the next
    # write is (very likely, given the 0.4s delay) in flight
    deadline = time.time() + 120
    while time.time() < deadline:
        done = glob.glob(ck + "/ckpt_*")
        if done:
            break
        time.sleep(0.02)
    assert glob.glob(ck + "/ckpt_*"), "no checkpoint ever landed"
    time.sleep(0.2)  # land inside the next delayed write window
    p.kill()
    p.wait(timeout=60)
    got = ckpt.load_latest(ck)
    assert got is not None, "no verified checkpoint after SIGKILL"
    # tmp files from the torn write may remain; they must not break resume
    X, y = _data()
    resumed = xgb.train(PARAMS, xgb.DMatrix(X, label=y), 6,
                        verbose_eval=False, resume_from=ck,
                        checkpoint_interval=1)
    clean = xgb.train(PARAMS, xgb.DMatrix(X, label=y), 6, verbose_eval=False)
    assert resumed.save_raw() == clean.save_raw()


# ---------------------------------------------------------------------------
# eval via predict_walk
# ---------------------------------------------------------------------------


def test_eval_routes_predict_walk_without_touching_training(monkeypatch):
    """Per-eval-round prediction resolves the predict_walk dispatch op
    (native on CPU when the walker builds); the trained MODEL is byte-
    equal across eval routes and the eval metrics agree to float
    tolerance."""
    X, y = _data(3000, 8, seed=1)
    dtr = lambda: xgb.DMatrix(X[:2000], label=y[:2000])  # noqa: E731
    dev = lambda: xgb.DMatrix(X[2000:], label=y[2000:])  # noqa: E731
    res1, res2 = {}, {}
    b1 = xgb.train(PARAMS, dtr(), 4, evals=[(dev(), "e")],
                   evals_result=res1, verbose_eval=False)
    route = dispatch.last_decisions().get("predict_walk")
    from xgboost_tpu.native import serving_lib_available

    if serving_lib_available():
        assert route == "native", route
    monkeypatch.setenv("XGBTPU_DISPATCH", "predict_walk=xla")
    b2 = xgb.train(PARAMS, dtr(), 4, evals=[(dev(), "e")],
                   evals_result=res2, verbose_eval=False)
    assert dispatch.last_decisions().get("predict_walk") == "xla"
    assert b1.save_raw() == b2.save_raw()
    np.testing.assert_allclose(res1["e"]["logloss"], res2["e"]["logloss"],
                               atol=1e-5)


# ---------------------------------------------------------------------------
# batcher idle fast-path
# ---------------------------------------------------------------------------


def test_batcher_idle_fastpath_skips_coalescing_window():
    """A lone request must not pay XGBTPU_BATCH_WAIT_US: with a 0.3s
    window armed, a single predict returns in a fraction of it and the
    fast-path counter moves."""
    from xgboost_tpu.observability import REGISTRY
    from xgboost_tpu.serving import ModelServer

    X, y = _data(400, 5)
    bst = xgb.train(PARAMS, xgb.DMatrix(X, label=y), 2, verbose_eval=False)

    def counter():
        fam = REGISTRY.get("serving_batch_fastpath_total")
        return 0.0 if fam is None else fam.labels().value

    srv = ModelServer(batch_wait_us=300_000)
    try:
        srv.load("m", bst)  # load()'s warm predict also rides the queue
        srv.predict("m", X[:2], timeout=30)  # warm compile outside timing
        c0 = counter()
        t0 = time.perf_counter()
        out = srv.predict("m", X[:4], timeout=30)
        lat = time.perf_counter() - t0
        assert counter() > c0, "idle fast-path never taken"
        assert lat < 0.15, f"lone request paid the window: {lat:.3f}s"
        np.testing.assert_array_equal(
            out, np.asarray(bst.inplace_predict(X[:4])))
    finally:
        srv.close()
