"""Sanitizer lanes. Address (XGBTPU_SAN=1): native sources build under
``-fsanitize=address,undefined -Wall -Wextra -Werror`` and a predict
round-trips through the ASan-instrumented serving walker with exact
parity and zero sanitizer reports. Thread (XGBTPU_SAN=thread): the same
sources build under ``-fsanitize=thread`` into ``.tsan.so`` variants,
and a training run drives the OpenMP tree-grow kernel plus the threaded
page prefetcher and the async checkpoint writer under a
``LD_PRELOAD=libtsan.so`` child with zero data-race reports.
Slow-marked: runs in the ``-m slow`` lane, not the tier-1 budget."""

import ctypes
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import xgboost_tpu as xgb
from xgboost_tpu import native
from xgboost_tpu.native import (_SAN_FLAGS, _compile, find_libasan,
                                find_libtsan)

HERE = os.path.dirname(os.path.abspath(__file__))

pytestmark = pytest.mark.slow


def _have_gxx() -> bool:
    try:
        subprocess.run(["g++", "--version"], capture_output=True,
                       timeout=30, check=True)
        return True
    except Exception:
        return False


def test_all_native_sources_build_sanitized(monkeypatch, tmp_path):
    """serving_walk.cpp / pagecache.cpp / fastparse.cpp compile clean under
    ASan+UBSan with warnings-as-errors (c_api.cpp is covered separately:
    it needs the Python embedding flags)."""
    if not _have_gxx():
        pytest.skip("no g++")
    monkeypatch.setenv("XGBTPU_SAN", "1")
    for src, extra in (
        (native._SV_SRC, ["-O2", "-fopenmp"]),
        (native._PC_SRC, ["-O2", "-std=c++17", "-pthread"]),
        (native._SRC, ["-O2"]),
    ):
        out = str(tmp_path / (os.path.basename(src)[:-4] + ".san.so"))
        ok = _compile(src, out, extra)
        if not ok and "-fopenmp" in extra:  # toolchain without OpenMP
            ok = _compile(src, out, [f for f in extra if f != "-fopenmp"])
        assert ok, f"sanitized build failed for {src}"


def test_capi_builds_sanitized(monkeypatch):
    if not _have_gxx():
        pytest.skip("no g++")
    monkeypatch.setenv("XGBTPU_SAN", "1")
    native._capi_tried = False
    native._capi_path = None
    path = None
    try:
        path = native.build_capi()
        assert path is not None and path.endswith(".san.so"), path
    finally:
        native._capi_tried = False
        native._capi_path = None
        if path and os.path.exists(path):
            os.unlink(path)


def test_asan_predict_round_trip(monkeypatch, tmp_path):
    """Train a model, then round-trip dense AND CSR predict through the
    ASan+UBSan serving walker in an LD_PRELOAD'd subprocess. ASan aborts
    (non-zero exit) on any OOB read/write or UB the walk performs; the
    child also checks margin parity against the XLA path's answers."""
    if not _have_gxx():
        pytest.skip("no g++")
    libasan = find_libasan()
    if libasan is None or not os.path.exists(libasan):
        pytest.skip("libasan runtime not found")

    # -- sanitized walker build (isolated artifact) ---------------------
    monkeypatch.setenv("XGBTPU_SAN", "1")
    san_lib = str(tmp_path / "libservingwalk.san.so")
    ok = _compile(native._SV_SRC, san_lib, ["-O2", "-fopenmp"]) or \
        _compile(native._SV_SRC, san_lib, ["-O2"])
    assert ok, "sanitized serving_walk build failed"
    monkeypatch.delenv("XGBTPU_SAN")

    # -- model + reference margins (XLA path: independent of the walker) -
    rng = np.random.RandomState(17)
    Xtr = rng.rand(400, 8).astype(np.float32)
    y = (Xtr[:, 0] + Xtr[:, 3] > 1.0).astype(np.float32)
    bst = xgb.train(
        {"max_depth": 3, "objective": "binary:logistic",
         "tree_method": "tpu_hist"},
        xgb.DMatrix(Xtr, label=y), num_boost_round=4)
    n = 129  # off-bucket row count, exercises edge blocks in the walker
    X = rng.rand(n, 8).astype(np.float32)
    X[rng.rand(n, 8) < 0.15] = np.nan  # missing routes default directions
    monkeypatch.setenv("XGBTPU_NATIVE_SERVING", "0")
    expected = np.asarray(
        bst.inplace_predict(X, predict_type="margin"), np.float32)
    if expected.ndim == 1:
        expected = expected[:, None]

    from xgboost_tpu.predictor.serving import _HostForest, _tree_weights_np

    forest, tw = bst._forest_snapshot(None)
    hf = _HostForest(forest)
    import scipy.sparse as sp

    # NaNs become stored entries (NaN != 0), absent entries are missing:
    # both missing encodings the walker supports, in one matrix
    Xcsr = sp.csr_matrix(X)

    npz = str(tmp_path / "roundtrip.npz")
    np.savez(
        npz,
        X=np.ascontiguousarray(X),
        indptr=np.ascontiguousarray(Xcsr.indptr, np.int64),
        indices=np.ascontiguousarray(Xcsr.indices, np.int32),
        values=np.ascontiguousarray(Xcsr.data, np.float32),
        left=hf.left, right=hf.right, feature=hf.feature, cond=hf.cond,
        default_left=hf.default_left, tree_group=hf.tree_group,
        tw=_tree_weights_np(forest, tw),
        base=np.full((n, 1), 0.0, np.float32),
        expected=expected,
    )

    child = str(tmp_path / "asan_child.py")
    with open(child, "w") as f:
        f.write(textwrap.dedent("""
            import ctypes, sys
            import numpy as np

            lib_path, npz_path = sys.argv[1], sys.argv[2]
            z = np.load(npz_path)
            lib = ctypes.CDLL(lib_path)
            c = ctypes
            lib.sv_predict_dense.argtypes = [
                c.c_void_p, c.c_int64, c.c_int64,
                c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p,
                c.c_void_p, c.c_void_p, c.c_int64, c.c_int64,
                c.c_void_p, c.c_void_p, c.c_int64,
            ]
            lib.sv_predict_dense.restype = c.c_int
            lib.sv_predict_csr.argtypes = [
                c.c_void_p, c.c_void_p, c.c_void_p, c.c_int64, c.c_int64,
                c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p,
                c.c_void_p, c.c_void_p, c.c_int64, c.c_int64,
                c.c_void_p, c.c_void_p, c.c_int64,
            ]
            lib.sv_predict_csr.restype = c.c_int

            def p(a):
                return a.ctypes.data

            # materialize EVERY array before taking pointers: each z[...]
            # access returns a fresh array, and a pointer into a temporary
            # is a use-after-free the walker would read (ASan proved it)
            arrs = {k: np.ascontiguousarray(z[k]) for k in z.files}
            X = arrs["X"].astype(np.float32)
            n, F = X.shape
            T, N = arrs["left"].shape
            base = arrs["base"]
            K = base.shape[1]
            expected = arrs["expected"]
            left, right = arrs["left"], arrs["right"]
            feature, cond = arrs["feature"], arrs["cond"]
            default_left, tree_group = arrs["default_left"], arrs["tree_group"]
            tw = arrs["tw"]
            indptr = arrs["indptr"].astype(np.int64)
            indices = arrs["indices"].astype(np.int32)
            values = arrs["values"].astype(np.float32)

            out = np.empty((n, K), np.float32)
            rc = lib.sv_predict_dense(
                p(X), n, F, p(left), p(right), p(feature),
                p(cond), p(default_left), p(tree_group),
                p(tw), T, N, p(base), p(out), K)
            assert rc == 0, f"dense walker rc={rc}"
            assert np.allclose(out, expected, rtol=1e-5, atol=1e-5), \\
                "dense parity failed"

            out2 = np.empty((n, K), np.float32)
            rc = lib.sv_predict_csr(
                p(indptr), p(indices), p(values),
                n, F, p(left), p(right), p(feature),
                p(cond), p(default_left), p(tree_group),
                p(tw), T, N, p(base), p(out2), K)
            assert rc == 0, f"csr walker rc={rc}"
            assert np.allclose(out2, expected, rtol=1e-5, atol=1e-5), \\
                "csr parity failed"
            print("PARITY OK")
        """))

    env = dict(os.environ)
    env["LD_PRELOAD"] = libasan
    # python itself is uninstrumented: leak noise off, link-order check off
    env["ASAN_OPTIONS"] = "detect_leaks=0:verify_asan_link_order=0"
    r = subprocess.run(
        [sys.executable, child, san_lib, npz],
        capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, f"ASan round-trip failed:\n{r.stdout}\n{r.stderr}"
    assert "PARITY OK" in r.stdout
    assert "ERROR: AddressSanitizer" not in r.stderr
    assert "runtime error" not in r.stderr  # UBSan report marker


# ---------------------------------------------------------------------------
# thread lane (XGBTPU_SAN=thread -> .tsan.so)
# ---------------------------------------------------------------------------


def test_all_native_sources_build_tsan(monkeypatch, tmp_path):
    """The same TU trio compiles clean under -fsanitize=thread, into
    isolated .tsan.so artifacts."""
    if not _have_gxx():
        pytest.skip("no g++")
    monkeypatch.setenv("XGBTPU_SAN", "thread")
    for src, extra in (
        (native._SV_SRC, ["-O2", "-fopenmp"]),
        (native._PC_SRC, ["-O2", "-std=c++17", "-pthread"]),
        (native._SRC, ["-O2"]),
    ):
        out = str(tmp_path / (os.path.basename(src)[:-4] + ".tsan.so"))
        ok = _compile(src, out, extra)
        if not ok and "-fopenmp" in extra:  # toolchain without OpenMP
            ok = _compile(src, out, [f for f in extra if f != "-fopenmp"])
        assert ok, f"tsan build failed for {src}"


def test_lib_variant_suffix_per_lane(monkeypatch):
    monkeypatch.delenv("XGBTPU_SAN", raising=False)
    assert native._lib_variant("libx.so") == "libx.so"
    monkeypatch.setenv("XGBTPU_SAN", "1")
    assert native._lib_variant("libx.so") == "libx.san.so"
    monkeypatch.setenv("XGBTPU_SAN", "address")
    assert native._lib_variant("libx.so") == "libx.san.so"
    monkeypatch.setenv("XGBTPU_SAN", "thread")
    assert native._lib_variant("libx.so") == "libx.tsan.so"


def test_tsan_training_round_trip(tmp_path):
    """Full training under the thread lane in a libtsan-preloaded child:
    OpenMP whole-tree grow (.tsan.so FFI kernels) over a paged
    external-memory matrix (threaded page prefetcher) with async
    checkpoint commits — zero ThreadSanitizer reports. Python/jaxlib are
    uninstrumented, so TSan only adjudicates accesses that involve the
    instrumented native kernels (ignore_noninstrumented_modules=1);
    uninstrumented-libgomp barrier noise is suppressed explicitly."""
    if not _have_gxx():
        pytest.skip("no g++")
    libtsan = find_libtsan()
    if libtsan is None or not os.path.exists(libtsan):
        pytest.skip("libtsan runtime not found")

    child = str(tmp_path / "tsan_child.py")
    with open(child, "w") as f:
        f.write(textwrap.dedent("""
            import os, sys

            import numpy as np

            import xgboost_tpu as xgb
            from xgboost_tpu import native
            from xgboost_tpu.data.external import (
                ExternalMemoryQuantileDMatrix)
            from xgboost_tpu.data.iterator import DataIter
            from xgboost_tpu.resilience import checkpoint

            ckpt_dir = sys.argv[1]
            rng = np.random.RandomState(5)
            X = rng.rand(600, 6).astype(np.float32)
            y = (X[:, 0] + X[:, 2] > 1.0).astype(np.float32)
            step = 200

            class _It(DataIter):
                def __init__(self):
                    self.i = 0

                def reset(self):
                    self.i = 0

                def next(self, input_data):
                    if self.i >= 3:
                        return 0
                    lo = self.i * step
                    input_data(data=X[lo:lo + step],
                               label=y[lo:lo + step])
                    self.i += 1
                    return 1

            dm = ExternalMemoryQuantileDMatrix(_It(), max_bin=16,
                                               page_rows=step)
            bst = xgb.train(
                {"max_depth": 3, "max_bin": 16,
                 "objective": "binary:logistic",
                 "tree_method": "tpu_hist"},
                dm, num_boost_round=3, verbose_eval=False)
            # the lane must actually be instrumented: the tree kernel
            # loaded from its .tsan.so variant (None would mean the run
            # silently fell back to the XLA path)
            assert native.get_tree_lib() is not None, \\
                "tsan treebuild variant did not load"
            w = checkpoint.async_writer()
            for r in (1, 2, 3):
                w.submit(ckpt_dir, bst, r)
            w.wait(ckpt_dir)
            p = bst.inplace_predict(X[:64], predict_type="margin")
            assert np.asarray(p).shape[0] == 64

            # ISSUE 19: drive the quant engine's row-slab parallel
            # accumulation directly — n spans 3 slabs of kSlabRows=4096,
            # and OMP_NUM_THREADS=4 (set by the parent) puts multiple
            # threads on disjoint slabs merging into the shared int64
            # lanes. TSan adjudicates the slab-partial writes and the
            # merge; two runs must also be byte-identical (the integer
            # determinism contract under the sanitizer's scheduler
            # perturbation).
            from types import SimpleNamespace

            import jax.numpy as jnp

            from xgboost_tpu.tree import tree_kernel

            # the paged training above drives the per-level kernels; the
            # whole-tree entry registers lazily on first use
            assert tree_kernel.tree_ffi_ready(), \\
                "tsan whole-tree kernel did not register"
            rq = np.random.RandomState(7)
            nq, Fq, Bq = 12288, 6, 16
            binsq = jnp.asarray(
                rq.randint(0, Bq + 1, (nq, Fq)).astype(np.uint8))
            ghq = jnp.asarray(
                rq.randn(2, nq).astype(np.float32) ** 2 + 0.1)
            cutsq = jnp.asarray(
                np.sort(rq.randn(Fq, Bq).astype(np.float32), axis=1))
            maskq = jnp.ones((Fq,), bool)
            G0 = jnp.float32(np.asarray(ghq)[0].sum())
            H0 = jnp.float32(np.asarray(ghq)[1].sum())
            splitq = SimpleNamespace(reg_lambda=1.0, reg_alpha=0.0,
                                     max_delta_step=0.0,
                                     min_child_weight=1.0)
            runs = []
            for _ in range(2):
                out = tree_kernel.tree_grow_native(
                    binsq, ghq, cutsq, maskq, G0, H0, max_depth=4,
                    B=Bq, sibling_sub=True, hist_acc="quant",
                    split=splitq)
                runs.append([np.asarray(a).tobytes() for a in out])
            assert runs[0] == runs[1], \\
                "quant slab accumulation not deterministic under TSan"
            print("TSAN DRIVE OK")
        """))

    supp = str(tmp_path / "tsan.supp")
    with open(supp, "w") as f:
        # uninstrumented libgomp's own barriers/teams look like races to
        # TSan; they are not this repo's accesses
        f.write("called_from_lib:libgomp\nrace:libgomp\n")

    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(HERE)] +
        ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    env["LD_PRELOAD"] = libtsan
    env["XGBTPU_SAN"] = "thread"
    # more threads than this box has cores: the row-slab quant
    # accumulation must interleave for TSan to have races to adjudicate
    env["OMP_NUM_THREADS"] = "4"
    env["TSAN_OPTIONS"] = (
        f"suppressions={supp}:ignore_noninstrumented_modules=1:"
        f"exitcode=66:history_size=4")
    ckpt_dir = str(tmp_path / "ckpt")
    os.makedirs(ckpt_dir, exist_ok=True)
    try:
        r = subprocess.run(
            [sys.executable, child, ckpt_dir],
            capture_output=True, text=True, timeout=600, env=env)
    finally:
        # the child builds .tsan.so artifacts next to the production
        # libs; drop them so no later plain run ever dlopens one
        import glob

        for p in glob.glob(os.path.join(
                os.path.dirname(native.__file__), "*.tsan.so")):
            os.unlink(p)
    assert r.returncode != 66, \
        f"ThreadSanitizer reported races:\n{r.stdout}\n{r.stderr}"
    assert r.returncode == 0, \
        f"tsan child failed:\n{r.stdout}\n{r.stderr}"
    assert "TSAN DRIVE OK" in r.stdout
    assert "WARNING: ThreadSanitizer" not in r.stderr
