"""The C API (native/c_api.cpp): reference-ABI surface over the TPU
runtime, exercised two ways — via ctypes from Python (GIL-sharing path)
and from a REAL C host program (embedded-interpreter path), both matching
the Python API's results bit-for-bit."""

import ctypes
import os
import subprocess
import sys

import numpy as np
import pytest

import xgboost_tpu as xgb
from xgboost_tpu.native import build_capi

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _data(n=600, F=5, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, F).astype(np.float32)
    y = ((X @ rng.randn(F)) > 0).astype(np.float32)
    return X, y


@pytest.fixture(scope="module")
def lib():
    path = build_capi()
    if path is None:
        pytest.skip("C API library could not be built")
    L = ctypes.CDLL(path)
    L.XGBGetLastError.restype = ctypes.c_char_p
    L.XGDMatrixCreateFromMat.argtypes = [
        ctypes.POINTER(ctypes.c_float), ctypes.c_uint64, ctypes.c_uint64,
        ctypes.c_float, ctypes.POINTER(ctypes.c_void_p)]
    L.XGBoosterPredict.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int, ctypes.c_uint,
        ctypes.c_int, ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.POINTER(ctypes.c_float))]
    return L


def _check(L, rc):
    assert rc == 0, L.XGBGetLastError().decode()


def test_c_api_train_predict_matches_python(lib, tmp_path):
    X, y = _data()
    n, F = X.shape

    h = ctypes.c_void_p()
    Xf = np.ascontiguousarray(X)
    _check(lib, lib.XGDMatrixCreateFromMat(
        Xf.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), n, F,
        ctypes.c_float(float("nan")), ctypes.byref(h)))

    yl = np.ascontiguousarray(y)
    _check(lib, lib.XGDMatrixSetFloatInfo(
        h, b"label", yl.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), n))

    out = ctypes.c_uint64()
    _check(lib, lib.XGDMatrixNumRow(h, ctypes.byref(out)))
    assert out.value == n
    _check(lib, lib.XGDMatrixNumCol(h, ctypes.byref(out)))
    assert out.value == F

    bh = ctypes.c_void_p()
    mats = (ctypes.c_void_p * 1)(h)
    _check(lib, lib.XGBoosterCreate(mats, 1, ctypes.byref(bh)))
    for k, v in [(b"objective", b"binary:logistic"), (b"max_depth", b"3"),
                 (b"eta", b"0.4"), (b"max_bin", b"32"), (b"seed", b"7"),
                 (b"verbosity", b"0")]:
        _check(lib, lib.XGBoosterSetParam(bh, k, v))
    for it in range(5):
        _check(lib, lib.XGBoosterUpdateOneIter(bh, it, h))

    # eval string has the reference's "[iter]\tname-metric:value" shape
    names = (ctypes.c_char_p * 1)(b"train")
    s = ctypes.c_char_p()
    _check(lib, lib.XGBoosterEvalOneIter(bh, 4, mats, names, 1,
                                         ctypes.byref(s)))
    assert s.value.decode().startswith("[4]") and "train-" in s.value.decode()

    plen = ctypes.c_uint64()
    pptr = ctypes.POINTER(ctypes.c_float)()
    _check(lib, lib.XGBoosterPredict(bh, h, 0, 0, 0, ctypes.byref(plen),
                                     ctypes.byref(pptr)))
    pred_c = np.ctypeslib.as_array(pptr, shape=(plen.value,)).copy()

    # the same model via the Python API must predict identically
    d = xgb.DMatrix(X, label=y)
    bst = xgb.train({"objective": "binary:logistic", "max_depth": 3,
                     "eta": 0.4, "max_bin": 32, "seed": 7, "verbosity": 0},
                    d, 5)
    pred_py = np.asarray(bst.predict(d), np.float32)
    np.testing.assert_array_equal(pred_c, pred_py)

    # save via C, reload via C into a fresh booster, margin predict
    mpath = str(tmp_path / "capi_model.json").encode()
    _check(lib, lib.XGBoosterSaveModel(bh, mpath))
    bh2 = ctypes.c_void_p()
    _check(lib, lib.XGBoosterCreate(None, 0, ctypes.byref(bh2)))
    _check(lib, lib.XGBoosterLoadModel(bh2, mpath))
    _check(lib, lib.XGBoosterPredict(bh2, h, 1, 0, 0, ctypes.byref(plen),
                                     ctypes.byref(pptr)))
    margin_c = np.ctypeslib.as_array(pptr, shape=(plen.value,)).copy()
    margin_py = np.asarray(bst.predict(d, output_margin=True), np.float32)
    np.testing.assert_array_equal(margin_c, margin_py)

    nf = ctypes.c_uint64()
    _check(lib, lib.XGBoosterGetNumFeature(bh2, ctypes.byref(nf)))
    assert nf.value == F

    # attributes round-trip
    _check(lib, lib.XGBoosterSetAttr(bh, b"best_iteration", b"4"))
    sa = ctypes.c_char_p()
    ok = ctypes.c_int()
    _check(lib, lib.XGBoosterGetAttr(bh, b"best_iteration",
                                     ctypes.byref(sa), ctypes.byref(ok)))
    assert ok.value == 1 and sa.value == b"4"

    _check(lib, lib.XGBoosterFree(bh))
    _check(lib, lib.XGBoosterFree(bh2))
    _check(lib, lib.XGDMatrixFree(h))


def test_c_api_error_contract(lib):
    bh = ctypes.c_void_p()
    _check(lib, lib.XGBoosterCreate(None, 0, ctypes.byref(bh)))
    rc = lib.XGBoosterSetParam(bh, b"tree_method", b"no_such_method")
    if rc == 0:  # params may validate lazily: force configure via predict
        rc = lib.XGBoosterLoadModel(bh, b"/nonexistent/path.json")
    assert rc == -1
    msg = lib.XGBGetLastError().decode()
    assert msg, "error message must be retrievable"
    _check(lib, lib.XGBoosterFree(bh))


def test_c_api_custom_objective_boost(lib):
    """XGBoosterBoostOneIter: caller-supplied gradients (the fobj path)."""
    X, y = _data(300, 4, seed=3)
    n, F = X.shape
    h = ctypes.c_void_p()
    Xf = np.ascontiguousarray(X)
    _check(lib, lib.XGDMatrixCreateFromMat(
        Xf.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), n, F,
        ctypes.c_float(float("nan")), ctypes.byref(h)))
    yl = np.ascontiguousarray(y)
    _check(lib, lib.XGDMatrixSetFloatInfo(
        h, b"label", yl.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), n))
    bh = ctypes.c_void_p()
    mats = (ctypes.c_void_p * 1)(h)
    _check(lib, lib.XGBoosterCreate(mats, 1, ctypes.byref(bh)))
    for k, v in [(b"max_depth", b"3"), (b"max_bin", b"16"),
                 (b"verbosity", b"0")]:
        _check(lib, lib.XGBoosterSetParam(bh, k, v))
    g = np.ascontiguousarray((0.5 - y).astype(np.float32))
    hs = np.ascontiguousarray(np.full(n, 0.25, np.float32))
    _check(lib, lib.XGBoosterBoostOneIter(
        bh, h, g.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        hs.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), n))
    plen = ctypes.c_uint64()
    pptr = ctypes.POINTER(ctypes.c_float)()
    _check(lib, lib.XGBoosterPredict(bh, h, 1, 0, 0, ctypes.byref(plen),
                                     ctypes.byref(pptr)))
    m = np.ctypeslib.as_array(pptr, shape=(plen.value,))
    assert np.isfinite(m).all() and m.std() > 0
    _check(lib, lib.XGBoosterFree(bh))
    _check(lib, lib.XGDMatrixFree(h))


C_HOST = r"""
#include <stdio.h>
#include <stdlib.h>
#include <string.h>
#include <math.h>

typedef unsigned long long bst_ulong;
extern const char *XGBGetLastError(void);
extern int XGDMatrixCreateFromMat(const float*, bst_ulong, bst_ulong,
                                  float, void**);
extern int XGDMatrixSetFloatInfo(void*, const char*, const float*,
                                 bst_ulong);
extern int XGDMatrixFree(void*);
extern int XGBoosterCreate(void**, bst_ulong, void**);
extern int XGBoosterSetParam(void*, const char*, const char*);
extern int XGBoosterUpdateOneIter(void*, int, void*);
extern int XGBoosterPredict(void*, void*, int, unsigned, int,
                            bst_ulong*, const float**);
extern int XGBoosterFree(void*);
extern int XGBoosterSaveJsonConfig(void*, bst_ulong*, const char**);
extern int XGBoosterSerializeToBuffer(void*, bst_ulong*, const char**);
extern int XGBoosterUnserializeFromBuffer(void*, const void*, bst_ulong);
extern int XGDMatrixSliceDMatrix(void*, const int*, bst_ulong, void**);
extern int XGBoosterSetStrFeatureInfo(void*, const char*, const char**,
                                      bst_ulong);
extern int XGBoosterGetStrFeatureInfo(void*, const char*, bst_ulong*,
                                      const char***);

#define CK(x) if ((x) != 0) { \
  fprintf(stderr, "FAIL: %s\n", XGBGetLastError()); return 1; }

int main(void) {
  enum { N = 256, F = 3 };
  static float data[N * F], label[N];
  unsigned s = 12345;
  for (int i = 0; i < N; ++i) {
    float acc = 0;
    for (int j = 0; j < F; ++j) {
      s = s * 1103515245u + 12345u;
      float v = ((float)(s >> 16) / 32768.0f) - 1.0f;
      data[i * F + j] = v;
      acc += v;
    }
    label[i] = acc > 0 ? 1.0f : 0.0f;
  }
  void *dmat = NULL, *bst = NULL;
  CK(XGDMatrixCreateFromMat(data, N, F, nanf(""), &dmat));
  CK(XGDMatrixSetFloatInfo(dmat, "label", label, N));
  void *mats[1] = {dmat};
  CK(XGBoosterCreate(mats, 1, &bst));
  CK(XGBoosterSetParam(bst, "objective", "binary:logistic"));
  CK(XGBoosterSetParam(bst, "max_depth", "3"));
  CK(XGBoosterSetParam(bst, "verbosity", "0"));
  for (int it = 0; it < 4; ++it) CK(XGBoosterUpdateOneIter(bst, it, dmat));
  bst_ulong len = 0;
  const float *out = NULL;
  CK(XGBoosterPredict(bst, dmat, 0, 0, 0, &len, &out));
  if (len != N) { fprintf(stderr, "bad len\n"); return 1; }
  int correct = 0;
  for (int i = 0; i < N; ++i)
    correct += (out[i] > 0.5f) == (label[i] > 0.5f);
  printf("C_HOST_ACC=%.3f\n", (double)correct / N);

  /* robustness surface (ISSUE 5 satellite): config JSON + full-state
     serialize/unserialize round-trip through a FRESH booster must
     reproduce predictions bit-for-bit */
  bst_ulong cfg_len = 0;
  const char *cfg = NULL;
  CK(XGBoosterSaveJsonConfig(bst, &cfg_len, &cfg));
  if (cfg_len == 0 || strstr(cfg, "learner") == NULL) {
    fprintf(stderr, "bad config json\n"); return 1;
  }
  bst_ulong ser_len = 0;
  const char *ser = NULL;
  CK(XGBoosterSerializeToBuffer(bst, &ser_len, &ser));
  void *bst2 = NULL;
  CK(XGBoosterCreate(NULL, 0, &bst2));
  CK(XGBoosterUnserializeFromBuffer(bst2, ser, ser_len));
  bst_ulong len2 = 0;
  const float *out2 = NULL;
  CK(XGBoosterPredict(bst2, dmat, 0, 0, 0, &len2, &out2));
  if (len2 != len) { fprintf(stderr, "bad unserialized len\n"); return 1; }
  for (bst_ulong i = 0; i < len; ++i) {
    if (out2[i] != out[i]) {
      fprintf(stderr, "unserialized predict mismatch at %llu\n", i);
      return 1;
    }
  }
  printf("C_HOST_SERIALIZE=OK\n");

  /* serving-adjacent breadth (ISSUE 8 satellite): row slicing and model
     feature metadata, both exercised from a real C host */
  int idx[64];
  for (int i = 0; i < 64; ++i) idx[i] = i * 2;
  /* predicting again through `bst` reuses its out-buffer: snapshot the
     full-matrix predictions before the slice predict overwrites them */
  static float full[N];
  memcpy(full, out, sizeof(float) * N);
  void *dslice = NULL;
  CK(XGDMatrixSliceDMatrix(dmat, idx, 64, &dslice));
  bst_ulong slen = 0;
  const float *sout = NULL;
  CK(XGBoosterPredict(bst, dslice, 0, 0, 0, &slen, &sout));
  if (slen != 64) { fprintf(stderr, "bad slice len\n"); return 1; }
  for (int i = 0; i < 64; ++i) {
    if (sout[i] != full[idx[i]]) {
      fprintf(stderr, "slice predict mismatch at %d\n", i);
      return 1;
    }
  }
  printf("C_HOST_SLICE=OK\n");

  const char *names[F] = {"alpha", "beta", "gamma"};
  CK(XGBoosterSetStrFeatureInfo(bst, "feature_name", names, F));
  bst_ulong nlen = 0;
  const char **got_names = NULL;
  CK(XGBoosterGetStrFeatureInfo(bst, "feature_name", &nlen, &got_names));
  if (nlen != F) { fprintf(stderr, "bad feature_name len\n"); return 1; }
  for (int j = 0; j < F; ++j) {
    if (strcmp(got_names[j], names[j]) != 0) {
      fprintf(stderr, "feature_name mismatch at %d: %s\n", j, got_names[j]);
      return 1;
    }
  }
  printf("C_HOST_FEATINFO=OK\n");

  CK(XGDMatrixFree(dslice));
  CK(XGBoosterFree(bst2));
  CK(XGBoosterFree(bst));
  CK(XGDMatrixFree(dmat));
  return 0;
}
"""


def test_c_api_from_real_c_host(lib, tmp_path):
    """Compile and run an actual C program against libxgbtpu.so: the
    embedded-interpreter path (Py_Initialize inside the library) — the
    reference's primary consumption mode (a non-Python host)."""
    path = build_capi()
    src = tmp_path / "host.c"
    src.write_text(C_HOST)
    exe = tmp_path / "host"
    libdir = os.path.dirname(path)
    r = subprocess.run(
        ["gcc", str(src), "-o", str(exe), f"-L{libdir}",
         "-l:libxgbtpu.so", f"-Wl,-rpath,{libdir}", "-lm"],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stderr[-2000:]
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([str(exe)], capture_output=True, text=True,
                        env=env, timeout=600)
    assert out.returncode == 0, (out.stdout, out.stderr[-2000:])
    acc = float(out.stdout.split("C_HOST_ACC=")[1].split()[0])
    assert acc > 0.9, out.stdout
    # the serialize/config surface ran and round-tripped bit-for-bit
    assert "C_HOST_SERIALIZE=OK" in out.stdout, out.stdout
    # slicing + model feature metadata from the C host (ISSUE 8 satellite)
    assert "C_HOST_SLICE=OK" in out.stdout, out.stdout
    assert "C_HOST_FEATINFO=OK" in out.stdout, out.stdout


def test_c_api_csr_dump_and_buffer_roundtrip(lib, tmp_path):
    """CSR ingestion (never-densified sparse path), model dump strings,
    and the save/load-from-buffer pair."""
    import scipy.sparse as sp

    rng = np.random.RandomState(1)
    X = sp.random(500, 6, density=0.4, format="csr", random_state=1,
                  dtype=np.float32)
    y = (np.asarray(X.sum(axis=1)).ravel() > 0.5).astype(np.float32)

    indptr = np.ascontiguousarray(X.indptr, np.uint64)
    indices = np.ascontiguousarray(X.indices, np.uint32)
    vals = np.ascontiguousarray(X.data, np.float32)
    h = ctypes.c_void_p()
    lib.XGDMatrixCreateFromCSREx.argtypes = [
        ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_uint32),
        ctypes.POINTER(ctypes.c_float), ctypes.c_size_t, ctypes.c_size_t,
        ctypes.c_size_t, ctypes.POINTER(ctypes.c_void_p)]
    _check(lib, lib.XGDMatrixCreateFromCSREx(
        indptr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        indices.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
        vals.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        len(indptr), len(vals), X.shape[1], ctypes.byref(h)))
    out = ctypes.c_uint64()
    _check(lib, lib.XGDMatrixNumRow(h, ctypes.byref(out)))
    assert out.value == 500
    yl = np.ascontiguousarray(y)
    _check(lib, lib.XGDMatrixSetFloatInfo(
        h, b"label", yl.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        len(y)))

    bh = ctypes.c_void_p()
    mats = (ctypes.c_void_p * 1)(h)
    _check(lib, lib.XGBoosterCreate(mats, 1, ctypes.byref(bh)))
    for k, v in [(b"objective", b"binary:logistic"), (b"max_depth", b"3"),
                 (b"verbosity", b"0"), (b"seed", b"5")]:
        _check(lib, lib.XGBoosterSetParam(bh, k, v))
    for it in range(3):
        _check(lib, lib.XGBoosterUpdateOneIter(bh, it, h))

    # dump: one string per tree, reference text-dump shape
    dlen = ctypes.c_uint64()
    darr = ctypes.POINTER(ctypes.c_char_p)()
    _check(lib, lib.XGBoosterDumpModel(bh, b"", 0, ctypes.byref(dlen),
                                       ctypes.byref(darr)))
    assert dlen.value == 3
    assert b"leaf" in darr[0]

    # buffer round-trip == Python save_raw
    blen = ctypes.c_uint64()
    bptr = ctypes.c_char_p()
    _check(lib, lib.XGBoosterSaveModelToBuffer(bh, b"{}",
                                               ctypes.byref(blen),
                                               ctypes.byref(bptr)))
    raw = ctypes.string_at(bptr, blen.value)
    bh2 = ctypes.c_void_p()
    _check(lib, lib.XGBoosterCreate(None, 0, ctypes.byref(bh2)))
    _check(lib, lib.XGBoosterLoadModelFromBuffer(bh2, raw, len(raw)))
    plen = ctypes.c_uint64()
    pptr = ctypes.POINTER(ctypes.c_float)()
    _check(lib, lib.XGBoosterPredict(bh, h, 0, 0, 0, ctypes.byref(plen),
                                     ctypes.byref(pptr)))
    p1 = np.ctypeslib.as_array(pptr, shape=(plen.value,)).copy()
    _check(lib, lib.XGBoosterPredict(bh2, h, 0, 0, 0, ctypes.byref(plen),
                                     ctypes.byref(pptr)))
    p2 = np.ctypeslib.as_array(pptr, shape=(plen.value,)).copy()
    np.testing.assert_array_equal(p1, p2)
    _check(lib, lib.XGBoosterFree(bh))
    _check(lib, lib.XGBoosterFree(bh2))
    _check(lib, lib.XGDMatrixFree(h))


def test_c_api_predict_from_dmatrix(lib):
    """The modern JSON-config predict entry (c_api.h:928): value, margin,
    leaf, and contribs types with explicit shape reporting, matching the
    Python API bit-for-bit."""
    X, y = _data(400, 4, seed=9)
    n, F = X.shape
    h = ctypes.c_void_p()
    Xf = np.ascontiguousarray(X)
    _check(lib, lib.XGDMatrixCreateFromMat(
        Xf.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), n, F,
        ctypes.c_float(float("nan")), ctypes.byref(h)))
    yl = np.ascontiguousarray(y)
    _check(lib, lib.XGDMatrixSetFloatInfo(
        h, b"label", yl.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), n))
    bh = ctypes.c_void_p()
    mats = (ctypes.c_void_p * 1)(h)
    _check(lib, lib.XGBoosterCreate(mats, 1, ctypes.byref(bh)))
    for k, v in [(b"objective", b"binary:logistic"), (b"max_depth", b"3"),
                 (b"seed", b"2"), (b"verbosity", b"0")]:
        _check(lib, lib.XGBoosterSetParam(bh, k, v))
    for it in range(4):
        _check(lib, lib.XGBoosterUpdateOneIter(bh, it, h))

    lib.XGBoosterPredictFromDMatrix.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_char_p,
        ctypes.POINTER(ctypes.POINTER(ctypes.c_uint64)),
        ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.POINTER(ctypes.c_float))]

    def run(cfg: bytes):
        shp = ctypes.POINTER(ctypes.c_uint64)()
        dim = ctypes.c_uint64()
        res = ctypes.POINTER(ctypes.c_float)()
        _check(lib, lib.XGBoosterPredictFromDMatrix(
            bh, h, cfg, ctypes.byref(shp), ctypes.byref(dim),
            ctypes.byref(res)))
        shape = tuple(shp[i] for i in range(dim.value))
        count = int(np.prod(shape))
        return np.ctypeslib.as_array(res, shape=(count,)).copy().reshape(
            shape)

    import xgboost_tpu as xgb

    d = xgb.DMatrix(X, label=y)
    bst = xgb.train({"objective": "binary:logistic", "max_depth": 3,
                     "seed": 2, "verbosity": 0}, d, 4)
    np.testing.assert_array_equal(run(b'{"type": 0}'),
                                  np.asarray(bst.predict(d), np.float32))
    np.testing.assert_array_equal(
        run(b'{"type": 1}'),
        np.asarray(bst.predict(d, output_margin=True), np.float32))
    leaf = run(b'{"type": 6}')
    assert leaf.shape == (n, 4)
    np.testing.assert_array_equal(
        leaf, np.asarray(bst.predict(d, pred_leaf=True), np.float32))
    contribs = run(b'{"type": 2}')
    assert contribs.shape == (n, F + 1)
    # iteration_range through the config
    p2 = run(b'{"type": 0, "iteration_begin": 0, "iteration_end": 2}')
    np.testing.assert_array_equal(
        p2, np.asarray(bst.predict(d, iteration_range=(0, 2)), np.float32))
    _check(lib, lib.XGBoosterFree(bh))
    _check(lib, lib.XGDMatrixFree(h))


def test_c_api_set_uint_info_exact_above_2_24(lib):
    """XGDMatrixSetUIntInfo regression (ISSUE 1 satellite): the uint32
    payload must survive the boundary EXACTLY — the old float32 detour
    rounded values >= 2^24 (adjacent qids merged, corrupting group
    structure)."""
    X, y = _data(4, 3, seed=5)
    n, F = X.shape
    h = ctypes.c_void_p()
    Xf = np.ascontiguousarray(X)
    _check(lib, lib.XGDMatrixCreateFromMat(
        Xf.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), n, F,
        ctypes.c_float(float("nan")), ctypes.byref(h)))
    # two ADJACENT huge qids: indistinguishable after a float32 round-trip
    big = np.uint32(1 << 24)
    qid = np.ascontiguousarray(
        np.asarray([big, big, big + 1, big + 1], np.uint32))
    _check(lib, lib.XGDMatrixSetUIntInfo(
        h, b"qid", qid.ctypes.data_as(ctypes.POINTER(ctypes.c_uint)), n))
    out_len = ctypes.c_uint64()
    out_ptr = ctypes.POINTER(ctypes.c_uint)()
    _check(lib, lib.XGDMatrixGetUIntInfo(
        h, b"group_ptr", ctypes.byref(out_len), ctypes.byref(out_ptr)))
    gp = np.ctypeslib.as_array(out_ptr, shape=(out_len.value,)).copy()
    # 2 groups of 2 rows each; the float detour collapsed them into one
    np.testing.assert_array_equal(gp, [0, 2, 4])
    _check(lib, lib.XGDMatrixFree(h))


def test_c_api_serialize_and_json_config(lib):
    """XGBoosterSerializeToBuffer/UnserializeFromBuffer and
    XGBoosterSaveJsonConfig/LoadJsonConfig (ISSUE 5 satellite; reference
    c_api.h:990-1040): full-state round-trip preserves BOTH the model and
    the learner configuration — the part Save/LoadModel drops."""
    import json

    X, y = _data(300, 4, seed=13)
    n, F = X.shape
    h = ctypes.c_void_p()
    Xf = np.ascontiguousarray(X)
    _check(lib, lib.XGDMatrixCreateFromMat(
        Xf.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), n, F,
        ctypes.c_float(float("nan")), ctypes.byref(h)))
    yl = np.ascontiguousarray(y)
    _check(lib, lib.XGDMatrixSetFloatInfo(
        h, b"label", yl.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), n))
    bh = ctypes.c_void_p()
    mats = (ctypes.c_void_p * 1)(h)
    _check(lib, lib.XGBoosterCreate(mats, 1, ctypes.byref(bh)))
    for k, v in [(b"objective", b"binary:logistic"), (b"max_depth", b"4"),
                 (b"eta", b"0.3"), (b"max_bin", b"16"), (b"seed", b"9"),
                 (b"verbosity", b"0")]:
        _check(lib, lib.XGBoosterSetParam(bh, k, v))
    for it in range(3):
        _check(lib, lib.XGBoosterUpdateOneIter(bh, it, h))

    # --- SaveJsonConfig: parses, carries the configured params ---
    lib.XGBoosterSaveJsonConfig.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_char_p)]
    clen = ctypes.c_uint64()
    cptr = ctypes.c_char_p()
    _check(lib, lib.XGBoosterSaveJsonConfig(bh, ctypes.byref(clen),
                                            ctypes.byref(cptr)))
    cfg = json.loads(ctypes.string_at(cptr, clen.value))
    assert cfg["learner"]["objective"]["name"] == "binary:logistic"
    assert cfg["learner"]["gradient_booster"]["params"]["max_depth"] == "4"

    # --- SerializeToBuffer -> fresh handle -> Unserialize: predictions
    # AND config survive (LoadModelFromBuffer drops the config) ---
    slen = ctypes.c_uint64()
    sptr = ctypes.c_char_p()
    _check(lib, lib.XGBoosterSerializeToBuffer(bh, ctypes.byref(slen),
                                               ctypes.byref(sptr)))
    blob = ctypes.string_at(sptr, slen.value)
    assert slen.value > 0
    bh2 = ctypes.c_void_p()
    _check(lib, lib.XGBoosterCreate(None, 0, ctypes.byref(bh2)))
    _check(lib, lib.XGBoosterUnserializeFromBuffer(bh2, blob, len(blob)))
    plen = ctypes.c_uint64()
    pptr = ctypes.POINTER(ctypes.c_float)()
    _check(lib, lib.XGBoosterPredict(bh, h, 0, 0, 0, ctypes.byref(plen),
                                     ctypes.byref(pptr)))
    p1 = np.ctypeslib.as_array(pptr, shape=(plen.value,)).copy()
    _check(lib, lib.XGBoosterPredict(bh2, h, 0, 0, 0, ctypes.byref(plen),
                                     ctypes.byref(pptr)))
    p2 = np.ctypeslib.as_array(pptr, shape=(plen.value,)).copy()
    np.testing.assert_array_equal(p1, p2)
    _check(lib, lib.XGBoosterSaveJsonConfig(bh2, ctypes.byref(clen),
                                            ctypes.byref(cptr)))
    cfg2 = json.loads(ctypes.string_at(cptr, clen.value))
    assert cfg2["learner"]["gradient_booster"]["params"]["max_depth"] == "4"
    assert cfg2["learner"]["objective"]["name"] == "binary:logistic"

    # --- LoadJsonConfig configures a fresh booster equivalently ---
    bh3 = ctypes.c_void_p()
    _check(lib, lib.XGBoosterCreate(mats, 1, ctypes.byref(bh3)))
    _check(lib, lib.XGBoosterLoadJsonConfig(
        bh3, ctypes.string_at(cptr, clen.value)))
    for it in range(3):
        _check(lib, lib.XGBoosterUpdateOneIter(bh3, it, h))
    _check(lib, lib.XGBoosterPredict(bh3, h, 0, 0, 0, ctypes.byref(plen),
                                     ctypes.byref(pptr)))
    p3 = np.ctypeslib.as_array(pptr, shape=(plen.value,)).copy()
    np.testing.assert_array_equal(p3, p1)
    # malformed buffer fails loudly with a retrievable message
    rc = lib.XGBoosterUnserializeFromBuffer(bh2, b"not json", 8)
    assert rc == -1 and lib.XGBGetLastError()
    _check(lib, lib.XGBoosterFree(bh))
    _check(lib, lib.XGBoosterFree(bh2))
    _check(lib, lib.XGBoosterFree(bh3))
    _check(lib, lib.XGDMatrixFree(h))


def _array_interface(arr: np.ndarray) -> bytes:
    """__array_interface__ JSON over a numpy array's live buffer — the
    payload XGBoosterPredictFromDense/CSR take (c_api.cc:833)."""
    import json

    return json.dumps({
        "data": [arr.ctypes.data, True],
        "shape": list(arr.shape),
        "typestr": arr.__array_interface__["typestr"],
        "version": 3,
    }).encode()


def _inplace_argtypes(lib):
    u64p = ctypes.POINTER(ctypes.c_uint64)
    f32pp = ctypes.POINTER(ctypes.POINTER(ctypes.c_float))
    lib.XGBoosterPredictFromDense.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_void_p,
        ctypes.POINTER(u64p), ctypes.POINTER(ctypes.c_uint64), f32pp]
    lib.XGBoosterPredictFromCSR.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
        ctypes.c_uint64, ctypes.c_char_p, ctypes.c_void_p,
        ctypes.POINTER(u64p), ctypes.POINTER(ctypes.c_uint64), f32pp]


def test_c_api_inplace_predict_dense_and_csr(lib):
    """XGBoosterPredictFromDense/CSR (zero-copy inplace, c_api.cc:833):
    value + margin types, missing sentinel, iteration_range — all matching
    the Python inplace_predict bit-for-bit."""
    import json

    import scipy.sparse as sp

    X, y = _data(400, 5, seed=21)
    n, F = X.shape
    d = xgb.DMatrix(X, label=y)
    params = {"objective": "binary:logistic", "max_depth": 3, "seed": 7,
              "verbosity": 0}
    bst = xgb.train(params, d, 4)
    blob = bst.save_raw()
    bh = ctypes.c_void_p()
    _check(lib, lib.XGBoosterCreate(None, 0, ctypes.byref(bh)))
    _check(lib, lib.XGBoosterLoadModelFromBuffer(bh, blob, len(blob)))
    _inplace_argtypes(lib)

    shp = ctypes.POINTER(ctypes.c_uint64)()
    dim = ctypes.c_uint64()
    res = ctypes.POINTER(ctypes.c_float)()

    def run_dense(arr, cfg: dict):
        _check(lib, lib.XGBoosterPredictFromDense(
            bh, _array_interface(arr), json.dumps(cfg).encode(), None,
            ctypes.byref(shp), ctypes.byref(dim), ctypes.byref(res)))
        shape = tuple(shp[i] for i in range(dim.value))
        count = int(np.prod(shape))
        return np.ctypeslib.as_array(res, shape=(count,)).copy().reshape(
            shape)

    Xc = np.ascontiguousarray(X)
    np.testing.assert_array_equal(
        run_dense(Xc, {"type": 0}),
        np.asarray(bst.inplace_predict(X), np.float32))
    np.testing.assert_array_equal(
        run_dense(Xc, {"type": 1}),
        np.asarray(bst.inplace_predict(X, predict_type="margin"),
                   np.float32))
    np.testing.assert_array_equal(
        run_dense(Xc, {"type": 0, "iteration_begin": 0,
                       "iteration_end": 2}),
        np.asarray(bst.inplace_predict(X, iteration_range=(0, 2)),
                   np.float32))
    # missing sentinel: -999 entries must route like NaN
    Xm = np.ascontiguousarray(np.where(np.isnan(X), np.float32(-999), X))
    Xm[::7, 0] = -999.0
    np.testing.assert_array_equal(
        run_dense(Xm, {"type": 0, "missing": -999.0}),
        np.asarray(bst.inplace_predict(Xm, missing=-999.0), np.float32))

    # ---- CSR ----
    Xs = sp.random(200, F, density=0.5, format="csr", random_state=3,
                   dtype=np.float32)
    indptr = np.ascontiguousarray(Xs.indptr.astype(np.uint64))
    indices = np.ascontiguousarray(Xs.indices.astype(np.uint32))
    values = np.ascontiguousarray(Xs.data)
    _check(lib, lib.XGBoosterPredictFromCSR(
        bh, _array_interface(indptr), _array_interface(indices),
        _array_interface(values), F, json.dumps({"type": 0}).encode(),
        None, ctypes.byref(shp), ctypes.byref(dim), ctypes.byref(res)))
    shape = tuple(shp[i] for i in range(dim.value))
    out = np.ctypeslib.as_array(
        res, shape=(int(np.prod(shape)),)).copy().reshape(shape)
    np.testing.assert_array_equal(
        out, np.asarray(bst.inplace_predict(Xs), np.float32))
    # iteration_begin with end=0 means rounds begin..end (review finding:
    # the range must not be dropped when only begin is set)
    np.testing.assert_array_equal(
        run_dense(Xc, {"type": 0, "iteration_begin": 2,
                       "iteration_end": 0}),
        np.asarray(bst.inplace_predict(X, iteration_range=(2, 0)),
                   np.float32))
    # unsupported type must fail loudly with a retrievable message
    rc = lib.XGBoosterPredictFromDense(
        bh, _array_interface(Xc), json.dumps({"type": 6}).encode(), None,
        ctypes.byref(shp), ctypes.byref(dim), ctypes.byref(res))
    assert rc == -1 and lib.XGBGetLastError()
    # malformed config (string where an int belongs) errors instead of
    # silently predicting with all trees
    rc = lib.XGBoosterPredictFromDense(
        bh, _array_interface(Xc),
        json.dumps({"type": 0, "iteration_end": "3"}).encode(), None,
        ctypes.byref(shp), ctypes.byref(dim), ctypes.byref(res))
    assert rc == -1 and lib.XGBGetLastError()
    _check(lib, lib.XGBoosterFree(bh))


def test_c_api_slice_dmatrix(lib):
    """XGDMatrixSliceDMatrix (ISSUE 8 satellite; reference c_api.h:240):
    the sliced handle carries the selected rows AND their metadata, and
    predictions on it match numpy-indexing the full matrix's output."""
    X, y = _data(300, 4, seed=17)
    n, F = X.shape
    h = ctypes.c_void_p()
    Xf = np.ascontiguousarray(X)
    _check(lib, lib.XGDMatrixCreateFromMat(
        Xf.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), n, F,
        ctypes.c_float(float("nan")), ctypes.byref(h)))
    yl = np.ascontiguousarray(y)
    _check(lib, lib.XGDMatrixSetFloatInfo(
        h, b"label", yl.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), n))

    idx = np.ascontiguousarray(np.arange(1, n, 3, dtype=np.int32))
    h2 = ctypes.c_void_p()
    _check(lib, lib.XGDMatrixSliceDMatrix(
        h, idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int)), len(idx),
        ctypes.byref(h2)))
    out = ctypes.c_uint64()
    _check(lib, lib.XGDMatrixNumRow(h2, ctypes.byref(out)))
    assert out.value == len(idx)
    _check(lib, lib.XGDMatrixNumCol(h2, ctypes.byref(out)))
    assert out.value == F

    # per-row metadata sliced along
    flen = ctypes.c_uint64()
    fptr = ctypes.POINTER(ctypes.c_float)()
    _check(lib, lib.XGDMatrixGetFloatInfo(h2, b"label", ctypes.byref(flen),
                                          ctypes.byref(fptr)))
    got = np.ctypeslib.as_array(fptr, shape=(flen.value,)).copy()
    np.testing.assert_array_equal(got, y[idx])

    # margin predictions on the slice == numpy-indexed full predictions
    bh = ctypes.c_void_p()
    mats = (ctypes.c_void_p * 1)(h)
    _check(lib, lib.XGBoosterCreate(mats, 1, ctypes.byref(bh)))
    for k, v in [(b"objective", b"binary:logistic"), (b"max_depth", b"3"),
                 (b"max_bin", b"16"), (b"seed", b"3"), (b"verbosity", b"0")]:
        _check(lib, lib.XGBoosterSetParam(bh, k, v))
    for it in range(3):
        _check(lib, lib.XGBoosterUpdateOneIter(bh, it, h))
    plen = ctypes.c_uint64()
    pptr = ctypes.POINTER(ctypes.c_float)()
    _check(lib, lib.XGBoosterPredict(bh, h, 1, 0, 0, ctypes.byref(plen),
                                     ctypes.byref(pptr)))
    full = np.ctypeslib.as_array(pptr, shape=(plen.value,)).copy()
    _check(lib, lib.XGBoosterPredict(bh, h2, 1, 0, 0, ctypes.byref(plen),
                                     ctypes.byref(pptr)))
    sliced = np.ctypeslib.as_array(pptr, shape=(plen.value,)).copy()
    np.testing.assert_array_equal(sliced, full[idx])
    _check(lib, lib.XGBoosterFree(bh))
    _check(lib, lib.XGDMatrixFree(h2))
    _check(lib, lib.XGDMatrixFree(h))


def test_c_api_str_feature_info_roundtrip(lib):
    """XGBoosterSetStrFeatureInfo/GetStrFeatureInfo (ISSUE 8 satellite;
    reference c_api.h:1146): names/types attach to the MODEL, round-trip
    through the C surface, and survive a save/load-from-buffer cycle."""
    X, y = _data(200, 3, seed=23)
    d = xgb.DMatrix(X, label=y)
    bst = xgb.train({"objective": "binary:logistic", "max_depth": 2,
                     "max_bin": 16, "verbosity": 0}, d, 2)
    blob = bst.save_raw()
    bh = ctypes.c_void_p()
    _check(lib, lib.XGBoosterCreate(None, 0, ctypes.byref(bh)))
    _check(lib, lib.XGBoosterLoadModelFromBuffer(bh, blob, len(blob)))

    lib.XGBoosterGetStrFeatureInfo.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.POINTER(ctypes.c_char_p))]
    names = [b"age", b"bmi", b"dose"]
    arr = (ctypes.c_char_p * len(names))(*names)
    _check(lib, lib.XGBoosterSetStrFeatureInfo(
        bh, b"feature_name", arr, len(names)))
    types = [b"float", b"float", b"int"]
    tarr = (ctypes.c_char_p * len(types))(*types)
    _check(lib, lib.XGBoosterSetStrFeatureInfo(
        bh, b"feature_type", tarr, len(types)))

    olen = ctypes.c_uint64()
    optr = ctypes.POINTER(ctypes.c_char_p)()
    _check(lib, lib.XGBoosterGetStrFeatureInfo(
        bh, b"feature_name", ctypes.byref(olen), ctypes.byref(optr)))
    assert [optr[i] for i in range(olen.value)] == names
    _check(lib, lib.XGBoosterGetStrFeatureInfo(
        bh, b"feature_type", ctypes.byref(olen), ctypes.byref(optr)))
    assert [optr[i] for i in range(olen.value)] == types

    # the info is model state: it survives a buffer round-trip
    blen = ctypes.c_uint64()
    bptr = ctypes.c_char_p()
    _check(lib, lib.XGBoosterSaveModelToBuffer(
        bh, b"{}", ctypes.byref(blen), ctypes.byref(bptr)))
    raw = ctypes.string_at(bptr, blen.value)
    bh2 = ctypes.c_void_p()
    _check(lib, lib.XGBoosterCreate(None, 0, ctypes.byref(bh2)))
    _check(lib, lib.XGBoosterLoadModelFromBuffer(bh2, raw, len(raw)))
    _check(lib, lib.XGBoosterGetStrFeatureInfo(
        bh2, b"feature_name", ctypes.byref(olen), ctypes.byref(optr)))
    assert [optr[i] for i in range(olen.value)] == names

    # clearing with size 0 empties the surface; bad fields fail loudly
    _check(lib, lib.XGBoosterSetStrFeatureInfo(bh, b"feature_name", None, 0))
    _check(lib, lib.XGBoosterGetStrFeatureInfo(
        bh, b"feature_name", ctypes.byref(olen), ctypes.byref(optr)))
    assert olen.value == 0
    rc = lib.XGBoosterSetStrFeatureInfo(bh, b"no_such_field", arr, 1)
    assert rc == -1 and lib.XGBGetLastError()
    _check(lib, lib.XGBoosterFree(bh))
    _check(lib, lib.XGBoosterFree(bh2))


def test_dmatrix_slice_python_semantics():
    """The Python side of XGDMatrixSliceDMatrix: bool masks, sparse stays
    sparse, and group structure refuses without allow_groups."""
    import scipy.sparse as sp

    X, y = _data(120, 4, seed=29)
    d = xgb.DMatrix(X, label=y, weight=np.arange(120, dtype=np.float32))
    mask = X[:, 0] > 0
    s = d.slice(mask)
    assert s.num_row() == int(mask.sum())
    np.testing.assert_array_equal(s.get_label(), y[mask])
    np.testing.assert_array_equal(
        s.get_weight(), np.arange(120, dtype=np.float32)[mask])

    Xs = sp.random(80, 5, density=0.4, format="csr", random_state=1,
                   dtype=np.float32)
    ds = xgb.DMatrix(Xs)
    ss = ds.slice(np.arange(0, 80, 2))
    assert ss._sparse is not None, "sparse slice densified"
    np.testing.assert_array_equal(
        np.asarray(ss.get_data().todense()),
        np.asarray(Xs[::2].todense()))

    dg = xgb.DMatrix(X, label=y, group=[60, 60])
    with pytest.raises(ValueError, match="group"):
        dg.slice(np.arange(10))
    assert dg.slice(np.arange(10), allow_groups=True).num_row() == 10
    with pytest.raises(IndexError):
        d.slice(np.asarray([200]))


def test_c_api_predict_ntree_limit_counts_trees(lib):
    """XGBoosterPredict regression (ISSUE 1 satellite): ntree_limit counts
    TREES, not rounds — on a multiclass model (num_class trees per round)
    it must slice whole rounds like Python's ntree_limit, not be passed
    through as an iteration count."""
    rng = np.random.RandomState(11)
    X = rng.randn(300, 4).astype(np.float32)
    y = rng.randint(0, 3, 300).astype(np.float32)
    n, F = X.shape
    h = ctypes.c_void_p()
    Xf = np.ascontiguousarray(X)
    _check(lib, lib.XGDMatrixCreateFromMat(
        Xf.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), n, F,
        ctypes.c_float(float("nan")), ctypes.byref(h)))
    yl = np.ascontiguousarray(y)
    _check(lib, lib.XGDMatrixSetFloatInfo(
        h, b"label", yl.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), n))
    bh = ctypes.c_void_p()
    mats = (ctypes.c_void_p * 1)(h)
    _check(lib, lib.XGBoosterCreate(mats, 1, ctypes.byref(bh)))
    params = {"objective": "multi:softprob", "num_class": "3",
              "max_depth": "3", "seed": "4", "verbosity": "0"}
    for k, v in params.items():
        _check(lib, lib.XGBoosterSetParam(bh, k.encode(), v.encode()))
    for it in range(4):
        _check(lib, lib.XGBoosterUpdateOneIter(bh, it, h))

    d = xgb.DMatrix(X, label=y)
    bst = xgb.train({k: (int(v) if v.isdigit() else v)
                     for k, v in params.items()}, d, 4)

    plen = ctypes.c_uint64()
    pptr = ctypes.POINTER(ctypes.c_float)()
    # ntree_limit=6 trees == first 2 rounds of a 3-class model
    _check(lib, lib.XGBoosterPredict(bh, h, 0, 6, 0, ctypes.byref(plen),
                                     ctypes.byref(pptr)))
    pred_c = np.ctypeslib.as_array(pptr, shape=(plen.value,)).copy()
    pred_py = np.asarray(bst.predict(d, ntree_limit=6), np.float32).ravel()
    np.testing.assert_array_equal(pred_c, pred_py)
    np.testing.assert_array_equal(
        pred_c,
        np.asarray(bst.predict(d, iteration_range=(0, 2)),
                   np.float32).ravel())
    _check(lib, lib.XGBoosterFree(bh))
    _check(lib, lib.XGDMatrixFree(h))
