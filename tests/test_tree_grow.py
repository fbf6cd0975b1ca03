"""The whole-tree native grow kernel (ISSUE 17) and its quantized
histogram engine (ISSUE 19): sibling-subtraction exactness on
count-valued data, the e2e model-equality matrix across {sibling_sub
on/off} x {hist_acc quant/float} x {tree_grow/per-level} routes, the
bit-identity kill-switch pins, quant-vs-float split identity and
count-valued exactness, wide-bin (B=256) determinism, OMP thread-count
invariance, and the dispatch-table rows."""

import numpy as np
import pytest

import xgboost_tpu as xgb
from conftest import require_native
from xgboost_tpu import dispatch
from xgboost_tpu.tree import tree_kernel

def _ffi_ready() -> bool:
    from xgboost_tpu.tree import hist_kernel

    return tree_kernel.tree_ffi_ready() and hist_kernel._ensure_ffi()


@pytest.fixture(autouse=True, scope="module")
def _native_or_fail():
    require_native(_ffi_ready(), "native tree/hist kernels")


@pytest.fixture(autouse=True)
def _fresh_traces():
    """Route decisions are captured at trace time inside the jitted
    drivers; tests here flip env pins, so every test starts AND ends
    with a clean jit cache to keep pinned routes from leaking."""
    import jax

    jax.clear_caches()
    yield
    jax.clear_caches()


def _data(n=4000, F=12, seed=7, missing=0.1):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, F).astype(np.float32)
    X[rng.rand(n, F) < missing] = np.nan
    y = ((np.nan_to_num(X) @ rng.randn(F)) > 0).astype(np.float32)
    return X, y


# ------------------------------------------------- subtraction exactness

def test_parent_minus_child_exact_on_counts():
    """The sibling-subtraction contract at its sharpest: with integer-
    valued g/h (exactly representable, sums < 2^24) the derived sibling
    parent - built_child equals the directly-built histogram BIT FOR
    BIT — f32 subtraction of exact integers is exact."""
    import jax.numpy as jnp

    from xgboost_tpu.tree.hist_kernel import fused_level_native

    rng = np.random.RandomState(3)
    n, F, B = 5000, 8, 16
    bins = jnp.asarray(rng.randint(0, B + 1, (n, F)).astype(np.uint8))
    gh = jnp.asarray(np.stack(
        [rng.randint(-3, 4, n), rng.randint(1, 5, n)])
        .astype(np.float32))  # [2, n]: rows on the lanes
    pos = jnp.zeros((1, n), jnp.int32)

    # level 0: root histogram (the parent of the first sibling pair)
    _, hist0 = fused_level_native(bins, pos, gh, jnp.zeros((1, 4),
                                  jnp.float32), K=1, Kp=0, B=B, d=0)

    # split the root, then build level 1 both ways from the same inputs
    ptab = jnp.asarray(np.array([[1.0, 2.0, B // 2, 1.0]], np.float32))
    pos_d, hist_direct = fused_level_native(
        bins, pos, gh, ptab, K=2, Kp=1, B=B, d=1)
    pos_s, hist_sub = tree_kernel.fused_level_sub_native(
        bins, pos, gh, ptab, hist0, K=2, Kp=1, B=B, d=1)

    assert np.array_equal(np.asarray(pos_d), np.asarray(pos_s))
    assert np.array_equal(np.asarray(hist_direct), np.asarray(hist_sub)), \
        "derived sibling (parent - child) diverged from the direct build"


def test_unsplit_pair_stays_zero():
    """A level-0 node that does NOT split routes every row to the spill
    slot; both level-1 children are empty and the sub path must leave
    their cells zero (= the direct build of zero rows), not garbage."""
    import jax.numpy as jnp

    from xgboost_tpu.tree.hist_kernel import fused_level_native

    rng = np.random.RandomState(4)
    n, F, B = 1000, 4, 8
    bins = jnp.asarray(rng.randint(0, B + 1, (n, F)).astype(np.uint8))
    gh = jnp.asarray(np.stack(
        [rng.randint(-2, 3, n), rng.randint(1, 3, n)])
        .astype(np.float32))
    pos = jnp.zeros((1, n), jnp.int32)
    _, hist0 = fused_level_native(bins, pos, gh, jnp.zeros((1, 4),
                                  jnp.float32), K=1, Kp=0, B=B, d=0)
    ptab = jnp.zeros((1, 4), jnp.float32)  # is_split = 0
    pos_d, hist_direct = fused_level_native(
        bins, pos, gh, ptab, K=2, Kp=1, B=B, d=1)
    pos_s, hist_sub = tree_kernel.fused_level_sub_native(
        bins, pos, gh, ptab, hist0, K=2, Kp=1, B=B, d=1)
    assert not np.asarray(hist_sub).any()
    assert np.array_equal(np.asarray(pos_d), np.asarray(pos_s))
    assert np.array_equal(np.asarray(hist_direct), np.asarray(hist_sub))


# ------------------------------------------------ e2e route/sub matrix

_PARAMS = {"objective": "binary:logistic", "max_depth": 4, "max_bin": 32,
           "verbosity": 0}


def _train_raw_and_preds(X, y, rounds=4):
    d = xgb.DMatrix(X, label=y)
    bst = xgb.train(_PARAMS, d, rounds, verbose_eval=False)
    return bst.save_raw(), np.asarray(bst.predict(xgb.DMatrix(X[:800])))


def test_route_matrix_model_equality(monkeypatch):
    """The acceptance matrix at depth 4: the whole-tree kernel with
    subtraction OFF and the float histogram core is byte-identical to
    the per-level path (the bit-identity contract now takes BOTH pins —
    the default hist_acc=quant core sums in fixed point), and the
    default route (sub on, quant) keeps the same trees up to the
    quantiser grid (predictions agree to 1e-5)."""
    import jax

    X, y = _data()
    assert dispatch.resolve("tree_grow").impl == "native"
    raw_default, pred_default = _train_raw_and_preds(X, y)

    monkeypatch.setenv("XGBTPU_DISPATCH", "sibling_sub=off,hist_acc=float")
    jax.clear_caches()
    raw_sub_off, pred_sub_off = _train_raw_and_preds(X, y)

    monkeypatch.setenv("XGBTPU_DISPATCH", "tree_grow=level")
    jax.clear_caches()
    raw_level, pred_level = _train_raw_and_preds(X, y)

    monkeypatch.setenv("XGBTPU_DISPATCH",
                       "tree_grow=level,sibling_sub=off,hist_acc=float")
    jax.clear_caches()
    raw_level_off, _ = _train_raw_and_preds(X, y)

    # sub off + float core == per-level, BITWISE (both pins are no-ops
    # on the level route)
    assert raw_sub_off == raw_level, \
        "tree_grow(sub=off, hist_acc=float) diverged from the per-level path"
    assert raw_level_off == raw_level
    # default (sub on, quant): same model within cross-program tolerance
    np.testing.assert_allclose(pred_default, pred_level, rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(pred_default, pred_sub_off, rtol=1e-5,
                               atol=1e-5)


def test_legacy_sibling_sub_kill_switch(monkeypatch):
    """XGBTPU_SIBLING_SUB=0 maps to the sibling_sub=off pin (deprecation
    shim) and — composed with the hist_acc=float pin — pins the kernel
    byte-identical to the per-level route."""
    import jax

    X, y = _data(n=1500, F=6)
    monkeypatch.setenv("XGBTPU_SIBLING_SUB", "0")
    monkeypatch.setenv("XGBTPU_DISPATCH", "hist_acc=float")
    jax.clear_caches()
    assert dispatch.resolve("sibling_sub").impl == "off"
    raw_kernel, _ = _train_raw_and_preds(X, y, rounds=2)
    monkeypatch.setenv("XGBTPU_DISPATCH", "tree_grow=level")
    jax.clear_caches()
    raw_level, _ = _train_raw_and_preds(X, y, rounds=2)
    assert raw_kernel == raw_level


# ------------------------------- quantized histogram engine (ISSUE 19)


def _train_bst(X, y, rounds=4, **extra):
    d = xgb.DMatrix(X, label=y)
    return xgb.train({**_PARAMS, **extra}, d, rounds, verbose_eval=False)


def _tree_shapes(bst):
    """Structural split description per tree: (feature, children,
    default) at every node — the quant engine must pick the SAME splits
    as the float core, only leaf values may move on the grid."""
    out = []
    for t in bst._gbm.model.trees:
        out.append((np.asarray(t.split_indices).tolist(),
                    np.asarray(t.left_children).tolist(),
                    np.asarray(t.right_children).tolist(),
                    np.asarray(t.default_left).tolist()))
    return out


def test_quant_same_splits_preds_close(monkeypatch):
    """hist_acc=quant (the CPU default) given the SAME gradients grows a
    structurally identical tree to hist_acc=float — same split feature,
    children and default direction at every node of round 0, where both
    routes see identical g/h (later rounds may legitimately flip a
    near-tie split once leaf values drift on the quantiser grid) — and
    e2e predictions over 4 rounds agree to 1e-5."""
    import jax

    X, y = _data()
    assert dispatch.resolve("hist_acc").impl == "quant"
    bst_q = _train_bst(X, y)
    pred_q = np.asarray(bst_q.predict(xgb.DMatrix(X[:800])))
    shapes_q = _tree_shapes(bst_q)

    monkeypatch.setenv("XGBTPU_DISPATCH", "hist_acc=float")
    jax.clear_caches()
    bst_f = _train_bst(X, y)
    pred_f = np.asarray(bst_f.predict(xgb.DMatrix(X[:800])))

    assert shapes_q[0] == _tree_shapes(bst_f)[0], \
        "quant core picked different splits than the float core on " \
        "identical gradients"
    np.testing.assert_allclose(pred_q, pred_f, rtol=1e-5, atol=1e-5)


def test_quant_bitwise_on_count_valued_gradients():
    """The exactness contract at its sharpest: with integer-valued g/h
    (exactly representable on the quantiser grid, sums < 2^24) the
    whole-tree kernel's quant core returns BIT-IDENTICAL outputs to the
    float core — gains, node stats, split conditions and row positions —
    because integer quantization, integer sums, integer sibling
    subtraction and power-of-two dequantization are all exact."""
    from types import SimpleNamespace

    import jax.numpy as jnp

    rng = np.random.RandomState(11)
    n, F, B, depth = 6000, 8, 16, 4
    bins = jnp.asarray(rng.randint(0, B + 1, (n, F)).astype(np.uint8))
    gh = jnp.asarray(np.stack(
        [rng.randint(-3, 4, n), rng.randint(1, 5, n)])
        .astype(np.float32))
    cut_values = jnp.asarray(
        np.sort(rng.randn(F, B).astype(np.float32), axis=1))
    tree_mask = jnp.ones((F,), bool)
    G0 = jnp.float32(np.asarray(gh)[0].sum())
    H0 = jnp.float32(np.asarray(gh)[1].sum())
    split = SimpleNamespace(reg_lambda=1.0, reg_alpha=0.0,
                            max_delta_step=0.0, min_child_weight=1.0)

    for sub in (True, False):
        out_f = tree_kernel.tree_grow_native(
            bins, gh, cut_values, tree_mask, G0, H0, max_depth=depth,
            B=B, sibling_sub=sub, hist_acc="float", split=split)
        out_q = tree_kernel.tree_grow_native(
            bins, gh, cut_values, tree_mask, G0, H0, max_depth=depth,
            B=B, sibling_sub=sub, hist_acc="quant", split=split)
        for i, (a, b) in enumerate(zip(out_f, out_q)):
            assert np.array_equal(np.asarray(a), np.asarray(b)), \
                f"output {i} diverged on count-valued data (sub={sub})"


def test_quant_level_entry_matches_float_on_counts():
    """The mirror's quant level entry against the float per-level build
    on count-valued data: root histogram bit-identical after dequant,
    and the carried int64 lanes dequantize to the same values."""
    import jax.numpy as jnp

    from xgboost_tpu.tree.hist_kernel import fused_level_native

    rng = np.random.RandomState(5)
    n, F, B = 5000, 8, 16
    bins = jnp.asarray(rng.randint(0, B + 1, (n, F)).astype(np.uint8))
    gh = jnp.asarray(np.stack(
        [rng.randint(-3, 4, n), rng.randint(1, 5, n)])
        .astype(np.float32))  # [2, n]: rows on the lanes
    pos = jnp.zeros((1, n), jnp.int32)
    ptab0 = jnp.zeros((1, 4), jnp.float32)

    _, hist_f = fused_level_native(bins, pos, gh, ptab0, K=1, Kp=0, B=B,
                                   d=0)
    prev_q = jnp.zeros((F, 0, B, 2), jnp.int32)
    _, hq, hist_q = tree_kernel.fused_level_quant_native(
        bins, pos, gh, ptab0, prev_q, K=1, Kp=0, B=B, d=0,
        sibling_sub=True)
    assert np.array_equal(np.asarray(hist_f), np.asarray(hist_q))
    assert np.asarray(hq).shape == (F, 2, B, 2)


def test_wide_bins_fb_clamp_and_determinism(monkeypatch):
    """B=256 x deep trees: at K=32 the cache-blocked float build runs
    multiple feature tiles (fb=4) and by K=256 the slab budget forces
    the fb >= 1 clamp — on both cores the result must be deterministic
    run-to-run (same process, repeated training), and quant must track
    float to 1e-5. Pins the tile-order independence of the histogram
    loops at the widest supported bin count."""
    import jax

    X, y = _data(n=3000, F=10)
    params = dict(max_bin=256, max_depth=9)
    for pin in ("hist_acc=quant", "hist_acc=float"):
        monkeypatch.setenv("XGBTPU_DISPATCH", pin)
        jax.clear_caches()
        bst_a = _train_bst(X, y, rounds=2, **params)
        raw_a = bst_a.save_raw()
        bst_b = _train_bst(X, y, rounds=2, **params)
        assert raw_a == bst_b.save_raw(), \
            f"non-deterministic model bytes at B=256 ({pin})"
        if pin == "hist_acc=quant":
            pred_q = np.asarray(bst_a.predict(xgb.DMatrix(X[:500])))
        else:
            pred_f = np.asarray(bst_a.predict(xgb.DMatrix(X[:500])))
    np.testing.assert_allclose(pred_q, pred_f, rtol=1e-5, atol=1e-5)


def test_model_bytes_independent_of_omp_threads():
    """OMP_NUM_THREADS in {1, 2, 8} produces byte-identical models on
    BOTH histogram cores: the quant core is invariant by construction
    (integer adds are associative, the merge order is fixed), the float
    core by its deterministic slab schedule. Subprocesses, because the
    OpenMP runtime binds its thread pool at first use."""
    import os
    import subprocess
    import sys
    import textwrap

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    child = textwrap.dedent("""
        import hashlib
        import numpy as np
        import xgboost_tpu as xgb
        rng = np.random.RandomState(7)
        n, F = 3000, 8
        X = rng.randn(n, F).astype(np.float32)
        X[rng.rand(n, F) < 0.1] = np.nan
        y = ((np.nan_to_num(X) @ rng.randn(F)) > 0).astype(np.float32)
        d = xgb.DMatrix(X, label=y)
        bst = xgb.train({"objective": "binary:logistic", "max_depth": 4,
                         "max_bin": 32, "verbosity": 0}, d, 2,
                        verbose_eval=False)
        print(hashlib.sha256(bytes(bst.save_raw())).hexdigest())
    """)
    for pin in ("hist_acc=quant", "hist_acc=float"):
        digests = set()
        for threads in ("1", "2", "8"):
            env = dict(os.environ, OMP_NUM_THREADS=threads,
                       XGBTPU_DISPATCH=pin,
                       PYTHONPATH=repo + os.pathsep
                       + os.environ.get("PYTHONPATH", ""))
            out = subprocess.run(
                [sys.executable, "-c", child], env=env, text=True,
                capture_output=True, timeout=600)
            assert out.returncode == 0, out.stderr[-2000:]
            digests.add(out.stdout.strip().splitlines()[-1])
        assert len(digests) == 1, \
            f"model bytes varied with OMP_NUM_THREADS on {pin}: {digests}"


# ------------------------------------------------------- dispatch table

def test_dispatch_rows_and_default_route():
    """The registry rows the docs promise: ``tree_grow`` resolves native
    on CPU (report ctx = the bench shape), ``sibling_sub`` defaults on,
    ``hist_acc`` leads quant on CPU with float as the pinnable
    bit-identity core, and all are rows in dispatch-report (the tier-0.5
    CI artifact)."""
    assert dispatch.resolve("tree_grow").impl == "native"
    assert dispatch.resolve("sibling_sub").impl == "on"
    assert dispatch.resolve("hist_acc").impl == "quant"
    assert "hist_acc" in dispatch.op_names()
    from xgboost_tpu.cli import cli_main
    assert cli_main(["dispatch-report"]) == 0


def test_out_of_envelope_configs_keep_level_route():
    """Features whose eval the C++ port does NOT replicate stay on the
    per-level path: max_delta_step > 0 (the FMA-contraction hazard —
    tree_build.cpp), per-level/per-node colsample draws, monotone and
    interaction constraints, categorical tables."""
    from xgboost_tpu.dispatch import Ctx

    base = dict(platform="cpu", pallas=False, interpret=False,
                sharded=False, has_cats=False, bins_dtype="uint8",
                depth=6, monotone=False, interaction=False,
                colsample_level=1.0, colsample_node=1.0,
                max_delta_step=0.0)
    assert dispatch.resolve("tree_grow", Ctx(**base)).impl == "native"
    for twist in ({"max_delta_step": 0.7}, {"colsample_level": 0.5},
                  {"colsample_node": 0.5}, {"monotone": True},
                  {"interaction": True}, {"has_cats": True},
                  {"sharded": True}, {"pallas": True},
                  {"platform": "tpu"}, {"bins_dtype": "int32"}):
        ctx = Ctx(**{**base, **twist})
        assert dispatch.resolve("tree_grow", ctx).impl == "level", twist
