"""The fault-contained native boundary (ISSUE 20): load-time canary
proving, contract-checked FFI dispatch, in-kernel guard mode, and
degrade-to-XLA survival of mid-train native faults."""

import os
import shutil

import numpy as np
import pytest

import xgboost_tpu as xgb
from conftest import require_native
from xgboost_tpu import dispatch, native
from xgboost_tpu.native import boundary, canary
from xgboost_tpu.observability import REGISTRY
from xgboost_tpu.resilience import HEALTHY, chaos, degrade


def _counter(name, **labels):
    fam = REGISTRY.get(name)
    if fam is None:
        return 0.0
    return fam.labels(**labels).value if labels else fam.value


def _count_obj(preds, dtrain):
    """Count-valued gradients: g in {-1, +1}, h == 1 — integer-valued
    f32, so histogram sums are exact in ANY accumulation order and the
    native and XLA routes grow byte-identical trees."""
    y = dtrain.get_label()
    g = np.where(np.asarray(preds).ravel() > y, 1.0, -1.0).astype(
        np.float32)
    return g, np.ones_like(g)


# ------------------------------------------------------- containment


def test_mid_train_native_fault_degrades_and_completes(monkeypatch):
    """The acceptance drill: a scripted SIGSEGV-equivalent at the native
    dispatch of round 3 degrades the library, the round retries on the
    XLA fallback route, training completes all rounds — and on
    count-valued gradients the hybrid model equals a pure-fallback run
    EXACTLY."""
    require_native(native.get_tree_lib() is not None, "native tree kernel")
    # pin the whole-tree kernel bit-identical to the per-level path so
    # route equality is byte-exact, not just statistical
    monkeypatch.setenv("XGBTPU_DISPATCH",
                       "sibling_sub=off,hist_acc=float")
    # deliberately off-round shapes: an identical (cfg, shapes) jit entry
    # traced by an EARLIER test would skip tracing here, and with it the
    # trace-time resolve that marks the native route active for chaos
    rng = np.random.RandomState(7)
    X = rng.randn(331, 5).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)
    params = {"max_depth": 3, "max_bin": 16, "verbosity": 0,
              "base_score": 0.0}

    f0 = _counter("native_faults_total", lib="tree_build", kind="crash")
    with chaos.configure("native_dispatch:crash:3") as plan:
        bst = xgb.train(params, xgb.DMatrix(X, label=y), 6,
                        obj=_count_obj, verbose_eval=False)
    assert plan.fired == [("native_dispatch", 3, "crash")]
    assert bst.num_boosted_rounds() == 6
    assert degrade.worst("native_tree") != HEALTHY
    assert dispatch.last_decisions().get("tree_grow") == "level"
    assert _counter("native_faults_total", lib="tree_build",
                    kind="crash") > f0
    preds = np.asarray(bst.predict(xgb.DMatrix(X), output_margin=True))

    degrade.reset()
    dispatch.reset()
    chaos.reset()
    monkeypatch.setenv("XGBTPU_DISPATCH",
                       "tree_grow=level,sibling_sub=off,hist_acc=float")
    ref = xgb.train(params, xgb.DMatrix(X, label=y), 6,
                    obj=_count_obj, verbose_eval=False)
    preds_ref = np.asarray(ref.predict(xgb.DMatrix(X),
                                       output_margin=True))
    np.testing.assert_array_equal(preds, preds_ref)


def test_native_retry_ignores_foreign_transients():
    """The round bracket retries ONLY contained faults: a transient that
    merely passes THROUGH it (a scripted kill from the restart harness, a
    user callback's hiccup) must surface on the first attempt — retrying
    it would defeat the harness that scripted it."""
    from xgboost_tpu.resilience.policy import RetryPolicy

    pol = RetryPolicy("native_dispatch", retries=2,
                      retry_types=(boundary.NativeFault,),
                      sleep=lambda s: None)
    calls = [0]

    def foreign():
        calls[0] += 1
        raise RuntimeError("passing through")

    with pytest.raises(RuntimeError, match="passing through"):
        pol.run(foreign)
    assert calls[0] == 1  # never retried

    def native():
        calls[0] += 1
        raise boundary.NativeFault("contained")

    with pytest.raises(boundary.NativeFault):
        pol.run(native)
    assert calls[0] == 4  # 1 + 2 retries


def test_contain_reraises_semantic_errors():
    """``contain`` wraps only faults that plausibly came from the native
    boundary; a ValueError raised DURING a native round (parameter
    validation, a user objective) surfaces unchanged."""
    with pytest.raises(ValueError, match="not a kernel fault"):
        boundary.contain(ValueError("not a kernel fault"))


def test_cap_snapshot_is_read_only():
    """The GrowParams static-key snapshot must poll via degrade.worst —
    taking it repeatedly never burns a DEGRADED entry's countdown."""
    cap = boundary.capability_for("tree_build")
    cap.failure(kind="permanent", retry_after=4)
    before = dict(boundary.cap_snapshot())["native_tree"]
    for _ in range(64):
        boundary.cap_snapshot()
    assert dict(boundary.cap_snapshot())["native_tree"] == before != \
        HEALTHY


# ------------------------------------------------------------- canary


def _healthy_hist_so():
    require_native(native.get_hist_lib() is not None, "native hist kernel")
    so = native._lib_variant(native._HB_LIB)
    assert os.path.exists(so), so
    return so


def test_canary_cache_miss_then_hit(tmp_path, monkeypatch):
    """A fresh build pays one subprocess; an unchanged build is ONE stat
    (cached verdict, no child). An mtime-only touch with identical bytes
    refreshes the entry without re-running."""
    so = str(tmp_path / "libhistbuild.so")
    shutil.copy(_healthy_hist_so(), so)
    runs = []

    def fake_run(lib, so_path):
        runs.append(so_path)
        return canary.HEALTHY, "fake golden pass"

    monkeypatch.setattr(canary, "run_subprocess", fake_run)
    assert canary.prove("hist_build", so)
    assert len(runs) == 1
    assert os.path.exists(so + ".canary.json")
    assert canary.prove("hist_build", so)  # cache hit: no second child
    assert len(runs) == 1
    os.utime(so, (os.path.getmtime(so) + 60,) * 2)  # mtime drift,
    assert canary.prove("hist_build", so)           # same bytes: re-hash
    assert len(runs) == 1                           # but no re-run
    with open(so, "ab") as f:                       # a genuinely new
        f.write(b"\0" * 16)                         # build re-proves
    assert canary.prove("hist_build", so)
    assert len(runs) == 2


def test_canary_crash_verdict_degrades_and_caches(tmp_path, monkeypatch):
    """End-to-end: a scripted crash INSIDE the proving child (the
    contained SIGSEGV) yields verdict=crash, refuses the load, degrades
    the capability — and the verdict is cached, so the next prove of the
    same build never re-spawns."""
    so = str(tmp_path / "libhistbuild.so")
    shutil.copy(_healthy_hist_so(), so)
    monkeypatch.setenv("XGBTPU_CHAOS", "native_canary:crash:1")
    f0 = _counter("native_faults_total", lib="hist_build", kind="crash")
    assert not canary.prove("hist_build", so)
    assert degrade.worst("native_hist") != HEALTHY
    assert _counter("native_faults_total", lib="hist_build",
                    kind="crash") > f0
    assert canary.cached_verdict(so)[0] == canary.CRASH
    gauge = REGISTRY.get("native_canary_state")
    assert gauge.labels(lib="hist_build").value == -1
    # cached verdict answers without a child even with chaos disarmed
    monkeypatch.delenv("XGBTPU_CHAOS")
    degrade.reset()

    def no_spawn(lib, so_path):  # pragma: no cover - failure path
        raise AssertionError("cached verdict must not re-spawn")

    monkeypatch.setattr(canary, "run_subprocess", no_spawn)
    assert not canary.prove("hist_build", so)


def test_canary_refuses_missing_symbols(tmp_path, monkeypatch):
    """The NB604 nm -D probe promoted to load time: a library missing a
    registered handler symbol is refused with NO subprocess at all."""
    require_native(native.get_serving_lib() is not None,
                   "native serving kernel")
    sv = native._lib_variant(native._SV_LIB)
    so = str(tmp_path / "libhistbuild.so")
    shutil.copy(sv, so)  # a real .so, but the wrong one

    def no_spawn(lib, so_path):  # pragma: no cover - failure path
        raise AssertionError("refused library must not spawn a child")

    monkeypatch.setattr(canary, "run_subprocess", no_spawn)
    assert not canary.prove("hist_build", so)
    assert degrade.worst("native_hist") != HEALTHY
    assert not os.path.exists(so + ".canary.json")  # refusal: no cache


def test_canary_disabled_skips(monkeypatch):
    monkeypatch.setenv("XGBTPU_NATIVE_CANARY", "0")

    def no_spawn(lib, so_path):  # pragma: no cover - failure path
        raise AssertionError("disabled canary must not spawn")

    monkeypatch.setattr(canary, "run_subprocess", no_spawn)
    assert canary.prove("hist_build", "/nonexistent/lib.so")


# --------------------------------------------------- guarded dispatch


def test_guard_mode_catches_oob_feature(monkeypatch):
    """XGBTPU_NATIVE_GUARD=1: a decision table whose feature column
    points outside [0, F) comes back as a typed in-kernel error — never
    the wild bins[i*F+f] read it would otherwise drive."""
    from xgboost_tpu.tree import hist_kernel

    require_native(hist_kernel._ensure_ffi(), "native hist kernel")
    import jax
    import jax.numpy as jnp

    monkeypatch.setenv("XGBTPU_NATIVE_GUARD", "1")
    n, F, B = 8, 2, 4
    bins = np.zeros((n, F), np.uint8)
    pos = np.zeros((n, 1), np.int32)
    bad = np.array([[1.0, 99.0, 1.0, 1.0]], np.float32)
    with pytest.raises(Exception, match="XGBTPU_NATIVE_GUARD"):
        np.asarray(boundary.ffi_call(
            "xgbtpu_hb_partition",
            jax.ShapeDtypeStruct((n, 1), jnp.int32),
            bins, pos, bad, Kp=1, B=B, prev_offset=0))
    # guard off: the same inactive-row table (is_split=0) passes through
    monkeypatch.setenv("XGBTPU_NATIVE_GUARD", "0")
    ok = np.array([[0.0, 99.0, 1.0, 1.0]], np.float32)
    out = np.asarray(boundary.ffi_call(
        "xgbtpu_hb_partition", jax.ShapeDtypeStruct((n, 1), jnp.int32),
        bins, pos, ok, Kp=1, B=B, prev_offset=0))
    np.testing.assert_array_equal(out, pos)


def test_contract_drift_refused(monkeypatch):
    """A call site that drifts from the binder signature is refused with
    a typed error BEFORE the handler runs, and the library degrades."""
    from xgboost_tpu.tree import hist_kernel

    require_native(hist_kernel._ensure_ffi(), "native hist kernel")
    import jax
    import jax.numpy as jnp

    n, F, B = 4, 2, 4
    bins = np.zeros((n, F), np.uint8)
    pos = np.zeros((n, 1), np.int32)
    ptab = np.zeros((1, 4), np.float32)
    f0 = _counter("native_faults_total", lib="hist_build",
                  kind="contract")
    with pytest.raises(boundary.NativeContractError):
        boundary.ffi_call(
            "xgbtpu_hb_partition",
            jax.ShapeDtypeStruct((n, 1), jnp.int32),
            bins, pos, ptab, Kp=1, B=B, wrong_attr=0)
    assert degrade.worst("native_hist") != HEALTHY
    assert _counter("native_faults_total", lib="hist_build",
                    kind="contract") > f0
    with pytest.raises(boundary.NativeContractError):
        boundary.ffi_call(  # operand arity drift
            "xgbtpu_hb_partition",
            jax.ShapeDtypeStruct((n, 1), jnp.int32),
            bins, pos, Kp=1, B=B, prev_offset=0)


def test_contract_unknown_target_passes_through():
    """Targets outside the production map (e.g. the canary's aliases)
    are not contract-checked — same posture as the NB6xx lint skipping
    what it cannot see."""
    boundary.check_contract("xgbtpu_canary_hb_level", (), (), {})


# ------------------------------------------------------ build failures


def test_build_failure_degrades_instead_of_raising(monkeypatch):
    """Satellite: a g++/dlopen failure counts native_build_failures_total
    and degrades the capability — every later resolve keeps the XLA
    impls; nothing raises at the call site."""
    monkeypatch.setattr(native, "_hb_lib", None)
    monkeypatch.setattr(native, "_hb_tried", False)
    monkeypatch.setattr(native, "_compile",
                        lambda *a, **k: False)
    f0 = _counter("native_build_failures_total", lib="hist_build")
    assert native.get_hist_lib() is None
    assert _counter("native_build_failures_total", lib="hist_build") > f0
    assert degrade.worst("native_hist") != HEALTHY


# ---------------------------------------- builds travel with the checkout


def test_build_and_verdict_are_keyed_on_source_flags_and_host(tmp_path):
    """The checkout is copied between machines with its ignored build
    products in it: a library is trusted only while its stamp carries
    this (source, flags, host) key — never by mtime — and a canary
    verdict only on the host that proved it."""
    import json

    if not shutil.which("g++"):
        pytest.skip("no g++")
    src = str(tmp_path / "t.cpp")
    so = str(tmp_path / "libt.so")
    with open(src, "w") as f:
        f.write('extern "C" int one() { return 1; }\n')

    def built(flags):
        before = os.stat(so).st_mtime_ns if os.path.exists(so) else None
        assert native._compile(src, so, flags)
        return os.stat(so).st_mtime_ns != before

    assert built(["-O1"])  # first build
    assert not built(["-O1"])  # same key: trusted
    assert built(["-O2"])  # other flags: rebuilt
    with open(src, "a") as f:
        f.write("// edited\n")
    assert built(["-O2"])  # other source: rebuilt
    # a library that arrived from another machine: newer than its source,
    # stamped with that machine's key
    with open(so + ".build.json", "w") as f:
        json.dump({"key": "built-elsewhere"}, f)
    os.utime(so)
    assert built(["-O2"])
    os.remove(so + ".build.json")  # or with no stamp at all
    assert built(["-O2"])

    st = os.stat(so)
    entry = {"lib": "hist_build", "host": native.host_key(),
             "mtime": st.st_mtime, "size": st.st_size,
             "sha256": canary._sha256(so), "verdict": canary.HEALTHY,
             "detail": "golden run passed"}
    canary._write_cache(so, entry)
    assert canary.cached_verdict(so) == (canary.HEALTHY, "golden run passed")
    canary._write_cache(so, dict(entry, host="another-machine"))
    assert canary.cached_verdict(so) is None
