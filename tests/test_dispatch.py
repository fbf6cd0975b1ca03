"""Kernel dispatch subsystem (ISSUE 14): the resolution matrix.

Pins win over preference, the legacy kill-switch envs still flip their
routes through the compat shim, degrade-state fallback resolves without
burning retry countdowns, forced per-op routes produce bit-identical (or
documented-allclose) outputs, and the report/observability surfaces are
live. Budget: one tiny shared shape; everything except the parity test
is pure host-side resolution."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from xgboost_tpu import dispatch
from xgboost_tpu.dispatch import Ctx
from xgboost_tpu.observability import REGISTRY
from xgboost_tpu.resilience import degrade

# one shared level shape for every forced-route parity check (pallas
# kernels require rows % TR == 0; keep F*B tiny so interpret mode and
# the XLA fallback both compile in ~a second)
N, F, B = 1024, 3, 4


def _lh_ctx(**kw):
    base = dict(platform="cpu", pallas=False, interpret=False, rows=N,
                features=F, nodes=1, bins=B, table_width=4,
                bins_dtype="uint8", sharded=False, onehot_width=0)
    base.update(kw)
    return Ctx(**base)


def _walk_ctx(**kw):
    base = dict(platform="cpu", has_cats=False, heap_layout=True)
    base.update(kw)
    return Ctx(**base)


# ---------------------------------------------------------------------------
# resolution rules
# ---------------------------------------------------------------------------


def test_default_preference_order():
    dec = dispatch.resolve("depth_scan", Ctx(
        platform="cpu", pallas=False, has_cats=False, sharded=False,
        depth=6))
    assert (dec.impl, dec.reason) == ("scanned", "preferred")
    # sharded / pallas contexts keep the unrolled loop; a categorical
    # table is carried at the deepest level's width like a numerical one
    for veto in (dict(sharded=True), dict(pallas=True)):
        base = dict(platform="cpu", pallas=False, has_cats=False,
                    sharded=False, depth=6)
        base.update(veto)
        assert dispatch.resolve("depth_scan", Ctx(**base)).impl == "unrolled"
    assert dispatch.resolve("depth_scan", Ctx(
        platform="cpu", pallas=False, has_cats=True, sharded=False,
        depth=6)).impl == "scanned"
    # level_hist on cpu: native when the FFI library builds, else xla
    dec = dispatch.resolve("level_hist", _lh_ctx())
    assert dec.impl in ("native", "xla")
    # wide bins (int32, the pallas widening) are outside the native
    # kernel's envelope
    assert dispatch.resolve(
        "level_hist", _lh_ctx(bins_dtype="int32")).impl == "xla"
    # tpu ctx: the pallas kernel owns the level
    assert dispatch.resolve(
        "level_hist", _lh_ctx(platform="tpu", pallas=True)).impl == "pallas"


def _lp_ctx(**kw):
    base = dict(platform="cpu", pallas=False, interpret=False, rows=N,
                features=F, nodes=4, table_width=4, bins_dtype="uint8",
                sharded=False)
    base.update(kw)
    return Ctx(**base)


_TPU_LP = dict(platform="tpu", pallas=True, bins_dtype="int32")


@pytest.mark.parametrize("ctx,pin,want", [
    # a TPU-flagged call site whose tile fits: the Mosaic routing kernel
    (_TPU_LP, "", ("pallas",)),
    (dict(_TPU_LP, sharded=True), "", ("pallas",)),
    (dict(_TPU_LP, table_width=5 + 256, nodes=128), "", ("pallas",)),
    # ragged rows, a bins row too wide for any tile, no pallas flag: as
    # before this row
    (dict(_TPU_LP, rows=N + 8), "", ("xla",)),
    (dict(_TPU_LP, features=513), "", ("pallas",)),  # a tile from the width
    (dict(_TPU_LP, features=6000), "", ("xla",)),
    (dict(_TPU_LP, pallas=False), "", ("xla",)),
    # off the TPU nothing changes route
    (dict(), "", ("native", "xla")),
    (dict(bins_dtype="int32"), "", ("xla",)),
    (dict(sharded=True), "", ("xla",)),
    (dict(interpret=True), "", ("xla",)),
    # the pin that tests and A/B runs use still wins
    (_TPU_LP, "level_partition=xla", ("xla",)),
    (_TPU_LP, "level_partition=!pallas", ("xla",)),
    (dict(), "level_partition=pallas", ("native", "xla")),
])
def test_level_partition_preference_matrix(monkeypatch, ctx, pin, want):
    if pin:
        monkeypatch.setenv("XGBTPU_DISPATCH", pin)
    dec = dispatch.resolve("level_partition", _lp_ctx(**ctx))
    assert dec.impl in want, dec
    if pin and ctx:
        assert dec.reason == "pinned"


def test_pins_win_over_preference(monkeypatch):
    ds = Ctx(platform="cpu", pallas=False, has_cats=False, sharded=False,
             depth=6)
    monkeypatch.setenv("XGBTPU_DISPATCH", "depth_scan=unrolled")
    dec = dispatch.resolve("depth_scan", ds)
    assert (dec.impl, dec.reason) == ("unrolled", "pinned")
    # ban syntax: the preferred impl is skipped, the fallback is
    # attributed to the pin
    monkeypatch.setenv("XGBTPU_DISPATCH", "depth_scan=!scanned")
    dec = dispatch.resolve("depth_scan", ds)
    assert (dec.impl, dec.reason) == ("unrolled", "pinned")
    # op=auto clears; unknown entries are ignored, not fatal
    monkeypatch.setenv("XGBTPU_DISPATCH", "depth_scan=auto,*=auto,bogus")
    assert dispatch.resolve("depth_scan", ds).impl == "scanned"
    # a pin that cannot run on this platform falls back to auto
    monkeypatch.setenv("XGBTPU_DISPATCH", "level_hist=pallas")
    assert dispatch.resolve("level_hist", _lh_ctx()).impl in ("native",
                                                              "xla")


def test_legacy_envs_flip_routes_via_shim(monkeypatch):
    """Each legacy kill switch still flips its route — now through the
    one compat shim (LEGACY_ENVS -> pins) instead of scattered reads."""
    from xgboost_tpu.tree.hist_kernel import use_native_hist

    monkeypatch.setenv("XGBTPU_NATIVE_HIST", "0")
    assert dispatch.resolve("level_hist", _lh_ctx()).impl == "xla"
    assert dispatch.resolve("level_partition", Ctx(
        platform="cpu", interpret=False, table_width=4,
        bins_dtype="uint8", sharded=False)).impl == "xla"
    assert not use_native_hist()
    monkeypatch.delenv("XGBTPU_NATIVE_HIST")

    monkeypatch.setenv("XGBTPU_DEPTH_SCAN", "0")
    assert dispatch.resolve("depth_scan", Ctx(
        platform="cpu", pallas=False, has_cats=False, sharded=False,
        depth=6)).impl == "unrolled"
    # the explicit grammar overrides the legacy shim
    monkeypatch.setenv("XGBTPU_DISPATCH", "depth_scan=scanned")
    assert dispatch.resolve("depth_scan", Ctx(
        platform="cpu", pallas=False, has_cats=False, sharded=False,
        depth=6)).impl == "scanned"
    monkeypatch.delenv("XGBTPU_DISPATCH")
    monkeypatch.delenv("XGBTPU_DEPTH_SCAN")

    monkeypatch.setenv("XGBTPU_NATIVE_SERVING", "0")
    dec = dispatch.resolve("predict_walk", _walk_ctx())
    assert dec.impl == "xla" and dec.reason == "pinned"


def test_degrade_fallback_resolves_without_burning_countdown():
    """A degraded device predict path routes to the native walker with
    reason="degraded" — and polling the table does NOT burn the
    capability's retry countdown (resolve reads degrade.worst, never
    allowed())."""
    cap = degrade.capability("pallas_predict")
    cap.failure(RuntimeError("synthetic vmem overflow"), key=("shape",),
                retry_after=7)
    dec = dispatch.resolve("predict_walk", _walk_ctx(platform="tpu"))
    assert (dec.impl, dec.reason) == ("native", "degraded")
    countdown = cap.snapshot()["entries"][repr(("shape",))]["countdown"]
    for _ in range(10):
        dispatch.resolve("predict_walk", _walk_ctx(platform="tpu"))
        assert dispatch.degraded("predict_walk")
    after = cap.snapshot()["entries"][repr(("shape",))]["countdown"]
    assert after == countdown == 7
    # on CPU the degrade state must NOT shed the bucket program: the
    # capability gates only the device impls
    assert dispatch.resolve(
        "predict_walk", _walk_ctx(), exclude=("native",)).impl == "xla"
    # the decision series is in the exposition, labelled by reason
    assert ('dispatch_decisions_total{impl="native",op="predict_walk",'
            'reason="degraded"}') in REGISTRY.exposition()


def test_degraded_last_resort_still_serves():
    """When EVERY healthy alternative is exhausted (a categorical forest
    on a degraded device: native inapplicable, pallas/xla degraded), the
    table serves on the degraded impl instead of raising — the
    pre-registry behavior for requests the fallback cannot take."""
    degrade.capability("pallas_predict").failure(
        RuntimeError("synthetic vmem overflow"), key=("cats",))
    dec = dispatch.resolve("predict_walk",
                           _walk_ctx(platform="tpu", has_cats=True))
    assert (dec.impl, dec.reason) == ("xla", "degraded")
    assert "no healthy alternative" in dec.detail
    # the envelope-reject path: native excluded, device impls degraded
    dec = dispatch.resolve("predict_walk", _walk_ctx(platform="tpu"),
                           exclude=("native",))
    assert dec.impl in ("pallas", "xla") and dec.reason == "degraded"


def test_route_change_recorded_in_flight_ring():
    from xgboost_tpu.observability import flight

    ctx = _walk_ctx(platform="tpu")
    assert dispatch.resolve("predict_walk", ctx).impl == "pallas"
    degrade.capability("pallas_predict").failure(
        RuntimeError("synthetic vmem overflow"), key=("s2",))
    assert dispatch.resolve("predict_walk", ctx).impl == "native"
    events = [r for r in flight.RECORDER.records()
              if r.get("event") == "dispatch_route_change"
              or r.get("name") == "dispatch_route_change"]
    assert dispatch.last_decisions()["predict_walk"] == "native"
    assert dispatch.table_snapshot()["predict_walk"]["reason"] == "degraded"
    assert events, "route change must land in the flight ring"


def test_dispatch_report_cli(capsys):
    from xgboost_tpu.dispatch.report import main

    assert main([]) == 0
    out = capsys.readouterr().out
    for op in ("level_hist", "level_partition", "level_update",
               "depth_scan", "onehot_build", "leaf_delta", "predict_walk"):
        assert op in out, out
    assert "resolve on cpu" in out


# ---------------------------------------------------------------------------
# forced-route parity (the matrix's correctness half)
# ---------------------------------------------------------------------------


def _level_inputs():
    rng = np.random.RandomState(7)
    bins = rng.randint(0, B + 1, size=(N, F)).astype(np.uint8)  # B=missing
    gh = np.stack([rng.randn(N), rng.rand(N) + 0.5]).astype(np.float32)
    pos = np.zeros((1, N), np.int32)  # rows on the lanes, every impl
    ptab = np.zeros((1, 4), np.float32)
    return (jnp.asarray(bins), jnp.asarray(pos), jnp.asarray(gh),
            jnp.asarray(ptab))


def test_forced_routes_parity(monkeypatch):
    """level_hist forced down each route produces the same result: xla vs
    native bit-identical, pallas (interpret) within the documented hi/lo
    bf16 tolerance (~2^-16 relative, hist_kernel.py module docstring)."""
    from xgboost_tpu.tree import hist_kernel as hk

    bins, pos, gh, ptab = _level_inputs()

    monkeypatch.setenv("XGBTPU_DISPATCH", "level_hist=xla,"
                       "level_partition=xla")
    pos_x, hist_x = hk.fused_level(bins, pos, gh, ptab, K=1, Kp=0, B=B,
                                   d=0, pallas=False)
    pos_x, hist_x = np.asarray(pos_x), np.asarray(hist_x)

    if hk.use_native_hist():
        monkeypatch.setenv("XGBTPU_DISPATCH", "level_hist=native")
        pos_n, hist_n = hk.fused_level(bins, pos, gh, ptab, K=1, Kp=0,
                                       B=B, d=0, pallas=False)
        np.testing.assert_array_equal(np.asarray(pos_n), pos_x)
        np.testing.assert_array_equal(np.asarray(hist_n), hist_x)

    monkeypatch.delenv("XGBTPU_DISPATCH")
    monkeypatch.setattr(hk, "_INTERPRET", True)
    pos_p, hist_p = hk.fused_level(bins.astype(jnp.int32), pos, gh, ptab,
                                   K=1, Kp=0, B=B, d=0, pallas=True)
    np.testing.assert_array_equal(np.asarray(pos_p), pos_x)
    np.testing.assert_allclose(np.asarray(hist_p), hist_x,
                               rtol=2e-3, atol=1e-4)


def test_serving_route_forced_vs_default(model_cache=[]):
    """predict_walk forced to the bucketed XLA program matches the
    preferred route (native walker when available) within the serving
    parity contract."""
    import os

    import xgboost_tpu as xgb

    rng = np.random.RandomState(3)
    X = rng.rand(64, 5).astype(np.float32)
    y = (X[:, 0] + X[:, 1] > 1.0).astype(np.float32)
    dtrain = xgb.DMatrix(X, label=y)
    bst = xgb.train({"max_depth": 2, "tree_method": "tpu_hist",
                     "objective": "binary:logistic", "max_bin": 16},
                    dtrain, num_boost_round=3)
    default = np.asarray(bst.inplace_predict(X))
    os.environ["XGBTPU_DISPATCH"] = "predict_walk=xla"
    try:
        forced = np.asarray(bst.inplace_predict(X))
    finally:
        os.environ.pop("XGBTPU_DISPATCH")
    np.testing.assert_allclose(forced, default, atol=1e-5)


def test_public_surface_is_what_the_docstring_lists():
    """``dispatch.__all__`` is exactly the names the package docstring
    lists, each of them is there, and the invocation seam that only the
    mirror grower called (ISSUE 28) is not."""
    import re

    listed = set(re.findall(r":(?:func|class|data):`(\w+)`",
                            dispatch.__doc__))
    assert listed == set(dispatch.__all__), (
        sorted(listed ^ set(dispatch.__all__)))
    assert len(dispatch.__all__) == len(set(dispatch.__all__))
    assert all(hasattr(dispatch, n) for n in dispatch.__all__)
    from xgboost_tpu.dispatch import core

    assert set(core.__all__) == set(dispatch.__all__)
    assert not [n for n in dir(dispatch) + dir(core) if "invoke" in n.lower()]
