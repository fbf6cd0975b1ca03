"""Unified telemetry subsystem (ISSUE 1): span tracing, metrics registry,
collective accounting, TrainingTelemetry — plus regression tests for the
satellite fixes that rode along (hoist-plan failure latch, multiclass
zero-weight residue)."""

import json
import os

import numpy as np
import pytest

import xgboost_tpu as xgb
from xgboost_tpu.observability import comms, metrics, trace
from xgboost_tpu.observability.report import format_report, summarize


@pytest.fixture(autouse=True)
def _clean_telemetry(monkeypatch, tmp_path):
    """Fresh trace state per test; XGBTPU_TRACE cleared so each test opts
    in explicitly (the suite may run under a CI-level trace env)."""
    monkeypatch.delenv("XGBTPU_TRACE", raising=False)
    trace.reset()
    yield
    trace.reset()


def _data(n=400, F=5, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, F).astype(np.float32)
    y = ((X @ rng.randn(F)) > 0).astype(np.float32)
    return X, y


# ---------------------------------------------------------------- tracing

def test_disabled_span_is_shared_noop():
    assert not trace.enabled()
    s1 = trace.span("a", k=1)
    s2 = trace.span("b")
    assert s1 is s2  # one branch, zero allocation
    with s1:
        pass
    trace.instant("nothing")  # no-op, no error
    assert trace.flush() is None


def test_span_nesting_flush_and_chrome_format(tmp_path):
    out = tmp_path / "t.trace.json"
    xgb.set_config(trace_path=str(out))
    try:
        assert trace.enabled()
        import time

        with trace.span("outer", phase="test"):
            with trace.span("inner"):
                time.sleep(0.002)
        trace.instant("mark", k=3)
        assert trace.flush() == str(out)
    finally:
        xgb.set_config(trace_path=None)
    events = trace.load_trace(str(out))
    spans = {e["name"]: e for e in events if e.get("ph") == "X"}
    assert set(spans) == {"outer", "inner"}
    for e in spans.values():  # Chrome trace-event required fields
        assert {"name", "ph", "ts", "dur", "pid", "tid"} <= set(e)
    o, i = spans["outer"], spans["inner"]
    assert i["dur"] >= 2000  # us
    # proper nesting: inner inside outer
    assert o["ts"] <= i["ts"]
    assert i["ts"] + i["dur"] <= o["ts"] + o["dur"]
    assert any(e.get("ph") == "i" and e["name"] == "mark" for e in events)
    # the on-disk form is line-delimited: every event line is JSON
    lines = [ln for ln in out.read_text().splitlines()
             if ln.strip() and ln.strip() != "["]
    for ln in lines:
        json.loads(ln.rstrip(","))


def test_trace_env_var_wins(tmp_path, monkeypatch):
    out = tmp_path / "env.trace.json"
    monkeypatch.setenv("XGBTPU_TRACE", str(out))
    with trace.span("env_span"):
        pass
    trace.flush()
    assert any(e["name"] == "env_span" for e in trace.load_trace(str(out)))


def test_ring_buffer_drops_oldest(tmp_path, monkeypatch):
    monkeypatch.setenv("XGBTPU_TRACE", str(tmp_path / "rb.json"))
    cap = trace._buffer.maxlen
    base = trace.dropped_count()
    for k in range(cap + 10):
        with trace.span("s", k=k):
            pass
    assert trace.dropped_count() - base == 10
    assert len(trace._buffer) == cap


def test_train_trace_covers_pipeline_phases(tmp_path, monkeypatch):
    out = tmp_path / "train.trace.json"
    monkeypatch.setenv("XGBTPU_TRACE", str(out))
    X, y = _data()
    d = xgb.DMatrix(X, label=y)
    dv = xgb.DMatrix(X[:100], label=y[:100])
    xgb.train({"max_depth": 3, "eval_metric": "logloss"}, d,
              num_boost_round=5, evals=[(dv, "val")], verbose_eval=False)
    trace.flush()
    events = trace.load_trace(str(out))
    names = {e["name"] for e in events if e.get("ph") == "X"}
    # >= 5 distinct phases across sketch / hist / update / eval
    assert {"sketch", "bins", "upload", "grow_tree", "update", "eval"} <= names
    assert len(names) >= 5


def test_trace_report_summarizes(tmp_path, monkeypatch):
    out = tmp_path / "r.trace.json"
    monkeypatch.setenv("XGBTPU_TRACE", str(out))
    X, y = _data(n=200)
    d = xgb.DMatrix(X, label=y)
    xgb.train({"max_depth": 2}, d, num_boost_round=3, verbose_eval=False)
    trace.flush()
    summary = summarize(trace.load_trace(str(out)))
    assert summary["n_spans"] > 0
    assert "grow_tree" in summary["spans"]
    g = summary["spans"]["grow_tree"]
    assert g["count"] == 3
    assert 0 <= g["self_us"] <= g["total_us"]
    # nested spans: the round's self time excludes its children
    r = summary["spans"].get("round") or summary["spans"]["update"]
    assert r["self_us"] < r["total_us"]
    text = format_report(summary)
    assert "grow_tree" in text and "rank 0" in text
    # CLI wiring
    from xgboost_tpu.cli import cli_main

    assert cli_main(["trace-report", str(out)]) == 0
    assert cli_main(["trace-report", str(tmp_path / "missing.json")]) == 1


# ---------------------------------------------------------------- metrics

def test_metrics_registry_counts_and_exposition():
    reg = metrics.MetricsRegistry()
    reg.counter("rounds_total", "rounds").inc()
    reg.counter("rounds_total").inc(4)
    reg.gauge("depth").set(6)
    h = reg.histogram("lat_seconds", buckets=(0.01, 0.1, 1.0))
    h.observe(0.005)
    h.observe(0.5)
    h.observe(50.0)
    ops = reg.counter("ops_total")
    ops.labels(op="psum").inc(2)
    ops.labels(op="gather").inc()

    assert reg.counter("rounds_total").value == 5
    with pytest.raises(ValueError):
        reg.gauge("rounds_total")  # kind mismatch
    with pytest.raises(ValueError):
        reg.counter("rounds_total").inc(-1)  # counters only go up

    text = reg.exposition()
    assert "# TYPE rounds_total counter" in text
    assert "rounds_total 5" in text
    assert "# HELP rounds_total rounds" in text
    assert 'ops_total{op="psum"} 2' in text
    assert 'ops_total{op="gather"} 1' in text
    # histogram exposition: cumulative buckets + +Inf + sum/count
    assert 'lat_seconds_bucket{le="0.01"} 1' in text
    assert 'lat_seconds_bucket{le="1"} 2' in text
    assert 'lat_seconds_bucket{le="+Inf"} 3' in text
    assert "lat_seconds_count 3" in text

    snap = reg.snapshot()
    json.dumps(snap)  # JSON-able
    assert snap["rounds_total"]["series"][0]["value"] == 5
    assert snap["lat_seconds"]["series"][0]["count"] == 3
    reg.reset()
    assert reg.exposition() == ""


def test_monitor_adapter_feeds_registry_and_trace(tmp_path, monkeypatch):
    out = tmp_path / "m.trace.json"
    monkeypatch.setenv("XGBTPU_TRACE", str(out))
    from xgboost_tpu.observability import REGISTRY
    from xgboost_tpu.utils import Monitor

    mon = Monitor("TestMon")
    with mon.section("Phase"):
        pass
    mon.start("open_only")  # stop never called: ignored
    assert mon.stats["Phase"][1] == 1
    assert "Phase" in mon.report()
    child = REGISTRY.histogram("monitor_seconds").labels(
        monitor="TestMon", section="Phase")
    assert child.count >= 1
    trace.flush()
    assert any(e["name"] == "Phase" for e in trace.load_trace(str(out)))


# ------------------------------------------------------------- collectives

def test_comms_record_and_snapshot():
    before = comms.snapshot().get("allreduce", {"ops": 0, "bytes": 0})
    comms.record("allreduce", 4096)
    after = comms.snapshot()["allreduce"]
    assert after["ops"] - before["ops"] == 1
    assert after["bytes"] - before["bytes"] == 4096


def test_distributed_sketch_accounts_allgather_bytes():
    import jax
    import jax.numpy as jnp

    from xgboost_tpu.parallel.mesh import make_mesh, shard_rows
    from xgboost_tpu.parallel.sketch import OVERSAMPLE, distributed_compute_cuts

    mesh = make_mesh()
    D = mesh.devices.size
    n, F, B = 16 * D, 3, 16
    X = jnp.asarray(np.random.RandomState(0).randn(n, F), jnp.float32)
    before = comms.snapshot().get("all_gather_sketch", {"ops": 0, "bytes": 0})
    cuts = distributed_compute_cuts(mesh, shard_rows(X, mesh), max_bin=B)
    after = comms.snapshot()["all_gather_sketch"]
    assert after["ops"] - before["ops"] == 4
    S = OVERSAMPLE * B
    assert after["bytes"] - before["bytes"] == D * (2 * F * S + 2 * F) * 4
    assert cuts.values.shape == (F, B)


def test_distributed_grow_accounts_psum_volume():
    expected = comms.grow_psum_bytes(max_depth=2, n_features=3, max_bin=8)
    # two levels: [3, 2, 8] + [3, 4, 8] f32 histograms + 8-byte root
    assert expected == (3 * 2 * 8 + 3 * 4 * 8) * 4 + 8
    before = comms.snapshot().get("psum_hist", {"ops": 0, "bytes": 0})
    comms.record_grow_collectives(2, 3, 8, n_trees=5)
    after = comms.snapshot()["psum_hist"]
    assert after["bytes"] - before["bytes"] == expected * 5
    assert after["ops"] - before["ops"] == 3 * 5


def test_mesh_training_records_collectives():
    from xgboost_tpu.parallel.mesh import make_mesh, mesh_context

    X, y = _data(n=256)
    d = xgb.DMatrix(X, label=y)
    before = comms.snapshot().get("psum_hist", {"ops": 0, "bytes": 0})
    with mesh_context(make_mesh()):
        bst = xgb.train({"max_depth": 2, "tree_method": "tpu_hist"}, d,
                        num_boost_round=2, verbose_eval=False)
    after = comms.snapshot()["psum_hist"]
    assert after["ops"] > before["ops"]
    assert after["bytes"] > before["bytes"]
    assert bst.num_boosted_rounds() == 2


# ------------------------------------------------------ TrainingTelemetry

def test_training_telemetry_records_per_round():
    from xgboost_tpu.callback import TrainingTelemetry

    reg = metrics.MetricsRegistry()
    X, y = _data()
    d = xgb.DMatrix(X, label=y)
    dv = xgb.DMatrix(X[:100], label=y[:100])
    xgb.train({"max_depth": 3, "eval_metric": "error"}, d,
              num_boost_round=4, evals=[(dv, "val")], verbose_eval=False,
              callbacks=[TrainingTelemetry(registry=reg)])
    snap = reg.snapshot()
    assert snap["round_seconds"]["series"][0]["count"] == 4
    assert snap["trees_total"]["series"][0]["value"] == 4
    assert snap["tree_depth"]["series"][0]["value"] <= 3
    assert snap["tree_leaves"]["series"][0]["value"] >= 2
    assert snap["split_gain"]["series"][0]["count"] > 0
    evals = {tuple(sorted(s["labels"].items())): s["value"]
             for s in snap["eval_score"]["series"]}
    assert (("data", "val"), ("metric", "error")) in evals


def test_rounds_total_counts_update_paths():
    from xgboost_tpu.observability import REGISTRY

    X, y = _data(n=200)
    d = xgb.DMatrix(X, label=y)
    fam = REGISTRY.counter("rounds_total")
    base = fam.value
    xgb.train({"max_depth": 2}, d, num_boost_round=3, verbose_eval=False)
    assert fam.value - base == 3


# ------------------------------------------------- satellite regressions

def test_hoist_plan_mesh_zero_after_onehot_failure():
    """data/quantile.py — a DISABLED one-hot build capability must zero
    the mesh hoist plan, or chunked scans retry the failed build in-jit.
    (The per-object build-failure latch became the process-wide
    ``onehot_build`` capability — ISSUE 5 tentpole.)"""
    from xgboost_tpu.data.quantile import _onehot_health
    from xgboost_tpu.parallel.mesh import make_mesh
    from xgboost_tpu.resilience import DISABLED

    X, _ = _data(n=64, F=3)
    d = xgb.DMatrix(X, label=np.zeros(64, np.float32))
    bm = d.get_binned(16)
    mesh = make_mesh()
    _onehot_health.failure(RuntimeError("synthetic mosaic reject"))
    assert _onehot_health.state() == DISABLED
    assert bm.hoist_plan_mesh(mesh) == 0
    assert bm.fused_onehot_mesh(mesh) is None


def test_multiclass_metrics_zero_weight_returns_residue():
    """metric/multiclass.py:30 — wsum == 0 returns the residue (0.0), not
    NaN (reference multiclass_metric.cu GetFinal)."""
    import jax.numpy as jnp

    from xgboost_tpu.metric import create_metric

    preds = jnp.asarray([[0.7, 0.2, 0.1], [0.1, 0.8, 0.1]], jnp.float32)
    label = jnp.asarray([0.0, 1.0])
    zero_w = jnp.asarray([0.0, 0.0])
    for name in ("merror", "mlogloss"):
        m = create_metric(name)
        val = m.evaluate(preds, label, zero_w)
        assert val == 0.0, (name, val)
        assert not np.isnan(val)
        # non-degenerate weights still behave
        v2 = m.evaluate(preds, label, jnp.asarray([1.0, 1.0]))
        assert np.isfinite(v2)


def test_telemetry_overhead_disabled_is_small():
    """With tracing off, span() must be a cheap branch: guard against
    accidental allocation/clock work on the disabled path."""
    import time

    assert not trace.enabled()
    n = 20000
    t0 = time.perf_counter()
    for _ in range(n):
        with trace.span("x", k=1):
            pass
    per_call = (time.perf_counter() - t0) / n
    assert per_call < 50e-6  # generous bound: noop should be ~1us
