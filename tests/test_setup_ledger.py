"""The set-up ledger (ISSUE 37): JAX's own trace, lowering, compile and
cache events as series of the registry, in self time; the data plane's
stages closed on their results; nothing of either on a round's path."""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import xgboost_tpu as xgb
from xgboost_tpu.data import quantile
from xgboost_tpu.observability import (REGISTRY, compile_ledger, flight,
                                       setup_ledger, trace)

STEPS = ("trace", "lower", "compile")


def _value(name, **labels):
    fam = REGISTRY.get(name)
    if fam is None:
        return 0.0
    want = {k: str(v) for k, v in labels.items()}
    return sum(child.value for have, child in fam.series()
               if all(have.get(k) == v for k, v in want.items()))


def _jit(step, fn):
    return (_value("jit_events_total", stage=step, fn=fn),
            _value("jit_seconds_total", stage=step, fn=fn))


def _stages():
    return {s: (_value("setup_stage_events_total", stage=s),
                _value("setup_stage_seconds_total", stage=s))
            for s in ("upload", "sketch", "bins", "onehot", "rank_layout")}


def _matrix(n=3000, F=12, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, F).astype(np.float32)
    y = (X[:, 0] + X[:, 3] > 1.0).astype(np.float32)
    return X, y


# ---------------------------------------------------------------------------
# the compile ledger
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("step", STEPS)
def test_a_fresh_program_books_one_event_a_step(step):
    """Compiled once: one event and some seconds under its ``fn``; the same
    shapes again: nothing; a new shape: one more."""
    def ledger_demo_fresh(x):
        return (x * 3.0).sum()

    f = jax.jit(ledger_demo_fresh)
    e0, s0 = _jit(step, "ledger_demo_fresh")
    f(jnp.ones((4, 8))).block_until_ready()
    e1, s1 = _jit(step, "ledger_demo_fresh")
    assert e1 == e0 + 1 and s1 > s0
    f(jnp.ones((4, 8))).block_until_ready()
    assert _jit(step, "ledger_demo_fresh") == (e1, s1)
    f(jnp.ones((5, 8))).block_until_ready()
    e2, s2 = _jit(step, "ledger_demo_fresh")
    assert e2 == e1 + 1 and s2 > s1


def test_an_inner_trace_is_booked_once():
    """JAX fires the trace event for the inner program while the outer is
    still being traced: the inner's seconds are taken out of the outer's, so
    the two sum to no more than the wall time of the call."""
    @jax.jit
    def ledger_demo_inner(x):
        time.sleep(0.05)  # Python the inner trace pays, once
        return jnp.sin(x)

    @jax.jit
    def ledger_demo_outer(x):
        return ledger_demo_inner(x) + 1.0

    t0 = time.time()
    ledger_demo_outer(jnp.ones(16)).block_until_ready()
    wall = time.time() - t0
    inner = _value("jit_seconds_total", stage="trace", fn="ledger_demo_inner")
    outer = _value("jit_seconds_total", stage="trace", fn="ledger_demo_outer")
    assert inner >= 0.05
    assert outer < 0.05  # the outer's own Python is a line
    assert inner + outer <= wall
    assert _value("jit_events_total", stage="trace",
                  fn="ledger_demo_inner") >= 1
    # the inner program was never lowered or compiled on its own
    assert _value("jit_events_total", stage="compile",
                  fn="ledger_demo_inner") == 0


def test_a_trace_inside_a_lowering_is_taken_out_of_it():
    """The listeners pair an open with its close whatever the step: fed by
    hand, a trace of 0.2 s inside a lowering of 1 s leaves 0.8 s."""
    lower = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    tr = "/jax/core/compile/jaxpr_trace_duration"
    jax.monitoring.record_scalar(lower, 100.0, fun_name="jit(ledger_demo_l)")
    jax.monitoring.record_scalar(tr, 100.3, fun_name="ledger_demo_t")
    jax.monitoring.record_event_time_span(tr, 100.3, 100.5,
                                          fun_name="ledger_demo_t")
    jax.monitoring.record_event_time_span(lower, 100.0, 101.0,
                                          fun_name="jit(ledger_demo_l)")
    assert _value("jit_seconds_total", stage="trace",
                  fn="ledger_demo_t") == pytest.approx(0.2)
    assert _value("jit_seconds_total", stage="lower",
                  fn="ledger_demo_l") == pytest.approx(0.8)
    # a close whose open was never seen is a leaf, and leaves no frame
    jax.monitoring.record_event_time_span(tr, 200.0, 200.5,
                                          fun_name="ledger_demo_orphan")
    assert _value("jit_seconds_total", stage="trace",
                  fn="ledger_demo_orphan") == pytest.approx(0.5)
    assert not compile_ledger._tls.open


@pytest.mark.parametrize("given, fn", [
    ("jit(_scan_rounds_impl)", "_scan_rounds_impl"),
    ("_scan_rounds_impl", "_scan_rounds_impl"),
    ("jit(run)", "run"), ("pmap(f)", "pmap(f)"), ("jit(", "jit(")])
def test_the_steps_of_a_program_meet_under_one_fn(given, fn):
    assert compile_ledger._fn(given) == fn


@pytest.mark.parametrize("event, series, labels, by", [
    ("/jax/compilation_cache/cache_hits",
     "compile_cache_events_total", {"result": "hit"}, None),
    ("/jax/compilation_cache/cache_misses",
     "compile_cache_events_total", {"result": "miss"}, None),
    ("/jax/compilation_cache/cache_retrieval_time_sec",
     "compile_cache_load_seconds_total", {}, 0.25),
    ("/jax/compilation_cache/compile_time_saved_sec",
     "compile_cache_saved_seconds_total", {}, 1.5)])
def test_the_cache_series_move_on_jaxs_events(event, series, labels, by):
    """The CPU backend keeps no persistent cache
    (``config.enable_compile_cache``), so the events are fired by hand."""
    before = _value(series, **labels)
    if by is None:
        jax.monitoring.record_event(event)
        assert _value(series, **labels) == before + 1
    else:
        jax.monitoring.record_event_duration_secs(event, by)
        assert _value(series, **labels) == pytest.approx(before + by)


def test_a_load_slower_than_its_compile_saves_nothing():
    before = _value("compile_cache_saved_seconds_total")
    jax.monitoring.record_event_duration_secs(
        "/jax/compilation_cache/compile_time_saved_sec", -0.4)
    assert _value("compile_cache_saved_seconds_total") == before


def test_registering_twice_counts_once():
    compile_ledger.install()
    compile_ledger.install()
    before = _value("compile_cache_events_total", result="hit")
    jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    assert _value("compile_cache_events_total", result="hit") == before + 1


def test_the_ledger_survives_a_registry_reset():
    REGISTRY.reset()
    assert REGISTRY.get("jit_events_total") is None

    def ledger_demo_after_reset(x):
        return x - 1.0

    jax.jit(ledger_demo_after_reset)(jnp.ones(3)).block_until_ready()
    for step in STEPS:
        assert _jit(step, "ledger_demo_after_reset")[0] == 1


def test_warm_calls_fire_no_listener():
    def ledger_demo_warm(x):
        return x * x

    f = jax.jit(ledger_demo_warm)
    x = jnp.ones((8, 8))
    f(x).block_until_ready()
    fired = []

    def probe(event, *a, **kw):
        fired.append(event)

    jax.monitoring.register_event_listener(probe)
    jax.monitoring.register_scalar_listener(probe)
    jax.monitoring.register_event_time_span_listener(probe)
    jax.monitoring.register_event_duration_secs_listener(probe)
    try:
        events = _value("jit_events_total")
        for _ in range(100):
            f(x)
        f(x).block_until_ready()
    finally:
        jax.monitoring.unregister_event_listener(probe)
        jax.monitoring.unregister_scalar_listener(probe)
        jax.monitoring.unregister_event_time_span_listener(probe)
        jax.monitoring.unregister_event_duration_listener(probe)
    assert fired == []
    assert _value("jit_events_total") == events


def test_with_tracing_on_a_compile_lies_inside_the_update_that_caused_it(
        tmp_path, monkeypatch):
    path = tmp_path / "trace.jsonl"
    monkeypatch.setenv("XGBTPU_TRACE", str(path))
    trace.reset()
    X, y = _matrix(n=3001, seed=7)  # rows no other test trains on
    d = xgb.DMatrix(X, label=y)
    bst = xgb.Booster({"objective": "binary:logistic", "max_depth": 5,
                       "max_bin": 32}, [d])
    bst.update(d, 0)
    trace.flush()
    events = [e for e in trace.load_trace(str(path)) if e.get("ph") == "X"]
    update = [e for e in events if e["name"] == "update"]
    assert len(update) == 1
    lo, hi = update[0]["ts"], update[0]["ts"] + update[0]["dur"]
    for name in ("jit.trace", "jit.lower", "jit.compile"):
        grower = [e for e in events if e["name"] == name
                  and e["args"]["fn"] == "_grow_tree_fused_impl"]
        assert len(grower) == 1, f"no {name} event of the tree program"
        e = grower[0]
        assert e["cat"] == "compile" and e["tid"] == update[0]["tid"]
        assert lo <= e["ts"] and e["ts"] + e["dur"] <= hi + 1
    # the sketch's program was built inside the sketch stage's span
    sketch = [e for e in events if e["name"] == "sketch"][0]
    assert any(e["name"] == "jit.compile" and sketch["ts"] <= e["ts"]
               and e["ts"] + e["dur"] <= sketch["ts"] + sketch["dur"] + 1
               for e in events)
    # a ``jnp`` function traced inside the tree program's trace is not on
    # the timeline (``emit``'s host-side rule: the outermost carries it),
    # though the registry has its own seconds
    g = [e for e in events if e["name"] == "jit.trace"
         and e["args"]["fn"] == "_grow_tree_fused_impl"][0]
    assert not [e for e in events if e["name"] == "jit.trace" and e is not g
                and g["ts"] <= e["ts"] < g["ts"] + g["dur"]]


def test_setup_ledger_is_plain_json():
    def ledger_demo_json(x):
        return x + 2.0

    jax.jit(ledger_demo_json)(jnp.ones(3)).block_until_ready()
    X, y = _matrix(n=500)
    xgb.DMatrix(X, label=y).get_binned(16)
    ledger = setup_ledger()
    assert json.loads(json.dumps(ledger)) == ledger
    assert set(ledger) == {"programs", "jit_seconds", "compile_cache",
                           "stages"}
    mine = ledger["programs"]["ledger_demo_json"]
    assert set(mine) == set(STEPS)
    assert all(mine[s]["events"] == 1 and mine[s]["seconds"] > 0
               for s in STEPS)
    assert set(ledger["jit_seconds"]) == set(STEPS)
    assert ledger["jit_seconds"]["trace"] == pytest.approx(sum(
        p["trace"]["seconds"] for p in ledger["programs"].values()
        if "trace" in p))
    assert set(ledger["compile_cache"]) == {"hits", "misses", "load_seconds",
                                            "saved_seconds"}
    assert {"upload", "sketch", "bins"} <= set(ledger["stages"])
    assert all(s["events"] >= 1 and s["seconds"] >= 0
               for s in ledger["stages"].values())


# ---------------------------------------------------------------------------
# the data plane's stages
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cols", [None, 5])
def test_get_binned_moves_upload_sketch_and_bins(monkeypatch, cols):
    """Whole matrix: one upload for the cuts and one for the bins. By
    blocks of five columns (three blocks of twelve): an upload a block a
    pass, which is what ``sketch_blocks_total`` counts."""
    monkeypatch.setattr(quantile, "_FORCE_BLOCK_COLS", cols)
    X, y = _matrix()
    d = xgb.DMatrix(X, label=y)
    before, blocks0 = _stages(), _value("sketch_blocks_total")
    t0 = time.perf_counter()
    binned = d.get_binned(32)
    wall = time.perf_counter() - t0
    after, blocks = _stages(), _value("sketch_blocks_total") - blocks0
    moved = {s: (after[s][0] - before[s][0], after[s][1] - before[s][1])
             for s in after}
    assert blocks == (0 if cols is None else 6)
    assert moved["upload"][0] == (2 if cols is None else blocks)
    assert moved["sketch"][0] == 1 and moved["bins"][0] == 1
    assert moved["onehot"][0] == 0 and moved["rank_layout"][0] == 0
    assert all(moved[s][1] > 0 for s in ("upload", "sketch", "bins"))
    assert sum(sec for _, sec in moved.values()) <= wall
    assert binned.bins.shape == (3000, 12)
    # the same bins either way
    monkeypatch.setattr(quantile, "_FORCE_BLOCK_COLS", None)
    whole = xgb.DMatrix(X, label=y).get_binned(32)
    np.testing.assert_array_equal(np.asarray(binned.bins),
                                  np.asarray(whole.bins))


def test_sparse_input_moves_the_same_stages():
    sp = pytest.importorskip("scipy.sparse")
    X, y = _matrix(n=800, F=20)
    X[X < 0.6] = 0.0
    d = xgb.DMatrix(sp.csr_matrix(X), label=y)
    before = _stages()
    d.get_binned(16)
    after = _stages()
    # 20 columns, 16 a block: two blocks a pass
    assert after["upload"][0] - before["upload"][0] == 4
    assert after["sketch"][0] - before["sketch"][0] == 1
    assert after["bins"][0] - before["bins"][0] == 1


def test_rounds_move_no_stage_series():
    X, y = _matrix(seed=3)
    d = xgb.DMatrix(X, label=y)
    bst = xgb.Booster({"objective": "binary:logistic", "max_depth": 3,
                       "max_bin": 32}, [d])
    bst.update_many(d, 0, 2, chunk=2)  # the first chunk builds what it needs
    before = _stages()
    bst.update_many(d, 2, 3, chunk=3)
    for i in range(5, 8):
        bst.update(d, i)
    bst.predict(d)
    assert _stages() == before


def test_the_sketch_stage_and_the_flight_recorder_share_one_reading():
    X, _ = _matrix(n=1000)
    stage0 = _value("setup_stage_seconds_total", stage="sketch")
    flight0 = flight.stage_totals().get("sketch", 0.0)
    quantile.compute_cuts(X, max_bin=16)
    stage = _value("setup_stage_seconds_total", stage="sketch") - stage0
    noted = flight.stage_totals().get("sketch", 0.0) - flight0
    assert stage > 0 and noted == pytest.approx(stage, rel=1e-9, abs=1e-12)


def test_a_stage_inside_a_stage_is_taken_out_of_it():
    outer0 = _value("setup_stage_seconds_total", stage="ledger_demo_outer")
    with trace.stage("ledger_demo_outer") as outer:
        time.sleep(0.02)
        with trace.stage("ledger_demo_inner") as inner:
            time.sleep(0.05)
    assert inner.seconds >= 0.05
    assert 0.02 <= outer.seconds < 0.05
    assert _value("setup_stage_seconds_total", stage="ledger_demo_outer") \
        == pytest.approx(outer0 + outer.seconds)
    assert getattr(trace._stage_tls, "open", None) is None


def test_a_stage_that_raises_is_still_booked_and_closed():
    e0 = _value("setup_stage_events_total", stage="ledger_demo_raises")
    with pytest.raises(RuntimeError):
        with trace.stage("ledger_demo_raises"):
            raise RuntimeError("boom")
    assert _value("setup_stage_events_total",
                  stage="ledger_demo_raises") == e0 + 1
    assert getattr(trace._stage_tls, "open", None) is None


def test_the_hbm_mark_is_the_stage_that_raised_it(monkeypatch):
    """The CPU keeps no memory statistics (no series at all); with the
    allocator's peak faked: a stage's first run sets its mark, a later run
    that does not raise the peak leaves it, one that does moves it."""
    assert REGISTRY.get("hbm_peak_bytes") is None \
        or not _value("hbm_peak_bytes", stage="ledger_demo_mark")
    peaks = iter([0, 3_000,          # first run: raised to 3,000
                  9_000, 9_000,      # another program raised it meanwhile
                  9_000, 12_000])    # this run raises it again
    monkeypatch.setattr(trace, "_hbm_peak", lambda: next(peaks))
    for want in (3_000, 3_000, 12_000):
        with trace.stage("ledger_demo_mark"):
            pass
        assert _value("hbm_peak_bytes", stage="ledger_demo_mark") == want
    assert setup_ledger()["stages"]["ledger_demo_mark"]["hbm_peak_bytes"] \
        == 12_000


def test_the_resident_one_hot_is_a_stage_once_a_matrix(monkeypatch):
    """Interpret mode with the Pallas route forced on, as the benchmark's
    rehearsal does: the build is the stage ``onehot``; the cached array a
    round fetches is not."""
    from xgboost_tpu.tree import hist_kernel as hk

    monkeypatch.setattr(hk, "_INTERPRET", True)
    monkeypatch.setattr(hk, "use_pallas", lambda: True)
    monkeypatch.setenv("XGBTPU_HOIST_BUDGET_MB", "64")
    X, y = _matrix(n=1024, F=6)
    binned = xgb.DMatrix(X, label=y).get_binned(16)
    e0 = _value("setup_stage_events_total", stage="onehot")
    oh = binned.fused_onehot(3)
    assert oh is not None and oh.shape[1] == 6 * 16
    assert _value("setup_stage_events_total", stage="onehot") == e0 + 1
    assert binned.fused_onehot(3) is oh
    assert _value("setup_stage_events_total", stage="onehot") == e0 + 1


def test_a_plan_that_hoists_nothing_is_still_one_stage_a_matrix():
    """Off the chip the plan is 0: the first call pads the bins and asks the
    plan under the stage; the calls a round makes after it open none."""
    X, y = _matrix(n=1000, F=6)
    binned = xgb.DMatrix(X, label=y).get_binned(16)
    e0 = _value("setup_stage_events_total", stage="onehot")
    for _ in range(3):
        assert binned.fused_onehot(3) is None
    assert _value("setup_stage_events_total", stage="onehot") == e0 + 1


def test_the_rank_layout_is_a_stage():
    from xgboost_tpu.objective import ranking

    e0 = _value("setup_stage_events_total", stage="rank_layout")
    label = np.array([0, 1, 2, 0, 1, 1, 0], np.float32)
    ranking._build_layout(label, np.array([0, 3, 7]), None)
    assert _value("setup_stage_events_total", stage="rank_layout") == e0 + 1


def test_a_sharded_matrix_moves_the_same_stages():
    """Under a mesh the float32 rows go up sharded for the distributed
    sketch and whole for the bins: two uploads, one sketch, one bins."""
    from xgboost_tpu.parallel import make_mesh, mesh_context

    X, y = _matrix(n=2048, F=8, seed=5)
    before = _stages()
    with mesh_context(make_mesh(4)):
        xgb.DMatrix(X, label=y).get_binned(16)
    after = _stages()
    assert after["upload"][0] - before["upload"][0] == 2
    assert after["sketch"][0] - before["sketch"][0] == 1
    assert after["bins"][0] - before["bins"][0] == 1
