"""Continuous train-to-serve delivery (xgboost_tpu/serving/delivery.py):
watched checkpoints, canaried promotion, SLO+quality gates, auto-rollback
— the ISSUE 12 acceptance surface.

Budget note (1-core container): one tiny 5-feature model shape is trained
once per module and reused everywhere (XLA:CPU compiles amortize);
delivery cycles run with millisecond poll/bake knobs and single-digit
canary minimums, so each end-to-end test costs seconds, not minutes.
"""

import json
import os
import shutil
import threading
import time

import numpy as np
import pytest

import xgboost_tpu as xgb
from xgboost_tpu.observability import REGISTRY
from xgboost_tpu.resilience import checkpoint as ckpt
from xgboost_tpu.serving import ModelServer, DeliveryController

PARAMS = {"objective": "binary:logistic", "max_depth": 3,
          "max_bin": 16, "verbosity": 0, "seed": 5}


def _counter(name, **labels):
    fam = REGISTRY.get(name)
    if fam is None:
        return 0.0
    return fam.labels(**labels).value


def _data(n=400, seed=7):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, 5).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] + 0.3 * rng.randn(n) > 0).astype(
        np.float32)
    return X, y


@pytest.fixture(scope="module")
def setup(tmp_path_factory):
    """Shared: a 3-round checkpointed train, its +2-round append
    continuation, and the raw checkpoint files of both stages (retention
    prunes the live directory, so tests materialize per-test watch dirs
    from these bytes)."""
    X, y = _data()
    base = tmp_path_factory.mktemp("ckpts")
    xgb.train(PARAMS, xgb.DMatrix(X, label=y), 3,
              resume_from=str(base), verbose_eval=False)
    p3 = ckpt.checkpoint_path(str(base), 3)
    with open(p3, "rb") as f:
        raw3 = f.read()
    bst5 = xgb.train(PARAMS, xgb.DMatrix(X, label=y), 2,
                     resume_from=str(base), resume_mode="append",
                     verbose_eval=False)
    p5 = ckpt.checkpoint_path(str(base), 5)
    with open(p5, "rb") as f:
        raw5 = f.read()
    return {"X": X, "y": y, "raw3": raw3, "raw5": raw5, "bst5": bst5}


def _seed_dir(tmp_path, *stages):
    """A watch dir holding the named checkpoint stages (3 and/or 5)."""
    d = tmp_path / "watch"
    d.mkdir(exist_ok=True)
    return str(d)


def _write_ckpt(watch_dir, raw, rounds):
    path = ckpt.checkpoint_path(watch_dir, rounds)
    ckpt.atomic_write_bytes(path, raw)
    return path


def _server(tmp_path, setup, **kw):
    watch = _seed_dir(tmp_path)
    _write_ckpt(watch, setup["raw3"], 3)
    srv = ModelServer({"m": ckpt.checkpoint_path(watch, 3)},
                      run_dir=str(tmp_path / "srv"),
                      batch_wait_us=0, **kw)
    return srv, watch


class _Traffic:
    """Background request stream; every request must resolve (ok or a
    typed error) — an unanswered future is a DROPPED request and fails
    the test."""

    def __init__(self, srv, X, rows=4):
        self.srv, self.X, self.rows = srv, X, rows
        self.stop = threading.Event()
        self.ok, self.failed, self.dropped = [], [], []
        self._t = threading.Thread(target=self._run, daemon=True)

    def __enter__(self):
        self._t.start()
        return self

    def __exit__(self, *exc):
        self.stop.set()
        self._t.join(30)

    def _run(self):
        i = 0
        while not self.stop.is_set():
            i += 1
            off = (i * 7) % 300
            try:
                out = self.srv.predict(
                    "m", self.X[off:off + self.rows], timeout=30,
                    request_id=f"r{i}")
                self.ok.append((off, out))
            except TimeoutError:
                self.dropped.append(i)
            except Exception as e:
                self.failed.append(e)
            time.sleep(0.002)


def _wait(predicate, timeout=60, period=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(period)
    return predicate()


def _event_names(srv):
    return [r["name"] for r in srv.obs.records() if r.get("t") == "event"]


# ---------------------------------------------------------------------------
# part 1: append-rounds resume (continuous training)
# ---------------------------------------------------------------------------


def test_append_rounds_resume_bit_identical(setup, tmp_path):
    """train(3) then append-resume +2 == train(5) straight through, bit
    for bit — the delivery loop never changes what the model would have
    been (acceptance pin)."""
    X, y = setup["X"], setup["y"]
    assert setup["bst5"].num_boosted_rounds() == 5
    straight = xgb.train(PARAMS, xgb.DMatrix(X, label=y), 5,
                         verbose_eval=False)
    assert setup["bst5"].save_raw() == straight.save_raw()


def test_append_rounds_fresh_data_improves_auc(tmp_path):
    """A fresh-data continuation (the online-learning loop): appending
    rounds trained on MORE data improves held-out AUC."""
    from xgboost_tpu.metric import create_metric

    X, y = _data(n=900, seed=11)
    Xh, yh = X[600:], y[600:]  # held out
    d = str(tmp_path / "cont")
    small = xgb.train(PARAMS, xgb.DMatrix(X[:150], label=y[:150]), 2,
                      resume_from=d, verbose_eval=False)
    auc_small = float(create_metric("auc").evaluate(
        np.asarray(small.inplace_predict(Xh)), yh))
    # fresh data arrives: continue the SAME checkpoint lineage on the
    # full training slice
    cont = xgb.train(PARAMS, xgb.DMatrix(X[:600], label=y[:600]), 6,
                     resume_from=d, resume_mode="append",
                     verbose_eval=False)
    assert cont.num_boosted_rounds() == 8
    auc_cont = float(create_metric("auc").evaluate(
        np.asarray(cont.inplace_predict(Xh)), yh))
    assert auc_cont > auc_small, (auc_small, auc_cont)


def test_resume_mode_validated():
    with pytest.raises(ValueError, match="resume_mode"):
        xgb.train(PARAMS, xgb.DMatrix(np.zeros((4, 2), np.float32),
                                      label=np.zeros(4)), 1,
                  resume_from="/nonexistent", resume_mode="sideways")


# ---------------------------------------------------------------------------
# part 2: checkpoint-inspect --json (the controller's poll primitive)
# ---------------------------------------------------------------------------


def test_checkpoint_inspect_json(setup, tmp_path, capsys):
    from xgboost_tpu.cli import checkpoint_inspect_main

    watch = _seed_dir(tmp_path)
    _write_ckpt(watch, setup["raw3"], 3)
    _write_ckpt(watch, setup["raw5"][:-7], 5)  # torn tail: must not win
    rc = checkpoint_inspect_main([watch, "--json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert doc["newest_verified_rounds"] == 3
    assert doc["newest_verified"] == ckpt.checkpoint_path(watch, 3)
    by_rounds = {r["rounds"]: r for r in doc["records"]}
    assert by_rounds[3]["verified"] and by_rounds[3]["newest_verified"]
    assert not by_rounds[5]["verified"]
    assert "truncated" in by_rounds[5]["detail"]
    # nothing verifiable -> exit 1, json still emitted
    empty = str(tmp_path / "none")
    os.makedirs(empty)
    rc = checkpoint_inspect_main([empty, "--json"])
    assert rc == 1
    assert json.loads(capsys.readouterr().out)["newest_verified"] is None
    # multi-rank dir: one newest-verified PER resume scope; the
    # top-level answer is the most advanced across scopes, not
    # whichever scope was listed last (rank1 here holds only rounds 3)
    multi = tmp_path / "multi"
    for sub, raw, rounds in (("rank0", setup["raw5"], 5),
                             ("rank1", setup["raw3"], 3)):
        os.makedirs(str(multi / sub))
        _write_ckpt(str(multi / sub), raw, rounds)
    rc = checkpoint_inspect_main([str(multi), "--json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert doc["newest_verified_rounds"] == 5
    assert doc["newest_verified"] == ckpt.checkpoint_path(
        str(multi / "rank0"), 5)


# ---------------------------------------------------------------------------
# part 3: arena pinning (satellite: incumbent survives a hot third tenant)
# ---------------------------------------------------------------------------


def test_pinned_entry_survives_lru_eviction(setup, tmp_path):
    from xgboost_tpu.serving import ModelRegistry

    reg = ModelRegistry(arena_mb=1e-4)  # ~100 bytes: one entry over budget
    reg.load("a", setup["raw3"][setup["raw3"].index(b"\n") + 1:])
    reg.pin("a", 1, True)
    reg.load("b", setup["raw3"][setup["raw3"].index(b"\n") + 1:])
    # budget forces eviction, but the pinned entry is shielded
    assert "a@v1" in reg.resident()
    reg.pin("a", 1, False)
    reg.load("c", setup["raw3"][setup["raw3"].index(b"\n") + 1:])
    assert "a@v1" not in reg.resident()  # unpinned: LRU reclaims it


# ---------------------------------------------------------------------------
# part 4: the delivery pipeline end to end
# ---------------------------------------------------------------------------


def test_fraction_canary_promotes(setup, tmp_path):
    """publish -> fractional canary -> gates pass -> warm promote; the
    new checkpoint appears mid-traffic and zero requests drop."""
    X, y = setup["X"], setup["y"]
    srv, watch = _server(tmp_path, setup)
    try:
        assert srv.registry.live_version("m") == 1
        ctl = srv.deliver("m", watch, mode="fraction", fraction=0.5,
                          min_requests=6, poll_s=0.02, bake_s=0.2,
                          eval_data=(X[:200], y[:200]),
                          canary_deadline_s=60, p99_ratio=10.0)
        p0 = _counter("delivery_promotions_total")
        with _Traffic(srv, X) as tr:
            _write_ckpt(watch, setup["raw5"], 5)  # training delivered
            assert _wait(lambda: ctl.status()["history"])
        st = ctl.status()
        assert st["history"][-1]["outcome"] == "promoted"
        assert srv.registry.live_version("m") == 2
        assert _counter("delivery_promotions_total") == p0 + 1
        assert not tr.dropped and not tr.failed
        # the promoted model serves: results now match the 5-round model
        got = srv.predict("m", X[:8], timeout=30)
        want = setup["bst5"].inplace_predict(X[:8])
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        events = _event_names(srv)
        for name in ("checkpoint_seen", "model_published", "canary_start",
                     "model_promoted"):
            assert name in events, (name, events)
        # pins released after the cycle
        assert not any(e.pinned for e in
                       srv.registry._entries.values())
        # both arms were observed
        c = st["history"][-1]
        assert c["version"] == 2
    finally:
        srv.close()


def test_corrupt_checkpoint_skipped_old_version_serves(setup, tmp_path):
    """A torn checkpoint is skipped and counted ONCE; the live version
    keeps serving; a later good checkpoint still delivers."""
    X, y = setup["X"], setup["y"]
    srv, watch = _server(tmp_path, setup)
    try:
        ctl = DeliveryController(
            srv, "m", watch, mode="fraction", fraction=0.5,
            min_requests=4, poll_s=0.02, bake_s=0.1,
            canary_deadline_s=30, p99_ratio=10.0)
        s0 = _counter("delivery_checkpoints_skipped_total",
                      reason="corrupt")
        _write_ckpt(watch, setup["raw5"][:-20], 5)  # torn
        assert ctl.poll() is None
        assert ctl.poll() is None  # second scan: not double-counted
        assert _counter("delivery_checkpoints_skipped_total",
                        reason="corrupt") == s0 + 1
        assert srv.registry.live_version("m") == 1
        assert srv.predict("m", X[:4], timeout=30) is not None
        assert "checkpoint_skipped" in _event_names(srv)
        # the good bytes land (training re-commits): delivery proceeds
        _write_ckpt(watch, setup["raw5"], 5)
        with _Traffic(srv, X):
            assert _wait(lambda: ctl.poll() is not None, timeout=30)
        assert srv.registry.live_version("m") == 2
    finally:
        srv.close()


def test_shadow_canary_gate_rejects_bad_model(setup, tmp_path):
    """Shadow mode: live responses stay bit-identical to the incumbent
    while the candidate (a model trained on FLIPPED labels) is diffed and
    rejected by the AUC gate — never promoted, counted by reason."""
    X, y = setup["X"], setup["y"]
    srv, watch = _server(tmp_path, setup)
    try:
        bad = xgb.train(dict(PARAMS, seed=9),
                        xgb.DMatrix(X, label=1.0 - y), 5,
                        verbose_eval=False)
        incumbent = xgb.Booster(PARAMS, model_file=ckpt.read_checkpoint(
            ckpt.checkpoint_path(watch, 3))[0])
        fleet_msgs = []

        def _bcast(msg):
            fleet_msgs.append(dict(msg))
            return {"ok": True}

        ctl = srv.deliver("m", watch, mode="shadow", fraction=1.0,
                          min_requests=5, poll_s=0.02, bake_s=0.1,
                          eval_data=(X[:200], y[:200]),
                          canary_deadline_s=60, p99_ratio=10.0,
                          broadcast=_bcast)
        with _Traffic(srv, X) as tr:
            # the (regressed) re-train lands while traffic flows
            ckpt.save_checkpoint(watch, bad, 9)
            assert _wait(lambda: ctl.status()["history"])
            # the controller records the rejection BEFORE it discards the
            # candidate and tells the fleet (delivery._deliver): wait for
            # the last of those steps, not for the first
            assert _wait(lambda: any(m["op"] == "unload"
                                     for m in list(fleet_msgs)))
        st = ctl.status()
        assert st["history"][-1]["outcome"] == "rejected"
        assert "auc" in st["history"][-1]["detail"]["reasons"]
        assert srv.registry.live_version("m") == 1  # never promoted
        assert _counter("delivery_canary_rejected_total",
                        reason="auc") >= 1
        assert "canary_rejected" in _event_names(srv)
        assert not tr.dropped and not tr.failed
        # shadow diffs ran and saw a real divergence; primary responses
        # bit-identical to serving the incumbent directly
        assert st["history"] is not None
        for off, out in tr.ok[:20]:
            want = incumbent.inplace_predict(X[off:off + 4])
            np.testing.assert_array_equal(np.asarray(out),
                                          np.asarray(want))
        assert _counter("delivery_canary_diffs_total") >= 1
        # a settled rejection is DISCARDED: arena entry, retained
        # source, manifest row and spilled bytes all released — an
        # online loop rejecting candidates must not grow disk forever
        assert "model_discarded" in _event_names(srv)
        assert ("m", 2) not in srv.registry.sources_snapshot()
        assert "m@v2" not in srv.registry.resident()
        with open(str(tmp_path / "srv" / "manifest.json")) as f:
            doc = json.load(f)
        assert "2" not in doc["models"]["m"]["versions"]
        spill = str(tmp_path / "srv" / "models" / "m@v2.json")
        assert not os.path.exists(spill)
        # the fleet saw the whole story: the publish broadcast ships the
        # manifest-spilled copy (survives training retention pruning the
        # .ckpt), and the rejection rides an unload broadcast
        by_op = {m["op"]: m for m in fleet_msgs}
        assert by_op["load"]["path"] == spill  # serving-plane-owned copy
        assert by_op["load"]["live"] is False
        assert by_op["unload"]["version"] == 2
    finally:
        srv.close()


def test_breaker_trip_rolls_back_and_quarantines(setup, tmp_path,
                                                 monkeypatch):
    """Post-promotion regression: the promoted version's dispatches fail
    (XGBTPU_CHAOS_MODEL), the NAME-keyed breaker trips, the controller
    re-swaps to last-good, quarantines the bad version in the manifest,
    and a restarted server + fresh controller never serve or re-promote
    it. Zero requests dropped throughout."""
    monkeypatch.setenv("XGBTPU_BREAKER_MIN", "4")
    monkeypatch.setenv("XGBTPU_BREAKER_WINDOW", "8")
    X, y = setup["X"], setup["y"]
    srv, watch = _server(tmp_path, setup)
    try:
        ctl = srv.deliver("m", watch, mode="fraction", fraction=0.5,
                          min_requests=5, poll_s=0.02, bake_s=20.0,
                          eval_data=(X[:200], y[:200]),
                          canary_deadline_s=60, p99_ratio=10.0)
        r0 = _counter("delivery_rollbacks_total")
        with _Traffic(srv, X) as tr:
            _write_ckpt(watch, setup["raw5"], 5)
            # promotion flips live to v2 and the bake window opens; then
            # the regression "ships" — only v2 dispatches fail
            assert _wait(lambda: srv.registry.live_version("m") == 2)
            monkeypatch.setenv("XGBTPU_CHAOS_MODEL", "m@v2")
            assert _wait(lambda: ctl.status()["history"])
            monkeypatch.delenv("XGBTPU_CHAOS_MODEL")
        st = ctl.status()
        assert st["history"][-1]["outcome"] == "rolled_back"
        assert srv.registry.live_version("m") == 1
        assert _counter("delivery_rollbacks_total") == r0 + 1
        assert srv.quarantined_versions("m")[2]["rounds"] == 5
        assert not tr.dropped, f"dropped: {tr.dropped}"
        # every failed request carried a typed, classified error
        from xgboost_tpu.serving import RequestError, RequestShed
        assert all(isinstance(e, (RequestError, RequestShed))
                   for e in tr.failed), tr.failed
        # breaker reset: restored incumbent serves immediately
        assert srv.predict("m", X[:4], timeout=30) is not None
        for name in ("model_rolled_back", "model_quarantined"):
            assert name in _event_names(srv)
        # the quarantined version is unaddressable on this server
        with pytest.raises(KeyError):
            srv.registry.get("m", 2)
        srv.stop_delivery("m")
    finally:
        srv.close()

    # crash-only restart: the manifest carries live pointer + quarantine;
    # a fresh watcher skips the quarantined round forever
    srv2 = ModelServer(run_dir=str(tmp_path / "srv"), batch_wait_us=0)
    try:
        assert srv2.registry.live_version("m") == 1
        assert 2 in srv2.quarantined_versions("m")
        with pytest.raises(KeyError):
            srv2.registry.get("m", 2)
        q0 = _counter("delivery_checkpoints_skipped_total",
                      reason="quarantined")
        ctl2 = DeliveryController(srv2, "m", watch, from_rounds=3,
                                  poll_s=0.02, bake_s=0.1)
        assert ctl2.poll() is None  # rounds-5 checkpoint never re-promoted
        assert _counter("delivery_checkpoints_skipped_total",
                        reason="quarantined") == q0 + 1
        assert srv2.registry.live_version("m") == 1
    finally:
        srv2.close()


def test_gate_p99_and_error_rate_deterministic(setup, tmp_path):
    """The SLO gate on synthetic, fully-controlled inputs: a candidate
    whose p99 blows the ratio (or whose error rate exceeds the
    incumbent's) is rejected with the right reasons; a clean candidate
    passes. Uses a model name unique to this test so the global latency
    histogram holds exactly the injected samples."""
    from xgboost_tpu.serving import CanaryState

    srv, watch = _server(tmp_path, setup)
    try:
        ctl = DeliveryController(srv, "gate_m", watch, from_rounds=0,
                                 min_requests=4, p99_ratio=1.25,
                                 poll_s=0.02, bake_s=0.1)
        fam = REGISTRY.get("predict_latency_seconds")
        assert fam is not None  # the module's servers already predicted
        for _ in range(50):
            fam.labels(model="gate_m@v1").observe(0.001)
            fam.labels(model="gate_m@v2").observe(0.1)  # 100x slower
        state = CanaryState("gate_m", 2, 1, mode="fraction",
                            fraction=0.5)
        for _ in range(10):
            state.observe("candidate", True)
            state.observe("incumbent", True)
        ok, detail = ctl._gate(state)
        assert not ok and detail["reasons"] == ["p99"], detail
        # error-rate gate: candidate fails where the incumbent does not
        state2 = CanaryState("gate_m", 3, 1, mode="fraction",
                             fraction=0.5)
        for i in range(10):
            state2.observe("candidate", i % 2 == 0)
            state2.observe("incumbent", True)
        for _ in range(50):
            fam.labels(model="gate_m@v3").observe(0.001)
        ok, detail = ctl._gate(state2)
        assert not ok and "error_rate" in detail["reasons"], detail
        # a clean candidate passes
        state3 = CanaryState("gate_m", 4, 1, mode="fraction",
                             fraction=0.5)
        for _ in range(10):
            state3.observe("candidate", True)
            state3.observe("incumbent", True)
        for _ in range(50):
            fam.labels(model="gate_m@v4").observe(0.001)
        ok, detail = ctl._gate(state3)
        assert ok, detail
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# part 5: the protocol surface (deliver/promote/rollback/quarantine ops)
# ---------------------------------------------------------------------------


def test_protocol_delivery_ops(setup, tmp_path):
    from xgboost_tpu.serving.server import _handle

    srv, watch = _server(tmp_path, setup)
    noop = lambda: None  # noqa: E731
    try:
        out = _handle(srv, {"op": "deliver", "action": "status",
                            "id": 1}, noop)
        assert out["ok"] and out["delivery"] == {} and out["id"] == 1
        # publish over the wire: load with live=False does not flip
        p5 = _write_ckpt(watch, setup["raw5"], 5)
        out = _handle(srv, {"op": "load", "model": "m", "path": p5,
                            "version": 2, "live": False}, noop)
        assert out["ok"] and out["version"] == "m@v2"
        assert srv.registry.live_version("m") == 1
        out = _handle(srv, {"op": "promote", "model": "m",
                            "version": 2}, noop)
        assert out["ok"] and srv.registry.live_version("m") == 2
        out = _handle(srv, {"op": "rollback", "model": "m",
                            "version": 1}, noop)
        assert out["ok"] and srv.registry.live_version("m") == 1
        out = _handle(srv, {"op": "quarantine", "model": "m",
                            "version": 2, "rounds": 5}, noop)
        assert out["ok"]
        assert srv.quarantined_versions("m")[2]["rounds"] == 5
        # a quarantined version refuses promotion, as a protocol error
        out = _handle(srv, {"op": "promote", "model": "m",
                            "version": 2}, noop)
        assert "quarantined" in out["error"]
        # deliver start/stop round trip
        out = _handle(srv, {"op": "deliver", "model": "m",
                            "watch": watch, "min_requests": 4,
                            "poll_s": 0.05}, noop)
        assert out["ok"]
        assert "m" in srv.delivery_status()
        out = _handle(srv, {"op": "deliver", "action": "stop",
                            "model": "m"}, noop)
        assert out["ok"] and srv.delivery_status() == {}
    finally:
        srv.close()


def test_serve_report_renders_delivery_timeline(setup, tmp_path, capsys):
    """Delivery events land on the recorder timeline and serve-report
    renders a "model delivery" section + machine-readable doc."""
    from xgboost_tpu.observability.serve_report import main as sr_main

    X, y = setup["X"], setup["y"]
    srv, watch = _server(tmp_path, setup)
    try:
        ctl = srv.deliver("m", watch, mode="fraction", fraction=0.5,
                          min_requests=4, poll_s=0.02, bake_s=0.1,
                          canary_deadline_s=60, p99_ratio=10.0)
        with _Traffic(srv, X):
            _write_ckpt(watch, setup["raw5"], 5)
            assert _wait(lambda: ctl.status()["history"])
        assert ctl.status()["history"][-1]["outcome"] == "promoted"
    finally:
        srv.close()
    rc = sr_main([str(tmp_path / "srv")])
    out = capsys.readouterr().out
    assert rc == 0
    assert "model delivery (train-to-serve loop):" in out
    for name in ("checkpoint_seen", "model_published", "canary_start",
                 "model_promoted"):
        assert name in out, (name, out)
    with open(str(tmp_path / "srv" / "obs" / "serve_report.json")) as f:
        doc = json.load(f)
    assert [r["event"] for r in doc["delivery"]].count(
        "model_promoted") == 1


# ---------------------------------------------------------------------------
# part 7: fault-plane isolation + watcher steady-state cost
# ---------------------------------------------------------------------------


def test_shadow_failures_never_shed_live_traffic(setup, tmp_path,
                                                 monkeypatch):
    """A candidate whose every dispatch FAILS (model-poison chaos on the
    candidate label) in shadow mode must lose its canary — and nothing
    else: the live NAME-keyed breaker stays closed, live requests keep
    flowing untouched ("zero user impact" is a contract, not a hope)."""
    from xgboost_tpu.serving import faults

    X, y = setup["X"], setup["y"]
    srv, watch = _server(tmp_path, setup)
    try:
        # arm BEFORE the canary starts: every candidate dispatch raises
        monkeypatch.setenv("XGBTPU_CHAOS_MODEL", "m@v2")
        ctl = srv.deliver("m", watch, mode="shadow", fraction=1.0,
                          min_requests=5, poll_s=0.02, bake_s=0.1,
                          canary_deadline_s=60, p99_ratio=10.0)
        with _Traffic(srv, X) as tr:
            _write_ckpt(watch, setup["raw5"], 5)
            assert _wait(lambda: ctl.status()["history"])
        st = ctl.status()
        assert st["history"][-1]["outcome"] == "rejected"
        assert "error_rate" in st["history"][-1]["detail"]["reasons"]
        # the poisoned shadow arm fed the CANARY verdict only: the live
        # breaker never opened, no live request was shed or failed
        assert srv.faults.breaker("m").state == faults.CLOSED
        assert srv.registry.live_version("m") == 1
        assert not tr.dropped and not tr.failed
    finally:
        srv.close()


def test_watch_steady_state_costs_no_file_io(setup, tmp_path,
                                             monkeypatch):
    """With nothing new on disk a poll must not re-read (let alone
    re-hash) the newest checkpoint's payload — a multi-hundred-MB model
    at poll_s=1 would be hashed every second forever. The filename is
    the hint; it is NEVER trusted for delivery: a corrupt file named
    beyond the processed mark is still fully verified and counted."""
    assert ckpt.path_rounds(ckpt.checkpoint_path("/x", 3)) == 3
    assert ckpt.path_rounds("/x/notackpt.json") is None

    srv, watch = _server(tmp_path, setup)
    try:
        ctl = DeliveryController(srv, "m", watch, poll_s=0.02,
                                 bake_s=0.0)  # not started: poll by hand
        assert ctl.status()["processed_rounds"] == 3

        def _no_verify(p):
            raise AssertionError(
                f"steady-state poll fully verified {p!r}")

        monkeypatch.setattr(ckpt, "verify_checkpoint", _no_verify)
        assert ctl.poll() is None  # settled territory: no reads at all
        monkeypatch.undo()

        # a corrupt checkpoint NAMED new (its intact header even claims
        # the already-settled rounds 3) must be verified and counted —
        # the name flags it new, verification rejects it, v1 keeps
        # serving and the scan falls back to settled territory
        with open(ckpt.checkpoint_path(watch, 9), "wb") as f:
            f.write(setup["raw3"][:-20])
        s0 = _counter("delivery_checkpoints_skipped_total",
                      reason="corrupt")
        assert ctl.poll() is None
        assert _counter("delivery_checkpoints_skipped_total",
                        reason="corrupt") == s0 + 1
        assert srv.registry.live_version("m") == 1
    finally:
        srv.close()


def test_quarantined_version_number_never_reused(setup, tmp_path):
    """Restart: quarantine scrubs the version's manifest row, so the
    registry cannot learn its number from the restored sources — the
    restarted server must still never hand the next published
    checkpoint a quarantined (unpromotable) version number, or delivery
    wedges forever on a ValueError at promote."""
    raw = setup["raw3"][setup["raw3"].index(b"\n") + 1:]  # model payload
    run = str(tmp_path / "srv")
    srv = ModelServer({"m": raw}, run_dir=run, batch_wait_us=0)
    srv.publish("m", raw)                       # -> m@v2
    srv.quarantine_version("m", 2, rounds=5)
    srv.close()

    srv2 = ModelServer(run_dir=run, batch_wait_us=0)
    try:
        assert 2 in srv2.quarantined_versions("m")
        label = srv2.publish("m", raw)          # must NOT be v2 again
        assert label == "m@v3", label
        assert srv2.promote("m", 3) == "m@v3"   # and it can go live
    finally:
        srv2.close()
