"""Self-healing serving plane (ISSUE 10, xgboost_tpu/serving/faults.py):
batch fault isolation + bisection, per-model circuit breakers, input
quarantine, admission validation, abandoned futures, the batcher-worker
watchdog, and the crash-only manifest/restart/drain contract.

Budget note (1-core container): every test shares one tiny trained model
shape (the same 400x5 the other serving files use, so XLA:CPU compiles
amortize across the process), servers run with small batch windows, and
the one subprocess test (cross-process chaos determinism) reuses the
PR-5 grammar contract with a single child interpreter.
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import xgboost_tpu as xgb
from xgboost_tpu.observability import REGISTRY
from xgboost_tpu.resilience import chaos, policy
from xgboost_tpu.serving import ModelServer, RequestError, RequestShed
from xgboost_tpu.serving.faults import (
    CLOSED, HALF_OPEN, OPEN, CircuitBreaker, Quarantine, fingerprint,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SEED_PARAMS = {"objective": "binary:logistic", "max_depth": 3,
               "max_bin": 16, "verbosity": 0}

POISON = 1e30  # the seeded poison sentinel value (XGBTPU_CHAOS_POISON)


def _counter(name, **labels):
    fam = REGISTRY.get(name)
    if fam is None:
        return 0.0
    return fam.labels(**labels).value


@pytest.fixture(scope="module")
def model():
    rng = np.random.RandomState(7)  # same X as test_model_server: shape
    X = rng.randn(400, 5).astype(np.float32)  # sharing across the file
    y = (X[:, 0] > 0).astype(np.float32)
    return xgb.train(SEED_PARAMS, xgb.DMatrix(X, label=y), 3), X


# ---------------------------------------------------------------------------
# batch fault isolation (the acceptance criterion)
# ---------------------------------------------------------------------------


def test_poison_isolated_innocents_bit_identical(model, monkeypatch):
    """Acceptance: N concurrent requests with 1 seeded poison member —
    exactly that request gets a typed RequestError (carrying its
    request_id); every innocent co-batched request returns results
    bit-identical to a fault-free run; the fault/bisection/breaker/
    quarantine series appear in the exposition."""
    bst, X = model
    N = 12
    inputs = [X[i:i + 1 + (i % 3)] for i in range(N)]

    def run_all(srv, with_poison):
        futs = [srv.predict_async("m", inputs[i], request_id=f"r{i}")
                for i in range(N // 2)]
        if with_poison:
            Xp = X[:1].copy()
            Xp[0, 2] = POISON
            pf = srv.predict_async("m", Xp, request_id="poison")
        futs += [srv.predict_async("m", inputs[i], request_id=f"r{i}")
                 for i in range(N // 2, N)]
        outs = [f.result(60) for f in futs]
        return outs, (pf if with_poison else None)

    # fault-free reference pass
    srv = ModelServer(batch_wait_us=50_000)
    try:
        srv.load("m", bst)
        ref, _ = run_all(srv, with_poison=False)
    finally:
        srv.close()

    monkeypatch.setenv("XGBTPU_CHAOS_POISON", str(POISON))
    f0 = _counter("serving_faults_total", site="serving_dispatch",
                  kind="permanent")
    p0 = _counter("serving_poison_requests_total")
    srv = ModelServer(batch_wait_us=50_000)
    try:
        srv.load("m", bst)
        outs, pf = run_all(srv, with_poison=True)
        with pytest.raises(RequestError) as exc:
            pf.result(60)
        assert exc.value.request_id == "poison"
        assert exc.value.site == "serving_dispatch"
        assert exc.value.kind == policy.PERMANENT
        for got, want in zip(outs, ref):
            np.testing.assert_array_equal(got, want)
        assert _counter("serving_faults_total", site="serving_dispatch",
                        kind="permanent") > f0
        assert _counter("serving_poison_requests_total") == p0 + 1
        exp = srv.metrics()
        assert 'serving_faults_total{kind="permanent",' \
               'site="serving_dispatch"}' in exp
        assert "serving_quarantined_inputs" in exp
        assert 'serving_breaker_state{model="m"}' in exp
    finally:
        srv.close()


def test_transient_dispatch_fault_retried_same_batch(model):
    """A TRANSIENT dispatch failure gets one bounded same-batch retry:
    nobody errors, no bisection, serving_batch_retries_total counts it."""
    bst, X = model
    srv = ModelServer(batch_wait_us=0)
    try:
        srv.load("m", bst)
        r0 = _counter("serving_batch_retries_total")
        b0 = _counter("serving_bisect_dispatches_total")
        with chaos.configure("serving_dispatch:transient:1"):
            out = srv.predict("m", X[:4], timeout=60)
        np.testing.assert_array_equal(
            out, np.asarray(bst.inplace_predict(X[:4])))
        assert _counter("serving_batch_retries_total") == r0 + 1
        assert _counter("serving_bisect_dispatches_total") == b0
    finally:
        srv.close()


def test_quarantine_repeat_offender_shed_at_admission(model, monkeypatch):
    """A poison fingerprint past XGBTPU_QUARANTINE_AFTER offenses is shed
    at admission (reason quarantine) instead of burning a bisection."""
    bst, X = model
    monkeypatch.setenv("XGBTPU_CHAOS_POISON", str(POISON))
    monkeypatch.setenv("XGBTPU_QUARANTINE_AFTER", "1")
    srv = ModelServer(batch_wait_us=0)
    try:
        srv.load("m", bst)
        Xp = X[:2].copy()
        Xp[1, 0] = POISON
        with pytest.raises(RequestError):
            srv.predict("m", Xp, timeout=60)
        q0 = _counter("requests_shed_total", reason="quarantine")
        with pytest.raises(RequestShed) as exc:
            srv.predict("m", Xp, timeout=60)
        assert exc.value.reason == "quarantine"
        assert _counter("requests_shed_total", reason="quarantine") == q0 + 1
        # a different payload still serves (quarantine keys on content)
        out = srv.predict("m", X[:2], timeout=60)
        np.testing.assert_array_equal(
            out, np.asarray(bst.inplace_predict(X[:2])))
    finally:
        srv.close()


def test_fingerprint_is_content_keyed():
    a = np.arange(10, dtype=np.float32).reshape(2, 5)
    assert fingerprint(a) == fingerprint(a.copy())
    b = a.copy()
    b[1, 4] += 1
    assert fingerprint(a) != fingerprint(b)
    assert fingerprint(a) != fingerprint(a.reshape(5, 2))
    q = Quarantine(after=2, cap=8)
    fp = fingerprint(a)
    assert not q.note(fp)          # first offense: not yet quarantined
    assert not q.quarantined(fp)
    assert q.note(fp)              # second offense crosses the threshold
    assert q.quarantined(fp)
    for i in range(20):            # LRU cap evicts the old offender
        q.note(1000 + i)
    assert not q.quarantined(fp)


# ---------------------------------------------------------------------------
# circuit breaker state machine
# ---------------------------------------------------------------------------


def test_breaker_trip_halfopen_probe_matrix():
    events = []
    b = CircuitBreaker("bm", window=8, threshold=0.5, min_samples=4,
                       open_s=0.08,
                       on_event=lambda name, **a: events.append(
                           (a["frm"], a["to"])))
    for _ in range(3):
        b.record(ok=True)
    assert b.state == CLOSED
    for _ in range(4):           # 4 fails / 7 outcomes >= 0.5
        b.record(ok=False)
    assert b.state == OPEN
    assert b.allow() is False    # OPEN sheds
    time.sleep(0.1)
    assert b.allow() is True     # cooldown over: this is the probe
    assert b.state == HALF_OPEN
    assert b.allow() is False    # concurrent arrival shed while probing
    b.record(ok=False)           # probe failed
    assert b.state == OPEN
    time.sleep(0.1)
    assert b.allow() is True
    b.record(ok=True)            # probe succeeded
    assert b.state == CLOSED
    assert b.allow() is True
    for _ in range(8):           # window was reset on recovery
        b.record(ok=True)
    assert b.state == CLOSED
    assert events == [("closed", "open"), ("open", "half_open"),
                      ("half_open", "open"), ("open", "half_open"),
                      ("half_open", "closed")]


def test_breaker_latency_trip_and_concurrent_feeds():
    b = CircuitBreaker("lm", window=8, threshold=0.5, min_samples=4,
                       open_s=30.0, latency_ms=5.0)
    for _ in range(4):           # "ok" but slower than the latency bar
        b.record(ok=True, latency_s=0.05)
    assert b.state == OPEN
    # concurrent trips: hammering from threads must neither crash nor
    # leave the machine in a non-state; exactly one OPEN transition fired
    t0 = REGISTRY.get("serving_breaker_transitions_total")
    t0 = t0.labels(model="cm", to="open").value if t0 else 0
    c = CircuitBreaker("cm", window=16, threshold=0.5, min_samples=4,
                       open_s=30.0)
    threads = [threading.Thread(
        target=lambda: [c.record(ok=False) for _ in range(10)])
        for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.state == OPEN
    assert _counter("serving_breaker_transitions_total",
                    model="cm", to="open") == t0 + 1


def test_breaker_open_sheds_at_admission_then_probe_recovers(model):
    """Server-level: an OPEN breaker sheds with reason breaker; after the
    cooldown the half-open probe dispatch recovers it."""
    bst, X = model
    srv = ModelServer(batch_wait_us=0)
    try:
        srv.load("m", bst)
        b = srv.faults.breaker("m")
        b.open_s = 0.08
        for _ in range(b.min_samples):
            b.record(ok=False)
        assert b.state == OPEN
        s0 = _counter("requests_shed_total", reason="breaker")
        with pytest.raises(RequestShed) as exc:
            srv.predict("m", X[:2], timeout=60)
        assert exc.value.reason == "breaker"
        assert _counter("requests_shed_total", reason="breaker") == s0 + 1
        time.sleep(0.1)
        # the next admitted request is the probe; its healthy dispatch
        # closes the breaker and traffic flows again
        out = srv.predict("m", X[:2], timeout=60)
        np.testing.assert_array_equal(
            out, np.asarray(bst.inplace_predict(X[:2])))
        assert b.state == CLOSED
        out = srv.predict("m", X[:4], timeout=60)
        assert out.shape == (4,)
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# admission validation + abandoned futures (satellites)
# ---------------------------------------------------------------------------


def test_invalid_payloads_rejected_before_the_queue(model, monkeypatch):
    bst, X = model
    monkeypatch.setenv("XGBTPU_MAX_REQUEST_ROWS", "8")
    srv = ModelServer(batch_wait_us=0)
    try:
        srv.load("m", bst)
        a0 = _counter("serving_admitted_total")
        i0 = _counter("requests_shed_total", reason="invalid")
        cases = [
            (X[:2, :3], "wrong width"),
            (np.full((1, 5), np.inf, np.float32), "inf values"),
            (X[:0], "empty payload"),
            (X[:9], "oversized rows"),
        ]
        for bad, why in cases:
            with pytest.raises(RequestShed) as exc:
                srv.predict("m", bad, timeout=60)
            assert exc.value.reason == "invalid", why
        assert _counter("requests_shed_total",
                        reason="invalid") == i0 + len(cases)
        # none of them was admitted into the batcher queue
        assert _counter("serving_admitted_total") == a0
        # NaN is NOT invalid — it is the missing-value sentinel
        out = srv.predict(
            "m", np.full((1, 5), np.nan, np.float32), timeout=60)
        assert out.shape == (1,)
    finally:
        srv.close()


def test_abandoned_future_skipped_at_dispatch_assembly(model):
    bst, X = model
    srv = ModelServer(batch_wait_us=150_000)
    try:
        srv.load("m", bst)
        a0 = _counter("serving_requests_total", outcome="abandoned")
        # cancel a submitted future before the worker claims it. The
        # ISSUE 15 idle fast-path dispatches a fully-assembled batch at
        # once, so the worker is held at the door of ``_run_batch`` (where
        # assembly claims each future) until the cancel has landed: on a
        # loaded box a bare cancel lost the claim race five times running
        gate = threading.Event()
        run_batch = srv.batcher._run_batch

        def held(batch, gen):
            assert gate.wait(60), "the test never opened the gate"
            return run_batch(batch, gen)

        srv.batcher._run_batch = held
        try:
            f1 = srv.predict_async("m", X[:1])
            assert f1.cancel(), "a pending future refused the cancel"
        finally:
            gate.set()
            srv.batcher._run_batch = run_batch
        f2 = srv.predict_async("m", X[1:3])
        np.testing.assert_array_equal(
            f2.result(60), np.asarray(bst.inplace_predict(X[1:3])))
        assert f1.cancelled()
        # the outcome is counted by the recorder's writer thread; drain()
        # is the readers' barrier (serving/obs.py)
        assert srv.obs.drain()
        assert _counter("serving_requests_total",
                        outcome="abandoned") == a0 + 1
        # the abandoned request's model pin was released
        assert srv.registry.get("m").inflight == 0
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# batcher-worker watchdog (crash-only worker)
# ---------------------------------------------------------------------------


def test_watchdog_fails_wedged_futures_and_respawns(model, monkeypatch):
    bst, X = model
    monkeypatch.setenv("XGBTPU_BATCHER_WATCHDOG", "0.3")
    srv = ModelServer(batch_wait_us=0)
    try:
        srv.load("m", bst)
        r0 = _counter("serving_worker_respawns_total")
        with chaos.configure("batcher_wedge:transient:1"):
            fut = srv.predict_async("m", X[:2], request_id="wedged")
            with pytest.raises(RequestError) as exc:
                fut.result(10)
            assert exc.value.site == "batcher_wedge"
            assert exc.value.request_id == "wedged"
            # the respawned worker serves the queue behind the wedge
            out = srv.predict("m", X[:2], timeout=30)
        np.testing.assert_array_equal(
            out, np.asarray(bst.inplace_predict(X[:2])))
        assert _counter("serving_worker_respawns_total") == r0 + 1
        assert _counter("serving_faults_total", site="batcher_wedge",
                        kind="transient") >= 1
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# crash-only restart: manifest + drain
# ---------------------------------------------------------------------------


def test_manifest_restart_refaults_lazily_and_drain_sheds(model, tmp_path):
    bst, X = model
    run_dir = str(tmp_path / "run")
    srv = ModelServer({"m": bst}, run_dir=run_dir, batch_wait_us=0)
    try:
        ref = srv.predict("m", X[:4], timeout=60)
    finally:
        srv.close()
    man = json.load(open(os.path.join(run_dir, "manifest.json")))
    assert man["format"] == "xgbtpu-manifest-v1"
    assert man["models"]["m"]["live"] == 1
    spec = man["models"]["m"]["versions"]["1"]
    assert spec["kind"] == "file" and os.path.exists(spec["path"])

    srv2 = ModelServer(run_dir=run_dir, batch_wait_us=0)
    try:
        # lazy: nothing resident until the first request faults it in
        assert srv2.registry.resident() == []
        m0 = _counter("serving_model_misses_total")
        out = srv2.predict("m", X[:4], timeout=60)
        np.testing.assert_array_equal(out, ref)
        assert _counter("serving_model_misses_total") == m0 + 1
        assert srv2.registry.resident() == ["m@v1"]
        # SIGTERM half: draining sheds new arrivals with a typed reason
        srv2.begin_drain()
        with pytest.raises(RequestShed) as exc:
            srv2.predict("m", X[:4])
        assert exc.value.reason == "draining"
        assert srv2.stats()["draining"] is True
    finally:
        srv2.close()


def test_manifest_tracks_swap_live_version(model, tmp_path):
    bst, X = model
    rng = np.random.RandomState(7)
    y2 = (X[:, 1] > 0).astype(np.float32)
    bst2 = xgb.train(dict(SEED_PARAMS, seed=9),
                     xgb.DMatrix(X, label=y2), 2)
    run_dir = str(tmp_path / "run")
    srv = ModelServer({"m": bst}, run_dir=run_dir, batch_wait_us=0)
    try:
        srv.swap("m", bst2)
    finally:
        srv.close()
    man = json.load(open(os.path.join(run_dir, "manifest.json")))
    assert man["models"]["m"]["live"] == 2
    assert set(man["models"]["m"]["versions"]) == {"1", "2"}
    srv2 = ModelServer(run_dir=run_dir, batch_wait_us=0)
    try:
        out = srv2.predict("m", X[:4], timeout=60)
        np.testing.assert_array_equal(
            out, np.asarray(bst2.inplace_predict(X[:4])))
    finally:
        srv2.close()
    del rng


# ---------------------------------------------------------------------------
# chaos-schedule determinism for the serving sites (PR-5 grammar contract)
# ---------------------------------------------------------------------------


def test_serving_chaos_sites_deterministic_cross_process():
    """The four serving sites obey the exact seeded-schedule grammar the
    PR-5 membership agent pins: the same plan armed in another
    interpreter fires at identical hit indices (no RNG state anywhere)."""
    cfg = ("serving_dispatch:transient:%5;"
           "serving_model_load:transient:p0.4@7;"
           "serving_swap:permanent:3;"
           "batcher_wedge:transient:2-4")
    sites = ("serving_dispatch", "serving_model_load", "serving_swap",
             "batcher_wedge")

    def fired_local():
        out = {}
        with chaos.configure(cfg):
            for site in sites:
                hits = []
                for n in range(1, 41):
                    try:
                        chaos.hit(site)
                    except chaos.ChaosError:
                        hits.append(n)
                out[site] = hits
        return out

    local = fired_local()
    assert local["serving_dispatch"] == [5, 10, 15, 20, 25, 30, 35, 40]
    assert local["serving_swap"] == [3]
    assert local["batcher_wedge"] == [2, 3, 4]
    assert local["serving_model_load"], "p0.4@7 fired nowhere in 40 hits"
    assert len(local["serving_model_load"]) < 40

    prog = (
        "import json\n"
        "from xgboost_tpu.resilience import chaos\n"
        f"cfg = {cfg!r}\n"
        f"sites = {sites!r}\n"
        "fired = {}\n"
        "with chaos.configure(cfg):\n"
        "    for site in sites:\n"
        "        hits = []\n"
        "        for n in range(1, 41):\n"
        "            try:\n"
        "                chaos.hit(site)\n"
        "            except chaos.ChaosError:\n"
        "                hits.append(n)\n"
        "        fired[site] = hits\n"
        "print(json.dumps(fired))\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONHASHSEED"] = "3"  # different interpreter state on purpose
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", prog], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=180)
    assert out.returncode == 0, out.stderr[-2000:]
    assert json.loads(out.stdout) == local, \
        "serving chaos schedules diverged across processes"
