"""The resilience subsystem (ISSUE 5 tentpole): failure classification,
retry/backoff policy, degradation state machine, chaos injection, atomic
checkpoints, watchdog — every degradation edge driven by seeded chaos
schedules, no hardware required."""

import json
import os
import time

import numpy as np
import pytest

import xgboost_tpu as xgb
from xgboost_tpu.observability import REGISTRY
from xgboost_tpu.resilience import (
    DEGRADED, DISABLED, HEALTHY, RetryPolicy, WatchdogTimeout,
    chaos, checkpoint, degrade, policy, watchdog,
)


# ---------------------------------------------------------------- policy

def test_classify_kinds():
    """Kinds per docs/resilience.md: permanent signatures checked before
    resource (a scoped-VMEM overflow also says 'exhausted'), transient is
    the default, chaos errors carry their scripted kind."""
    assert policy.classify(RuntimeError("RESOURCE_EXHAUSTED: 1GB")) == \
        policy.RESOURCE
    assert policy.classify(MemoryError()) == policy.RESOURCE
    assert policy.classify(RuntimeError("Mosaic lowering failed")) == \
        policy.PERMANENT
    assert policy.classify(RuntimeError("scoped vmem exhausted")) == \
        policy.PERMANENT
    assert policy.classify(NotImplementedError("no lowering")) == \
        policy.PERMANENT
    assert policy.classify(ConnectionError("connection reset")) == \
        policy.TRANSIENT
    assert policy.classify(RuntimeError("anything else")) == policy.TRANSIENT
    assert policy.classify(chaos.ChaosResource("s", 1)) == policy.RESOURCE
    assert policy.classify(chaos.ChaosPermanent("s", 1)) == policy.PERMANENT


def test_retry_env_grammar(monkeypatch):
    """XGBTPU_RETRY mirrors XGBTPU_RETRACE_BUDGET: bare int or
    site=N,*=M."""
    monkeypatch.delenv("XGBTPU_RETRY", raising=False)
    assert policy.retry_budget("x") is None
    monkeypatch.setenv("XGBTPU_RETRY", "4")
    assert policy.retry_budget("x") == 4
    monkeypatch.setenv("XGBTPU_RETRY", "pager_io=2,*=1")
    assert policy.retry_budget("pager_io") == 2
    assert policy.retry_budget("other") == 1
    monkeypatch.setenv("XGBTPU_RETRY", "garbage=zz,pager_io=3")
    assert policy.retry_budget("pager_io") == 3
    assert policy.retry_budget("other") is None  # malformed parts skipped


def test_retry_policy_bounded_backoff_and_kinds(monkeypatch):
    monkeypatch.delenv("XGBTPU_RETRY", raising=False)
    sleeps = []
    p = RetryPolicy("site_a", retries=3, sleep=sleeps.append)
    n = [0]

    def flaky():
        n[0] += 1
        if n[0] < 3:
            raise RuntimeError("transient hiccup")
        return "ok"

    r0 = _counter("retries_total", site="site_a")
    assert p.run(flaky) == "ok"
    assert len(sleeps) == 2
    assert _counter("retries_total", site="site_a") - r0 == 2
    # deterministic jitter: same (site, attempt, seed) -> same backoff
    assert p.backoff(1) == RetryPolicy("site_a", seed=0).backoff(1)
    assert RetryPolicy("site_a", seed=1).backoff(1) != p.backoff(1)
    # non-retryable kind raises immediately
    p2 = RetryPolicy("site_a", retries=5, sleep=sleeps.append)
    calls = [0]

    def resource_fail():
        calls[0] += 1
        raise RuntimeError("RESOURCE_EXHAUSTED")

    with pytest.raises(RuntimeError):
        p2.run(resource_fail)
    assert calls[0] == 1  # no retry on resource kind
    # exhausted budget re-raises the original error
    with pytest.raises(ValueError):
        RetryPolicy("site_a", retries=1, sleep=lambda s: None).run(
            lambda: (_ for _ in ()).throw(ValueError("always")))


def test_retry_policy_env_overrides_and_records_faults(monkeypatch):
    monkeypatch.setenv("XGBTPU_RETRY", "site_b=0")
    calls = [0]

    def always():
        calls[0] += 1
        raise RuntimeError("transient")

    f0 = _counter("faults_total", site="site_b", kind="transient")
    with pytest.raises(RuntimeError):
        RetryPolicy("site_b", retries=9, sleep=lambda s: None).run(always)
    assert calls[0] == 1  # env budget 0 wins over ctor retries=9
    assert _counter("faults_total", site="site_b", kind="transient") > f0


def _counter(name, **labels):
    fam = REGISTRY.get(name)
    if fam is None:
        return 0.0
    return fam.labels(**labels).value if labels else fam.value


# --------------------------------------------------------------- degrade

def test_degrade_full_lifecycle_driven_by_chaos():
    """Every edge of HEALTHY -> DEGRADED(retry-after-N) -> DISABLED plus
    recovery, driven by a seeded chaos schedule at a synthetic site
    (acceptance criterion)."""
    cap = degrade.capability("lifecycle_cap", retry_after=2,
                             disable_after=3)

    def attempt():
        if not cap.allowed():
            return "fallback"
        try:
            chaos.hit("lifecycle_site")
            cap.success()
            return "ok"
        except chaos.ChaosError as e:
            cap.failure(e)
            return "failed"

    # schedule: hits 1 and 4 fail with a resource fault; rest succeed
    with chaos.configure("lifecycle_site:resource:1,4"):
        assert attempt() == "failed"                 # HEALTHY -> DEGRADED
        assert cap.state() == DEGRADED
        assert attempt() == "fallback"               # countdown 2 -> 1
        assert attempt() == "fallback"               # countdown expires
        assert cap.state() == HEALTHY
        assert attempt() == "ok"                     # probe (hit 2) works
        assert cap.state() == HEALTHY
        assert cap.snapshot()["entries"] == {}       # recovery cleared fails
        assert attempt() == "ok"                     # hit 3
        assert attempt() == "failed"                 # hit 4 -> DEGRADED
        assert cap.state() == DEGRADED
    # two more non-transient failures accumulate to disable_after=3
    cap.failure(kind=policy.RESOURCE)
    assert cap.state() == DEGRADED
    cap.failure(kind=policy.PERMANENT)
    assert cap.state() == DISABLED
    assert not cap.allowed()
    cap.success()  # success never resurrects DISABLED
    assert cap.state() == DISABLED
    assert 'degrade_state{capability="lifecycle_cap"} 2' in \
        REGISTRY.exposition()
    # only reset() clears terminal state
    cap.reset()
    assert cap.state() == HEALTHY and cap.allowed()


def test_degrade_transient_failures_never_change_state():
    cap = degrade.capability("transient_cap", retry_after=5)
    kind = cap.failure(RuntimeError("some hiccup"))
    assert kind == policy.TRANSIENT
    assert cap.state() == HEALTHY and cap.allowed()
    # but the fault is still counted
    assert _counter("faults_total", site="transient_cap",
                    kind="transient") >= 1


def test_degrade_keys_are_independent():
    cap = degrade.capability("keyed_cap", retry_after=1)
    cap.failure(RuntimeError("vmem"), key=("shape", 1))
    assert cap.worst_state() == DEGRADED
    assert not cap.allowed(("shape", 1))  # burns the 1-call countdown
    assert cap.allowed(("shape", 2))  # other keys unaffected
    assert cap.allowed(("shape", 1))  # countdown expired: probe allowed


def test_onehot_resource_failure_degrades_not_disables():
    """Review finding: temporary HBM pressure during the hoisted one-hot
    build must DEGRADE (later fits re-probe once memory frees), while a
    Mosaic reject (permanent, deterministic per runtime) still DISABLES
    for the process."""
    from xgboost_tpu.data.quantile import _onehot_health

    kind = _onehot_health.failure(RuntimeError("RESOURCE_EXHAUSTED: HBM"))
    assert kind == policy.RESOURCE
    assert _onehot_health.state() == DEGRADED  # not DISABLED
    assert not _onehot_health.allowed()  # this fit falls back...
    assert _onehot_health.allowed()  # ...the next fit probes again
    _onehot_health.success()
    # a compiler reject is terminal
    _onehot_health.failure(RuntimeError("Mosaic lowering failed"))
    assert _onehot_health.state() == DISABLED
    assert not _onehot_health.allowed()


def test_exposition_lists_every_registered_capability():
    """Acceptance: every capability's state is visible in
    REGISTRY.exposition() — including the package-owned ones registered
    at import, while HEALTHY."""
    degrade.capability("vis_cap")
    exp = REGISTRY.exposition()
    for name in ("vis_cap", "pallas_predict", "onehot_build"):
        assert f'degrade_state{{capability="{name}"}}' in exp, (name, exp)


# ----------------------------------------------------------------- chaos

def test_chaos_schedule_grammar():
    fired = []
    with chaos.configure("g:transient:2,5-6,9+,%4") as plan:
        for i in range(1, 13):
            try:
                chaos.hit("g")
                fired.append(0)
            except chaos.ChaosTransient:
                fired.append(1)
    # hits: 2 (exact), 4 (%4), 5,6 (range), 8 (%4), 9..12 (9+)
    assert fired == [0, 1, 0, 1, 1, 1, 0, 1, 1, 1, 1, 1]
    assert plan.hits("g") == 12


def test_chaos_probabilistic_schedule_is_seed_deterministic():
    def firings(seed):
        out = []
        with chaos.configure(f"p:transient:p0.4@{seed}"):
            for i in range(30):
                try:
                    chaos.hit("p")
                except chaos.ChaosError:
                    out.append(i)
        return out

    a, b = firings(11), firings(11)
    assert a == b and 0 < len(a) < 30  # deterministic, non-trivial
    assert firings(12) != a


def test_chaos_env_var_arms_and_rearms(monkeypatch):
    monkeypatch.setenv("XGBTPU_CHAOS", "envsite:permanent:1")
    chaos.reset()  # drop any cached plan
    with pytest.raises(chaos.ChaosPermanent):
        chaos.hit("envsite")
    chaos.hit("other_site")  # unscripted site: silent
    # flipping the env re-parses without reimport
    monkeypatch.setenv("XGBTPU_CHAOS", "envsite:resource:2")
    with pytest.raises(chaos.ChaosResource):
        chaos.hit("envsite")
        chaos.hit("envsite")
    monkeypatch.delenv("XGBTPU_CHAOS")
    chaos.hit("envsite")  # disarmed


def test_chaos_bad_config_raises():
    with pytest.raises(ValueError):
        chaos.ChaosPlan("site-only")
    with pytest.raises(ValueError):
        chaos.ChaosPlan("s:notakind:1")
    with pytest.raises(ValueError):
        chaos.ChaosPlan("s:transient:")


def test_chaos_drives_pallas_capability_degrade():
    """An injected permanent fault at the predictor's ``pallas`` site must
    walk the pallas_predict capability through the same degrade edge a
    real Mosaic reject would — without TPU hardware. (The TPU-only branch
    guard is bypassed by driving failure() with the chaos error, exactly
    what predict_margin's except path does.)"""
    from xgboost_tpu.predictor import _pallas_health

    key = ("chaos", "shape")
    with chaos.configure("pallas:permanent:1"):
        try:
            chaos.hit("pallas")
            raise AssertionError("chaos did not fire")
        except chaos.ChaosError as e:
            kind = _pallas_health.failure(e, key=key, retry_after=2)
    assert kind == policy.PERMANENT
    assert _pallas_health.state(key) == DEGRADED
    assert not _pallas_health.allowed(key)


def test_chaos_at_collective_site(monkeypatch):
    """comms.record is the collective choke point: a scripted fault there
    surfaces from the accounting path (the rabit-mock analog)."""
    from xgboost_tpu.observability import comms

    with chaos.configure("collective:transient:1"):
        with pytest.raises(chaos.ChaosTransient):
            comms.record("allreduce", 8)
        comms.record("allreduce", 8)  # second hit passes


def test_chaos_at_fault_inject_bridge():
    """utils/fault.py's per-round dispatch sites double as chaos sites:
    a grow-site schedule kills round dispatch without arming a spec."""
    from xgboost_tpu.utils import fault

    with chaos.configure("grow:transient:1"):
        with pytest.raises(chaos.ChaosTransient):
            fault.inject("grow")
        fault.inject("grow")  # exhausted
        fault.inject("gradient")  # other sites unscripted


def test_chaos_pager_io_retry_absorbs_transients(tmp_path, monkeypatch):
    """External-memory page reads retry transient IO faults under
    XGBTPU_RETRY: seeded chaos at pager_io must be absorbed and training
    must produce the same model as a chaos-free run."""
    from xgboost_tpu.data.iterator import DataIter

    rng = np.random.RandomState(0)
    X = rng.randn(600, 4).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)

    class It(DataIter):
        def __init__(self):
            super().__init__()
            self.i = 0

        def next(self, input_data):
            if self.i >= 3:
                return 0
            lo, hi = self.i * 200, (self.i + 1) * 200
            input_data(data=X[lo:hi], label=y[lo:hi])
            self.i += 1
            return 1

        def reset(self):
            self.i = 0

    params = {"objective": "binary:logistic", "max_depth": 3,
              "max_bin": 16, "verbosity": 0}

    def build_and_train(prefix):
        d = xgb.ExternalMemoryQuantileDMatrix(
            It(), cache_prefix=str(tmp_path / prefix), max_bin=16,
            page_rows=256)
        return xgb.train(params, d, 3, verbose_eval=False)

    monkeypatch.setenv("XGBTPU_RETRY", "pager_io=3")
    ref = build_and_train("ref")
    with chaos.configure("pager_io:transient:2,4,%5") as plan:
        got = build_and_train("chaos")
    assert plan.fired, "chaos never reached the pager"
    assert json.loads(got.save_raw()) == json.loads(ref.save_raw())


# ------------------------------------------------------------ checkpoint

class _FakeBooster:
    def __init__(self, blob: bytes):
        self._blob = blob

    def save_raw(self):
        return self._blob


def test_checkpoint_atomic_roundtrip_and_retention(tmp_path):
    d = str(tmp_path)
    for r in (1, 2, 3):
        checkpoint.save_checkpoint(d, _FakeBooster(b"m%d" % r), r)
    assert len(checkpoint.list_checkpoints(d)) == 2  # retain=2
    payload, rounds = checkpoint.load_latest(d)
    assert (payload, rounds) == (b"m3", 3)
    assert not [f for f in os.listdir(d) if f.endswith(".tmp")]


def test_checkpoint_detects_truncation_and_bitflips(tmp_path):
    """Acceptance: truncated AND bit-flipped checkpoints are detected and
    load falls back to the previous good snapshot."""
    d = str(tmp_path)
    checkpoint.save_checkpoint(d, _FakeBooster(b"good-old"), 1)
    checkpoint.save_checkpoint(d, _FakeBooster(b"good-new"), 2)
    p2 = checkpoint.checkpoint_path(d, 2)
    c0 = _counter("checkpoint_corrupt_total")
    # bit-flip inside the payload
    raw = bytearray(open(p2, "rb").read())
    raw[-3] ^= 0x10
    open(p2, "wb").write(bytes(raw))
    assert checkpoint.read_checkpoint(p2) is None
    assert checkpoint.load_latest(d) == (b"good-old", 1)
    # truncation (retain=3 keeps round 1 as the previous-good floor)
    checkpoint.save_checkpoint(d, _FakeBooster(b"good-newer"), 3, retain=3)
    p3 = checkpoint.checkpoint_path(d, 3)
    with open(p3, "r+b") as f:
        f.seek(0, 2)
        f.truncate(f.tell() - 4)
    assert checkpoint.load_latest(d) == (b"good-old", 1)
    assert _counter("checkpoint_corrupt_total") > c0
    # garbage header
    open(p3, "wb").write(b"not a checkpoint at all")
    assert checkpoint.read_checkpoint(p3) is None


def test_checkpoint_write_chaos_is_retried(tmp_path, monkeypatch):
    monkeypatch.setenv("XGBTPU_RETRY", "checkpoint_write=3")
    d = str(tmp_path)
    with chaos.configure("checkpoint_write:transient:1,2") as plan:
        checkpoint.save_checkpoint(d, _FakeBooster(b"x"), 1)
    assert len(plan.fired) == 2
    assert checkpoint.load_latest(d) == (b"x", 1)
    # budget exhausted -> the fault surfaces
    monkeypatch.setenv("XGBTPU_RETRY", "checkpoint_write=0")
    with chaos.configure("checkpoint_write:transient:1"):
        with pytest.raises(chaos.ChaosTransient):
            checkpoint.save_checkpoint(d, _FakeBooster(b"y"), 2)
    # and the atomic contract held: no torn round-2 file, round 1 intact
    assert checkpoint.load_latest(d) == (b"x", 1)


# -------------------------------------------------------------- watchdog

def test_watchdog_times_out_and_is_observable():
    t0 = time.time()
    cb = []
    with pytest.raises(WatchdogTimeout) as ei:
        with watchdog.watchdog("wd_site", 0.3,
                               on_timeout=lambda: cb.append(1)):
            for _ in range(200):
                time.sleep(0.05)
    assert ei.value.site == "wd_site"
    assert cb == [1]
    assert time.time() - t0 < 3
    assert _counter("watchdog_timeouts_total", site="wd_site") >= 1


def test_watchdog_noop_cases(monkeypatch):
    with watchdog.watchdog("wd_site", 10.0):
        pass  # completes under deadline: nothing raised
    with watchdog.watchdog("wd_site", None):  # env unset -> disabled
        time.sleep(0.01)
    monkeypatch.setenv("XGBTPU_WATCHDOG", "wd2=0.2,*=9")
    assert watchdog.deadline_for("wd2") == 0.2
    assert watchdog.deadline_for("other") == 9
    monkeypatch.setenv("XGBTPU_WATCHDOG", "0")
    with watchdog.watchdog("wd_site"):  # <= 0 disables
        time.sleep(0.01)


def test_train_watchdog_aborts_and_checkpoints(tmp_path, monkeypatch):
    """ISSUE 5 tentpole + ISSUE 20 containment: a PERSISTENTLY wedged
    per-round dispatch is retried under the native-dispatch policy
    (3 watchdog expiries), then aborts cleanly — the contained fault
    surfaces with WatchdogTimeout as its original AND the committed
    rounds land in an atomic checkpoint — instead of hanging the run."""
    from xgboost_tpu.native.boundary import NativeFault

    rng = np.random.RandomState(0)
    X = rng.randn(400, 4).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    d = xgb.DMatrix(X, label=y)
    params = {"objective": "binary:logistic", "max_depth": 2,
              "max_bin": 16, "verbosity": 0}

    # warm the jit caches first: the deadline must measure DISPATCH, not
    # the first-round XLA:CPU compile (which legitimately takes seconds)
    xgb.train(params, xgb.DMatrix(X, label=y), 1, verbose_eval=False)

    from xgboost_tpu.learner import Booster

    orig_update = Booster.update
    calls = [0]

    def wedge_from_third_round(self, dtrain, iteration, fobj=None):
        calls[0] += 1
        if calls[0] >= 3:  # simulate the wedged dispatch — every retry
            for _ in range(600):  # of round 3 wedges again
                time.sleep(0.05)
        return orig_update(self, dtrain, iteration, fobj)

    monkeypatch.setattr(Booster, "update", wedge_from_third_round)
    monkeypatch.setenv("XGBTPU_WATCHDOG", "round_dispatch=5")
    ck = str(tmp_path / "wd_ck")
    t0 = time.time()
    with pytest.raises((NativeFault, WatchdogTimeout)) as ei:
        xgb.train(params, d, 6, verbose_eval=False, resume_from=ck)
    if isinstance(ei.value, NativeFault):  # contained (native route live)
        assert isinstance(ei.value.original, WatchdogTimeout)
    assert time.time() - t0 < 45  # ≤ 3 deadlines + backoff, not 30s wedge
    # the 2 committed rounds were checkpointed on the abort path
    got = checkpoint.load_latest(ck)
    assert got is not None and got[1] == 2
    # and a rerun resumes from them (watchdog off now)
    monkeypatch.delenv("XGBTPU_WATCHDOG")
    monkeypatch.setattr(Booster, "update", orig_update)
    bst = xgb.train(params, d, 6, verbose_eval=False, resume_from=ck)
    assert bst.num_boosted_rounds() == 6
