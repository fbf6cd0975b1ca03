"""ISSUE 13: the async pipelined training executor, buffer donation, the
fused depth scan, the native FFI histogram and the quantized collective
reduction. One shared tiny dataset keeps the XLA:CPU compile budget at a
handful of programs for the whole file (single-core tier-1 budget)."""

import json
import os
import signal
import subprocess
import sys

import numpy as np
import pytest

import xgboost_tpu as xgb
from xgboost_tpu import collective
from xgboost_tpu.pipeline import RoundPipeline, completion_probe

# 2048 = the kernel row tile: n_pad == n, so the scan path's donated
# margin IS the caller's buffer (the donation test pins exactly that)
N, F = 2048, 6
PARAMS = {"objective": "binary:logistic", "max_depth": 3, "max_bin": 16,
          "verbosity": 0, "seed": 3}


def _data():
    rng = np.random.RandomState(0)
    X = rng.randn(N, F).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    return X, y


def _train_raw(X, y, rounds=5, per_round=False, **params):
    d = xgb.DMatrix(X, label=y)
    b = xgb.Booster({**PARAMS, **params}, [d])
    if per_round:
        for i in range(rounds):
            b.update(d, i)
    else:
        b.update_many(d, 0, rounds, chunk=2)
    return b.save_raw()


# ---------------------------------------------------------------------------
# pipeline executor
# ---------------------------------------------------------------------------


def test_pipeline_depth_determinism(monkeypatch):
    """Async depth 0 (sync) vs 1 vs 2 must produce bit-identical models on
    BOTH the per-round and the chunked-scan paths: the pipeline only
    changes WHEN the host waits, never what the device computes."""
    X, y = _data()
    for per_round in (False, True):
        models = []
        for depth in ("0", "1", "2"):
            monkeypatch.setenv("XGBTPU_PIPELINE_DEPTH", depth)
            models.append(_train_raw(X, y, per_round=per_round))
        assert models[0] == models[1] == models[2], \
            f"pipeline depth changed the model (per_round={per_round})"


def test_pipeline_bounds_inflight_and_drains():
    pipe = RoundPipeline(depth=2)
    import jax.numpy as jnp

    for i in range(6):
        pipe.admit(i, jnp.ones((4,)) * i)
        assert len(pipe) <= 2
    pipe.drain()
    assert len(pipe) == 0


def test_pipeline_attributes_async_fault():
    """A handle that fails at the sync point surfaces with the originating
    round attributed on the exception and in the flight event stream."""
    from xgboost_tpu.observability import flight

    class _Boom:
        def block_until_ready(self):
            raise RuntimeError("injected async fault")

    pipe = RoundPipeline(depth=1)
    pipe.admit(7, _Boom())
    with pytest.raises(RuntimeError) as ei:
        pipe.admit(8, _Boom())  # exceeds depth -> syncs round 7
    assert ei.value.pipeline_round == 7
    ev = [r for r in flight.RECORDER.records()
          if r.get("t") == "event" and r.get("name") == "pipeline_fault"]
    assert ev and ev[-1]["args"]["round"] == 7


def test_completion_probe_survives_donation():
    """The probe admits readiness handles that stay valid after the
    producing buffer is donated into the next round's program (the margin
    chain)."""
    import jax.numpy as jnp
    from xgboost_tpu.analysis.retrace import guard_jit

    step = guard_jit(lambda m: m + 1.0, name="_probe_test_step",
                     donate_argnames=("m",))
    m = jnp.ones((64, 1))
    probes = []
    for _ in range(4):
        probes.append(completion_probe(m))
        m = step(m)  # donates the previous buffer
    pipe = RoundPipeline(depth=0)
    for i, p in enumerate(probes):
        pipe.admit(i, p)  # depth 0: blocks immediately; must not raise
    assert float(m[0, 0]) == 5.0


# ---------------------------------------------------------------------------
# donation
# ---------------------------------------------------------------------------


def test_margin_donation_keeps_live_buffers_flat():
    """The scan path's carried margin is donated: the previous chunk's
    buffer is DELETED (reused in place), so the per-round live-buffer
    watermark stays flat instead of growing one [n, K] margin per chunk."""
    X, y = _data()
    d = xgb.DMatrix(X, label=y)
    b = xgb.Booster(dict(PARAMS), [d])
    b.update_many(d, 0, 2, chunk=2)
    entry = b._caches[id(d)]
    old = entry.margin
    b.update_many(d, 2, 2, chunk=2)
    assert old.is_deleted(), "chunk margin was not donated"
    # per-round path: the margin-add donates the previous cache buffer
    d2 = xgb.DMatrix(X, label=y)
    b2 = xgb.Booster(dict(PARAMS), [d2])
    b2.update(d2, 0)
    old2 = b2._caches[id(d2)].margin
    b2.update(d2, 1)
    assert old2.is_deleted(), "per-round margin was not donated"


# ---------------------------------------------------------------------------
# native FFI histogram + fused depth scan
# ---------------------------------------------------------------------------


def test_native_hist_matches_xla(monkeypatch):
    """The native FFI kernel computes the exact segment_sum result — the
    standalone level output is bit-identical to ``fused_level_xla`` — and
    full training through it agrees with the XLA path to the established
    cross-program tolerance (inside a compiled program XLA fuses the
    scatter with downstream reductions, so low-bit rounding can tie-flip
    a near-equal split; each path is itself deterministic)."""
    import jax
    import jax.numpy as jnp

    from xgboost_tpu.tree.hist_kernel import (
        fused_level_native,
        fused_level_xla,
        use_native_hist,
    )

    if not use_native_hist():
        pytest.skip("native hist kernel unavailable on this toolchain")

    # exact level-kernel equivalence, missing values included
    rng = np.random.RandomState(1)
    B, K, d = 16, 4, 2
    bins = jnp.asarray(rng.randint(0, B + 1, (1024, F)).astype(np.uint8))
    pos = jnp.asarray(
        (1 + rng.randint(0, 2, 1024))[None, :].astype(np.int32))
    gh = jnp.asarray(rng.randn(2, 1024).astype(np.float32))
    ptab = np.zeros((2, 4), np.float32)
    ptab[:, 0] = 1
    ptab[:, 1] = rng.randint(0, F, 2)
    ptab[:, 2] = rng.randint(0, B, 2)
    ptab = jnp.asarray(ptab)
    pn, hn = fused_level_native(bins, pos, gh, ptab, K=K, Kp=2, B=B, d=d)
    px, hx = fused_level_xla(bins, pos, gh, ptab, K=K, Kp=2, B=B, d=d)
    assert np.array_equal(np.asarray(pn), np.asarray(px))
    assert np.array_equal(np.asarray(hn), np.asarray(hx))

    # end-to-end agreement at the cross-program tolerance
    X = rng.randn(N, F).astype(np.float32)
    X[rng.rand(N, F) < 0.1] = np.nan
    y = (np.nan_to_num(X[:, 0]) > 0).astype(np.float32)

    def _preds():
        dm = xgb.DMatrix(X, label=y)
        b = xgb.Booster(dict(PARAMS), [dm])
        b.update_many(dm, 0, 3, chunk=3)
        return np.asarray(b.predict(xgb.DMatrix(X[:500])))

    p_native = _preds()
    monkeypatch.setenv("XGBTPU_NATIVE_HIST", "0")
    jax.clear_caches()
    p_xla = _preds()
    np.testing.assert_allclose(p_native, p_xla, rtol=1e-4, atol=1e-4)


def test_depth_scan_bit_identical_to_unrolled(monkeypatch):
    """The fused depth scan (one lax.scan over levels at fixed width) and
    the unrolled level loop grow bit-identical trees — the spill-lane
    self-masking argument, pinned."""
    X, y = _data()
    scanned = _train_raw(X, y, rounds=3, per_round=True, max_depth=5)
    monkeypatch.setenv("XGBTPU_DEPTH_SCAN", "0")
    import jax

    jax.clear_caches()
    unrolled = _train_raw(X, y, rounds=3, per_round=True, max_depth=5)
    assert scanned == unrolled


def test_narrow_bins_reach_the_grower():
    """The quantized matrix stays in its narrow storage dtype on the
    non-pallas path (the int8 packing half: no widened int32 copy)."""
    X, y = _data()
    d = xgb.DMatrix(X, label=y)
    binned = d.get_binned(16)
    bins, _ = binned.fused_bins()
    assert bins.dtype == np.uint8
    binned256 = d.get_binned(256)
    bins256, _ = binned256.fused_bins()
    assert bins256.dtype == np.uint16  # missing bin == 256 needs 16 bits


# ---------------------------------------------------------------------------
# quantized collective reduction
# ---------------------------------------------------------------------------


def test_reduce_histogram_exact_requantization():
    """Count-valued and fixed-point-valued f32 histograms take the int16
    wire and come back as the EXACT sum; arbitrary f32 falls back to full
    precision unchanged; integer payloads narrow losslessly. (P=1 here:
    the wire plan + requantization round-trip is what is being pinned —
    the multichip dryrun records the byte ratio.)"""
    rng = np.random.RandomState(0)
    counts = rng.randint(0, 3000, (4, 8, 16)).astype(np.float32)
    out = collective.reduce_histogram(counts, site="unit_counts")
    assert out.dtype == np.float32 and np.array_equal(out, counts)

    fixed = (rng.randint(-2000, 2000, (64,)) * 0.25).astype(np.float32)
    out = collective.reduce_histogram(fixed, site="unit_fixed")
    assert np.array_equal(out, fixed)

    arbitrary = rng.randn(256).astype(np.float32)
    out = collective.reduce_histogram(arbitrary, site="unit_arb")
    assert np.array_equal(out, arbitrary)  # full-precision fallback

    ints = rng.randint(0, 1000, (128,)).astype(np.int64)
    out = collective.reduce_histogram(ints, site="unit_int")
    assert out.dtype == np.int64 and np.array_equal(out, ints)

    zeros = np.zeros((32,), np.float32)
    assert np.array_equal(
        collective.reduce_histogram(zeros, site="unit_zero"), zeros)


def test_reduce_histogram_prequantized_scale():
    """The ISSUE 19 wire path: ``scale=`` marks an already-quantized
    integer payload (the quant engine's fixed-point lanes on the shared
    per-round grid). No grid detection, no requantization round-trip —
    the integers ship as-is, the sum runs in int64, and ONE dequantizing
    multiply at the end yields f32. Exact even where the generic f32
    path would be ineligible (magnitudes past the int16 window)."""
    rng = np.random.RandomState(3)
    E = 18
    q = rng.randint(-(1 << 20), 1 << 20, (8, 4, 16)).astype(np.int32)
    out = collective.reduce_histogram(q, site="unit_preq",
                                      scale=2.0 ** -E)
    assert out.dtype == np.float32
    ref = (q.astype(np.float64) * 2.0 ** -E).astype(np.float32)
    assert np.array_equal(out, ref)

    # int64 lanes (the engine's merge dtype) take the same path
    q64 = q.astype(np.int64) * 3
    out64 = collective.reduce_histogram(q64, site="unit_preq64",
                                        scale=2.0 ** -E)
    ref64 = (q64.astype(np.float64) * 2.0 ** -E).astype(np.float32)
    assert out64.dtype == np.float32 and np.array_equal(out64, ref64)

    # a float payload with scale= is a contract violation, not a silent
    # requantization
    with pytest.raises(TypeError, match="integer payload"):
        collective.reduce_histogram(
            q.astype(np.float32), site="unit_preq_bad", scale=2.0 ** -E)


def test_reduce_histogram_wire_narrows_bytes():
    """The accounted collective bytes for an eligible payload are the
    NARROW wire bytes (int16), not the naive f32 size."""
    from xgboost_tpu.observability.metrics import REGISTRY

    def total():
        fam = REGISTRY.get("collective_bytes_total")
        return 0.0 if fam is None else sum(
            c.value for _, c in fam.series())

    counts = np.arange(4096, dtype=np.float32) % 1000
    b0 = total()
    collective.reduce_histogram(counts, site="unit_bytes")
    wire = total() - b0
    assert wire < counts.nbytes, (wire, counts.nbytes)


# ---------------------------------------------------------------------------
# SIGKILL mid-pipelined-round (slow lane: fresh-interpreter subprocess)
# ---------------------------------------------------------------------------

_KILL_SCRIPT = r"""
import os, signal, sys
import numpy as np
import xgboost_tpu as xgb
from xgboost_tpu.callback import TrainingCallback

run_dir, ck = sys.argv[1], sys.argv[2]

class KillAt(TrainingCallback):
    def after_iteration(self, model, epoch, evals_log):
        if epoch == 3:
            os.kill(os.getpid(), signal.SIGKILL)
        return False

rng = np.random.RandomState(0)
X = rng.randn(2048, 6).astype(np.float32)
y = (X[:, 0] > 0).astype(np.float32)
os.environ["XGBTPU_FLIGHT"] = run_dir
xgb.train({"objective": "binary:logistic", "max_depth": 3, "max_bin": 16,
           "verbosity": 0, "seed": 3}, xgb.DMatrix(X, label=y), 6,
          verbose_eval=False, resume_from=ck, checkpoint_interval=1,
          callbacks=[KillAt()])
print("COMPLETED")
"""


@pytest.mark.slow
def test_sigkill_mid_pipelined_round_recovers(tmp_path):
    """SIGKILL while pipelined rounds are in flight: flight.jsonl stays
    parseable line-wise, and resuming from the committed checkpoints
    produces a model bit-identical to an uninterrupted run."""
    script = tmp_path / "killrun.py"
    script.write_text(_KILL_SCRIPT)
    run_dir, ck = str(tmp_path / "obs"), str(tmp_path / "ck")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", XGBTPU_PIPELINE_DEPTH="2",
               PYTHONPATH=repo + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""))
    r = subprocess.run([sys.executable, str(script), run_dir, ck],
                       capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode == -signal.SIGKILL, r.stderr[-2000:]

    flight_path = os.path.join(run_dir, "obs", "rank0", "flight.jsonl")
    assert os.path.exists(flight_path)
    rounds = []
    with open(flight_path) as f:
        for line in f:
            rec = json.loads(line)  # every line parseable
            if rec.get("t") == "round":
                rounds.append(rec["round"])
    assert rounds, "no round records survived the SIGKILL"

    # resume completes and matches a clean 6-round run bit for bit
    X, y = _data()
    bst = xgb.train(dict(PARAMS), xgb.DMatrix(X, label=y), 6,
                    verbose_eval=False, resume_from=ck,
                    checkpoint_interval=1)
    clean = xgb.train(dict(PARAMS), xgb.DMatrix(X, label=y), 6,
                      verbose_eval=False)
    assert bst.num_boosted_rounds() == 6
    assert bst.save_raw() == clean.save_raw()
