"""Elastic multi-host training: fault-tolerant collectives, membership
resize, checkpoint-replay recovery (ISSUE 6 tentpole).

Reference analog: rabit's mock-engine recovery tests
(``rabit/src/allreduce_mock.h`` — kill a worker at a scripted point,
prove the job completes from the last checkpoint) lifted to whole-process
SIGKILL under the JAX runtime: a 2-process CPU (gloo) run loses a worker
mid-round, the survivor quiesces at the round boundary, resizes the
world to one, re-shards rows through the ``data_fn`` (load_row_split)
contract, and replays from the newest verified checkpoint — with the
result proven BIT-IDENTICAL to uninterrupted training at the final
world size (canonical-cuts binning makes the quantization
sharding-invariant; block sharding keeps the global row order)."""

import json
import os
import signal
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "elastic_worker.py")

# must mirror tests/elastic_worker.py
N, F = 2400, 5
PARAMS = {"objective": "binary:logistic", "max_depth": 3, "eta": 0.3,
          "max_bin": 16, "seed": 7, "verbosity": 0}


def _data():
    rng = np.random.RandomState(0)
    X = rng.randn(N, F).astype(np.float32)
    w = rng.randn(F)
    y = ((X @ w) + 0.5 * rng.randn(N) > 0).astype(np.float32)
    return X, y


def _free_port() -> int:
    s = socket.socket()
    s.bind(("localhost", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def _run_elastic_pair(tmp_path, kill_hit: int, rounds: int = 6,
                      timeout: int = 420):
    """Launch the 2-worker elastic run with ``worker_kill`` armed on
    rank 1 at its ``kill_hit``-th round boundary; wait for both. Returns
    (rank0 returncode, rank1 returncode, outputs)."""
    port = _free_port()
    outdir = str(tmp_path)
    envs = []
    for r in (0, 1):
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        if r == 1:
            env["XGBTPU_CHAOS"] = f"worker_kill:permanent:{kill_hit}"
        envs.append(env)
    procs = [
        subprocess.Popen(
            [sys.executable, WORKER, str(r), str(port), outdir,
             str(rounds)],
            cwd=REPO, env=envs[r], stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)
        for r in (0, 1)
    ]
    outs = []
    try:
        for p in procs:
            outs.append(p.communicate(timeout=timeout)[0])
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    return procs[0].returncode, procs[1].returncode, outs


def _train_reference(rounds: int, xgb_model=None):
    import xgboost_tpu as xgb

    X, y = _data()
    return xgb.train(PARAMS, xgb.DMatrix(X, label=y), rounds,
                     xgb_model=xgb_model, verbose_eval=False)


def _model_json(bst):
    import tempfile

    p = tempfile.mktemp(suffix=".json")
    bst.save_model(p)
    try:
        with open(p) as f:
            return json.load(f)
    finally:
        os.unlink(p)


def test_elastic_sigkill_midrun_resize_and_replay(tmp_path):
    """The tier-1 elastic case: rank 1 is SIGKILLed at its round-2
    boundary (rank 0 is mid-collective for round 2 when the peer dies).
    The survivor must detect the loss, quiesce, resize 2 -> 1, re-shard
    to the full dataset and replay from the newest verified checkpoint
    to all 6 rounds — and every post-resize round must be bit-identical
    to an uninterrupted single-worker continuation from the preserved
    quiesce snapshot (round-for-round equivalence at the final world
    size). The elastic metrics must be in the exposition."""
    rc0, rc1, outs = _run_elastic_pair(tmp_path, kill_hit=3)
    assert rc1 == -signal.SIGKILL, f"rank1 was not SIGKILLed:\n{outs[1]}"
    assert rc0 == 0, f"survivor failed:\n{outs[0][-4000:]}"

    meta = json.loads((tmp_path / "meta_rank0.json").read_text())
    assert meta["rounds"] == 6

    # the preserved quiesce snapshot is what the resize replayed from
    qdir = tmp_path / "quiesce"
    qfiles = sorted(os.listdir(qdir))
    assert qfiles, "resize must preserve its quiesce checkpoint"
    from xgboost_tpu.resilience.checkpoint import read_checkpoint

    raw, done = read_checkpoint(str(qdir / qfiles[0]))
    assert 0 < done < 6, done

    # round-for-round: a clean single-worker continuation from the same
    # snapshot over the same final sharding (full data, canonical cuts)
    # must produce the identical final model, bit for bit
    ref = _model_json(_train_reference(6 - done, xgb_model=bytes(raw)))
    elastic = json.loads((tmp_path / "model_rank0.json").read_text())
    assert ref == elastic, \
        "elastic recovery diverged from the uninterrupted continuation"

    # elastic telemetry (satellite: exported through the registry)
    prom = (tmp_path / "metrics_rank0.prom").read_text()
    assert "membership_changes_total 1" in prom
    assert "worker_restarts_total 1" in prom
    assert "elastic_resume_rounds_replayed" in prom
    assert 'worker_alive{rank="0"} 1' in prom
    assert 'worker_alive{rank="1"} 0' in prom
    assert 'faults_total' in prom


@pytest.mark.slow
def test_elastic_kill_before_first_checkpoint_clean_identity(tmp_path):
    """Full-matrix variant: the worker dies before ANY checkpoint commits
    (round-0 boundary), so recovery replays from scratch at world 1 —
    and the result must be bit-identical to a COMPLETELY clean
    single-worker run on the same final sharding (the canonical-cuts
    binning is what makes this exact; without it the shard-dependent
    sketch would already differ in the cut values)."""
    rc0, rc1, outs = _run_elastic_pair(tmp_path, kill_hit=1)
    assert rc1 == -signal.SIGKILL
    assert rc0 == 0, f"survivor failed:\n{outs[0][-4000:]}"
    ref = _model_json(_train_reference(6))
    elastic = json.loads((tmp_path / "model_rank0.json").read_text())
    assert ref == elastic, \
        "elastic from-scratch recovery diverged from a clean run"


@pytest.mark.slow
def test_elastic_three_to_two_reexec_resize(tmp_path):
    """Full-matrix variant: a 3-worker world loses one worker; the TWO
    survivors agree on the new membership, re-execute themselves
    (world > 1 cannot re-form the runtime in-process), rendezvous on the
    generation-1 coordinator port, and finish as a 2-worker world with
    bit-identical models."""
    port = _free_port()
    outdir = str(tmp_path)
    procs = []
    for r in (0, 1, 2):
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        env["XGBTPU_HEARTBEAT"] = "1.0"
        env["XGBTPU_HEARTBEAT_DEADLINE"] = "12"
        if r == 2:
            env["XGBTPU_CHAOS"] = "worker_kill:permanent:2"
        procs.append(subprocess.Popen(
            [sys.executable, WORKER, str(r), str(port), outdir, "6", "3"],
            cwd=REPO, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True))
    outs = []
    try:
        for p in procs:
            outs.append(p.communicate(timeout=420)[0])
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    assert procs[2].returncode == -signal.SIGKILL
    for r in (0, 1):
        assert procs[r].returncode == 0, \
            f"survivor {r} failed:\n{outs[r][-4000:]}"
        assert "re-executing worker for generation 1" in outs[r]
    m0 = json.loads((tmp_path / "model_rank0.json").read_text())
    m1 = json.loads((tmp_path / "model_rank1.json").read_text())
    assert m0 == m1, "re-formed world produced divergent models"
    assert json.loads(
        (tmp_path / "meta_rank0.json").read_text())["rounds"] == 6


def test_chaos_schedule_determinism_across_processes(tmp_path):
    """Seeded chaos schedules must fire at IDENTICAL hits in every
    process (the contract the elastic kill/drop scripting depends on):
    two separate interpreters arm the same ``%K`` and ``pP@seed``
    schedules and record which of 60 hits fire — the traces must match
    exactly, and the probabilistic one must be seed-deterministic, not
    RNG-state-dependent. The ISSUE 20 native-boundary sites ride the same
    contract with their crash/timeout/corrupt modes: the mode must arrive
    on the error (``chaos_mode``) at exactly the same hits too, or the
    canary/dispatch drills would diverge between trainer processes."""
    prog = r"""
import json, sys
from xgboost_tpu.resilience import chaos
from xgboost_tpu.resilience.chaos import ChaosError
fired = {}
sched = ("tick:transient:%7;tock:transient:p0.3@42;"
         "native_canary:crash:%11;native_dispatch:corrupt:p0.25@7")
with chaos.configure(sched) as plan:
    for site in ("tick", "tock", "native_canary", "native_dispatch"):
        hits = []
        for n in range(1, 61):
            try:
                chaos.hit(site)
            except ChaosError as e:
                hits.append([n, getattr(e, "chaos_mode", "")])
        fired[site] = hits
print(json.dumps(fired))
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONHASHSEED"] = "0"
    results = []
    for seed_env in ("1", "2"):  # different hash seeds: no accidental
        env["PYTHONHASHSEED"] = seed_env  # dependence on interpreter state
        out = subprocess.run(
            [sys.executable, "-c", prog], env=env, cwd=REPO,
            capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr[-2000:]
        results.append(json.loads(out.stdout))
    assert results[0] == results[1], \
        "seeded chaos schedules diverged across processes"
    assert results[0]["tick"] == [[n, ""] for n in
                                  (7, 14, 21, 28, 35, 42, 49, 56)]
    assert results[0]["tock"], "p0.3@42 fired nowhere in 60 hits"
    assert len(results[0]["tock"]) < 60
    assert results[0]["native_canary"] == [[n, "crash"] for n in
                                           (11, 22, 33, 44, 55)]
    nd = results[0]["native_dispatch"]
    assert nd and len(nd) < 60, "p0.25@7 corrupt fired never/always"
    assert {mode for _, mode in nd} == {"corrupt"}


def test_membership_detection_and_heartbeat_drop(tmp_path, monkeypatch):
    """Membership unit contract: (a) a couple of chaos-dropped beats is
    jitter, not death (deadline = 5x interval); (b) sustained silence —
    the worker process dying, here via its agent being stopped — is
    detected within one deadline; (c) a tombstone fences the named rank.
    Heartbeats come from an agent SUBPROCESS (env-armed chaos applies in
    the agent), so beats survive GIL-holding collective stalls and stop
    only with the worker itself."""
    monkeypatch.setenv("XGBTPU_HEARTBEAT", "0.2")
    # (a): both agents drop beats 2-3 (a 0.4s gap, under the 1s deadline)
    monkeypatch.setenv("XGBTPU_CHAOS", "heartbeat_drop:transient:2-3")
    from xgboost_tpu.parallel.membership import Membership, hb_deadline

    d = str(tmp_path / "members")
    m0 = Membership(d, 0, [0, 1]).start()
    m1 = Membership(d, 1, [0, 1]).start()
    try:
        time.sleep(0.7)  # spans the dropped-beat window
        assert m0.scan() == [], "dropped beats below deadline killed a peer"

        # (b) rank 1's beats stop entirely: dead within one deadline
        m1.stop()
        t0 = time.monotonic()
        while m0.scan() == [] and time.monotonic() - t0 < 8.0:
            time.sleep(0.05)
        took = time.monotonic() - t0
        assert m0.dead_ranks() == [1]
        assert took < hb_deadline() + 2.0, \
            f"detection took {took:.2f}s, deadline {hb_deadline():.2f}s"

        # (c) fencing: a tombstone against rank 0 flips its fenced flag
        m1.declare_dead(0)
        m0.scan()
        assert m0.fenced
    finally:
        m0.stop()
        m1.stop()


def test_guarded_collective_classification():
    """The guarded entry point must classify and wrap failures instead of
    leaking raw RuntimeError: a peer-death signature sets worker_lost, a
    scripted ``collective_timeout`` presents as a transient fault at the
    site, and the retry budget (XGBTPU_RETRY) is honored."""
    from xgboost_tpu import collective
    from xgboost_tpu.observability.metrics import REGISTRY
    from xgboost_tpu.resilience import chaos

    def dead_peer():
        raise RuntimeError(
            "Gloo all-reduce failed: Connection closed by peer")

    with pytest.raises(collective.CollectiveError) as ei:
        collective.guarded("unit_dead", dead_peer)
    assert ei.value.worker_lost
    assert ei.value.kind == "transient"
    exp = REGISTRY.exposition()
    assert 'faults_total' in exp and "collective_unit_dead" in exp

    # scripted timeout: one injected expiry, absorbed by one env retry
    calls = {"n": 0}

    def ok():
        calls["n"] += 1
        return 42

    import os as _os
    _os.environ["XGBTPU_RETRY"] = "collective_unit_to=1"
    try:
        with chaos.configure("collective_timeout:transient:1"):
            assert collective.guarded("unit_to", ok) == 42
    finally:
        del _os.environ["XGBTPU_RETRY"]
    assert calls["n"] == 1  # first attempt died at injection, retry ran

    # without a retry budget the scripted timeout surfaces, typed
    with chaos.configure("collective_timeout:transient:1"):
        with pytest.raises(collective.CollectiveError) as ei:
            collective.guarded("unit_to2", ok)
    assert ei.value.kind == "transient"


def test_checkpoint_inspect_cli(tmp_path, capsys):
    """checkpoint-inspect lists rounds/size/verify status and marks the
    newest verified snapshot, surviving a corrupt newest file. Driven
    through the CLI dispatch in-process (a fresh interpreter per
    invocation would pay the package import twice for no coverage)."""
    import xgboost_tpu as xgb
    from xgboost_tpu.cli import cli_main

    X, y = _data()
    ck = str(tmp_path / "ck")
    xgb.train(PARAMS, xgb.DMatrix(X[:400], label=y[:400]), 3,
              verbose_eval=False, resume_from=ck)
    # corrupt the newest checkpoint: the previous good one must be marked
    from xgboost_tpu.resilience.checkpoint import list_checkpoints

    newest = list_checkpoints(ck)[-1]
    with open(newest, "r+b") as f:
        f.seek(-1, os.SEEK_END)
        f.write(b"\x00")
    assert cli_main(["checkpoint-inspect", ck]) == 0
    lines = capsys.readouterr().out.splitlines()
    assert any("CORRUPT" in ln and "ckpt_00000003" in ln for ln in lines)
    assert any(ln.startswith("*") and "ckpt_00000002" in ln
               and "verified" in ln for ln in lines)

    # an empty directory reports failure (nothing to resume from)
    assert cli_main(["checkpoint-inspect", str(tmp_path / "nothing")]) == 1
