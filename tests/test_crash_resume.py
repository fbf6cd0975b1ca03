"""Crash-safe resume (ISSUE 5 tentpole + satellite): SIGKILL a training
run mid-round, resume from the atomic checkpoint directory by rerunning
the SAME command, and prove the final model is byte-identical to an
uninterrupted run — single-process and 2-process-distributed (the
reference's rabit-mock recovery contract, ``allreduce_mock.h`` +
``test_fault_tolerance``)."""

import json
import os
import signal
import socket
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    s = socket.socket()
    s.bind(("localhost", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def _env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


# Worker: trains ROUNDS rounds with per-round atomic checkpointing. When
# KILL_AFTER is set, a user callback SIGKILLs the process right after
# that round's after_iteration — i.e. AFTER the round committed but
# BEFORE its checkpoint is written (user callbacks run first), so the
# resume genuinely starts from the previous round's checkpoint: the
# mid-round-kill shape that ended bench round 5.
_WORKER = r"""
import os, signal, sys
os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np
import xgboost_tpu as xgb
from xgboost_tpu.callback import TrainingCallback

ckdir = sys.argv[1]
out = sys.argv[2]
kill_after = int(os.environ.get("KILL_AFTER", "0"))
ROUNDS = 6

rng = np.random.RandomState(0)
X = rng.randn(2000, 5).astype(np.float32)
w = rng.randn(5)
y = ((X @ w) + 0.5 * rng.randn(2000) > 0).astype(np.float32)
d = xgb.DMatrix(X, label=y)
params = {"objective": "binary:logistic", "max_depth": 3, "max_bin": 16,
          "eta": 0.3, "seed": 11, "verbosity": 0}


class Killer(TrainingCallback):
    def __init__(self):
        self.rounds = 0

    def after_iteration(self, model, epoch, evals_log):
        self.rounds += 1
        if kill_after and self.rounds == kill_after:
            os.kill(os.getpid(), signal.SIGKILL)  # no cleanup, no atexit
        return False


bst = xgb.train(params, d, ROUNDS, verbose_eval=False, resume_from=ckdir,
                callbacks=[Killer()], checkpoint_interval=1)
bst.save_model(out)
print("done", bst.num_boosted_rounds(), flush=True)
"""


def test_sigkill_resume_equivalence_single_process(tmp_path):
    worker = tmp_path / "worker.py"
    worker.write_text(_WORKER)
    ckdir = str(tmp_path / "ck")
    out = str(tmp_path / "model.json")

    # phase 1: killed mid-run by SIGKILL after round 3 committed
    env = _env()
    env["KILL_AFTER"] = "3"
    r = subprocess.run([sys.executable, str(worker), ckdir, out], cwd=REPO,
                       env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == -signal.SIGKILL, (r.returncode, r.stderr[-2000:])
    assert not os.path.exists(out), "killed run must not have finished"
    from xgboost_tpu.resilience import checkpoint

    got = checkpoint.load_latest(ckdir)
    assert got is not None and 1 <= got[1] <= 3

    # phase 2: the SAME command resumes and completes
    env.pop("KILL_AFTER")
    r = subprocess.run([sys.executable, str(worker), ckdir, out], cwd=REPO,
                       env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "done 6" in r.stdout

    # phase 3: uninterrupted reference run, fresh checkpoint dir
    out_ref = str(tmp_path / "model_ref.json")
    r = subprocess.run(
        [sys.executable, str(worker), str(tmp_path / "ck_ref"), out_ref],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]

    m_resumed = json.loads(open(out).read())
    m_ref = json.loads(open(out_ref).read())
    assert m_resumed == m_ref, \
        "resumed model must equal the uninterrupted run round-for-round"


_WORKER_DIST = r"""
import os, signal, sys
rank = int(sys.argv[1])
port = sys.argv[2]
ckdir = sys.argv[3]
outdir = sys.argv[4]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"

import numpy as np
import xgboost_tpu as xgb
from xgboost_tpu.callback import TrainingCallback
from xgboost_tpu.parallel import init_distributed, mesh_context

kill_after = int(os.environ.get("KILL_AFTER", "0"))
ROUNDS = 6

mesh = init_distributed(coordinator_address=f"localhost:{port}",
                        num_processes=2, process_id=rank)

rng = np.random.RandomState(0)
n, F = 2000, 5
X = rng.randn(n, F).astype(np.float32)
w = rng.randn(F)
y = ((X @ w) + 0.5 * rng.randn(n) > 0).astype(np.float32)
lo, hi = rank * n // 2, (rank + 1) * n // 2
dtrain = xgb.DMatrix(X[lo:hi], label=y[lo:hi])
params = {"objective": "binary:logistic", "max_depth": 3, "max_bin": 16,
          "eta": 0.3, "seed": 4, "verbosity": 0}


class Killer(TrainingCallback):
    def __init__(self):
        self.rounds = 0

    def after_iteration(self, model, epoch, evals_log):
        self.rounds += 1
        if kill_after and self.rounds == kill_after:
            # BOTH ranks reach this point in the same round (the round's
            # collectives completed) and SIGKILL themselves: the whole
            # job dies mid-run, like a preempted pod
            os.kill(os.getpid(), signal.SIGKILL)
        return False


with mesh_context(mesh):
    bst = xgb.train(params, dtrain, ROUNDS, verbose_eval=False,
                    resume_from=ckdir, callbacks=[Killer()])
bst.save_model(os.path.join(outdir, f"model_rank{rank}.json"))
print(f"rank {rank} done {bst.num_boosted_rounds()}", flush=True)
"""


def _run_pair(worker, port, ckdir, outdir, env):
    procs = [
        subprocess.Popen(
            [sys.executable, str(worker), str(r), str(port), ckdir,
             str(outdir)],
            cwd=REPO, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)
        for r in (0, 1)
    ]
    try:
        outs = [p.communicate(timeout=540)[0] for p in procs]
    finally:
        for p in procs:  # never leak a wedged worker into the CI process
            if p.poll() is None:
                p.kill()
    return [(p.returncode, o) for p, o in zip(procs, outs)]


@pytest.mark.slow  # ~29s of tier-1 budget (1-core box); tier-1 keeps
# the single-process SIGKILL-resume pin AND the 2-proc elastic
# worker_kill recovery test (test_elastic.py), which exercises this
# same 2-process kill->resume path end to end
def test_sigkill_resume_equivalence_two_process(tmp_path):
    """Acceptance criterion: SIGKILL a 2-process distributed run
    mid-round, resume both ranks from their atomic checkpoints (per-rank
    subdirectories), and the final models are bit-identical to an
    uninterrupted 2-process run."""
    worker = tmp_path / "worker_dist.py"
    worker.write_text(_WORKER_DIST)
    ckdir = str(tmp_path / "ck")

    # phase 1: both ranks SIGKILL after round 3
    env = _env()
    env["KILL_AFTER"] = "3"
    res = _run_pair(worker, _free_port(), ckdir, tmp_path, env)
    for rc, out in res:
        assert rc == -signal.SIGKILL, (rc, out[-2000:])

    from xgboost_tpu.resilience import checkpoint

    for rank in (0, 1):
        got = checkpoint.load_latest(os.path.join(ckdir, f"rank{rank}"))
        assert got is not None and 1 <= got[1] <= 3, (rank, got)

    # phase 2: rerun the SAME command — resumes and completes
    env.pop("KILL_AFTER")
    res = _run_pair(worker, _free_port(), ckdir, tmp_path, env)
    for rc, out in res:
        assert rc == 0, out[-3000:]
        assert "done 6" in out

    m0 = json.loads((tmp_path / "model_rank0.json").read_text())
    m1 = json.loads((tmp_path / "model_rank1.json").read_text())
    assert m0 == m1, "resumed ranks must stay bit-identical"

    # phase 3: uninterrupted reference pair
    ref_dir = tmp_path / "ref"
    ref_dir.mkdir()
    res = _run_pair(worker, _free_port(), str(tmp_path / "ck_ref"),
                    ref_dir, env)
    for rc, out in res:
        assert rc == 0, out[-3000:]
    m_ref = json.loads((ref_dir / "model_rank0.json").read_text())
    assert m0 == m_ref, \
        "resumed distributed model must equal the uninterrupted run"

    # quality: the recovered model still learned the signal
    rng = np.random.RandomState(0)
    n, F = 2000, 5
    X = rng.randn(n, F).astype(np.float32)
    w = rng.randn(F)
    y = ((X @ w) + 0.5 * rng.randn(n) > 0).astype(np.float32)
    import xgboost_tpu as xgb
    from xgboost_tpu.metric import create_metric

    bst = xgb.Booster(model_file=str(tmp_path / "model_rank0.json"))
    auc = float(create_metric("auc").evaluate(
        bst.predict(xgb.DMatrix(X)), y))
    assert auc > 0.85, auc
