"""Two-process jax.distributed training (the reference's LocalCluster dask
test role, tests/python/test_with_dask.py:45-125): spawn 2 CPU processes,
jax.distributed.initialize against a localhost coordinator, each process
ingests ITS OWN row slice (load_row_split model), trains update_many chunks
inside the global mesh, and the resulting models must be BIT-IDENTICAL
across processes (trees are replicated by construction — the property the
reference asserts with gpu_hist's debug_synchronize)."""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_WORKER = r"""
import os, sys
rank = int(sys.argv[1])
port = sys.argv[2]
outdir = sys.argv[3]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"

import numpy as np
import xgboost_tpu as xgb
from xgboost_tpu.parallel import init_distributed, mesh_context

mesh = init_distributed(coordinator_address=f"localhost:{port}",
                        num_processes=2, process_id=rank)

# deterministic global dataset; each process takes its own half
rng = np.random.RandomState(0)
n, F = 4000, 6
X = rng.randn(n, F).astype(np.float32)
w = rng.randn(F)
y = ((X @ w) + 0.5 * rng.randn(n) > 0).astype(np.float32)
lo, hi = rank * n // 2, (rank + 1) * n // 2
dtrain = xgb.DMatrix(X[lo:hi], label=y[lo:hi])

params = {"objective": "binary:logistic", "max_depth": 4, "eta": 0.3,
          "max_bin": 32, "seed": 5}
with mesh_context(mesh):
    bst = xgb.Booster(params, [dtrain])
    bst.update_many(dtrain, 0, 6, chunk=3)

bst.save_model(os.path.join(outdir, f"model_rank{rank}.json"))
pred = bst.predict(xgb.DMatrix(X[lo:hi]))
np.save(os.path.join(outdir, f"pred_rank{rank}.npy"), pred)

# the rabit/collective compatibility shim, across real processes
from xgboost_tpu import collective

assert collective.get_world_size() == 2
assert collective.get_rank() == rank
s = collective.allreduce(np.array([float(rank + 1)]), collective.Op.SUM)
assert float(s[0]) == 3.0, s
m = collective.allreduce(np.array([float(rank)]), collective.Op.MAX)
assert float(m[0]) == 1.0, m

# mesh-LESS multi-process: with jax.distributed initialized but no
# mesh_context, training and metrics must be purely LOCAL — DART is
# outside the scan envelope (would raise under a mesh), and the ranks
# evaluate a DIFFERENT number of times, so any hidden collective in
# either path would raise or deadlock here (collective_active gate)
d_loc = xgb.DMatrix(X[lo:hi], label=y[lo:hi])
bst_loc = xgb.train({"objective": "binary:logistic", "booster": "dart",
                     "max_depth": 3, "eta": 0.3, "max_bin": 16,
                     "seed": rank}, d_loc, num_boost_round=3)
for _ in range(rank + 1):
    ev = bst_loc.eval(d_loc)
assert isinstance(ev, str) and "logloss" in ev, ev
print(f"rank {rank} done", flush=True)
"""


def _free_port() -> int:
    s = socket.socket()
    s.bind(("localhost", 0))
    p = s.getsockname()[1]
    s.close()
    return p


_WORKER_LARGE = r"""
import os, sys
rank = int(sys.argv[1])
port = sys.argv[2]
outdir = sys.argv[3]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"

import json
import numpy as np
import xgboost_tpu as xgb
from xgboost_tpu.parallel import init_distributed, mesh_context

mesh = init_distributed(coordinator_address=f"localhost:{port}",
                        num_processes=2, process_id=rank)

# >=100k rows, UNEVEN split (70k/50k): per-process padding masks and
# process-major row accounting must hold at a size where mistakes surface
# (review r4 next #7; reference oracle test_with_dask.py:45-125)
rng = np.random.RandomState(1)
n, F = 120_000, 10
X = rng.randn(n, F).astype(np.float32)
w = rng.randn(F)
y = ((X @ w) + 1.0 * rng.randn(n) > 0).astype(np.float32)
cut = 70_000
lo, hi = (0, cut) if rank == 0 else (cut, n)
dtrain = xgb.DMatrix(X[lo:hi], label=y[lo:hi])

nv = 20_000
Xv = rng.randn(nv, F).astype(np.float32)
yv = ((Xv @ w) + 1.0 * rng.randn(nv) > 0).astype(np.float32)
vcut = 8_000  # uneven eval shards too
vlo, vhi = (0, vcut) if rank == 0 else (vcut, nv)
dval = xgb.DMatrix(Xv[vlo:vhi], label=yv[vlo:vhi])

params = {"objective": "binary:logistic", "max_depth": 5, "eta": 0.2,
          "max_bin": 64, "seed": 7, "eval_metric": ["logloss", "auc"]}
res = {}
with mesh_context(mesh):
    bst = xgb.train(params, dtrain, num_boost_round=60,
                    evals=[(dval, "val")], early_stopping_rounds=5,
                    evals_result=res, verbose_eval=False)

bst.save_model(os.path.join(outdir, f"large_model_rank{rank}.json"))
with open(os.path.join(outdir, f"large_meta_rank{rank}.json"), "w") as f:
    json.dump({"best_iteration": bst.best_iteration,
               "best_score": float(bst.best_score),
               "val_auc": res["val"]["auc"],
               "val_logloss": res["val"]["logloss"]}, f)

# broadcast must ship ROOT's value to the other rank (rank-dependent
# payloads are the case the shim exists for — ADVICE r4)
from xgboost_tpu import collective

got = collective.broadcast({"thresh": 0.25 + rank, "rank": rank}, root=0)
assert got == {"thresh": 0.25, "rank": 0}, got
got1 = collective.broadcast(np.arange(3) + rank, root=1)
np.testing.assert_array_equal(got1, np.arange(3) + 1)
print(f"rank {rank} done", flush=True)
"""


@pytest.mark.slow  # ~57s of tier-1 budget (1-core box); run with -m slow
def test_two_process_large_eval_early_stop(tmp_path):
    """>=100k rows, uneven shards, eval set + early stopping through the
    public train(): metrics must be GLOBAL (dist_reduce) so both ranks
    stop at the same round with bit-identical models; broadcast must move
    rank-dependent values."""
    worker = tmp_path / "worker_large.py"
    worker.write_text(_WORKER_LARGE)
    port = _free_port()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    procs = [
        subprocess.Popen(
            [sys.executable, str(worker), str(r), str(port), str(tmp_path)],
            cwd=REPO, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)
        for r in (0, 1)
    ]
    try:
        outs = [p.communicate(timeout=900)[0] for p in procs]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {r} failed:\n{out[-4000:]}"

    m0 = json.loads((tmp_path / "large_model_rank0.json").read_text())
    m1 = json.loads((tmp_path / "large_model_rank1.json").read_text())
    assert m0 == m1, "replicated models must be bit-identical across ranks"

    meta0 = json.loads((tmp_path / "large_meta_rank0.json").read_text())
    meta1 = json.loads((tmp_path / "large_meta_rank1.json").read_text())
    # same stopping decision, same (global) metric history on both ranks
    assert meta0["best_iteration"] == meta1["best_iteration"]
    assert meta0["best_score"] == meta1["best_score"]
    assert meta0["val_auc"] == meta1["val_auc"], \
        "per-rank eval metrics must be globally reduced, not shard-local"
    assert meta0["val_logloss"] == meta1["val_logloss"]
    # the model learned the signal
    assert meta0["val_auc"][meta0["best_iteration"]] > 0.85


def test_two_process_training_model_equality(tmp_path):
    worker = tmp_path / "worker.py"
    worker.write_text(_WORKER)
    port = _free_port()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    procs = [
        subprocess.Popen(
            [sys.executable, str(worker), str(r), str(port), str(tmp_path)],
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
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {r} failed:\n{out[-4000:]}"

    m0 = json.loads((tmp_path / "model_rank0.json").read_text())
    m1 = json.loads((tmp_path / "model_rank1.json").read_text())
    assert m0 == m1, "replicated models must be bit-identical across ranks"
    assert len(m0["learner"]["gradient_booster"]["model"]["trees"]) == 6

    # quality: the jointly-trained model must have learned the signal on
    # each process's local shard
    from xgboost_tpu.metric import create_metric

    rng = np.random.RandomState(0)
    n, F = 4000, 6
    X = rng.randn(n, F).astype(np.float32)
    w = rng.randn(F)
    y = ((X @ w) + 0.5 * rng.randn(n) > 0).astype(np.float32)
    for r in (0, 1):
        pred = np.load(tmp_path / f"pred_rank{r}.npy")
        lo, hi = r * n // 2, (r + 1) * n // 2
        auc = float(create_metric("auc").evaluate(pred, y[lo:hi]))
        assert auc > 0.9, (r, auc)
