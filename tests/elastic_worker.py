"""Elastic-training worker, shared by ``tests/test_elastic.py`` and the
``tests/ci.sh`` chaos lane: one process of a 2-worker CPU (gloo) elastic
run over a deterministic dataset, with ``XGBTPU_CHAOS=worker_kill:...``
armed on whichever rank the parent chose.

argv: rank port outdir num_rounds [world]
  - rank: this worker's base rank
  - port: base coordinator port (generation g uses port+g)
  - outdir: the shared elastic run directory; outputs land here too
  - num_rounds: total boosting rounds
  - world: initial world size (default 2)

On completion the surviving worker writes ``model_rank<r>.json``,
``metrics_rank<r>.prom`` (the full registry exposition) and
``meta_rank<r>.json``, then leaves via ``elastic_exit`` (a survivor of a
peer death must not walk into the runtime's exit-time shutdown barrier).
"""

import json
import os
import sys

rank = int(sys.argv[1])
port = int(sys.argv[2])
outdir = sys.argv[3]
num_rounds = int(sys.argv[4])
world = int(sys.argv[5]) if len(sys.argv) > 5 else 2

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
os.environ.setdefault("XGBTPU_HEARTBEAT", "0.25")

import numpy as np  # noqa: E402

import xgboost_tpu as xgb  # noqa: E402

N, F = 2400, 5
PARAMS = {"objective": "binary:logistic", "max_depth": 3, "eta": 0.3,
          "max_bin": 16, "seed": 7, "verbosity": 0}


def make_data():
    rng = np.random.RandomState(0)
    X = rng.randn(N, F).astype(np.float32)
    w = rng.randn(F)
    y = ((X @ w) + 0.5 * rng.randn(N) > 0).astype(np.float32)
    return X, y


def data_fn(r, world):
    """Contiguous block shards of one fixed global row order — the
    bit-exact-replay contract of elastic_train's data_fn."""
    X, y = make_data()
    lo = r * N // world
    hi = (r + 1) * N // world
    return xgb.DMatrix(X[lo:hi], label=y[lo:hi])


bst = xgb.elastic_train(
    PARAMS, data_fn, num_rounds,
    run_dir=outdir, world=world, rank=rank,
    coordinator=f"localhost:{port}",
)

from xgboost_tpu.observability import REGISTRY  # noqa: E402

my_rank = rank
bst.save_model(os.path.join(outdir, f"model_rank{my_rank}.json"))
with open(os.path.join(outdir, f"metrics_rank{my_rank}.prom"), "w") as f:
    f.write(REGISTRY.exposition())
with open(os.path.join(outdir, f"meta_rank{my_rank}.json"), "w") as f:
    json.dump({"rounds": bst.num_boosted_rounds(), "rank": my_rank}, f)
print(f"rank {my_rank} done ({bst.num_boosted_rounds()} rounds)",
      flush=True)
xgb.elastic_exit(0)
