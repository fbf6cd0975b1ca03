"""The categorical cell's files: the generator keeps the task's columns and
their counts of codes, the manifest lists the cell where its self-tests
need it, the kind refuses a program without the scan gate at once, and the
replay's controls come out as not ok on the CPU stand-in (the Pallas
kernel bodies interpreted) while the program as it is comes out ok."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench_paths import BENCH, REPO, harness, load

gen = load("generators/acs_like.py")
CELL = "acs_employment_cat_train"


def test_the_generator_keeps_the_tasks_columns_and_codes():
    X, y = gen.generate(rows=60_000, cols=16, seed=2**31 + 11)
    assert X.shape == (60_000, 16) and X.dtype == np.float32
    assert not np.isnan(X).any()
    assert 16 <= X[:, 0].min() and X[:, 0].max() <= 90
    for f, card in enumerate(gen.CARDS):
        if card:
            codes = np.unique(X[:, f])
            assert np.array_equal(codes, np.arange(card)), gen.COLUMNS[f]
    assert abs(float(y.mean()) - gen.POSITIVE_SHARE) < 0.02
    types_ = gen.feature_types()
    assert types_.count("c") == 15 and types_[0] == "q"
    # the same rows from the same seed
    X2, y2 = gen.generate(rows=60_000, cols=16, seed=2**31 + 11)
    assert np.array_equal(X2, X) and np.array_equal(y2, y)


def test_the_manifest_lists_the_cell():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        m = json.load(f)
    (cell,) = [w for w in m["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 1 and cell["traffic"] == "cat_train_window_c25"
    doc = harness.load_cell(BENCH, CELL)["config_doc"]
    p, d = doc["params"], doc["data"]
    assert (p["objective"], p["max_depth"], p["eta"], p["max_bin"],
            p["max_cat_to_onehot"]) == ("binary:logistic", 6, 0.3, 256, 4)
    assert (d["rows_train"], d["rows_holdout"], d["cols"]) == \
        (2_588_885, 647_222, 16)
    cards = dict(zip(gen.COLUMNS, gen.CARDS))
    assert d["categorical"] == {k: v for k, v in cards.items() if v}
    metrics = {x["name"]: x for x in m["per_layer"] + m["end_to_end"]}
    assert metrics["cat_split_ms_per_round"]["workloads"] == [CELL]
    for name in ("train_rounds_per_s", "hbm_peak_gb", "compiles_in_window"):
        assert metrics[name]["workloads"][-2:] == [CELL,
                                                   "higgs_leafwise_train"]


def test_a_program_without_the_scan_gate_fails_at_once(monkeypatch):
    """The parent's program keeps a categorical matrix off the scan chunk:
    the kind fails before a row is drawn."""
    import xgboost_tpu as xgb
    from xgboost_tpu.gbm import gbtree

    kind = load("traffic/cat_train_window.py")
    params = dict(harness.load_cell(BENCH, CELL)["config_doc"]["params"])
    kind.require_categorical_scan(xgb, params, gen.feature_types())
    monkeypatch.setattr(gbtree.GBTree, "scan_rounds_supported",
                        lambda self, binned, obj, n: not tuple(
                            getattr(binned, "categorical", ())))
    with pytest.raises(harness.BenchFailure, match="one round at a time"):
        kind.require_categorical_scan(xgb, params, gen.feature_types())


@pytest.fixture(scope="module")
def control_readings():
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "controls", "cat_controls.py"),
         "--rehearsal", "--workload", "tiny_train_cat", "--seeds", "1",
         "--control", "none", "--control", "bf16", "--control", "onehot",
         "--control", "numerical"],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return {r["control"]: r for r in map(json.loads, (
        ln for ln in proc.stdout.splitlines() if ln.startswith("{")))}


@pytest.mark.parametrize("control", ["none", "bf16", "onehot", "numerical"])
def test_the_controls_fail_and_the_program_passes(control_readings,
                                                  control):
    r = control_readings[control]
    assert r["ok"] == (control == "none"), r
    assert r["full"]["ok"] == (control == "none"), r
    if control == "bf16":
        assert r["full"]["leaf_tol_exceeded"] > 0
        assert r["sample"]["leaf_tol_exceeded"] > 0
    if control in ("onehot", "numerical"):
        assert r["full"]["mismatches"] > 0
