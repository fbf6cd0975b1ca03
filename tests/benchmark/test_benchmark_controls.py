"""The replay's controls come out as not ok at a size a test run can hold
(``benchmark/controls/replay_readings.py`` on the tiny binary stand-in, the
Pallas kernel bodies interpreted), and the program as it is comes out ok."""

import json
import os
import subprocess
import sys

import pytest

from bench_paths import BENCH, REPO, load

grower = load("reference/grower.py")
SEEDS = ["1", "2", "3"]


@pytest.fixture(scope="module")
def readings():
    proc = subprocess.run(
        [sys.executable,
         os.path.join(BENCH, "controls", "replay_readings.py"),
         "--rehearsal", "--workload", "tiny_train", "--interpret", "--seeds",
         *SEEDS, "--control", "none", "--control", "no_mcw", "--control",
         "bf16"],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith("{")]
    assert len(lines) == 3 * len(SEEDS)
    return lines


@pytest.mark.parametrize("seed", SEEDS)
def test_the_program_as_it_is_replays_ok(readings, seed):
    (r,) = [x for x in readings
            if x["control"] == "none" and x["seed"] == int(seed)]
    assert r["ok"] and r["nodes"] > 30 and r["mcw_short"] <= grower.MCW_RTOL


@pytest.mark.parametrize("seed", SEEDS)
def test_a_dropped_min_child_weight_is_caught_by_the_shortfall(readings,
                                                               seed):
    (r,) = [x for x in readings
            if x["control"] == "no_mcw" and x["seed"] == int(seed)]
    assert not r["ok"] and r["mismatches"] > 0
    # the upper reading: a hundred times the limit or more
    assert r["mcw_short"] > 100 * grower.MCW_RTOL


@pytest.mark.parametrize("seed", SEEDS)
def test_plain_bfloat16_histograms_are_caught_by_the_leaves(readings, seed):
    (r,) = [x for x in readings
            if x["control"] == "bf16" and x["seed"] == int(seed)]
    assert not r["ok"] and r["leaf_tol_exceeded"] > 0


@pytest.mark.parametrize("fault,correct,over", [
    ("none", True, None),
    ("bf16", False, "oracle_leaf_tol_exceeded"),
    ("no_mcw", False, "oracle_mcw_short")])
def test_a_whole_run_on_a_broken_path_is_not_correct(fault, correct, over):
    """``harness.run_cell`` past its look for a chip, the fault planted
    under it: ``correct`` is false, and the number over its limit stands in
    the result line's last key and in the last lines of standard error."""
    tag = "[CPU REHEARSAL - not a chip result] "
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "controls", "broken_run.py"),
         "--workload", "tiny_train", "--fault", fault],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.splitlines()[-1][len(tag):])
    assert out["correct"] is correct
    assert list(out)[-1] == "compared" and len(out["compared"]) >= 9
    err = proc.stderr.splitlines()
    assert err[-1] == f"{tag}correct: {correct}"
    said = [ln for ln in err[-1 - len(out["compared"]):-1]]
    assert all(ln.startswith(tag + "compared ") for ln in said), said[:2]
    over_limit = [k for k, c in out["compared"].items()
                  if "limit" in c and not isinstance(c["limit"], list)
                  and k != "train_loss_last" and c["value"] > c["limit"]]
    if over is None:
        assert not over_limit
    else:
        assert over in over_limit
        assert any(ln.startswith(f"{tag}compared {over}: value ")
                   for ln in said)
