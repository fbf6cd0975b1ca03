"""The CPU stand-ins run end to end through the real runner, and the real
cells refuse a CPU."""

import glob
import json
import os
import subprocess
import sys

import pytest

from bench_paths import BENCH, REPO

TAG = "[CPU REHEARSAL - not a chip result] "
CELLS = sorted(os.path.basename(p)[:-5] for p in glob.glob(
    os.path.join(BENCH, "rehearsal", "workloads", "*.json")))


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    return env


@pytest.mark.parametrize("cell", CELLS)
def test_stand_in_runs_end_to_end(cell):
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "rehearsal", "rehearse.py"),
         "--workload", cell, "--seconds", "1.5", "--trace", "1"],
        cwd=REPO, env=_env(), capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    # every printed line is labelled as no chip result
    assert all(ln.startswith(TAG) for ln in lines), lines[:3]
    out = json.loads(lines[-1][len(TAG):])
    assert set(out) >= {"correct", "attempted", "failed", "metrics", "device",
                        "breakdown"}
    assert out["correct"] is True and out["attempted"] > 0
    assert out["failed"] == 0
    assert out["device"]["platform"] == "cpu"
    assert out["metrics"]["compiles_in_window"]["value"] == 0


def test_real_cell_refuses_a_cpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "anchor_train", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=_env(), capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "not a TPU" in proc.stderr
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())


def test_unknown_cell_exits_non_zero():
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "no_such_cell", "--seed", "1", "--seconds", "1"],
        cwd=REPO, env=_env(), capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0 and "unknown workload" in proc.stderr
