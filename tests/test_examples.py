"""Smoke-run the examples/ suite (reference: tests/python/test_demos.py
executes demo/ scripts the same way)."""

import os
import subprocess
import sys

import pytest

_EX = os.path.join(os.path.dirname(__file__), "..", "examples")

# these demos load the reference checkout's demo data, which is not part
# of this container image: skip rather than fail when it is absent
_NEEDS_REFERENCE = {"binary_classification.py", "survival_aft.py"}
_REFERENCE_DATA = "/root/reference/demo/data"


@pytest.mark.parametrize("script", [
    "binary_classification.py",
    "sklearn_interface.py",
    "ranking.py",
    "survival_aft.py",
    # ~50s of 8-device XLA:CPU compile: outside the tier-1 time budget
    pytest.param("distributed_mesh.py", marks=pytest.mark.slow),
    "external_memory.py",
])
def test_example_runs(script):
    if script in _NEEDS_REFERENCE and not os.path.isdir(_REFERENCE_DATA):
        pytest.skip(f"reference demo data absent ({_REFERENCE_DATA})")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    root = os.path.abspath(os.path.join(_EX, ".."))
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, os.path.join(_EX, script)],
        capture_output=True, text=True, timeout=600, env=env,
        cwd=os.path.join(_EX, ".."),
    )
    assert r.returncode == 0, r.stderr[-2000:]
