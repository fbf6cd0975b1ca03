"""Test configuration: force a virtual 8-device CPU mesh.

This is the analog of the reference's LocalCluster-based multi-worker tests
(tests/python/test_with_dask.py:45) — multi-device logic is exercised on one
host via XLA's host-platform device-count trick (SURVEY.md §4).

The tier-1 command sets ``JAX_PLATFORMS=cpu`` itself; the ``setdefault``
and ``jax.config.update`` below cover a bare ``pytest`` invocation, and the
env vars are inherited by the subprocesses tests start.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

try:
    jax.config.update("jax_platforms", "cpu")
except Exception:  # backends already initialized; tests will use what exists
    pass

# NOTE: do NOT enable the persistent compilation cache for CPU test runs.
# XLA:CPU's AOT cache loading is machine-feature-sensitive (observed:
# "+prefer-no-scatter not supported on the host machine" warnings followed
# by a SIGSEGV inside backend_compile_and_load when reloading entries).
# TPU entry points keep their own cache (config.enable_compile_cache),
# where this path is safe.

# NOTE on full-suite stability: running every test file in ONE process
# occasionally segfaults inside XLA:CPU's backend_compile_and_load (LLVM
# flake under the suite's compile volume; the crashing test varies, every
# file passes in isolation, and ~half of single-process full runs are
# clean). tests/ci.sh splits the suite into two processes to sidestep it.

import shutil  # noqa: E402

import pytest  # noqa: E402


def require_native(available: bool, what: str) -> None:
    """Gate for tests of a native (C++) route. Where ``g++`` is on the
    path an unavailable route is a FAILURE — a moved JAX API once hid
    behind "toolchain unavailable" skips for the whole native stack;
    only a box without a compiler skips."""
    if available:
        return
    if shutil.which("g++"):
        pytest.fail(f"{what} unavailable although g++ is on the path")
    pytest.skip(f"{what} unavailable (no g++)")


@pytest.fixture(autouse=True)
def _reset_resilience_state():
    """Capability health and chaos plans are PROCESS-wide by design (the
    resilience layer replaced per-object latches); tests that degrade a
    capability or arm a chaos plan must not poison later tests."""
    yield
    from xgboost_tpu import dispatch
    from xgboost_tpu.resilience import chaos, degrade

    chaos.reset()
    degrade.reset()
    # resolved-route cache and deprecation warn-once state are process-
    # wide too; a test that pins/degrades a route must not leak its
    # decisions (the cache key includes env + capability state, but the
    # route-change history and last-decision map are cumulative)
    dispatch.reset()
    # the async checkpoint writer parks a failed write's exception for
    # the next sync point — drain and drop it so a chaos test's injected
    # fault never surfaces inside an unrelated later test
    from xgboost_tpu.resilience import checkpoint as _ckpt

    _ckpt.async_writer().reset()
