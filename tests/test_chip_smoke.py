"""chip_smoke.py's contract, as far as a CPU box can hold it: the
rehearsal runs every stage and says what it is, the plain command refuses
any platform but a TPU, and the compile cache can be placed from outside.
The chip half of the contract is the script itself, run through the chip
tool (CHANGES.md records each pass)."""

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REHEARSAL_TAG = "[CPU REHEARSAL - not a chip result]"


def _run(*args, **env_over):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XGBTPU_DISPATCH", None)
    env.pop("XGBTPU_HOIST_BUDGET_MB", None)
    env.update(env_over)
    return subprocess.run(
        [sys.executable, "chip_smoke.py", *args], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=600)


def test_rehearsal_runs_every_stage_and_labels_itself():
    # one device: the four-chip stage reports that and stands down (the
    # mesh itself is tests/test_distributed.py's job)
    out = _run("--rehearse", XLA_FLAGS="")
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    lines = [ln for ln in out.stdout.splitlines() if ln.strip()]
    # every line but the result says it is a rehearsal, not a chip result
    assert all(ln.startswith(REHEARSAL_TAG) for ln in lines[:-1]), out.stdout
    for stage in ("kernels", "train", "predict", "serve", "reference"):
        assert any(f"[{stage}] passed" in ln for ln in lines), stage
    assert any("the four-chip stage needs four" in ln for ln in lines)
    res = json.loads(lines[-1])
    assert res["ok"] is True and res["rehearsal"] is True
    assert res["device"]["platform"] == "cpu"  # never posed as a chip
    # the kernels ran interpreted on the routes the chip takes
    assert any("level_hist=pallas" in ln for ln in lines)
    assert any("predict_walk=pallas" in ln for ln in lines)


def test_plain_command_refuses_anything_but_a_tpu():
    t0 = time.monotonic()
    out = _run()
    assert out.returncode not in (0, None)
    assert time.monotonic() - t0 < 60
    assert "'cpu'" in out.stderr and "not a TPU" in out.stderr, out.stderr
    # no result line: nothing on stdout parses as the contract's JSON
    assert not any(ln.startswith("{") for ln in out.stdout.splitlines())


def test_rehearsal_requires_the_caller_to_pin_cpu():
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    out = subprocess.run(
        [sys.executable, "chip_smoke.py", "--rehearse"], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 2
    assert "requires JAX_PLATFORMS=cpu" in out.stderr


def test_compile_cache_is_placeable_from_outside(monkeypatch):
    import jax

    from xgboost_tpu import config

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert config.compile_cache_dir() == os.path.join(REPO, ".jax_cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert config.compile_cache_dir() == "/some/dir"

    # on a TPU backend: the directory is configured in code only when the
    # variable is unset; the key takes the ops' metadata in either way, so
    # that a cached executable never brings back the scope names and source
    # lines of an older tree (ISSUE 24)
    in_key = ("jax_compilation_cache_include_metadata_in_key", True)
    updates = []
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    assert config.enable_compile_cache() == "/some/dir"
    assert updates == [in_key] and \
        os.environ["JAX_COMPILATION_CACHE_DIR"] == "/some/dir"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    del updates[:]
    assert config.enable_compile_cache() == os.path.join(REPO, ".jax_cache")
    assert updates == [("jax_compilation_cache_dir",
                        os.path.join(REPO, ".jax_cache")), in_key]
    assert "JAX_COMPILATION_CACHE_DIR" not in os.environ


def test_compile_cache_stays_off_on_the_cpu_backend(monkeypatch):
    from xgboost_tpu import config

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert config.enable_compile_cache() is None


def test_anchor_data_is_the_benchmark_generators_byte_for_byte(monkeypatch):
    """The smoke trains on the rows the benchmark's anchor cell trains on:
    ``chip_smoke.anchor_data`` is ``benchmark/generators/linear_logit.py``
    for the same seed, under the anchor configuration's own
    ``generator_params`` (ISSUE 28: it used to import ``bench._make_data``)."""
    import importlib.util

    import numpy as np

    monkeypatch.syspath_prepend(REPO)
    import chip_smoke as smoke

    spec = importlib.util.spec_from_file_location(
        "linear_logit_under_test",
        os.path.join(REPO, "benchmark", "generators", "linear_logit.py"))
    gen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gen)
    with open(os.path.join(REPO, "benchmark", "configs",
                           "anchor-1mx50.json")) as f:
        data = json.load(f)["data"]
    assert data["generator"] == "linear_logit"
    for seed in (smoke.SEED, 7):
        X, y = smoke.anchor_data(4096, data["cols"], seed)
        Xg, yg = gen.generate(rows=4096, cols=data["cols"], seed=seed,
                              **data["generator_params"])
        assert X.dtype == np.float32 and X.shape == (4096, data["cols"])
        assert X.tobytes() == Xg.tobytes() and y.tobytes() == yg.tobytes()
