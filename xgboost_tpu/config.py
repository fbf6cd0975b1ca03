"""Process-wide global configuration.

TPU-native analog of the reference's ``GlobalConfiguration``
(``include/xgboost/global_config.h:17``) and its Python surface
``set_config/get_config/config_context`` (``python-package/xgboost/config.py``).
"""

from __future__ import annotations

import contextlib
import os
import threading
from typing import Any, Dict, Iterator, Mapping, Optional

_DEFAULTS: Dict[str, Any] = {
    "verbosity": 1,
    # use float64 accumulation where supported (analog of the reference's
    # double-precision histogram option, updater_quantile_hist.cc:90-99)
    "use_x64": False,
    # deterministic fixed-point histogram accumulation
    # (gpu_hist/histogram.cu:81-120 rounding trick)
    "deterministic_histogram": True,
    # span-trace destination (Chrome trace-event JSONL); the XGBTPU_TRACE
    # env var takes precedence — see observability/trace.py
    "trace_path": None,
}

_local = threading.local()


def _state() -> Dict[str, Any]:
    if not hasattr(_local, "cfg"):
        _local.cfg = dict(_DEFAULTS)
    return _local.cfg


def set_config(**kwargs: Any) -> None:
    cfg = _state()
    for k, v in kwargs.items():
        if k not in cfg:
            raise ValueError(f"Unknown global config key: {k}")
        cfg[k] = v


def get_config() -> Dict[str, Any]:
    return dict(_state())


@contextlib.contextmanager
def config_context(**kwargs: Any) -> Iterator[None]:
    saved = get_config()
    set_config(**kwargs)
    try:
        yield
    finally:
        _state().update(saved)


# ---------------------------------------------------------------------------
# debug opt-ins: env vars -> jax.config flags (the jax analog of the
# reference's sanitizer builds — see docs/static_analysis.md)
# ---------------------------------------------------------------------------

#: env var -> jax.config flag. XGBTPU_DEBUG_NANS makes any NaN produced
#: inside a jitted program raise FloatingPointError at the producing op
#: (instead of surfacing rounds later as a corrupt model);
#: XGBTPU_CHECK_TRACER_LEAKS makes a tracer escaping its trace (stashed in
#: a module global, returned through a callback) raise at the leak site
#: instead of erroring cryptically on next use.
DEBUG_ENV_FLAGS: Dict[str, str] = {
    "XGBTPU_DEBUG_NANS": "jax_debug_nans",
    "XGBTPU_CHECK_TRACER_LEAKS": "jax_check_tracer_leaks",
}

_FALSY = ("", "0", "false", "no", "off")  # compared case/space-folded


def apply_debug_env(
        environ: Optional[Mapping[str, str]] = None) -> Dict[str, bool]:
    """Map ``XGBTPU_DEBUG_NANS`` / ``XGBTPU_CHECK_TRACER_LEAKS`` onto
    ``jax.config``. Called once at package import (so the env var is the
    only thing a debugging session needs to set) and callable directly by
    tests with an explicit ``environ``. Returns {flag: value} for every
    flag it touched — flags whose env var is unset are left alone, so the
    opt-in never fights an explicit ``jax.config.update`` elsewhere."""
    env = os.environ if environ is None else environ
    touched: Dict[str, bool] = {}
    for var, flag in DEBUG_ENV_FLAGS.items():
        raw = env.get(var)
        if raw is None:
            continue
        value = raw.strip().lower() not in _FALSY
        import jax

        jax.config.update(flag, value)
        touched[flag] = value
    return touched


# ---------------------------------------------------------------------------
# persistent compile cache: one place, placeable from outside
# ---------------------------------------------------------------------------

_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def compile_cache_dir() -> str:
    """Where the persistent compile cache lives: ``JAX_COMPILATION_CACHE_DIR``
    when the caller placed it, else ``<checkout>/.jax_cache`` — a fixed
    absolute path beside the package (the path is part of the cache key,
    so a directory that moves never hits)."""
    placed = os.environ.get(_CACHE_ENV)
    if placed:
        return placed
    return os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent compile cache on for a process whose backend
    is a TPU, and return the directory in use. With
    ``JAX_COMPILATION_CACHE_DIR`` set nothing is configured in code — JAX
    reads the variable itself. Returns None, doing nothing, on the CPU
    backend: XLA:CPU's AOT cache reload is machine-feature-sensitive
    (tests/conftest.py)."""
    import jax

    if jax.default_backend() != "tpu":
        return None
    path = compile_cache_dir()
    if not os.environ.get(_CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    # the cache's key otherwise leaves out the ops' metadata, and an
    # executable compiled before an edit comes back with its old scope
    # names and source lines: a profile would name phases that did not run
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return path
