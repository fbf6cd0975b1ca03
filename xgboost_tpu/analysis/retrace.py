"""Runtime retrace detector: recompile accounting + hard budgets.

Every ``jax.jit`` cache miss re-executes the wrapped Python function to
build a new program — so a thin shim that bumps a counter *inside* the
traced callable counts exactly the (re)traces, costs nothing on cache
hits (the Python body never runs again), and needs no private JAX API.

``guard_jit(fn, name=...)`` is a drop-in ``jax.jit`` replacement used on
the hot entry points (``tree/grow_fused.py``, ``tree/hist_kernel.py``,
``predictor/serving.py``). Each trace:

- increments ``recompiles_total{fn=<name>}`` in the process metrics
  registry (``observability.metrics.REGISTRY``) — the serving bench's
  "≤ 9 compiles for 1000 ragged batches" claim becomes a scrapeable
  time series;
- checks ``XGBTPU_RETRACE_BUDGET`` and raises ``RetraceBudgetExceeded``
  once the function's trace count passes its budget — the invariant is
  *enforced*, not just measured. Budget syntax: a bare int applies to
  every guarded function (``XGBTPU_RETRACE_BUDGET=16``); per-function
  overrides with a ``*`` default compose as
  ``XGBTPU_RETRACE_BUDGET=predict_serving=9,grow_tree_fused=4,*=64``.
  Unset (the default) means count-only: zero behavior change.

The env var is re-read on every retrace *event* (not every call), so
tests and operators can flip enforcement without reimporting anything.

What a trace, a lowering and a compile of *any* program cost (guarded or
not) is in the set-up ledger, from JAX's own events
(``observability/compile_ledger.py``).
"""

from __future__ import annotations

import functools
import os
import threading
from typing import Callable, Dict, Optional

__all__ = [
    "RetraceBudgetExceeded", "guard_jit", "note_retrace", "retrace_counts",
    "reset_retrace_counts", "retrace_budget",
]

_ENV_BUDGET = "XGBTPU_RETRACE_BUDGET"

_counts: Dict[str, int] = {}
_lock = threading.Lock()


class RetraceBudgetExceeded(RuntimeError):
    """A guarded function recompiled past its XGBTPU_RETRACE_BUDGET."""


def retrace_budget(name: str) -> Optional[int]:
    """The budget for ``name`` per the current env, or None (count-only)."""
    raw = os.environ.get(_ENV_BUDGET)
    if not raw:
        return None
    default: Optional[int] = None
    for part in raw.split(","):
        part = part.strip()
        if not part:
            continue
        if "=" in part:
            k, _, v = part.partition("=")
            k, v = k.strip(), v.strip()
        else:
            k, v = "*", part
        try:
            iv = int(v)
        except ValueError:
            continue  # malformed env must never break training
        if k == name:
            return iv
        if k == "*":
            default = iv
    return default


def note_retrace(name: str) -> None:
    """Record one (re)trace of ``name``: bump the counter and enforce the
    budget. Called from inside tracing, so a raise aborts the compile and
    surfaces at the jit call site — which also makes it the ``compile``
    chaos-injection site: ``XGBTPU_CHAOS="compile:..."`` scripts a failing
    guarded compile (resilience tentpole)."""
    from ..resilience import chaos

    chaos.hit("compile")
    with _lock:
        count = _counts.get(name, 0) + 1
        _counts[name] = count
    from ..observability.metrics import REGISTRY

    REGISTRY.counter(
        "recompiles_total",
        "Traces (== XLA compiles) of guarded jit entry points",
    ).labels(fn=name).inc()
    budget = retrace_budget(name)
    if budget is not None and count > budget:
        raise RetraceBudgetExceeded(
            f"{name} recompiled {count} times, budget is {budget} "
            f"({_ENV_BUDGET}). A retrace means a new (shape, dtype, "
            f"static-arg) signature reached the jit boundary — check for "
            f"unbucketed ragged batches or non-static Python scalars "
            f"(python -m xgboost_tpu lint, rules RH2xx). The count is "
            f"CUMULATIVE for this process: size the budget for every "
            f"model shape the process legitimately serves, and call "
            f"analysis.retrace.reset_retrace_counts({name!r}) on planned "
            f"transitions like a model refresh.")


def retrace_counts() -> Dict[str, int]:
    """Snapshot of per-function trace counts (host-side, this process)."""
    with _lock:
        return dict(_counts)


def reset_retrace_counts(name: Optional[str] = None) -> None:
    """Zero the host-side counts (tests). The registry counter is owned by
    the metrics layer and keeps its monotone history."""
    with _lock:
        if name is None:
            _counts.clear()
        else:
            _counts.pop(name, None)


def guard_jit(fun: Optional[Callable] = None, *, name: Optional[str] = None,
              **jit_kwargs) -> Callable:
    """``jax.jit`` with retrace accounting. Usable as a decorator factory
    (``@guard_jit(name="grow_tree_fused", static_argnames=("cfg",))``) or
    called directly (``guard_jit(run, name="predict_serving")``).

    The counting shim runs only while JAX traces ``fun``; steady-state
    dispatch pays one thin forwarding frame. ``functools.wraps`` preserves
    the signature, so ``static_argnames`` resolve exactly as on the
    undecorated function. The underlying jit object is reachable as
    ``<wrapper>._guarded_jit`` for AOT callers."""
    if fun is None:
        return functools.partial(guard_jit, name=name, **jit_kwargs)
    import jax

    label = name or getattr(fun, "__qualname__", repr(fun))

    @functools.wraps(fun)
    def traced(*args, **kwargs):
        note_retrace(label)
        return fun(*args, **kwargs)

    jitted = jax.jit(traced, **jit_kwargs)

    @functools.wraps(fun)
    def dispatch(*args, **kwargs):
        return jitted(*args, **kwargs)

    dispatch._guarded_jit = jitted  # escape hatch for AOT callers
    return dispatch

