"""The set-up ledger: what every program cost to trace, lower and compile,
from JAX's own events, beside the data plane's stages.

JAX (0.9) publishes, through ``jax.monitoring``, a duration event at each
of a program's three steps (``jax/_src/dispatch.py``: the Python trace to
a jaxpr, the lowering to an MLIR module, the backend compile, which holds
a persistent-cache load where there is one), each with the function's
name, and plain events from the persistent compilation cache. ``install()``
registers listeners that turn them into series of the one ``REGISTRY``:

- ``jit_seconds_total{stage, fn}`` / ``jit_events_total{stage, fn}``,
  ``stage`` one of ``trace``, ``lower``, ``compile``;
- ``compile_cache_events_total{result="hit"|"miss"}``,
  ``compile_cache_load_seconds_total``,
  ``compile_cache_saved_seconds_total``.

They cover every program of the process, guarded by ``guard_jit`` or not
(``recompiles_total`` counts the guarded ones and stays what the retrace
budget and the benchmark's ``compiles_in_window`` read). ``fn`` is JAX's
``fun_name`` with the ``jit(...)`` the lowering and the compile wrap round
it taken off, so a program's three steps meet under one label; no shape
enters a label.

**The seconds are self time.** JAX fires the trace event at every level of
nesting (an inner ``jit`` is traced while the outer one still is, and every
``jnp`` function is one), and a lowering can trace again. An event that
opened and closed inside another on the same thread is taken out of it, so
the sum over ``fn`` and ``stage`` is wall time and the per-``fn`` table
says whose Python it was. ``jit_events_total`` counts every event as fired.

A listener runs only when JAX traces, lowers or compiles: a warm call of a
jitted function fires none. With ``XGBTPU_TRACE`` on, an event that closes
on the host side (not inside another program's trace: ``trace.emit``'s
rule) also goes to the span buffer as ``jit.<stage>`` with ``cat="compile"``
and ``fn``, so a timeline shows the compile inside the ``update``,
``chunk.dispatch`` or ``dmatrix_build`` span that caused it.

``setup_ledger()`` returns the whole table as plain JSON.
"""

from __future__ import annotations

import threading
from typing import Any, Dict

from . import trace
from .metrics import REGISTRY

__all__ = ["install", "setup_ledger"]

_STAGE_OF = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_CACHE_RESULT_OF = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
_CACHE_SECONDS_OF = {
    "/jax/compilation_cache/cache_retrieval_time_sec":
        ("compile_cache_load_seconds_total",
         "Seconds spent reading executables from the persistent "
         "compilation cache"),
    "/jax/compilation_cache/compile_time_saved_sec":
        ("compile_cache_saved_seconds_total",
         "Compile seconds the persistent cache's hits stood for, less "
         "their load time (JAX's own estimate)"),
}
_SECONDS_HELP = ("Own seconds of JAX's trace, lowering and backend compile "
                 "of each program (events inside an event are taken out "
                 "of it)")
_EVENTS_HELP = "JAX trace, lowering and backend-compile events, as fired"

_install_lock = threading.Lock()
_installed = False
_tls = threading.local()  # .open: [[event, start, seconds inside], ...]


def _fn(fun_name: Any) -> str:
    name = str(fun_name)
    if name.startswith("jit(") and name.endswith(")"):
        return name[4:-1]
    return name


def _on_open(event: str, value, **kwargs) -> None:
    """JAX records a stage's start time as a scalar when the stage opens."""
    if event in _STAGE_OF:
        frames = getattr(_tls, "open", None)
        if frames is None:
            frames = _tls.open = []
        frames.append([event, value, 0.0])


def _on_close(event: str, start: float, end: float, **kwargs) -> None:
    stage = _STAGE_OF.get(event)
    if stage is None:
        return
    wall = max(end - start, 0.0)
    inside = 0.0
    frames = getattr(_tls, "open", None) or []
    for i in range(len(frames) - 1, -1, -1):
        if frames[i][0] == event and frames[i][1] == start:
            inside = frames[i][2]
            del frames[i:]  # with it, whatever opened inside and never closed
            break
    if frames:
        frames[-1][2] += wall
    fn = _fn(kwargs.get("fun_name", ""))
    REGISTRY.counter("jit_seconds_total", _SECONDS_HELP).labels(
        stage=stage, fn=fn).inc(max(wall - inside, 0.0))
    REGISTRY.counter("jit_events_total", _EVENTS_HELP).labels(
        stage=stage, fn=fn).inc()
    trace.emit("jit." + stage, trace.from_unix_s(start),
               trace.from_unix_s(end), cat="compile", fn=fn)


def _on_event(event: str, **kwargs) -> None:
    result = _CACHE_RESULT_OF.get(event)
    if result is not None:
        REGISTRY.counter(
            "compile_cache_events_total",
            "Persistent compilation cache: executables found (hit) and "
            "executables compiled and written (miss)",
        ).labels(result=result).inc()


def _on_duration(event: str, seconds: float, **kwargs) -> None:
    series = _CACHE_SECONDS_OF.get(event)
    if series is not None:
        # JAX's saved time is compile time less load time: under zero where
        # the load took longer than the compile had
        REGISTRY.counter(*series).inc(max(float(seconds), 0.0))


def install() -> None:
    """Register the listeners, once a process however often it is called
    (the package does at import). They hold no family of the registry, so
    the ledger goes on after ``REGISTRY.reset()``."""
    global _installed
    with _install_lock:
        if _installed:
            return
        from jax import monitoring

        monitoring.register_scalar_listener(_on_open)
        monitoring.register_event_time_span_listener(_on_close)
        monitoring.register_event_listener(_on_event)
        monitoring.register_event_duration_secs_listener(_on_duration)
        _installed = True


def _series(name: str):
    fam = REGISTRY.get(name)
    return fam.series() if fam is not None else []


def setup_ledger() -> Dict[str, Any]:
    """The process's set-up so far, as a JSON-able dict.

    ``programs``: per ``fn`` the own seconds and the event count of each
    step, ``{"trace": {"seconds", "events"}, "lower": ..., "compile": ...}``
    (a step that never ran is left out); ``jit_seconds``: the three steps'
    totals; ``compile_cache``: hits, misses, load and saved seconds;
    ``stages``: per data-plane stage its own seconds, its events and, where
    the backend keeps statistics, ``hbm_peak_bytes`` (``trace.stage``).
    A cold start reads: large ``compile`` seconds with cache misses; a warm
    one: the same programs with hits, ``compile`` near the load seconds,
    and what is left is ``trace`` and ``lower``, which no cache saves."""
    programs: Dict[str, Dict[str, Dict[str, float]]] = {}
    totals = {"trace": 0.0, "lower": 0.0, "compile": 0.0}
    for labels, child in _series("jit_seconds_total"):
        step = programs.setdefault(labels["fn"], {}).setdefault(
            labels["stage"], {"seconds": 0.0, "events": 0})
        step["seconds"] = child.value
        totals[labels["stage"]] += child.value
    for labels, child in _series("jit_events_total"):
        programs.setdefault(labels["fn"], {}).setdefault(
            labels["stage"], {"seconds": 0.0, "events": 0}
        )["events"] = int(child.value)
    cache = {"hit": 0, "miss": 0}
    for labels, child in _series("compile_cache_events_total"):
        cache[labels["result"]] = int(child.value)
    stages: Dict[str, Dict[str, float]] = {}
    for labels, child in _series("setup_stage_seconds_total"):
        stages.setdefault(labels["stage"], {})["seconds"] = child.value
    for labels, child in _series("setup_stage_events_total"):
        stages.setdefault(labels["stage"], {})["events"] = int(child.value)
    for labels, child in _series("hbm_peak_bytes"):
        stages.setdefault(labels["stage"], {})["hbm_peak_bytes"] = int(
            child.value)
    return {
        "programs": programs,
        "jit_seconds": totals,
        "compile_cache": {
            "hits": cache["hit"], "misses": cache["miss"],
            "load_seconds": sum(
                c.value for _, c in
                _series("compile_cache_load_seconds_total")),
            "saved_seconds": sum(
                c.value for _, c in
                _series("compile_cache_saved_seconds_total")),
        },
        "stages": stages,
    }
