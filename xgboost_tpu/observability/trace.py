"""Structured span tracing: host-side timeline -> Chrome trace-event JSONL.

The reference ships wall-clock accumulators (``common::Monitor``) and
compile-gated NVTX ranges; neither produces a machine-readable timeline.
This module is the unified replacement: a ``span("hist_build", node=k)``
context manager records Chrome trace-event "X" (complete) events —
viewable in Perfetto / ``chrome://tracing`` — into an in-memory ring
buffer, flushed to the path named by ``XGBTPU_TRACE=<path>`` or
``set_config(trace_path=...)``.

Design constraints (ISSUE 1):

- **Two clocks, one call site**: while a ``jax.profiler`` session is live
  (``XGBTPU_PROFILE``, the benchmark's traced run, a capture from the
  profiler server), ``span(name)`` also opens a
  ``jax.profiler.TraceAnnotation("xgb." + name)`` for its extent, whether
  or not ``XGBTPU_TRACE`` is set, so the profile shows the package's host
  steps on the profiler's clock, on the thread that ran them, beside the
  device ops. Call sites keep their bare names; only the annotation
  carries the prefix. ``emit()`` / ``emit_async*()`` own their clock reads
  and are not bridged.
- **Cheap when nothing listens**: with ``XGBTPU_TRACE`` unset and no
  profiler session, ``span()`` asks the profiler whether a session is live
  (one activity check, 0.03 us), makes the enabled check (an environment
  read plus a thread-local dict get) and returns a shared no-op context
  manager: no allocation, no clock read, 1.9 us an enter/exit on this
  sandbox's CPU (jax 0.9.0; ``tests/test_device_phases.py`` prints it and
  holds it under 5 us).
- **Host-side only**: spans measure the Python-side view — argument prep,
  dispatch, and blocking host syncs — never device internals, and a span
  opened while JAX is *tracing* a function (inside ``jit``/``shard_map``
  staging) is suppressed (``jax.core.trace_state_clean``), so wrapped
  growers can be staged into larger programs without emitting bogus
  trace-time events. What runs on the device is named by
  ``jax.named_scope("xgb.<phase>")`` where the programs are written
  (docs/observability.md, "Reading a device profile").
- **Ring buffered**: the newest ``XGBTPU_TRACE_BUFFER`` (default 65536)
  events are retained; older ones are dropped and counted in the
  ``trace_events_dropped_total`` metric. ``flush()`` drains the buffer to
  disk (appending), and runs automatically at interpreter exit.

File format: a Chrome trace-event JSON array written one event per line
(the spec's trailing-``]``-optional form, which both Perfetto and
``chrome://tracing`` load), so the file doubles as JSONL — each event
line (modulo the trailing comma) is a complete JSON object, and
``load_trace`` parses any prefix of a partially written file. Multi-process
runs write one file per rank (``<path>.rank<r>``), with the rank as the
Chrome ``pid``.
"""

from __future__ import annotations

import collections
import functools
import json
import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional

__all__ = [
    "span", "stage", "instant", "emit", "emit_async", "emit_async_track",
    "enabled", "trace_path", "flush", "reset", "load_trace",
    "clock_base", "from_unix_s", "set_sink",
]

_ENV_PATH = "XGBTPU_TRACE"
_ENV_BUFFER = "XGBTPU_TRACE_BUFFER"
# a span's name on the profiler's clock: every host step and every device
# scope of the package starts with it, so a reader of a profile needs no list
_ANNOTATION_PREFIX = "xgb."

_lock = threading.RLock()
_buffer: "collections.deque[Dict[str, Any]]" = collections.deque(
    maxlen=max(int(os.environ.get(_ENV_BUFFER, "65536") or 65536), 16))
_dropped = 0
_headers_written: set = set()
_tid_map: Dict[int, int] = {}
_rank_cache: Optional[tuple] = None  # (rank, world)
_sink: Optional[str] = None  # flight-recorder sink (observability/flight.py)
# the two clock reads are adjacent on purpose: _EPOCH_UNIX_NS is the
# wall-clock instant at which event timestamps are 0, the per-rank clock
# base cross-rank merging aligns on (obs-report; skew < 1us)
_EPOCH_NS = time.perf_counter_ns()
_EPOCH_UNIX_NS = time.time_ns()


def clock_base() -> Dict[str, Any]:
    """The mapping from this process's event timestamps to wall-clock
    time: an event's ``ts`` (microseconds) is relative to ``unix_ns``.
    Persisted per rank (``obs/rank<k>/clock.json``) so ``obs-report``
    can merge ranks onto one clock-aligned timeline."""
    return {"unix_ns": _EPOCH_UNIX_NS, "ts_unit": "us"}


def from_unix_s(seconds: float) -> int:
    """A ``time.time()`` reading as a ``perf_counter_ns`` value of this
    process (the inverse of ``clock_base``), so an interval somebody else
    measured on the wall clock can go to ``emit()``: JAX stamps its compile
    events so (``observability/compile_ledger.py``)."""
    return int(seconds * 1e9) - _EPOCH_UNIX_NS + _EPOCH_NS


def set_sink(path: Optional[str]) -> None:
    """Install (or clear) a process-wide fallback trace destination —
    the flight recorder's per-rank ``trace.jsonl``. Explicit choices
    (``XGBTPU_TRACE``, ``set_config(trace_path=...)``) still win, and a
    sink path is written EXACTLY (no ``.rank<r>`` suffix: the sink is
    already rank-scoped)."""
    global _sink
    with _lock:
        _sink = path


def trace_path() -> Optional[str]:
    """The active trace destination, or None when tracing is off. The
    ``XGBTPU_TRACE`` env var wins; otherwise the (thread-local)
    ``set_config(trace_path=...)`` value."""
    p = os.environ.get(_ENV_PATH)
    if p:
        return p
    from ..config import _state  # direct read: no per-span dict copy

    return _state().get("trace_path") or _sink or None


def enabled() -> bool:
    return trace_path() is not None


@functools.lru_cache(maxsize=1)
def _not_staging(jax):
    """JAX's own "no program is being staged" predicate. jax 0.9 keeps it
    private; the public lookup alone used to fail into "always host side",
    and staged spans were recorded."""
    fn = getattr(jax.core, "trace_state_clean", None)
    if fn is None:
        try:
            from jax._src.core import trace_state_clean as fn
        except ImportError:
            fn = lambda: True  # noqa: E731
    return fn


def _host_side() -> bool:
    """False while JAX is staging (tracing) a program: a span opened there
    would measure trace-time, not run-time, and would fire once per
    compilation instead of once per execution."""
    jax = sys.modules.get("jax")
    return jax is None or _not_staging(jax)()


def _rank_world() -> tuple:
    # lock-guarded (lint CC402): resolving the rank can initialize the JAX
    # backend; two flushing threads racing the latch would both pay that
    # (and one could read a half-initialized backend)
    global _rank_cache
    with _lock:
        if _rank_cache is None:
            try:
                jax = sys.modules.get("jax")
                if jax is None:
                    raise RuntimeError("jax not imported")
                _rank_cache = (jax.process_index(), jax.process_count())
            except Exception:
                _rank_cache = (0, 1)
        return _rank_cache


def _tid() -> int:
    ident = threading.get_ident()
    t = _tid_map.get(ident)
    if t is None:
        with _lock:
            t = _tid_map.setdefault(ident, len(_tid_map))
    return t


def _record(ev: Dict[str, Any]) -> None:
    global _dropped
    with _lock:
        if len(_buffer) == _buffer.maxlen:
            _dropped += 1
            from .metrics import REGISTRY

            REGISTRY.counter(
                "trace_events_dropped_total",
                "Trace events evicted from the ring buffer before flush",
            ).inc()
        _buffer.append(ev)


class _Span:
    """An open span; emits one Chrome 'X' (complete) event on exit, and
    holds the profiler annotation of the same extent open meanwhile."""

    __slots__ = ("name", "args", "_note", "_t0")

    def __init__(self, name: str, args: Dict[str, Any], note=None):
        self.name = name
        self.args = args
        self._note = note

    def __enter__(self) -> "_Span":
        if self._note is not None:
            self._note.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        t1 = time.perf_counter_ns()
        if self._note is not None:
            self._note.__exit__(exc_type, exc, tb)
        # NOTE: no rank lookup here — the rank is constant per process and
        # resolving it can initialize the JAX backend (hundreds of ms);
        # ``flush`` stamps every event's ``pid`` once instead.
        ev = {
            "name": self.name,
            "ph": "X",
            "ts": (self._t0 - _EPOCH_NS) // 1000,
            "dur": max((t1 - self._t0) // 1000, 1),
            "tid": _tid(),
        }
        if self.args:
            ev["args"] = self.args
        _record(ev)
        return False


class _NoopSpan:
    __slots__ = ()

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        return False


_NOOP = _NoopSpan()


def span(name: str, **args: Any):
    """Context manager timing a host-side phase. ``args`` become the
    event's Chrome ``args`` payload (keep them JSON-scalar) and the
    annotation's. With a ``jax.profiler`` session live the span is open on
    the profiler's clock as ``xgb.<name>``; with tracing on it is recorded
    as a Chrome event; with neither, or while JAX is staging, the call
    returns a shared no-op."""
    jax = sys.modules.get("jax")
    profiled = (jax is not None
                and jax.profiler.TraceAnnotation.is_enabled())
    traced = enabled()
    if not (profiled or traced) or not _host_side():
        return _NOOP
    note = (jax.profiler.TraceAnnotation(_ANNOTATION_PREFIX + name, **args)
            if profiled else None)
    return _Span(name, args, note) if traced else note


_stage_tls = threading.local()  # .open: the innermost open _Stage


def _hbm_peak() -> Optional[int]:
    """``peak_bytes_in_use`` of the fullest local device: the allocator's
    high-water mark since the process started. None where the backend
    keeps no statistics (the CPU)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    peak = None
    for d in jax.local_devices():
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peak = max(peak or 0, int(stats["peak_bytes_in_use"]))
    return peak


class _Stage:
    """An open set-up stage (``stage()``). ``seconds``, set at the close,
    is the stage's own time: its extent less the stages that ran inside
    it."""

    __slots__ = ("name", "seconds", "_span", "_parent", "_inner", "_t0",
                 "_peak0")

    def __init__(self, name: str, args: Dict[str, Any]):
        self.name = name
        self.seconds = 0.0
        self._span = span(name, **args)
        self._inner = 0.0

    def __enter__(self) -> "_Stage":
        self._parent = getattr(_stage_tls, "open", None)
        _stage_tls.open = self
        self._peak0 = _hbm_peak()
        self._span.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        wall = time.perf_counter() - self._t0
        self._span.__exit__(exc_type, exc, tb)
        _stage_tls.open = self._parent
        if self._parent is not None:
            self._parent._inner += wall
        self.seconds = max(wall - self._inner, 0.0)
        from .metrics import REGISTRY

        REGISTRY.counter(
            "setup_stage_seconds_total",
            "Own seconds of the data plane's set-up stages, each closed on "
            "its result (stages inside a stage are taken out of it)",
        ).labels(stage=self.name).inc(self.seconds)
        REGISTRY.counter(
            "setup_stage_events_total", "Set-up stages closed",
        ).labels(stage=self.name).inc()
        peak = _hbm_peak()
        if peak is not None:
            mark = REGISTRY.gauge(
                "hbm_peak_bytes",
                "The device's peak_bytes_in_use at the close of the stage's "
                "last run that raised it (fullest local device)",
            ).labels(stage=self.name)
            if not mark.value or peak > (self._peak0 or 0):
                mark.set(peak)
        return False


def stage(name: str, **args: Any) -> _Stage:
    """Context manager round one stage of the data plane's set-up (upload,
    sketch, bins, one-hot, rank layout: once a ``DMatrix`` or once a fit,
    never a round). Always on: it adds the stage's own seconds to
    ``setup_stage_seconds_total{stage}``, counts it in
    ``setup_stage_events_total{stage}`` and, where the backend keeps memory
    statistics, sets ``hbm_peak_bytes{stage}`` to the device's high-water
    mark if this run of the stage raised it (so a later, smaller run of the
    stage does not take over a mark another program set). It opens the
    ``span`` of the same name for its extent. The call site ends the block
    on ``jax.block_until_ready`` of the stage's result, which is what makes
    the seconds the work's and not the enqueue's."""
    return _Stage(name, args)


def emit(name: str, start_ns: int, end_ns: int, cat: Optional[str] = None,
         **args: Any) -> None:
    """Record a complete event from a pre-measured ``perf_counter_ns``
    interval — for instrumentation that already owns its clock reads
    (``utils.timer.Monitor``). ``cat`` becomes the Chrome category
    (``trace-report`` groups span time by it: serving vs train vs
    collective)."""
    if not enabled() or not _host_side():
        return
    ev = {
        "name": name,
        "ph": "X",
        "ts": (start_ns - _EPOCH_NS) // 1000,
        "dur": max((end_ns - start_ns) // 1000, 1),
        "tid": _tid(),
    }
    if cat:
        ev["cat"] = cat
    if args:
        ev["args"] = args
    _record(ev)


def emit_async(name: str, track: str, start_ns: int, end_ns: int,
               cat: str = "serving", **args: Any) -> None:
    """Record one nestable-async span (Chrome phases 'b'/'e') on the
    track keyed ``(cat, track)`` — Perfetto renders every event sharing
    that key as one async lane, so a serving request's whole lifetime
    (queue -> batch wait -> dispatch) reads as a single track regardless
    of which thread touched it. Timestamps are pre-measured
    ``perf_counter_ns`` values (the serving layer stamps stages as they
    happen but emits only at completion, off the hot path)."""
    emit_async_track(track, [(name, start_ns, end_ns, args or None)],
                     cat=cat)


def emit_async_track(track: str,
                     spans: List[tuple],
                     cat: str = "serving") -> None:
    """Batched :func:`emit_async`: every ``(name, start_ns, end_ns,
    args-or-None)`` in ``spans`` lands on the ``(cat, track)`` async lane
    with ONE enabled check and one buffer lock acquisition. The serving
    recorder emits a request's whole track (request + queue_wait +
    batch_wait + dispatch) per completion, so per-event overhead is what
    the ≤2% serving pin actually measures."""
    if not spans or not enabled() or not _host_side():
        return
    tid = _tid()
    sid = str(track)
    epoch = _EPOCH_NS
    events: List[Dict[str, Any]] = []
    push = events.append
    for name, start_ns, end_ns, args in spans:
        ts0 = (start_ns - epoch) // 1000
        ts1 = (end_ns - epoch) // 1000
        begin: Dict[str, Any] = {"name": name, "ph": "b", "cat": cat,
                                 "id": sid, "ts": ts0, "tid": tid}
        if args:
            begin["args"] = args
        push(begin)
        push({"name": name, "ph": "e", "cat": cat, "id": sid,
              "ts": ts1 if ts1 > ts0 else ts0 + 1, "tid": tid})
    global _dropped
    dropped = 0
    with _lock:
        for ev in events:
            if len(_buffer) == _buffer.maxlen:
                dropped += 1
            _buffer.append(ev)
        _dropped += dropped
    if dropped:
        from .metrics import REGISTRY

        REGISTRY.counter(
            "trace_events_dropped_total",
            "Trace events evicted from the ring buffer before flush",
        ).inc(dropped)


def instant(name: str, **args: Any) -> None:
    """A zero-duration marker event (Chrome phase 'i')."""
    if not enabled() or not _host_side():
        return
    ev = {
        "name": name,
        "ph": "i",
        "s": "t",
        "ts": (time.perf_counter_ns() - _EPOCH_NS) // 1000,
        "tid": _tid(),
    }
    if args:
        ev["args"] = args
    _record(ev)


def _out_path(path: str) -> str:
    if path == _sink:
        return path  # the sink is already a rank-scoped destination
    rank, world = _rank_world()
    return f"{path}.rank{rank}" if world > 1 else path


def flush(path: Optional[str] = None) -> Optional[str]:
    """Drain the ring buffer to ``path`` (default: the active trace path),
    appending to earlier flushes. Returns the written path, or None when
    tracing is off and no path was given."""
    path = path or trace_path()
    if path is None:
        return None
    path = _out_path(path)
    with _lock:
        events = list(_buffer)
        _buffer.clear()
        need_header = path not in _headers_written
        _headers_written.add(path)
    if need_header:
        try:
            need_header = os.path.getsize(path) == 0
        except OSError:
            need_header = True
    rank, _ = _rank_world()
    with open(path, "a") as f:
        if need_header:
            f.write("[\n")
            meta = {
                "name": "process_name", "ph": "M", "pid": rank, "tid": 0,
                "args": {"name": f"xgboost_tpu rank {rank}"},
            }
            f.write(json.dumps(meta) + ",\n")
        for ev in events:
            ev.setdefault("pid", rank)
            f.write(json.dumps(ev) + ",\n")
    return path


def reset() -> None:
    """Clear buffered events and per-path header state (tests)."""
    global _dropped, _rank_cache
    with _lock:
        _buffer.clear()
        _headers_written.clear()
        _dropped = 0
        _rank_cache = None


def dropped_count() -> int:
    return _dropped


def load_trace(path: str) -> List[Dict[str, Any]]:
    """Parse a trace file written by ``flush`` (or any Chrome trace-event
    JSON: complete array, trailing-comma/unterminated array, JSONL, or a
    ``{"traceEvents": [...]}`` wrapper) into a list of event dicts."""
    with open(path) as f:
        text = f.read().strip()
    if not text:
        return []
    try:
        doc = json.loads(text)
    except json.JSONDecodeError:
        doc = None
    if doc is None and text.startswith("["):
        # the spec's unterminated-array form: close it
        doc = json.loads(text.rstrip().rstrip(",") + "\n]")
    if isinstance(doc, dict):
        doc = doc.get("traceEvents", [])
    if doc is None:
        # JSONL: one event object per line
        doc = [json.loads(ln.rstrip(",")) for ln in text.splitlines()
               if ln.strip() and ln.strip() not in ("[", "]")]
    if not isinstance(doc, list) or not all(
            isinstance(e, dict) for e in doc):
        raise ValueError(f"{path}: not a Chrome trace event file")
    return doc


import atexit  # noqa: E402

atexit.register(lambda: flush() if enabled() and len(_buffer) else None)
