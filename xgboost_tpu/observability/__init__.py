"""Unified telemetry: span tracing, metrics registry, comms accounting.

The one observability layer for the training stack (ISSUE 1), replacing
the reference's three disconnected tools (``common::Monitor`` wall-clock
accumulators, NVTX ranges, ``TrainingObserver`` dumps):

- ``trace`` — ``span("hist_build", node=k)`` context managers emitting a
  Chrome trace-event timeline (Perfetto / ``chrome://tracing``), enabled
  by ``XGBTPU_TRACE=<path>`` or ``set_config(trace_path=...)``;
- ``metrics`` — the process-wide ``REGISTRY`` of counters / gauges /
  histograms with Prometheus text exposition and JSON snapshots
  (``utils.timer.Monitor`` feeds it as a thin adapter);
- ``comms`` — collective ops/bytes accounting for ``collective.py`` and
  the mesh psum / all_gather paths;
- ``compile_ledger`` — the always-on set-up ledger: every program's
  trace, lowering, compile and cache load from ``jax.monitoring``'s own
  events, beside the data plane's stages (``trace.stage``: upload, sketch,
  bins, one-hot, each closed on its result with the HBM mark);
  ``setup_ledger()`` returns the table;
- ``flight`` — the always-on per-round flight recorder (ring buffer,
  durable ``run_dir/obs/rank<k>/`` sink, black-box dumps, profiling
  window) — ISSUE 7;
- ``report`` — the ``python -m xgboost_tpu trace-report`` summarizer
  (per-span self times, span-category totals: serving vs train vs
  collective);
- ``fleet`` — the ``python -m xgboost_tpu obs-report`` cross-rank
  merger (clock-aligned trace, metrics rollup, per-round fleet table);
- ``serve_report`` — the ``python -m xgboost_tpu serve-report``
  serving-plane report (per-model latency percentiles, shed/degrade
  timeline, coalescing, worst-request exemplars) over a model server's
  ``run_dir/obs/server/`` sink (``serving/obs.py`` — ISSUE 9).

Everything is a shared no-op per call site when disabled (``trace.py`` says
what that costs), and never records from inside ``jit``-traced code
(host-side only). While a ``jax.profiler`` session is live every ``span()``
is also open on the profiler's clock as ``xgb.<name>``.
"""

from . import comms, metrics, trace  # noqa: F401
from . import compile_ledger, flight  # noqa: F401  (they build on both)
from .compile_ledger import setup_ledger  # noqa: F401
from .flight import RECORDER  # noqa: F401
from .metrics import REGISTRY, MetricsRegistry, get_registry  # noqa: F401
from .trace import (  # noqa: F401
    emit,
    enabled,
    flush,
    instant,
    load_trace,
    span,
    stage,
    trace_path,
)

__all__ = [
    "trace", "metrics", "comms", "flight", "compile_ledger",
    "span", "stage", "instant", "emit", "enabled", "flush", "trace_path",
    "load_trace", "setup_ledger",
    "REGISTRY", "MetricsRegistry", "get_registry", "RECORDER",
]

# the listeners of the set-up ledger, from the package's import on: the
# first program a process builds is in it
compile_ledger.install()
