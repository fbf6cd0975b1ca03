"""Collective-communication accounting: ops and bytes per reduction.

The reference's rabit layer had a single choke point for every collective;
here comms happen at two very different altitudes, and both report into the
same two counter families:

- **Host-side collectives** (``collective.allreduce``/``broadcast``, the
  ``multihost_utils.process_allgather`` helpers in ``parallel.mesh``):
  instrumented inline — exact payload byte counts, one record per call.
- **Device-side collectives** (the ``psum``/``all_gather`` ops *inside*
  compiled programs: histogram reductions in ``tree.grow_fused``, summary
  gathers in ``parallel.sketch``): an XLA program cannot call back into
  Python per op, so the *dispatch site* records the analytic per-execution
  volume (shapes are static, so the estimate is exact up to compiler
  rewrites). See ``record_grow_collectives`` / callers in
  ``parallel.grow`` and ``parallel.sketch``.

Metric families (in ``observability.metrics.REGISTRY``):

- ``collective_ops_total{op=...}``   — logical collective operations
- ``collective_bytes_total{op=...}`` — payload bytes reduced / gathered

``snapshot()`` returns ``{op: {"ops": n, "bytes": b}}`` for result
files.
"""

from __future__ import annotations

from typing import Dict

from .metrics import REGISTRY

__all__ = ["record", "snapshot", "grow_psum_bytes", "record_grow_collectives"]

_OPS_HELP = "Logical collective operations by kind"
_BYTES_HELP = "Payload bytes moved through collectives by kind"


def record(op: str, nbytes: int, n_ops: int = 1) -> None:
    """Account ``n_ops`` collective operations moving ``nbytes`` total
    payload bytes under the kind ``op`` (e.g. ``allreduce``, ``broadcast``,
    ``psum_hist``, ``all_gather_sketch``, ``process_allgather``). Doubles
    as the ``collective`` chaos-injection site: every accounted collective
    passes this choke point, so ``XGBTPU_CHAOS="collective:..."`` scripts
    a failing reduction without hardware (rabit-mock analog). Lazy import:
    the resilience layer depends on this package, not vice versa."""
    from ..resilience import chaos

    chaos.hit("collective")
    REGISTRY.counter("collective_ops_total", _OPS_HELP).labels(
        op=op).inc(n_ops)
    REGISTRY.counter("collective_bytes_total", _BYTES_HELP).labels(
        op=op).inc(nbytes)


def snapshot() -> Dict[str, Dict[str, float]]:
    out: Dict[str, Dict[str, float]] = {}
    for name, key in (("collective_ops_total", "ops"),
                      ("collective_bytes_total", "bytes")):
        fam = REGISTRY.get(name)
        if fam is None:
            continue
        for labels, child in fam.series():
            op = labels.get("op", "")
            out.setdefault(op, {"ops": 0.0, "bytes": 0.0})[key] = child.value
    return out


def grow_psum_bytes(max_depth: int, n_features: int, max_bin: int) -> int:
    """Per-tree histogram-AllReduce volume of the depthwise growers: one
    ``[F, 2K, B]`` float32 psum per level (K doubling each level) plus the
    8-byte root-total psum — the two collective sites of
    ``grow_tree_fused`` (the reference's hist/histogram.h:201 +
    InitRoot)."""
    total = 8  # root (G0, H0)
    for d in range(max_depth):
        total += n_features * (2 << d) * max_bin * 4
    return total


def record_grow_collectives(max_depth: int, n_features: int, max_bin: int,
                            n_trees: int = 1) -> None:
    """Account the device-side psums of ``n_trees`` distributed tree
    builds. Called at the dispatch site (host), since the psums themselves
    execute inside the compiled program."""
    record("psum_hist",
           grow_psum_bytes(max_depth, n_features, max_bin) * n_trees,
           n_ops=(max_depth + 1) * n_trees)
