"""Failure classification + bounded retry with backoff and deadlines.

The reference's robustness layer classifies failures implicitly — rabit
retries transient socket errors (``allreduce_base.h`` ReConnectLinks),
``gpu_hist`` treats allocation failure as a sizing problem, and anything
else kills the worker so the tracker restarts it from the last checkpoint.
Here the classification is explicit and shared by every fallible path:

- ``TRANSIENT``  — worth retrying in place (device busy, dropped link,
  injected chaos, interrupted IO). The default for anything unrecognized:
  a misclassified transient costs one wasted retry, a misclassified
  permanent poisons a capability.
- ``RESOURCE``   — the attempt was too big for the machine (HBM OOM,
  ``RESOURCE_EXHAUSTED``). Retrying the same shape is futile; callers
  shrink the job or degrade the capability.
- ``PERMANENT``  — this configuration can never work on this runtime
  (Mosaic rejects, scoped-vmem overflow, ``NotImplementedError``).

``RetryPolicy`` is the one retry loop of the package: bounded attempts,
exponential backoff with *deterministic* jitter (no RNG — reproducible
schedules), an optional wall-clock deadline, and per-site budgets from
``XGBTPU_RETRY`` (a bare int, or ``site=N,*=M`` — the same grammar as
``XGBTPU_RETRACE_BUDGET``, ``analysis/retrace.py``). Every failure is
recorded as ``faults_total{site,kind}`` in the metrics registry and every
retry as ``retries_total{site}``, so a run's metrics snapshot carries its
full fault history.
"""

from __future__ import annotations

import os
import time
import zlib
from typing import Callable, Optional, Sequence, Tuple

__all__ = [
    "TRANSIENT", "RESOURCE", "PERMANENT", "KINDS",
    "classify", "record_failure", "retry_budget", "RetryPolicy",
    "is_worker_loss", "should_reroute",
]

TRANSIENT = "transient"
RESOURCE = "resource"
PERMANENT = "permanent"
KINDS = (TRANSIENT, RESOURCE, PERMANENT)

_ENV_RETRY = "XGBTPU_RETRY"

# compiler-layer failure signatures: this (shape, kernel) pair can never
# compile on this runtime. Checked BEFORE the resource signatures — a
# scoped-VMEM overflow message also says "exhausted", but re-trying or
# shrinking rows won't fix a kernel whose working set missed VMEM.
_PERMANENT_TYPES = ("NotImplementedError", "MosaicError")
_PERMANENT_SUBSTRINGS = ("vmem", "mosaic")

# allocator-layer failure signatures: the attempt outgrew the device/host.
_RESOURCE_SUBSTRINGS = (
    "resource_exhausted", "resource exhausted", "out of memory", "oom",
    "bytes_limit", "failed to allocate", "allocation failure",
)


# peer-death failure signatures: a collective that broke because the far
# end went away (gloo ring break, TCP reset, coordination-service loss).
# Distinct from plain TRANSIENT: retrying in place is futile AND unsafe
# (a one-sided retry desyncs SPMD lockstep) — the elastic layer responds
# by resizing the world instead (docs/distributed.md, Elastic training).
_WORKER_LOSS_SUBSTRINGS = (
    "connection closed by peer", "connection reset", "connection refused",
    "broken pipe", "socket closed", "peer closed",
    # specific gloo op failures only — a bare "gloo" would classify
    # setup/config errors ("gloo transport is not available") as deaths
    "gloo all-reduce failed", "gloo allgather failed",
    "gloo all-gather failed", "gloo broadcast failed", "gloo reduce failed",
    "heartbeat timeout", "task has failed", "worker_lost",
)


def is_worker_loss(exc: BaseException) -> bool:
    """Whether ``exc``'s signature reads as a dead communication peer.
    Chaos faults injected at the ``worker_kill`` / ``heartbeat_drop``
    sites count as peer loss (they script exactly that failure)."""
    site = getattr(exc, "site", None)
    if site in ("worker_kill", "heartbeat_drop"):
        return True
    msg = str(exc).lower()
    return any(t in msg for t in _WORKER_LOSS_SUBSTRINGS)


def should_reroute(exc: BaseException) -> bool:
    """The serving-fleet verdict for a request that failed *in transit*
    to a replica (``serving/fleet/router.py``): True when the failure
    reads as a lost or draining peer — a bare connection exception type
    (reset / refused / broken pipe / EOF mid-response), a socket timeout,
    or any :func:`is_worker_loss` message signature. The router then
    retries the request ONCE on a healthy replica: predict requests are
    idempotent, so a re-route can duplicate work but never corrupt an
    answer. Failures the *replica itself* reported (a typed RequestError,
    a shed) ride the response line and are never re-routed — the replica
    is alive and already classified them."""
    if isinstance(exc, (ConnectionError, EOFError, TimeoutError)):
        return True
    return is_worker_loss(exc)


def classify(exc: BaseException) -> str:
    """Map an exception to a failure kind. Chaos-injected faults carry
    their scripted kind (``chaos.ChaosError``); everything else is
    recognized by type name or message signature, with TRANSIENT as the
    default — XlaRuntimeError/JaxRuntimeError wrap transient runtime
    failures (device busy, dropped link) as well as compile-layer ones, so
    the type alone must never condemn a configuration (ADVICE r4)."""
    scripted = getattr(exc, "chaos_kind", None)
    if scripted in KINDS:
        return scripted
    if isinstance(exc, MemoryError):
        return RESOURCE
    name = type(exc).__name__
    msg = str(exc).lower()
    if name in _PERMANENT_TYPES or any(
            t in msg for t in _PERMANENT_SUBSTRINGS):
        return PERMANENT
    if any(t in msg for t in _RESOURCE_SUBSTRINGS):
        return RESOURCE
    return TRANSIENT


def record_failure(site: str, exc: Optional[BaseException] = None,
                   kind: Optional[str] = None) -> str:
    """Classify (unless ``kind`` is given) and account one failure at
    ``site``: bumps ``faults_total{site,kind}`` and drops an instant event
    on the active trace. Returns the kind."""
    if kind is None:
        kind = classify(exc) if exc is not None else TRANSIENT
    from ..observability.metrics import REGISTRY
    from ..observability import trace

    REGISTRY.counter(
        "faults_total", "Failures observed at resilience sites by kind",
    ).labels(site=site, kind=kind).inc()
    trace.instant("fault", site=site, kind=kind,
                  error=type(exc).__name__ if exc is not None else "")
    return kind


def retry_budget(site: str) -> Optional[int]:
    """Retry count for ``site`` per ``XGBTPU_RETRY``, or None when the env
    var is unset / names neither the site nor ``*``. Grammar mirrors
    ``XGBTPU_RETRACE_BUDGET``: bare int, or ``site=N,*=M``."""
    raw = os.environ.get(_ENV_RETRY)
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
        if k == site:
            return iv
        if k == "*":
            default = iv
    return default


def _jitter(site: str, attempt: int, seed: int) -> float:
    """Deterministic jitter factor in [0.5, 1.0): hashed from (site,
    attempt, seed) so two processes with different seeds desynchronize
    their retries while a rerun of the same process reproduces its
    schedule exactly (no RNG state anywhere)."""
    h = zlib.crc32(f"{site}:{attempt}:{seed}".encode()) & 0xFFFFFFFF
    return 0.5 + (h / 2**32) * 0.5


class RetryPolicy:
    """Bounded retry for one site.

    ``retries`` is the number of RE-tries after the first attempt; the
    ``XGBTPU_RETRY`` env budget overrides it when set (so operators can
    turn retries on/off without code changes). Only failures whose
    classified kind is in ``retry_kinds`` are retried — by default just
    TRANSIENT: resource failures need shrinking and permanent ones need
    disabling, both the caller's decision. ``deadline`` bounds the TOTAL
    wall clock including backoff sleeps.
    """

    def __init__(self, site: str, retries: int = 0, *,
                 backoff_base: float = 0.05, backoff_cap: float = 2.0,
                 deadline: Optional[float] = None, seed: int = 0,
                 retry_kinds: Sequence[str] = (TRANSIENT,),
                 retry_types: Optional[Tuple[type, ...]] = None,
                 sleep: Callable[[float], None] = time.sleep):
        self.site = site
        self.retries = retries
        self.backoff_base = backoff_base
        self.backoff_cap = backoff_cap
        self.deadline = deadline
        self.seed = seed
        self.retry_kinds = tuple(retry_kinds)
        # when set, ONLY these exception types are retryable — a bracket
        # around a broad dispatch (the native round loop) must not absorb
        # unrelated transients that merely pass through it
        self.retry_types = retry_types
        self._sleep = sleep

    def attempts(self) -> int:
        env = retry_budget(self.site)
        n = self.retries if env is None else env
        return 1 + max(0, int(n))

    def backoff(self, attempt: int) -> float:
        """Sleep before retry ``attempt`` (1-based): exponential from
        ``backoff_base``, capped, scaled by deterministic jitter."""
        raw = min(self.backoff_base * (2 ** (attempt - 1)), self.backoff_cap)
        return raw * _jitter(self.site, attempt, self.seed)

    def run(self, fn: Callable, *args, **kwargs):
        """Call ``fn`` under the policy. Non-retryable kinds, exhausted
        budgets, and blown deadlines re-raise the original exception (the
        caller sees exactly what the operation saw)."""
        from ..observability.metrics import REGISTRY

        attempts = self.attempts()
        t0 = time.monotonic()
        for attempt in range(1, attempts + 1):
            try:
                return fn(*args, **kwargs)
            except Exception as e:
                kind = record_failure(self.site, e)
                if (self.retry_types is not None
                        and not isinstance(e, self.retry_types)) \
                        or kind not in self.retry_kinds \
                        or attempt >= attempts:
                    raise
                delay = self.backoff(attempt)
                if self.deadline is not None and (
                        time.monotonic() - t0 + delay) > self.deadline:
                    raise
                REGISTRY.counter(
                    "retries_total",
                    "Retry attempts issued by RetryPolicy",
                ).labels(site=self.site).inc()
                self._sleep(delay)
        raise AssertionError("unreachable")  # pragma: no cover


def retry_call(site: str, fn: Callable, *args, retries: int = 0,
               **policy_kwargs):
    """One-shot convenience: ``RetryPolicy(site, retries, ...).run(fn)``."""
    return RetryPolicy(site, retries, **policy_kwargs).run(fn, *args)
