"""Per-capability health state machine: HEALTHY → DEGRADED → DISABLED.

This module REPLACES the package's scattered fallback latches — the
pallas-predict shape blacklist (``predictor/__init__.py``) and the
hoisted one-hot build latch (``data/quantile.py``) — with one observable,
lock-guarded policy. The reference encodes the same idea structurally: ``gpu_hist``
sizes itself to the device instead of crash-looping, and rabit's mock
engine proves that a failed worker degrades to restart-from-checkpoint
rather than wedging the ring.

States (per capability, optionally per key — e.g. per forest shape):

- ``HEALTHY``   — the capability runs.
- ``DEGRADED``  — it recently failed; the next ``retry_after`` calls skip
  it (callers take their fallback), then ONE probe attempt is allowed. A
  "permanent" classification is really a heuristic (exception type +
  message matching), so nothing is condemned forever by default.
- ``DISABLED``  — ``disable_after`` cumulative failures (when configured):
  the capability stays off for the life of the process. ``success()``
  never resurrects a DISABLED entry; only ``reset()`` (tests/operator)
  does.

Every transition sets the ``degrade_state{capability=...}`` gauge (0/1/2,
worst state across keys), counts into ``faults_total{site,kind}`` (via
``policy.record_failure``), emits a trace instant, and logs — the
observable state the ad-hoc latches never had.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, Hashable, List, Optional, Tuple

from . import policy

__all__ = [
    "HEALTHY", "DEGRADED", "DISABLED", "STATE_NAMES",
    "CapabilityHealth", "capability", "capabilities",
    "snapshot", "worst", "reset",
]

HEALTHY = 0
DEGRADED = 1
DISABLED = 2
STATE_NAMES = {HEALTHY: "healthy", DEGRADED: "degraded",
               DISABLED: "disabled"}


def _publish(name: str, state: int) -> None:
    from ..observability.metrics import REGISTRY

    REGISTRY.gauge(
        "degrade_state",
        "Capability health: 0 healthy, 1 degraded, 2 disabled",
    ).labels(capability=name).set(state)


def _announce(name: str, key: Hashable, old: int, new: int,
              detail: str) -> None:
    from ..observability import flight, trace
    from ..utils import console_logger

    trace.instant("degrade_transition", capability=name,
                  key=repr(key) if key is not None else "",
                  frm=STATE_NAMES[old], to=STATE_NAMES[new])
    flight.RECORDER.event("degrade_transition", capability=name,
                          frm=STATE_NAMES[old], to=STATE_NAMES[new])
    msg = (f"capability {name!r}"
           + (f" key={key!r}" if key is not None else "")
           + f": {STATE_NAMES[old]} -> {STATE_NAMES[new]} ({detail})")
    if new == HEALTHY:
        console_logger.info(msg)
    else:
        console_logger.warning(msg)


class CapabilityHealth:
    """Health of one capability, optionally keyed (``key=None`` is the
    process-wide entry; the pallas predictor keys by forest shape so one
    impossible shape does not blacklist the others)."""

    def __init__(self, name: str, retry_after: int = 64,
                 disable_after: Optional[int] = None,
                 disable_kinds: Tuple[str, ...] = (policy.RESOURCE,
                                                   policy.PERMANENT)):
        self.name = name
        self.retry_after = max(1, int(retry_after))
        self.disable_after = disable_after
        # only these kinds count toward disable_after: a capability whose
        # PERMANENT failure is deterministic-per-runtime (compiler reject)
        # can exclude RESOURCE, so temporary memory pressure degrades
        # (retry later) instead of disabling for the process lifetime
        self.disable_kinds = tuple(disable_kinds)
        self._lock = threading.Lock()
        # key -> [state, countdown, cumulative_fails]
        self._entries: Dict[Hashable, List[int]] = {}

    # ------------------------------------------------------------------
    def allowed(self, key: Hashable = None) -> bool:
        """Whether the capability should be attempted now. While DEGRADED
        each call burns one unit of the countdown and returns False (the
        caller takes its fallback); when the countdown expires the entry
        returns to HEALTHY — with its failure count retained, so repeated
        degrade cycles still walk toward ``disable_after`` — and the NEXT
        call probes the capability again."""
        transition: Optional[Tuple[Hashable, int, int, str]] = None
        with self._lock:
            e = self._entries.get(key)
            if e is None:
                out = True
            elif e[0] == DISABLED:
                out = False
            elif e[0] == DEGRADED:
                e[1] -= 1
                if e[1] <= 0:
                    e[0] = HEALTHY
                    transition = (key, DEGRADED, HEALTHY,
                                  "retry window expired; next call probes")
                    self._publish_locked()
                out = False
            else:
                out = True  # HEALTHY probation entry (fails retained)
        if transition is not None:
            _announce(self.name, *transition)
        return out

    def failure(self, exc: Optional[BaseException] = None, *,
                key: Hashable = None, kind: Optional[str] = None,
                retry_after: Optional[int] = None) -> str:
        """Record a failed attempt. TRANSIENT failures count (``faults_total``)
        but do not change state — the caller falls back this once and may
        try again immediately. RESOURCE / PERMANENT failures degrade the
        entry for ``retry_after`` calls, or disable it outright once
        ``disable_after`` cumulative failures accrue. Returns the kind."""
        kind = policy.record_failure(self.name, exc, kind=kind)
        if kind == policy.TRANSIENT:
            return kind
        transition = None
        with self._lock:
            e = self._entries.setdefault(key, [HEALTHY, 0, 0])
            old = e[0]
            if old == DISABLED:
                return kind
            e[2] += 1
            if (self.disable_after is not None
                    and kind in self.disable_kinds
                    and e[2] >= self.disable_after):
                e[0] = DISABLED
                detail = (f"{e[2]} failures >= disable_after="
                          f"{self.disable_after}")
            else:
                e[0] = DEGRADED
                e[1] = max(1, int(retry_after if retry_after is not None
                                  else self.retry_after))
                detail = f"kind={kind}; retry after {e[1]} skipped calls"
            if e[0] != old:
                transition = (key, old, e[0], detail)
            self._publish_locked()
        if transition is not None:
            _announce(self.name, *transition)
        return kind

    def success(self, key: Hashable = None) -> None:
        """A working attempt: full recovery (entry dropped, fails zeroed)
        — unless DISABLED, which only ``reset()`` clears."""
        with self._lock:
            e = self._entries.get(key)
            if e is None or e[0] == DISABLED:
                return
            old = e[0]
            fails = e[2]
            del self._entries[key]
            self._publish_locked()
        if fails:
            _announce(self.name, key, old, HEALTHY, "attempt succeeded")

    # ------------------------------------------------------------------
    def state(self, key: Hashable = None) -> int:
        with self._lock:
            e = self._entries.get(key)
            return HEALTHY if e is None else e[0]

    def worst_state(self) -> int:
        with self._lock:
            return max((e[0] for e in self._entries.values()),
                       default=HEALTHY)

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "capability": self.name,
                "worst": STATE_NAMES[max(
                    (e[0] for e in self._entries.values()),
                    default=HEALTHY)],
                "entries": {
                    repr(k): {"state": STATE_NAMES[e[0]],
                              "countdown": e[1], "fails": e[2]}
                    for k, e in self._entries.items()
                },
            }

    def reset(self) -> None:
        with self._lock:
            self._entries.clear()
            self._publish_locked()

    def _publish_locked(self) -> None:
        _publish(self.name, max((e[0] for e in self._entries.values()),
                                default=HEALTHY))


# ---------------------------------------------------------------------------
# process-wide capability registry
# ---------------------------------------------------------------------------

_registry_lock = threading.Lock()
_capabilities: Dict[str, CapabilityHealth] = {}


def capability(name: str, *, retry_after: int = 64,
               disable_after: Optional[int] = None,
               disable_kinds: Tuple[str, ...] = (policy.RESOURCE,
                                                 policy.PERMANENT)
               ) -> CapabilityHealth:
    """Get-or-create the named capability. Creation publishes its gauge so
    every registered capability is visible in ``REGISTRY.exposition()``
    even while healthy. Config args apply only on creation (first caller
    wins — capabilities are owned by the module that guards the path)."""
    with _registry_lock:
        cap = _capabilities.get(name)
        created = cap is None
        if created:
            cap = _capabilities[name] = CapabilityHealth(
                name, retry_after=retry_after, disable_after=disable_after,
                disable_kinds=disable_kinds)
    if created:
        _publish(name, HEALTHY)
    return cap


def capabilities() -> Dict[str, CapabilityHealth]:
    with _registry_lock:
        return dict(_capabilities)


def snapshot() -> Dict[str, Any]:
    """JSON-able view of every capability (result-file sidecars)."""
    return {name: cap.snapshot() for name, cap in capabilities().items()}


def worst(name: str) -> int:
    """Worst state across the named capability's keys — ``HEALTHY`` when
    the capability was never registered. Read-only: unlike ``allowed()``
    this burns no retry countdown, so routing layers (the serving
    admission controller) can poll it per request without racing the
    owner's own probe schedule."""
    with _registry_lock:
        cap = _capabilities.get(name)
    return HEALTHY if cap is None else cap.worst_state()


def reset() -> None:
    """Clear every capability's state (tests). Registered capabilities
    stay registered; their gauges return to HEALTHY."""
    for cap in capabilities().values():
        cap.reset()
