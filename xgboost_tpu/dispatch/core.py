"""Kernel-op dispatch registry: ONE table that routes every op.

The reference builds every pluggable tier on ``dmlc::Registry`` factory
glue (PAPER.md §1); ``registry.py`` already applies that at the framework
level (objectives / metrics / updaters / boosters). This module extends
the pattern DOWN to the kernel layer, replacing the ad-hoc per-call-site
backend branches (pallas-vs-XLA ``if``s in ``tree/hist_kernel.py`` and
``tree/grow_fused.py``, ``XGBTPU_NATIVE_*`` env kill switches, the
serving thread-local ``force_native`` route) with a single lookup:

    dispatch.resolve("level_hist", Ctx(platform=..., features=F, ...))

Each op (``level_hist``, ``level_partition``, ``depth_scan``,
``onehot_build``, ``predict_walk``, ``leaf_delta``, ``level_update``)
registers its implementations (``pallas`` / ``xla`` / ``native`` / ...)
with applicability predicates and a per-platform preference order
(``dispatch/ops.py``). ``resolve`` integrates, in order:

- **pins** — ``XGBTPU_DISPATCH="level_hist=native,depth_scan=unrolled,
  predict_walk=!native,*=auto"``: ``op=impl`` forces an impl, ``op=!impl``
  bans one, ``op=auto`` clears. The legacy kill switches
  (``XGBTPU_NATIVE_HIST=0``, ``XGBTPU_DEPTH_SCAN=0``,
  ``XGBTPU_NATIVE_SERVING=0``) are translated to pins HERE — one compat
  shim, deprecation-warned once — so they keep flipping their routes.
- **capability state** — an impl carrying a ``resilience.degrade``
  capability is skipped (read-only ``degrade.worst``: no retry countdown
  is burned) while that capability is non-HEALTHY; the fallback decision
  carries ``reason="degraded"``. This replaces the serving-side
  ``serving_context(force_native=)`` TLS hack: degrade routing is now a
  property of the table, not of the calling thread.
- **preference** — deterministic per-platform rank; first applicable +
  available impl wins with ``reason="preferred"`` (or ``"unavailable"``
  when a preferred impl's build/runtime probe failed).

Observability: every resolution counts into
``dispatch_decisions_total{op,impl,reason}``; a route *change* for a
given (op, ctx) emits a trace instant and a flight-recorder event; the
flight black box embeds the resolved table (``table_snapshot()``); and
``python -m xgboost_tpu dispatch-report`` prints the fully-resolved
op × impl × reason table for the current platform.

Resolution is cached per (op, ctx-key, pins, capability-state) — the env
tuple and capability states ARE the cache key, so a pin or degrade
change re-resolves naturally and everything else is a dict hit. Training
ops resolve at trace time (once per compile); the serving op resolves
per request at ~µs cost.
"""

from __future__ import annotations

import os
import threading
from typing import (Any, Callable, Dict, Hashable, List, NamedTuple,
                    Optional, Sequence, Tuple)

__all__ = [
    "Ctx", "Decision", "DispatchError", "KernelImpl",
    "register", "set_report_ctx", "resolve", "note", "explain", "op_names",
    "pinned_off", "degraded", "last_decisions", "table_snapshot",
    "reset", "LEGACY_ENVS",
]

#: legacy kill-switch env vars -> the pin each one translates to
#: (the ONE place the old grammar is still understood)
LEGACY_ENVS: Tuple[Tuple[str, str, Tuple[Tuple[str, str], ...]], ...] = (
    ("XGBTPU_NATIVE_HIST", "0", (("level_hist", "!native"),
                                 ("level_partition", "!native"))),
    ("XGBTPU_DEPTH_SCAN", "0", (("depth_scan", "unrolled"),)),
    ("XGBTPU_NATIVE_SERVING", "0", (("predict_walk", "!native"),)),
    ("XGBTPU_SIBLING_SUB", "0", (("sibling_sub", "off"),)),
)

_DISPATCH_ENV = "XGBTPU_DISPATCH"

_CACHE_MAX = 512  # resolved decisions (keys include forest/level shapes)


class Ctx:
    """Immutable, hashable bag of the STATIC routing inputs a call site
    knows (platform, shape/bin widths, dtypes, flags). Everything
    volatile that predicates need must be passed in here by the call
    site — resolution is a pure function of (ctx, pins, capability
    state), which is exactly what makes it cacheable."""

    __slots__ = ("_items",)

    def __init__(self, **kw: Any) -> None:
        object.__setattr__(self, "_items", tuple(sorted(kw.items())))

    def get(self, key: str, default: Any = None) -> Any:
        for k, v in self._items:
            if k == key:
                return v
        return default

    @property
    def key(self) -> Tuple:
        return self._items

    def __setattr__(self, *a: Any) -> None:  # pragma: no cover
        raise AttributeError("Ctx is immutable")

    def __repr__(self) -> str:
        inner = ", ".join(f"{k}={v!r}" for k, v in self._items)
        return f"Ctx({inner})"


class KernelImpl(NamedTuple):
    """One registered implementation of an op."""

    op: str
    name: str
    pref: Tuple[Tuple[str, int], ...]  # platform -> rank ("*" = default)
    applicable: Callable[[Ctx], bool]
    available: Callable[[Ctx], bool]
    capability: Optional[str]  # resilience.degrade capability gating it
    cap_platforms: Optional[Tuple[str, ...]]  # None = every platform

    def rank(self, platform: str) -> int:
        d = dict(self.pref)
        return d.get(platform, d.get("*", 50))

    def cap_for(self, platform: str) -> Optional[str]:
        if self.capability is None:
            return None
        if self.cap_platforms is not None \
                and platform not in self.cap_platforms:
            return None
        return self.capability


class Decision(NamedTuple):
    """The resolved route for one (op, ctx)."""

    op: str
    impl: str
    reason: str  # preferred | pinned | degraded | unavailable
    detail: str = ""


class DispatchError(RuntimeError):
    """No implementation of an op resolves for the given context."""


class _State:
    """All mutable module state, lock-guarded behind one object (keeps
    traced callers from ever closing over a module-level dict — the
    RH202 hazard the lint gate fences)."""

    def __init__(self) -> None:
        self.lock = threading.Lock()
        # separate lock for the one-time ops import: register() takes
        # self.lock during that import, so the import must not hold it
        self.ops_lock = threading.Lock()
        self.impls: Dict[str, List[KernelImpl]] = {}
        self.report_ctx: Dict[str, Callable[[], Ctx]] = {}
        self.cache: Dict[Hashable, Decision] = {}
        self.routes: Dict[Hashable, str] = {}  # (op, ctx, excl) -> impl
        self.last: Dict[str, Decision] = {}  # op -> most recent decision
        self.pins_memo: Dict[Tuple, Tuple[Dict[str, str],
                                          Dict[str, Tuple[str, ...]]]] = {}
        self.warned: Dict[str, bool] = {}
        self.ops_loaded = False


_STATE = _State()


# ---------------------------------------------------------------------------
# registration
# ---------------------------------------------------------------------------


def register(op: str, name: str, *,
             pref: Sequence[Tuple[str, int]] = (("*", 50),),
             applicable: Optional[Callable[[Ctx], bool]] = None,
             available: Optional[Callable[[Ctx], bool]] = None,
             capability: Optional[str] = None,
             cap_platforms: Optional[Sequence[str]] = None) -> KernelImpl:
    """Register implementation ``name`` of ``op``. ``applicable`` gates
    on ctx facts (platform, shapes, dtypes) and skipping it is silent;
    ``available`` gates on build/runtime probes (toolchain, FFI load) and
    skipping it surfaces as ``reason="unavailable"``; ``capability``
    names the ``resilience.degrade`` capability that sheds this impl
    while non-HEALTHY (optionally only on ``cap_platforms``).

    Re-registering an (op, name) pair REPLACES the entry (last writer
    wins): a partially-failed ops import that re-runs must not wedge on
    its own survivors, and tests/plugins can override a row."""
    impl = KernelImpl(
        op=op, name=name, pref=tuple(pref),
        applicable=applicable or (lambda ctx: True),
        available=available or (lambda ctx: True),
        capability=capability,
        cap_platforms=tuple(cap_platforms) if cap_platforms else None)
    with _STATE.lock:
        row = _STATE.impls.setdefault(op, [])
        row[:] = [i for i in row if i.name != name]
        row.append(impl)
        _STATE.cache.clear()
    return impl


def set_report_ctx(op: str, factory: Callable[[], Ctx]) -> None:
    """Representative ctx for ``op`` on the current platform — what
    ``dispatch-report`` (and ``resolve(op)`` with no ctx) resolves."""
    with _STATE.lock:
        _STATE.report_ctx[op] = factory


def _ensure_ops() -> None:
    """Import the default op table exactly once. The loaded flag is set
    only AFTER the import succeeds (under its own lock), so a concurrent
    first resolver waits for the full table instead of racing a partial
    one, and a failed import is retried on the next resolve rather than
    latching the process broken."""
    if _STATE.ops_loaded:
        return
    with _STATE.ops_lock:
        if _STATE.ops_loaded:
            return
        from . import ops as _ops  # noqa: F401  (registers the table)

        with _STATE.lock:
            _STATE.ops_loaded = True


def op_names() -> List[str]:
    _ensure_ops()
    with _STATE.lock:
        return sorted(_STATE.impls)


# ---------------------------------------------------------------------------
# pins (XGBTPU_DISPATCH grammar + the legacy kill-switch shim)
# ---------------------------------------------------------------------------


def _warn_once(key: str, msg: str) -> None:
    with _STATE.lock:
        if _STATE.warned.get(key):
            return
        _STATE.warned[key] = True
    from ..utils import console_logger

    console_logger.warning(msg)


def _env_key() -> Tuple:
    return tuple(os.environ.get(name) for name, _, _ in LEGACY_ENVS) + (
        os.environ.get(_DISPATCH_ENV),)


def _parse_pins(env_key: Tuple) -> Tuple[Dict[str, str],
                                         Dict[str, Tuple[str, ...]]]:
    """(pins, bans) for the current env. Memoized on the raw env tuple so
    monkeypatched/updated env vars re-parse, unchanged ones hit a dict.
    Legacy envs are translated first; explicit ``XGBTPU_DISPATCH``
    entries override them (``op=auto`` clears both)."""
    with _STATE.lock:
        hit = _STATE.pins_memo.get(env_key)
        if hit is not None:
            return hit
    pins: Dict[str, str] = {}
    bans: Dict[str, List[str]] = {}

    def apply(op: str, val: str) -> None:
        if val == "auto":
            pins.pop(op, None)
            bans.pop(op, None)
        elif val.startswith("!"):
            bans.setdefault(op, []).append(val[1:])
        else:
            pins[op] = val

    for (name, trigger, mapped), raw in zip(LEGACY_ENVS, env_key):
        if raw == trigger:
            for op, val in mapped:
                apply(op, val)
            pin_text = ",".join(f"{op}={val}" for op, val in mapped)
            _warn_once(
                f"legacy:{name}",
                f"{name}={trigger} is deprecated: it now maps to the "
                f"dispatch pin XGBTPU_DISPATCH=\"{pin_text}\" "
                f"(docs/perf.md, 'Choosing a kernel')")
    spec = env_key[-1]
    if spec:
        for part in spec.split(","):
            part = part.strip()
            if not part:
                continue
            op, sep, val = part.partition("=")
            op, val = op.strip(), val.strip()
            if not sep or not val:
                _warn_once(f"badpin:{part}",
                           f"ignoring malformed {_DISPATCH_ENV} entry "
                           f"{part!r} (grammar: op=impl, op=!impl, op=auto)")
                continue
            if op == "*":
                continue  # *=auto is the documented explicit default
            apply(op, val)
    out = (pins, {op: tuple(v) for op, v in bans.items()})
    with _STATE.lock:
        if len(_STATE.pins_memo) > 64:
            _STATE.pins_memo.clear()
        _STATE.pins_memo[env_key] = out
    return out


def pinned_off(op: str, impl: str) -> bool:
    """Whether pins (legacy or explicit) route ``op`` away from ``impl``
    — banned outright, or positively pinned to a different impl. The
    compat read the old kill-switch helpers (``use_native_hist``)
    delegate to."""
    pins, bans = _parse_pins(_env_key())
    if impl in bans.get(op, ()):
        return True
    pin = pins.get(op)
    return pin is not None and pin != impl


# ---------------------------------------------------------------------------
# resolution
# ---------------------------------------------------------------------------


def _worst(cap: str) -> int:
    from ..resilience import degrade

    return degrade.worst(cap)


def _healthy() -> int:
    from ..resilience import degrade

    return degrade.HEALTHY


def _cap_states(op: str) -> Tuple:
    """(capability, worst-state) for every capability any impl of ``op``
    references — read-only (``degrade.worst``), so polling it per resolve
    never burns a DEGRADED entry's retry countdown."""
    with _STATE.lock:
        caps = sorted({i.capability for i in _STATE.impls.get(op, ())
                       if i.capability is not None})
    return tuple((c, _worst(c)) for c in caps)


def degraded(op: str) -> bool:
    """Whether any capability gating one of ``op``'s impls is currently
    non-HEALTHY (the serving admission controller's per-dispatch poll)."""
    _ensure_ops()
    healthy = _healthy()
    return any(state != healthy for _, state in _cap_states(op))


def _report_ctx(op: str) -> Ctx:
    with _STATE.lock:
        factory = _STATE.report_ctx.get(op)
    return factory() if factory is not None else Ctx(platform="cpu")


def _resolve_uncached(op: str, ctx: Ctx, exclude: Tuple[str, ...],
                      pins: Dict[str, str],
                      bans: Dict[str, Tuple[str, ...]]) -> Decision:
    with _STATE.lock:
        impls = [i for i in _STATE.impls.get(op, ())
                 if i.name not in exclude]
    if not impls:
        raise DispatchError(f"no implementations registered for op {op!r}"
                            + (f" outside {exclude}" if exclude else ""))
    platform = str(ctx.get("platform", ""))
    impls.sort(key=lambda i: (i.rank(platform), i.name))
    healthy = _healthy()
    op_bans = bans.get(op, ())
    pin = pins.get(op)
    blocker: Optional[str] = None
    if pin is not None and pin not in exclude:
        pinned = next((i for i in impls if i.name == pin), None)
        if pinned is None:
            _warn_once(f"unknownpin:{op}:{pin}",
                       f"dispatch pin {op}={pin} names no registered impl "
                       f"of {op!r}; auto-resolving")
        elif pinned.applicable(ctx) and pinned.available(ctx):
            return Decision(op, pin, "pinned", "pinned by env")
        else:
            blocker = "unavailable"
            _warn_once(f"deadpin:{op}:{pin}:{platform}",
                       f"dispatch pin {op}={pin} is not usable on "
                       f"{platform or 'this platform'}; auto-resolving")
    skipped: List[str] = []
    degraded_fallback: Optional[KernelImpl] = None
    for impl in impls:
        if impl.name in op_bans:
            blocker = blocker or "pinned"
            skipped.append(f"{impl.name}: banned by pin")
            continue
        if not impl.applicable(ctx):
            skipped.append(f"{impl.name}: inapplicable")
            continue
        cap = impl.cap_for(platform)
        if cap is not None and _worst(cap) != healthy:
            blocker = blocker or "degraded"
            skipped.append(f"{impl.name}: capability {cap!r} degraded")
            if degraded_fallback is None and impl.available(ctx):
                degraded_fallback = impl
            continue
        if not impl.available(ctx):
            blocker = blocker or "unavailable"
            skipped.append(f"{impl.name}: unavailable")
            continue
        detail = "; ".join(skipped) if skipped else ""
        return Decision(op, impl.name, blocker or "preferred", detail)
    if degraded_fallback is not None:
        # every healthy alternative is exhausted: serving on the degraded
        # impl beats failing the request outright (the pre-registry
        # behavior — e.g. a categorical forest on a degraded device still
        # predicted through the device path)
        return Decision(op, degraded_fallback.name, "degraded",
                        "no healthy alternative; serving on degraded impl: "
                        + "; ".join(skipped))
    raise DispatchError(
        f"op {op!r} resolves to nothing on {platform or 'this platform'}: "
        + "; ".join(skipped))


def resolve(op: str, ctx: Optional[Ctx] = None,
            exclude: Sequence[str] = ()) -> Decision:
    """Resolve ``op`` for ``ctx`` (default: the op's representative
    report ctx). ``exclude`` drops named impls from consideration — the
    call-site escape when a chosen impl's runtime envelope rejects the
    actual input (e.g. the native walker returning None) and the next
    candidate must be picked without re-fighting the whole table."""
    _ensure_ops()
    if ctx is None:
        ctx = _report_ctx(op)
    exclude = tuple(exclude)
    env_key = _env_key()
    cap_key = _cap_states(op)
    cache_key = (op, ctx.key, exclude, env_key, cap_key)
    with _STATE.lock:
        dec = _STATE.cache.get(cache_key)
    if dec is None:
        pins, bans = _parse_pins(env_key)
        dec = _resolve_uncached(op, ctx, exclude, pins, bans)
        with _STATE.lock:
            if len(_STATE.cache) > _CACHE_MAX:
                _STATE.cache.clear()
            _STATE.cache[cache_key] = dec
    # route-change tracking runs on hits AND misses: a recovery flip
    # (degrade clears -> the original healthy cache entry hits again)
    # must announce just like the first degrade did
    route_key = (op, ctx.key, exclude)
    with _STATE.lock:
        prev = _STATE.routes.get(route_key)
        _STATE.routes[route_key] = dec.impl
        _STATE.last[op] = dec
    if prev is not None and prev != dec.impl:
        _announce_route_change(op, prev, dec)
    _count(dec)
    return dec


def note(op: str, impl: Any, reason: str = "observed") -> None:
    """Count a route a call site took from what it observed in its own
    input (``level_trees``: how many of a round's trees a level call
    carried), into the same ``dispatch_decisions_total`` and route table
    as a resolution. No table row stands behind it: nothing to pin, ban
    or degrade."""
    dec = Decision(op, str(impl), reason)
    with _STATE.lock:
        _STATE.last[op] = dec
    _count(dec)


def _count(dec: Decision) -> None:
    from ..observability.metrics import REGISTRY

    REGISTRY.counter(
        "dispatch_decisions_total",
        "Kernel dispatch resolutions by op, chosen impl and reason",
    ).labels(op=dec.op, impl=dec.impl, reason=dec.reason).inc()


def _announce_route_change(op: str, frm: str, dec: Decision) -> None:
    from ..observability import flight, trace

    trace.instant("dispatch_route_change", op=op, frm=frm, to=dec.impl,
                  reason=dec.reason)
    flight.RECORDER.event("dispatch_route_change", op=op, frm=frm,
                          to=dec.impl, reason=dec.reason)


# ---------------------------------------------------------------------------
# introspection (report CLI, flight black box, the benchmark's printed routes)
# ---------------------------------------------------------------------------


def explain(op: str, ctx: Optional[Ctx] = None) -> List[Dict[str, str]]:
    """Per-impl verdicts for ``op`` under ``ctx`` — the report's rows.
    Status: chosen | pinned-off | degraded | unavailable | inapplicable |
    fallback (usable, outranked)."""
    _ensure_ops()
    if ctx is None:
        ctx = _report_ctx(op)
    env_key = _env_key()
    pins, bans = _parse_pins(env_key)
    try:
        dec: Optional[Decision] = resolve(op, ctx)
    except DispatchError:
        dec = None
    platform = str(ctx.get("platform", ""))
    healthy = _healthy()
    with _STATE.lock:
        impls = list(_STATE.impls.get(op, ()))
    impls.sort(key=lambda i: (i.rank(platform), i.name))
    rows: List[Dict[str, str]] = []
    for impl in impls:
        if dec is not None and impl.name == dec.impl:
            status, note = "chosen", dec.reason
        elif impl.name in bans.get(op, ()) or (
                pins.get(op) is not None and pins.get(op) != impl.name):
            status, note = "pinned-off", "pins route elsewhere"
        elif not impl.applicable(ctx):
            status, note = "inapplicable", f"not applicable on {platform}"
        else:
            cap = impl.cap_for(platform)
            if cap is not None and _worst(cap) != healthy:
                status, note = "degraded", f"capability {cap!r} unhealthy"
            elif not impl.available(ctx):
                status, note = "unavailable", "build/runtime probe failed"
            else:
                status, note = "fallback", "usable, outranked by preference"
        rows.append({"impl": impl.name, "status": status, "note": note})
    return rows


def last_decisions() -> Dict[str, str]:
    """op -> most recently chosen impl (this process). The benchmark
    prints this so perf deltas are attributable to routing."""
    with _STATE.lock:
        return {op: dec.impl for op, dec in sorted(_STATE.last.items())}


def table_snapshot() -> Dict[str, Dict[str, str]]:
    """JSON-able resolved table for the flight black box: every op that
    resolved this process, with impl + reason."""
    with _STATE.lock:
        return {op: {"impl": dec.impl, "reason": dec.reason}
                for op, dec in sorted(_STATE.last.items())}


def reset() -> None:
    """Drop cached decisions/route history (tests). Registered ops and
    report ctxs survive — they are code, not state."""
    with _STATE.lock:
        _STATE.cache.clear()
        _STATE.routes.clear()
        _STATE.last.clear()
        _STATE.pins_memo.clear()
        _STATE.warned.clear()
