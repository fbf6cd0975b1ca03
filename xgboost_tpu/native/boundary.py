"""The guarded native boundary: contract-checked FFI dispatch + fault
containment (ISSUE 20 tentpole, parts b/c).

Every native kernel invocation crosses HERE. The module owns three
things:

* **The capability map** — one ``resilience.degrade`` capability per
  native library (``native_tree``, ``native_hist``, ``native_sketch``,
  ``native_serving``). ``dispatch/ops.py`` attaches them to the native
  impl rows, so a degraded library re-routes ``resolve`` onto the
  XLA/per-level impls with a ``dispatch_route_change`` flight event —
  no call site carries fallback logic of its own.
* **``ffi_call``** — a wrapper over ``jax.ffi.ffi_call`` that
  first validates the call against the binder signature parsed from the
  handler's C++ TU (``analysis/ffi_contract.parse_cpp_handlers`` — the
  same parse NB6xx lints with, now enforced at run time): operand
  arity, attr name-set, result count, and every statically-known dtype.
  A drifted call raises a typed :class:`NativeContractError` (and
  degrades the library) instead of letting the handler reinterpret
  device memory. The checks run at TRACE time — ``ffi_call`` sites
  execute once per compilation, never per round — so the guard adds no
  per-round host work (acceptance: no rounds/s regression). The
  wrapper is named ``ffi_call`` on purpose: the NB6xx scanner matches
  any call whose attribute chain ends in ``ffi_call``, so call sites
  routed through it keep their static lint coverage.
* **Containment** — :func:`contain` classifies a fault raised while a
  native train route was active, burns the owning libraries' degrade
  countdowns, counts ``native_faults_total{lib,kind}`` and returns a
  TRANSIENT-classified :class:`NativeFault` for
  ``RetryPolicy("native_dispatch")`` to retry: the re-run re-resolves
  dispatch (capability state is part of the cache key) and lands on the
  fallback route. :func:`tick` burns one unit of each degraded
  library's countdown per round so a transient fault heals — the route
  flips back (another ``dispatch_route_change``) after ``retry_after``
  rounds. Canary verdicts (``native/canary.py``) use a process-lifetime
  countdown instead: a build that failed its golden run is never
  retried by time alone.

The in-kernel half of the guard (``XGBTPU_NATIVE_GUARD=1`` bounds
checks inside hist_build.cpp / tree_build.cpp) is documented in
docs/resilience.md, "The native boundary".
"""

from __future__ import annotations

import os
import threading
from typing import Dict, Optional, Tuple

from ..resilience import degrade, policy

__all__ = [
    "CAPS", "OP_LIBS", "TRAIN_OPS", "NativeContractError", "NativeFault",
    "ffi_call", "contain", "round_chaos", "tick", "degrade_lib",
    "record_native_fault", "record_build_failure", "capability_for",
    "cap_snapshot",
]

#: native library -> its degrade capability
CAPS: Dict[str, str] = {
    "tree_build": "native_tree",
    "hist_build": "native_hist",
    "sketch_bin": "native_sketch",
    "serving_walk": "native_serving",
}

#: dispatch op -> the native library its ``native`` impl dispatches into
OP_LIBS: Dict[str, str] = {
    "tree_grow": "tree_build",
    "level_hist": "hist_build",
    "level_partition": "hist_build",
    "sketch_cuts": "sketch_bin",
    "bin_matrix": "sketch_bin",
    "predict_walk": "serving_walk",
}

#: the ops the per-round training containment watches
TRAIN_OPS: Tuple[str, ...] = ("tree_grow", "level_hist", "level_partition")

#: FFI target -> (C++ TU basename, handler symbol): the run-time edge of
#: the NB6xx static map. ``xgbtpu_canary_*`` targets alias the same
#: symbols from the canary child's registrations.
TARGETS: Dict[str, Tuple[str, str]] = {
    "xgbtpu_tree_grow": ("tree_build.cpp", "XgbtpuTreeGrow"),
    "xgbtpu_hb_level_sub": ("tree_build.cpp", "XgbtpuHbLevelSub"),
    "xgbtpu_hb_level_quant": ("tree_build.cpp", "XgbtpuHbLevelQuant"),
    "xgbtpu_hb_level": ("hist_build.cpp", "XgbtpuHbLevel"),
    "xgbtpu_hb_partition": ("hist_build.cpp", "XgbtpuHbPartition"),
    "xgbtpu_sketch_cuts": ("sketch_bin.cpp", "XgbtpuSketchCuts"),
    "xgbtpu_bin_matrix_u8": ("sketch_bin.cpp", "XgbtpuBinMatrixU8"),
    "xgbtpu_bin_matrix_u16": ("sketch_bin.cpp", "XgbtpuBinMatrixU16"),
}

#: runtime faults heal after this many skipped rounds; canary verdicts
#: stick for the process (a failed golden run condemns the BUILD)
RUNTIME_RETRY_AFTER = 32
PROCESS_RETRY_AFTER = 1 << 30


class NativeContractError(TypeError):
    """An ``ffi_call`` whose operands/attrs/results drifted from the
    handler's binder signature — refused before the handler runs."""

    chaos_kind = policy.PERMANENT  # a drifted call never self-heals


class NativeFault(RuntimeError):
    """A contained native-boundary fault. Classified TRANSIENT so the
    round-level ``RetryPolicy("native_dispatch")`` retries it — the
    retry re-resolves dispatch and runs on the fallback route (the
    original kind already burned the library's degrade countdown)."""

    chaos_kind = policy.TRANSIENT

    def __init__(self, msg: str, original: Optional[BaseException] = None):
        super().__init__(msg)
        self.original = original


def cap_snapshot() -> Tuple[Tuple[str, int], ...]:
    """Read-only (capability, worst-state) snapshot of every native
    capability, via ``degrade.worst`` (no retry countdown burned). Baked
    into ``GrowParams.native_caps`` so the compiled tree builder's static
    key tracks route health — trace-time resolves re-run on any flip."""
    return tuple((name, degrade.worst(name))
                 for name in sorted(set(CAPS.values())))


def capability_for(lib: str) -> Optional[degrade.CapabilityHealth]:
    name = CAPS.get(lib)
    if name is None:
        return None
    return degrade.capability(name, retry_after=RUNTIME_RETRY_AFTER)


def record_native_fault(lib: str, kind: str) -> None:
    from ..observability.metrics import REGISTRY

    REGISTRY.counter(
        "native_faults_total",
        "Faults observed at the native boundary by library and kind",
    ).labels(lib=lib, kind=kind).inc()


def record_build_failure(lib: str, detail: str = "") -> None:
    """A ``_compile``/dlopen failure for ``lib`` (``native/__init__.py``):
    counted and — for canaried libraries — degraded for the process, so
    a pure-Python box resolves every op to the XLA impls out of the box
    instead of re-probing a toolchain that is not there."""
    from ..observability.metrics import REGISTRY

    REGISTRY.counter(
        "native_build_failures_total",
        "Native library build/load failures by library",
    ).labels(lib=lib).inc()
    cap = capability_for(lib)
    if cap is not None:
        cap.failure(kind=policy.PERMANENT, retry_after=PROCESS_RETRY_AFTER)
    from ..utils import console_logger

    console_logger.info(
        f"native library {lib!r} unavailable"
        + (f" ({detail})" if detail else "")
        + "; dispatch keeps the XLA/level impls")


def degrade_lib(lib: str, *, kind_hint: str = "", detail: str = "",
                for_process: bool = False) -> None:
    """Burn ``lib``'s degrade capability. ``kind_hint`` is a boundary
    fault label (crash/timeout/corrupt/mismatch/refused/...) mapped onto
    the resilience kinds; TRANSIENT is promoted to RESOURCE because
    ``CapabilityHealth.failure`` deliberately ignores transients and the
    boundary's whole point is to re-route the next rounds."""
    cap = capability_for(lib)
    if cap is None:
        return
    kind = {"timeout": policy.RESOURCE, "resource": policy.RESOURCE,
            "transient": policy.RESOURCE}.get(kind_hint, policy.PERMANENT)
    cap.failure(kind=kind,
                retry_after=(PROCESS_RETRY_AFTER if for_process
                             else RUNTIME_RETRY_AFTER))
    if detail:
        from ..utils import console_logger

        console_logger.warning(f"native library {lib!r} degraded: {detail}")


# ---------------------------------------------------------------------------
# guarded ffi_call (tentpole part b, Python half)
# ---------------------------------------------------------------------------

_contract_lock = threading.Lock()
_contracts: Dict[str, Optional[object]] = {}  # target -> CppHandler | None


def _handler_for(target: str):
    """The parsed binder signature for ``target``, memoized. None when
    the TU is absent (prebuilt-only deployment) or the parse finds no
    handler — the guard then passes the call through unchecked, exactly
    like the NB6xx lint skips what it cannot see."""
    with _contract_lock:
        if target in _contracts:
            return _contracts[target]
    handler = None
    spec = TARGETS.get(target)
    if spec is not None:
        from ..analysis.ffi_contract import parse_cpp_handlers

        cpp, symbol = spec
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)), cpp)
        for h in parse_cpp_handlers(path, cpp):
            if h.symbol == symbol:
                handler = h
                break
    with _contract_lock:
        _contracts[target] = handler
    return handler


def _dtype_name(x) -> Optional[str]:
    dt = getattr(x, "dtype", None)
    return None if dt is None else str(dt)


def _refuse(target: str, msg: str) -> NativeContractError:
    spec = TARGETS.get(target)
    libname = ""
    if spec is not None:
        libname = spec[0].rsplit(".", 1)[0]
        record_native_fault(libname, "contract")
        degrade_lib(libname, kind_hint="permanent",
                    detail=f"contract violation at target {target!r}")
    return NativeContractError(
        f"ffi_call target {target!r} refused: {msg} — the call drifted "
        f"from the binder signature"
        + (f" in native/{spec[0]}" if spec else ""))


def check_contract(target: str, ret_specs, operands, attrs: dict) -> None:
    """Validate one ffi_call against its handler's parsed binder. Raises
    :class:`NativeContractError` on drift; silently passes targets whose
    TU is unavailable. Trace-time only — never on the per-round path."""
    h = _handler_for(target)
    if h is None:
        return
    if len(operands) != len(h.args):
        raise _refuse(target, f"{len(operands)} operands passed, binder "
                              f"declares {len(h.args)}")
    want_attrs = {a for a, _ in h.attrs}
    got_attrs = set(attrs)
    if want_attrs != got_attrs:
        raise _refuse(
            target,
            f"attr set {sorted(got_attrs)} != binder {sorted(want_attrs)}")
    rets = (list(ret_specs) if isinstance(ret_specs, (tuple, list))
            else [ret_specs])
    if len(rets) != len(h.rets):
        raise _refuse(target, f"{len(rets)} result specs passed, binder "
                              f"declares {len(h.rets)}")
    for i, (op, want) in enumerate(zip(operands, h.args)):
        got = _dtype_name(op)
        if got is not None and want != "any" and got != want:
            raise _refuse(target, f"operand {i} dtype {got} != binder "
                                  f"ffi::Buffer<{want}>")
    for i, (spec, want) in enumerate(zip(rets, h.rets)):
        got = _dtype_name(spec)
        if got is not None and want != "any" and got != want:
            raise _refuse(target, f"result {i} dtype {got} != binder "
                                  f"ffi::Buffer<{want}>")


def ffi_call(target: str, ret_specs, *operands, **attrs):
    """Contract-checked ``jax.ffi.ffi_call(target, ret_specs)(*operands,
    **attrs)`` — every production native call site routes through here."""
    check_contract(target, ret_specs, operands, attrs)
    import jax

    return jax.ffi.ffi_call(target, ret_specs)(*operands, **attrs)


# ---------------------------------------------------------------------------
# run-time containment (tentpole part c)
# ---------------------------------------------------------------------------


def _active_native_libs() -> Tuple[str, ...]:
    """Libraries behind the native TRAIN routes most recently resolved —
    the candidates a mid-round fault condemns. Decisions are recorded at
    TRACE time only, so a round served from a warm jit cache leaves no
    fresh decision even though it runs native kernels; when no train op
    has resolved native this process, fall back to the train libraries
    already dlopened in — ground truth for 'native code can be running'
    that a warm cache cannot disarm."""
    from .. import dispatch

    decs = dispatch.last_decisions()
    libs = []
    for op in TRAIN_OPS:
        if decs.get(op) == "native":
            lib = OP_LIBS[op]
            if lib not in libs:
                libs.append(lib)
    if not libs and not any(op in decs for op in TRAIN_OPS):
        # no train op resolved AT ALL this process: routing evidence is
        # absent (not 'resolved to XLA'), so trust the dlopen memos
        import xgboost_tpu.native as _native

        train_libs = set(OP_LIBS[op] for op in TRAIN_OPS)
        libs = [lib for lib in _native.loaded_libs() if lib in train_libs]
    return tuple(libs)


def _looks_native(exc: Exception) -> bool:
    """Only faults that plausibly ORIGINATE at the native boundary are
    containable: the scripted native chaos modes, a wedged dispatch
    (watchdog), an XLA runtime failure (the FFI handler's typed errors
    and crashes both present as ``XlaRuntimeError``), or a resource
    death. A ``ValueError`` from parameter validation — or the legacy
    ``InjectedFault`` kill drill — is semantics, not a kernel fault;
    re-routing a round around it would mask a real bug (or defeat the
    restart harness that scripted it)."""
    if getattr(exc, "chaos_mode", "") in ("crash", "timeout", "corrupt"):
        return True
    from ..resilience.watchdog import WatchdogTimeout

    if isinstance(exc, (NativeContractError, WatchdogTimeout,
                        MemoryError, OSError)):
        return True
    return any(t.__name__ == "XlaRuntimeError"
               for t in type(exc).__mro__)


def contain(exc: BaseException) -> NativeFault:
    """Classify a round-dispatch fault. When a native train route was
    active AND the fault plausibly came from the boundary: degrade the
    owning libraries, count the fault, and RETURN a :class:`NativeFault`
    for the caller to raise into its RetryPolicy. Otherwise (pure-XLA
    round, a non-Exception like KeyboardInterrupt, or a semantic error
    that merely happened DURING a native round) the original exception
    is re-raised — the boundary only contains faults it can re-route
    around."""
    if not isinstance(exc, Exception) or isinstance(exc, NativeFault):
        raise exc
    if not _looks_native(exc):
        raise exc
    libs = _active_native_libs()
    if not libs:
        raise exc
    kind = getattr(exc, "chaos_mode", "") or policy.classify(exc)
    for lib in libs:
        record_native_fault(lib, kind)
        degrade_lib(lib, kind_hint=kind,
                    detail=f"round fault {type(exc).__name__} ({kind})")
    from ..observability import flight

    flight.RECORDER.event("native_fault_contained", libs=",".join(libs),
                          kind=kind, error=type(exc).__name__)
    return NativeFault(
        f"contained native fault ({kind}) in {'/'.join(libs)}: "
        f"{type(exc).__name__}: {exc}", original=exc)


def round_chaos() -> None:
    """The ``native_dispatch`` chaos site's training edge: fires once per
    boosting round while a native train route is active (and never on
    pure-XLA rounds — the site scripts NATIVE faults)."""
    if not _active_native_libs():
        return
    from ..resilience import chaos

    chaos.hit("native_dispatch")


def tick() -> None:
    """Once per round: burn one unit of each DEGRADED native capability's
    recovery countdown. ``resolve`` reads capability state read-only
    (``degrade.worst``), so without this the countdown would never move
    and a transiently-degraded library could never route back in."""
    caps = degrade.capabilities()
    for name in CAPS.values():
        cap = caps.get(name)
        if cap is not None and cap.worst_state() == degrade.DEGRADED:
            cap.allowed()
