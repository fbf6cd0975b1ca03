"""Serving fast path: shape-bucketed, LRU-bounded compiled-predict cache.

The training-side predictor (``predictor/__init__.py``) is jitted per exact
input shape — fine for training loops that predict the same matrix every
round, fatal for a serving frontend fed ragged request sizes: every new
batch size is a fresh XLA compile (hundreds of ms on CPU, seconds on a
TPU). This module is the layer a serving frontend sits on:

- **row bucketing** — batch rows pad up to a power-of-two bucket (min 16,
  capped at 8192; beyond the cap, buckets are multiples of 8192 so huge
  batches don't pay up-to-2x padding). A stream of arbitrary sizes in
  [1, 4096] touches at most 9 buckets (16, 32, ..., 4096), so at most 9
  compiles per (forest-shape, output-kind) — the compile amortizes across
  the stream, and the bound is enforceable via
  ``XGBTPU_RETRACE_BUDGET=predict_serving=9`` (docs/static_analysis.md).
  Padding rows are NaN: they walk default directions and are sliced off on
  the host, never re-dispatched.
- **compiled-program cache** — one ``jax.jit`` wrapper per (bucket,
  forest-shape, output-kind) key, held in an LRU-bounded ``OrderedDict``.
  Each entry owns its wrapper, so eviction genuinely releases the
  underlying executable (a shared wrapper would pin every shape ever seen).
  The output transform (sigmoid / softmax / exp — all traceable) is fused
  into the program: one dispatch, one device->host readback per request.
- **observability** — counters in the process registry
  (``observability.metrics.REGISTRY``): ``predict_bucket_cache_hits_total``,
  ``predict_bucket_cache_misses_total`` (== program builds == compiles),
  ``predict_bucket_cache_evictions_total``, gauge
  ``predict_bucket_cache_entries``, and ``inplace_predict_rows_total``.

Reference analogs: the adapter-templated inplace predictors
(``src/c_api/c_api.cc:833`` / ``src/predictor/cpu_predictor.cc``
``InplacePredict``) skip DMatrix construction the same way; the
pad-to-bucket idea is the serving-batch discipline of NVIDIA's Forest
Inference Library (padded SoA trees, fixed-shape kernels).
"""

from __future__ import annotations

import contextlib
import functools
import os
import threading
import time
from collections import OrderedDict
from typing import Callable, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis.retrace import guard_jit, note_retrace
from ..observability import REGISTRY as _REGISTRY
from . import StackedForest, _predict_margin_impl, predict_margin

__all__ = ["bucket_rows", "ServingCache", "SERVING_CACHE", "predict_serving",
           "serving_context", "last_route"]

_POW2_CAP = 8192  # largest power-of-two bucket
_BIG_STEP = 8192  # above the cap: round up to a multiple of this
_MIN_BUCKET = 16  # tiny batches share one bucket (walking 16 rows is free)


def bucket_rows(n: int) -> int:
    """Padded row count for a batch of ``n`` rows."""
    if n <= _MIN_BUCKET:
        return _MIN_BUCKET
    if n <= _POW2_CAP:
        return 1 << (n - 1).bit_length()
    return -(-n // _BIG_STEP) * _BIG_STEP


def _forest_sig(forest: StackedForest) -> Tuple:
    """Shape signature of a forest: everything the compiled program is
    specialized on. Content (split values, leaf weights) is a runtime
    argument — refreshing a model of the same shape reuses the program."""
    return (
        forest.left.shape, forest.cat_bits.shape[-1], forest.max_depth,
        forest.n_groups, forest.has_cats, forest.heap_layout,
    )


def _resolve_walk(forest: StackedForest, exclude=()):
    """Route this forest's predicts through the kernel dispatch registry
    (``predict_walk``): native walker / shared pallas dispatcher /
    bucketed XLA program, with pins, platform preference and the
    ``pallas_predict`` degrade state integrated in ONE lookup — the
    replacement for the old thread-local ``force_native`` routing and the
    per-site ``_native_route_ok`` / ``_shared_pallas_route`` gates."""
    from .. import dispatch

    from . import _INTERPRET

    return dispatch.resolve("predict_walk", dispatch.Ctx(
        platform=jax.default_backend(), interpret=bool(_INTERPRET),
        has_cats=bool(forest.has_cats),
        heap_layout=bool(forest.heap_layout),
        trees=int(forest.left.shape[0]),
        nodes=int(forest.left.shape[1])), exclude=exclude)


def _build_program(n_groups: int, max_depth: int, has_cats: bool,
                   transform: Optional[Callable]) -> Callable:
    """A fresh jit wrapper computing margins (and optionally the fused
    output transform) for one cache entry. The wrapper owns its executable:
    dropping the entry releases the compiled program. Retrace-guarded as
    ``predict_serving``: every build traces exactly once, so
    ``recompiles_total{fn="predict_serving"}`` counts serving compiles and
    ``XGBTPU_RETRACE_BUDGET=predict_serving=N`` turns the bucketing
    contract (9 buckets cover any stream in [1, 4096]) into a hard
    invariant instead of a bench observation."""

    def run(X, left, right, feature, cond, default_left, split_type,
            cat_bits, tree_group, tw, base):
        margin = _predict_margin_impl(
            X, left, right, feature, cond, default_left, split_type,
            cat_bits, tree_group, tw, base,
            n_groups=n_groups, max_depth=max_depth, has_cats=has_cats)
        if transform is None:
            return margin
        return transform(margin[:, 0] if n_groups == 1 else margin)

    return guard_jit(run, name="predict_serving")


class ServingCache:
    """LRU-bounded cache of compiled predict programs.

    Keys are (rows_bucket, n_features, forest signature, output kind);
    values are callables. ``maxsize`` bounds resident executables
    (``XGBTPU_SERVING_CACHE_SIZE``, default 64)."""

    def __init__(self, maxsize: Optional[int] = None) -> None:
        if maxsize is None:
            try:
                maxsize = int(
                    os.environ.get("XGBTPU_SERVING_CACHE_SIZE", "64"))
            except ValueError:  # malformed env: default, don't break import
                maxsize = 64
        self.maxsize = max(1, int(maxsize))
        self._programs: "OrderedDict[Tuple, Callable]" = OrderedDict()
        self._lock = threading.Lock()

    def __len__(self) -> int:
        return len(self._programs)

    def clear(self) -> None:
        with self._lock:
            self._programs.clear()
            _REGISTRY.gauge(
                "predict_bucket_cache_entries",
                "Live compiled serving programs").set(0)

    def program(self, key: Tuple, build: Callable[[], Callable]) -> Callable:
        with self._lock:
            prog = self._programs.get(key)
            if prog is not None:
                self._programs.move_to_end(key)
                _REGISTRY.counter(
                    "predict_bucket_cache_hits_total",
                    "Serving predicts served by a cached program").inc()
                return prog
        # build outside the lock: creating the jit wrapper is cheap but the
        # first call through it compiles, and other threads' hits on other
        # keys must not wait on that
        prog = build()
        with self._lock:
            existing = self._programs.get(key)
            if existing is not None:
                self._programs.move_to_end(key)
                _REGISTRY.counter(
                    "predict_bucket_cache_hits_total",
                    "Serving predicts served by a cached program").inc()
                return existing
            self._programs[key] = prog
            _REGISTRY.counter(
                "predict_bucket_cache_misses_total",
                "Serving programs built (== compiles)").inc()
            while len(self._programs) > self.maxsize:
                self._programs.popitem(last=False)
                _REGISTRY.counter(
                    "predict_bucket_cache_evictions_total",
                    "Serving programs dropped by the LRU bound").inc()
            _REGISTRY.gauge(
                "predict_bucket_cache_entries",
                "Live compiled serving programs").set(len(self._programs))
        return prog


#: process-wide cache shared by every Booster (programs are keyed on forest
#: SHAPE, not identity, so same-shaped models share compiles)
SERVING_CACHE = ServingCache()

#: pallas-route serving keys already counted in recompiles_total: the cache
#: entry there is a thin closure over the shared ``predict_margin``
#: dispatcher, so an LRU-evicted key that is re-touched (or a build race
#: losing to another thread) rebuilds the closure WITHOUT any XLA compile —
#: counting those would overcount and spuriously trip the retrace budget.
#: One count per key per process matches the dispatcher's own jit cache.
_PALLAS_COUNTED: set = set()
_PALLAS_COUNTED_LOCK = threading.Lock()


# ---------------------------------------------------------------------------
# Native CPU traversal (xgboost_tpu/native/serving_walk.cpp): the XLA gather
# walk costs ~2-3ns per gathered element on XLA:CPU, which puts a 10-tree
# 100k-row predict at ~200ms; the pointer-chase over the same SoA arrays is
# an order of magnitude faster (reference: cpu_predictor.cc block-of-rows
# kernel). CPU-backend only — on TPU the pallas/XLA programs own the walk.
# ---------------------------------------------------------------------------


class _HostForest:
    """C-contiguous host copies of a StackedForest's traversal arrays (one
    device->host sync per model snapshot, reused across serving calls)."""

    __slots__ = ("left", "right", "feature", "cond", "default_left",
                 "tree_group", "max_feature")

    def __init__(self, forest: StackedForest) -> None:
        self.left = np.ascontiguousarray(np.asarray(forest.left), np.int32)
        self.right = np.ascontiguousarray(np.asarray(forest.right), np.int32)
        self.feature = np.ascontiguousarray(
            np.asarray(forest.feature), np.int32)
        self.cond = np.ascontiguousarray(np.asarray(forest.cond), np.float32)
        self.default_left = np.ascontiguousarray(
            np.asarray(forest.default_left), np.uint8)
        self.tree_group = np.ascontiguousarray(
            np.asarray(forest.tree_group), np.int32)
        # highest feature index any INTERNAL node reads: inputs narrower
        # than this cannot take the native path (the C walker indexes raw
        # memory; the XLA gather merely clamps)
        internal = self.left >= 0
        self.max_feature = (int(self.feature[internal].max())
                            if internal.any() else -1)


#: id(forest.left) -> (pin, _HostForest); the pin keeps the device array
#: alive so the id cannot be recycled while the entry is cached
_HOST_FORESTS: "OrderedDict[int, Tuple]" = OrderedDict()
_HOST_FORESTS_MAX = 8
_HOST_FORESTS_LOCK = threading.Lock()


def _host_forest(forest: StackedForest) -> _HostForest:
    key = id(forest.left)
    with _HOST_FORESTS_LOCK:
        hit = _HOST_FORESTS.get(key)
        if hit is not None and hit[0] is forest.left:
            _HOST_FORESTS.move_to_end(key)
            return hit[1]
    hf = _HostForest(forest)  # device->host sync outside the lock
    with _HOST_FORESTS_LOCK:
        _HOST_FORESTS[key] = (forest.left, hf)
        while len(_HOST_FORESTS) > _HOST_FORESTS_MAX:
            _HOST_FORESTS.popitem(last=False)
    return hf


#: (id(forest.left), id(tree_weights)) -> (pins, device tw): the padded
#: weight vector is invariant per snapshot, so the XLA route must not pay
#: a host rebuild + device upload on every cache-hit predict
_TW_CACHE: "OrderedDict[Tuple[int, int], Tuple]" = OrderedDict()


def _device_tree_weights(forest: StackedForest, tree_weights) -> jax.Array:
    key = (id(forest.left), id(tree_weights))
    with _HOST_FORESTS_LOCK:
        hit = _TW_CACHE.get(key)
        if hit is not None and hit[0] is forest.left \
                and hit[1] is tree_weights:
            _TW_CACHE.move_to_end(key)
            return hit[2]
    tw = jnp.asarray(_tree_weights_np(forest, tree_weights))
    with _HOST_FORESTS_LOCK:
        _TW_CACHE[key] = (forest.left, tree_weights, tw)
        while len(_TW_CACHE) > _HOST_FORESTS_MAX:
            _TW_CACHE.popitem(last=False)
    return tw


#: per-thread serving context set by the model server's dispatch loop
#: (serving/batcher.py): carries the tenant LABEL for per-model latency
#: series. Observability only — routing (including the degrade route to
#: the native walker) is the dispatch registry's (``_resolve_walk``),
#: never thread-local state. Each batcher worker labels only its own
#: dispatches.
_SERVING_TLS = threading.local()


@contextlib.contextmanager
def serving_context(model: str = "") -> Iterator[None]:
    """Scope every ``predict_serving`` call on this thread to a tenant.

    ``model`` labels the request's ``predict_latency_seconds`` sample
    (``{model="name@vN"}``) so a multi-tenant server's tail latency is
    scrapeable per model. Contexts nest; the innermost wins. Entering
    clears :func:`last_route` (exiting deliberately does NOT restore it)
    so a dispatch that never reaches ``predict_serving`` — e.g. a
    gblinear booster falling back to the DMatrix predict path — reads as
    ``""`` afterwards instead of the previous dispatch's stale route.

    The old ``force_native`` flag is gone: degrade routing to the native
    CPU walker is now the ``predict_walk`` table's verdict
    (``dispatch.resolve`` integrates the ``pallas_predict`` capability
    state — docs/serving.md, "Degrade routing")."""
    prev = getattr(_SERVING_TLS, "model", "")
    _SERVING_TLS.model = model
    _SERVING_TLS.route = ""
    try:
        yield
    finally:
        _SERVING_TLS.model = prev


def last_route() -> str:
    """Which route the most recent ``predict_serving`` call on THIS
    thread took: ``native`` (CPU SoA walker), ``pallas`` (shared pallas
    dispatcher), ``xla`` (bucketed compiled program) or ``base`` (no
    trees). The model server's dispatch loop reads this right after a
    coalesced dispatch to stamp the route onto the request records and
    the dispatch flight ring (ISSUE 9) — thread-local, so concurrent
    servers/tests never see each other's routes. Empty string before the
    first call on a thread, and after a ``serving_context`` dispatch
    that bypassed ``predict_serving`` entirely."""
    return getattr(_SERVING_TLS, "route", "")


def _note_route(route: str) -> str:
    _SERVING_TLS.route = route
    return route


def _tree_weights_np(forest: StackedForest, tree_weights) -> np.ndarray:
    T = forest.left.shape[0]
    if tree_weights is None:
        return np.ones((T,), np.float32)
    tw = np.zeros((T,), np.float32)
    w = np.asarray(tree_weights, np.float32)
    tw[: w.shape[0]] = w[:T]
    return np.ascontiguousarray(tw)


def _native_margin(forest: StackedForest, X, base: np.ndarray,
                   tree_weights) -> Optional[np.ndarray]:
    """Margins via the native walker; None when the library is unavailable
    or the input is outside the walker's safety envelope (caller falls
    back to the compiled-program path). ``X`` is a dense float32
    NaN-missing array or a normalized scipy CSR."""
    from ..native import get_serving_lib

    lib = get_serving_lib()
    if lib is None:
        return None
    hf = _host_forest(forest)
    T, N = hf.left.shape
    n = X.shape[0]
    F = X.shape[1]
    K = base.shape[1]
    if F <= hf.max_feature:
        # validate_features=False with an input narrower than the model:
        # the C walker would read raw memory out of bounds — the XLA
        # gather path clamps instead (the pre-serving behavior)
        return None
    tw = _tree_weights_np(forest, tree_weights)
    base = np.ascontiguousarray(base, np.float32)
    out = np.empty((n, K), np.float32)

    def p(a: np.ndarray) -> int:
        return a.ctypes.data
    if hasattr(X, "indptr"):  # scipy CSR, values already NaN-normalized
        indptr = np.ascontiguousarray(X.indptr, np.int64)
        indices = np.ascontiguousarray(X.indices, np.int32)
        values = np.ascontiguousarray(X.data, np.float32)
        rc = lib.sv_predict_csr(
            p(indptr), p(indices), p(values), n, F,
            p(hf.left), p(hf.right), p(hf.feature), p(hf.cond),
            p(hf.default_left), p(hf.tree_group), p(tw), T, N,
            p(base), p(out), K)
    else:
        Xc = np.ascontiguousarray(X, np.float32)
        rc = lib.sv_predict_dense(
            p(Xc), n, F,
            p(hf.left), p(hf.right), p(hf.feature), p(hf.cond),
            p(hf.default_left), p(hf.tree_group), p(tw), T, N,
            p(base), p(out), K)
    if rc == 2:
        # the walker's in-loop bounds check tripped: scipy does NOT
        # validate caller-built index arrays, and a bad index is an input
        # ERROR (would be an OOB write), not a fallback case
        raise ValueError("CSR column indices out of range for "
                         f"{F} features")
    if rc != 0:
        return None
    _REGISTRY.counter(
        "predict_native_rows_total",
        "Rows served by the native CPU forest walker").inc(n)
    return out


def _pad_rows(a: np.ndarray, bucket: int, fill: float) -> np.ndarray:
    out = np.full((bucket,) + a.shape[1:], fill, np.float32)
    out[: a.shape[0]] = a
    return out


def _transform_bucketed(margin: np.ndarray, transform: Callable,
                        K: int) -> np.ndarray:
    """Apply an objective's (traceable) transform to host margins with the
    same bucket discipline as the compiled programs: eager jax ops compile
    per shape, so ragged sizes must be padded to the bucket before the
    dispatch or the transform re-introduces the per-size compiles the
    cache exists to prevent. Zero-padded rows are sliced off after."""
    n = margin.shape[0]
    bucket = bucket_rows(n)
    mp = margin if bucket == n else _pad_rows(margin, bucket, 0.0)
    out = np.asarray(transform(jnp.asarray(mp[:, 0] if K == 1 else mp)))
    return out[:n]


# serving latencies live between ~30us (native walker, small batch) and
# whole-second cold compiles — the default seconds ladder is too coarse
# at the fast end for a meaningful p50
_LATENCY_BUCKETS = (
    0.00001, 0.000025, 0.00005, 0.0001, 0.00025, 0.0005, 0.001, 0.0025,
    0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
)


def predict_serving(
    forest: StackedForest,
    X: np.ndarray,
    base: np.ndarray,
    tree_weights: Optional[jax.Array] = None,
    transform: Optional[Callable] = None,
    cache: Optional[ServingCache] = None,
) -> np.ndarray:
    """Margins (or transformed outputs) for raw float rows, through the
    native CPU walker when eligible, else the bucketed program cache.
    ``X`` is ``[n, F]`` float32 with NaN missing — or a ``CSRStorage`` /
    scipy sparse matrix, which the native walker consumes without
    densification. ``base`` is ``[n, K]``; ``transform`` (an objective's
    traceable ``pred_transform``) is fused into the compiled program (or
    applied once post-walk on the native route). Returns a host numpy
    array of ``n`` rows.

    Every request observes into the ``predict_latency_seconds``
    histogram (p50/p99 via ``REGISTRY.snapshot()`` — ISSUE 7), so a
    serving frontend's tail latency is scrapeable without wrapping this
    call."""
    t0 = time.perf_counter()
    out = _predict_serving_impl(forest, X, base, tree_weights, transform,
                                cache)
    fam = _REGISTRY.histogram(
        "predict_latency_seconds",
        "End-to-end serving predict latency per request",
        buckets=_LATENCY_BUCKETS)
    dt = time.perf_counter() - t0
    # unlabelled child stays the process-wide series (admission's p99
    # estimate reads it); a tenant label adds a per-model series beside it
    fam.observe(dt)
    model = getattr(_SERVING_TLS, "model", "")
    if model:
        fam.labels(model=model).observe(dt)
    return out


def _predict_serving_impl(
    forest: StackedForest,
    X: np.ndarray,
    base: np.ndarray,
    tree_weights: Optional[jax.Array] = None,
    transform: Optional[Callable] = None,
    cache: Optional[ServingCache] = None,
) -> np.ndarray:
    cache = cache or SERVING_CACHE
    if hasattr(X, "tocsr") and not hasattr(X, "dense_rows"):
        # raw scipy input: wrap so absent-entry-is-NaN densification has
        # ONE implementation (data/sparse.py), not a copy here
        from ..data.sparse import CSRStorage

        X = CSRStorage(X)
    n = X.shape[0]
    K = max(forest.n_groups, 1)
    _REGISTRY.counter(
        "inplace_predict_rows_total",
        "Rows served through the inplace/serving fast path").inc(n)
    if forest.left.shape[0] == 0:  # no trees: margins are the base alone
        _note_route("base")
        out = np.asarray(base, np.float32)
        if transform is not None:
            out = _transform_bucketed(out, transform, K)
        return out[:n]
    sparse = hasattr(X, "dense_rows")
    dec = _resolve_walk(forest)
    if dec.impl == "native":
        if n:
            try:
                # ``native_dispatch`` chaos site, serving edge: one hit
                # per native-walker predict
                from ..resilience import chaos as _chaos

                _chaos.hit("native_dispatch")
                margin = _native_margin(forest, X.csr if sparse else X,
                                        base, tree_weights)
            except ValueError:
                raise  # typed input error (CSR OOB index): the caller's
            except Exception as e:
                # native-walker fault: contain it — degrade the library
                # (``dispatch_route_change`` fires on the re-resolve) and
                # serve THIS request on the compiled-program path
                from ..native import boundary
                from ..resilience import policy as _policy

                kind = (getattr(e, "chaos_mode", "")
                        or _policy.classify(e))
                boundary.record_native_fault("serving_walk", kind)
                boundary.degrade_lib(
                    "serving_walk", kind_hint=kind,
                    detail=f"predict fault {type(e).__name__} ({kind})")
                margin = None
            if margin is not None:
                _note_route("native")
                if transform is None:
                    return margin
                return _transform_bucketed(margin, transform, K)
        # the walker's runtime envelope rejected this input (or n == 0,
        # or its fault was just contained): re-resolve without it — same
        # table, next candidate
        dec = _resolve_walk(forest, exclude=("native",))
    if sparse:  # bucket path is dense: one densify implementation
        X = X.toarray()
    bucket = bucket_rows(n)
    Xp = X if bucket == n else _pad_rows(X, bucket, np.nan)
    bp = base if bucket == n else _pad_rows(base, bucket, 0.0)
    tw = _device_tree_weights(forest, tree_weights)

    out_kind = "margin" if transform is None else (
        "value", getattr(transform, "__qualname__", repr(transform)))
    key = (bucket, X.shape[1], _forest_sig(forest), out_kind)

    if dec.impl == "pallas":
        # shared dispatcher (pallas walk + blacklist): the cache entry is a
        # thin closure — bucketing still de-dups compiles inside it. The
        # forest is a runtime ARGUMENT (never captured): entries are keyed
        # on shape, and a same-shaped refreshed model must not read stale
        # trees out of a closure.
        def build():
            # the pallas route compiles inside predict_margin's own jits,
            # so count the build here to keep recompiles_total{fn=
            # "predict_serving"} == serving program builds on BOTH routes
            # (and the retrace budget enforcing bucketing on both) —
            # first touch of a key only: closure rebuilds are not compiles.
            # The key is marked AFTER note_retrace returns: an over-budget
            # raise leaves it unmarked, so a retried predict re-raises
            # instead of silently slipping past enforcement.
            with _PALLAS_COUNTED_LOCK:
                if key not in _PALLAS_COUNTED:
                    note_retrace("predict_serving")
                    _PALLAS_COUNTED.add(key)

            def run_shared(fr, Xp, bp, tw):
                m = predict_margin(fr, jnp.asarray(Xp), jnp.asarray(bp), tw)
                if transform is None:
                    return m
                return transform(m[:, 0] if K == 1 else m)
            return run_shared

        prog = cache.program(key + ("pallas",), build)
        _note_route("pallas")
        return np.asarray(prog(forest, Xp, bp, tw))[:n]

    _note_route("xla")
    prog = cache.program(key, functools.partial(
        _build_program, forest.n_groups, forest.max_depth, forest.has_cats,
        transform))
    out = prog(
        jnp.asarray(Xp), forest.left, forest.right, forest.feature,
        forest.cond, forest.default_left, forest.split_type,
        forest.cat_bits, forest.tree_group, tw, jnp.asarray(bp))
    return np.asarray(out)[:n]
