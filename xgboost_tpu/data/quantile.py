"""Weighted quantile sketch -> HistogramCuts -> binned matrix, TPU-style.

Reference equivalents:
- CPU WQSummary/GK sketch: ``src/common/quantile.{h,cc}`` (merge/prune).
- GPU SketchContainer: ``src/common/quantile.{cuh,cu}`` — sort-based.
- ``HistogramCuts`` / ``SearchBin``: ``src/common/hist_util.h:38``.
- ELLPACK quantized matrix: ``src/data/ellpack_page.cuh``.

TPU-first design (SURVEY.md §7 hard-part 4): instead of the sequential GK
merge/prune, each feature's cuts come from a full sort + weighted-CDF
selection — exactly what the GPU SketchContainer effectively computes, but as
one fixed-shape XLA program over the dense ``[n, F]`` matrix. Distributed
merging (the ``quantile.cc:270`` AllReduce site) happens by gathering
fixed-size per-shard summaries (see ``parallel/sketch.py``).

Bin semantics (identical to the reference's SearchBin/upper_bound):
``bin(x) = #{cuts[f] <= x}``; a split at bin ``b`` with condition
``cuts[f][b]`` sends ``x < cuts[f][b]`` (i.e. ``bin <= b``) left. The last
cut is a sentinel strictly greater than the feature max so every finite
value lands in ``[0, max_bin)``. Missing values get the dedicated bin id
``max_bin`` (the ELLPACK null-symbol trick, ``ellpack_page.cuh:109``).
"""

from __future__ import annotations

import dataclasses
import threading
from functools import lru_cache, partial
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..resilience import chaos as _chaos, degrade as _degrade, policy as _policy

__all__ = [
    "HistogramCuts", "compute_cuts", "compute_exact_cuts", "bin_matrix",
    "BinnedMatrix", "apply_categorical_identity",
]

# Health of the hoisted one-hot build (the on-device Pallas tile build,
# tree/hist_kernel.py:build_onehot). A PERMANENT failure — a Mosaic
# reject of the int8 tile store on this runtime — DISABLES the capability
# for the process (disable_after=1: a compiler reject is deterministic
# per runtime, so re-trying it per fit would just re-pay the failed
# compile). A RESOURCE failure (temporary HBM pressure) only DEGRADES —
# the next fit after the 1-call retry window probes the build again, so a
# long-lived process recovers the fast path when memory frees. Training
# proceeds on the in-kernel construct path either way. Replaces the
# per-object boolean latch of earlier rounds (resilience tentpole): state
# is process-visible as ``degrade_state{capability="onehot_build"}``.
_onehot_health = _degrade.capability(
    "onehot_build", retry_after=1, disable_after=1,
    disable_kinds=(_policy.PERMANENT,))


def apply_categorical_identity(values: np.ndarray, min_vals: np.ndarray,
                               categorical: Sequence[int]) -> None:
    """Overwrite categorical features' cuts with identity thresholds
    ``[1..max_bin]`` so category code ``c`` lands in bin ``c`` — the
    one-bin-per-category layout the reference builds for categorical data
    (``hist_util.cc`` AddCutPoint categorical path). Shared by the local
    and distributed sketches so the layouts cannot drift."""
    max_bin = values.shape[1]
    ident = np.arange(1, max_bin + 1, dtype=np.float32)
    for f in categorical:
        values[f] = ident
        min_vals[f] = 0.0


@dataclasses.dataclass
class HistogramCuts:
    """Per-feature cut thresholds, padded to a uniform ``max_bin`` width.

    values[f, b] is the (upper-exclusive) threshold of bin b. Padding via
    duplicate thresholds is harmless: duplicated cuts produce empty bins that
    can never win split evaluation. min_vals is kept for model dumps
    (reference keeps it for display, hist_util.h).
    """

    values: np.ndarray  # [n_features, max_bin] float32
    min_vals: np.ndarray  # [n_features] float32

    @property
    def max_bin(self) -> int:
        return int(self.values.shape[1])

    @property
    def n_features(self) -> int:
        return int(self.values.shape[0])

    @property
    def missing_bin(self) -> int:
        return self.max_bin


@partial(jax.jit, static_argnames=("max_bin",))
def _cuts_kernel(X: jax.Array, weights: jax.Array, max_bin: int):
    """[n, F] -> ([F, max_bin] cut values, [F] min vals).

    Sort each feature column, build the weighted CDF, and read off
    ``max_bin - 1`` evenly spaced weighted quantiles plus a strict-upper
    sentinel cut.
    """
    n = X.shape[0]
    Xt = X.T  # [F, n]
    valid = ~jnp.isnan(Xt)
    big = jnp.float32(np.finfo(np.float32).max)
    keys = jnp.where(valid, Xt, big)  # NaN sorts to the end
    order = jnp.argsort(keys, axis=1)
    svals = jnp.take_along_axis(keys, order, axis=1)
    w = jnp.where(valid, weights[None, :], 0.0)
    sw = jnp.take_along_axis(w, order, axis=1)
    if jax.default_backend() == "cpu":
        # explicitly SEQUENTIAL f32 prefix sum: XLA:CPU's cumsum lowering
        # may reassociate the adds (parallel prefix), which flips a
        # quantile selection on a near-tie — the native sketch kernel
        # (native/sketch_bin.cpp) accumulates sequentially, and the two
        # routes are pinned bit-identical, so the reference route must
        # accumulate in the same order. CPU-only: on device backends a
        # 100k-step scan would serialize the sketch for no contract (the
        # native route never runs there).
        def _step(acc, col):
            acc = acc + col
            return acc, acc

        _, cdf_t = jax.lax.scan(
            _step, jnp.zeros((Xt.shape[0],), sw.dtype), sw.T)
        cdf = cdf_t.T  # [F, n]
    else:
        cdf = jnp.cumsum(sw, axis=1)  # [F, n]
    total = cdf[:, -1:]

    # quantile levels for the max_bin-1 interior cuts at k/B of total weight;
    # the sentinel cut closes the last bin (q_{(B-1)/B}, max]
    levels = (jnp.arange(1, max_bin, dtype=jnp.float32) / max_bin) * total  # [F, B-1]
    # first sorted index where cdf >= level  (vectorized searchsorted per row)
    idx = jax.vmap(lambda c, l: jnp.searchsorted(c, l, side="left"))(cdf, levels)
    idx = jnp.clip(idx, 0, n - 1)
    interior = jnp.take_along_axis(svals, idx, axis=1)  # [F, B-1]

    n_valid = valid.sum(axis=1)
    max_val = jnp.where(n_valid > 0, jnp.take_along_axis(svals, (n_valid - 1)[:, None], axis=1)[:, 0], 0.0)
    min_val = jnp.where(n_valid > 0, svals[:, 0], 0.0)
    sentinel = max_val + jnp.maximum(1.0, jnp.abs(max_val))
    # degenerate all-missing feature: make a monotone dummy cut set
    interior = jnp.where((n_valid > 0)[:, None], interior, 0.0)
    cuts = jnp.concatenate([interior, sentinel[:, None]], axis=1)  # [F, B]
    return cuts, min_val


# ---------------------------------------------------------------------------
# Native sketch + binning (ISSUE 15 tentpole): XLA FFI custom calls
# (native/sketch_bin.cpp) doing the same float ops in the same order as the
# XLA kernels above/below — BIT-IDENTICAL cuts and bins (pinned), ~an order
# of magnitude faster on XLA:CPU where the sort/searchsorted pipeline was
# the DMatrix-construction floor. Routed per call through the kernel
# dispatch registry (ops ``sketch_cuts`` / ``bin_matrix`` — docs/perf.md,
# "The data plane"), so pins (XGBTPU_DISPATCH) and platform preference
# apply like any other kernel op.
# ---------------------------------------------------------------------------

_sketch_ffi_lock = threading.Lock()
_sketch_ffi_state = {"registered": None}  # None = not tried


def _ensure_sketch_ffi() -> bool:
    """Build/load the native sketch+bin library and register its FFI
    handlers with XLA (once per process). False when the library is
    unavailable (no toolchain, failed build, canary refusal) — the
    dispatch table then resolves the ops to the XLA impls; a JAX API error
    propagates."""
    with _sketch_ffi_lock:
        if _sketch_ffi_state["registered"] is None:
            from ..native import get_sketch_lib

            lib = get_sketch_lib()
            if lib is not None:
                jax.ffi.register_ffi_target(
                    "xgbtpu_sketch_cuts",
                    jax.ffi.pycapsule(lib.XgbtpuSketchCuts), platform="cpu")
                jax.ffi.register_ffi_target(
                    "xgbtpu_bin_matrix_u8",
                    jax.ffi.pycapsule(lib.XgbtpuBinMatrixU8), platform="cpu")
                jax.ffi.register_ffi_target(
                    "xgbtpu_bin_matrix_u16",
                    jax.ffi.pycapsule(lib.XgbtpuBinMatrixU16), platform="cpu")
            _sketch_ffi_state["registered"] = lib is not None
        return _sketch_ffi_state["registered"]


@lru_cache(maxsize=64)
def _native_cuts_prog(n: int, F: int, B: int):
    """Jitted wrapper around the XgbtpuSketchCuts custom call for one
    shape (the jit guarantees executable caching for eager invocation)."""
    from ..native import boundary

    def run(X, w):
        return boundary.ffi_call(
            "xgbtpu_sketch_cuts",
            (jax.ShapeDtypeStruct((F, B), jnp.float32),
             jax.ShapeDtypeStruct((F,), jnp.float32)),
            X, w, B=B)

    return jax.jit(run)


@lru_cache(maxsize=64)
def _native_bins_prog(n: int, F: int, B: int, dtype_name: str):
    from ..native import boundary

    target = ("xgbtpu_bin_matrix_u8" if dtype_name == "uint8"
              else "xgbtpu_bin_matrix_u16")

    def run(X, cut_values):
        return boundary.ffi_call(
            target, jax.ShapeDtypeStruct((n, F), jnp.dtype(dtype_name)),
            X, cut_values)

    return jax.jit(run)


def _cuts_dispatch(Xj: jax.Array, wj: jax.Array, max_bin: int):
    """(cut values [F, B], min vals [F]) for one dense block, routed
    through the ``sketch_cuts`` dispatch op. Shared by the whole-matrix
    sketch and the CSR column-blocked sketch so both take the same route
    (and stay bit-identical to each other)."""
    from ..dispatch import Ctx, resolve

    n, F = int(Xj.shape[0]), int(Xj.shape[1])
    dec = resolve("sketch_cuts", Ctx(
        platform=jax.default_backend(), rows=n, features=F,
        bins=int(max_bin)))
    if dec.impl == "native":
        return _native_cuts_prog(n, F, int(max_bin))(Xj, wj)
    return _cuts_kernel(Xj, wj, max_bin)


# The whole-matrix sketch program holds about seven ``[n, F]`` 4-byte arrays
# at its peak (the float32 block, the sort keys, the argsort's int32 order,
# the sorted values, the weights twice, the CDF): 7.4 GB at 2.27M x 136, and
# more than a 16 GB chip has past about 500M cells. A column's cuts and bins
# depend on that column alone, so a matrix the device cannot take at once
# goes through the same programs a block of columns at a time, and the
# result is the whole-matrix program's to the bit.
_SKETCH_BYTES_PER_CELL = 28

# test hook: columns a block, whatever the device's memory says
_FORCE_BLOCK_COLS: Optional[int] = None


def sketch_block_cols(n: int, F: int) -> int:
    """Columns the sketch and the binning take in one device program: all
    ``F`` (the whole-matrix route every narrow matrix keeps) where the
    program's working set, ``_SKETCH_BYTES_PER_CELL`` a cell, is within
    three quarters of the device's free memory or the backend reports none
    (the CPU); else equal blocks of at most the columns that fit half of
    it, an exact divisor of ``F`` where one is near (one compiled shape)."""
    if _FORCE_BLOCK_COLS is not None:
        return max(1, min(int(_FORCE_BLOCK_COLS), F))
    from ..tree.hist_kernel import device_free_bytes

    free = device_free_bytes()
    if free is None or _SKETCH_BYTES_PER_CELL * n * F <= 0.75 * free:
        return F
    fit = max(1, int(0.5 * free) // (_SKETCH_BYTES_PER_CELL * max(n, 1)))
    blocks = -(-F // fit)
    for nb in range(blocks, 2 * blocks + 1):
        if F % nb == 0:
            return F // nb
    return -(-F // blocks)


def _column_blocks(X, what: str, cols: Optional[int] = None):
    """The one column-block loop of the sketch and the binning: ``X`` a
    block of columns at a time as float32 device arrays, each under a
    ``sketch_block`` span and counted in ``sketch_blocks_total``; yields
    ``(f0, f1, block)``. ``X`` is a dense matrix, cut by
    ``sketch_block_cols`` (one block, the whole matrix as it always went
    up, where it fits), or a CSR storage, whose NaN-filled dense columns
    (``dense_cols``) come ``cols`` at a time. A block's way to the device
    is the set-up stage ``upload``, closed on the block's arrival and so
    taken out of the ``sketch`` or ``bins`` stage that asked for it."""
    from ..observability import REGISTRY, trace

    n, F = int(X.shape[0]), int(X.shape[1])
    take = getattr(X, "dense_cols", None)
    if cols is None:
        cols = sketch_block_cols(n, F)
    if take is None and cols >= F:
        with trace.stage("upload", cols=F, what=what):
            whole = jax.block_until_ready(jnp.asarray(X, dtype=jnp.float32))
        yield 0, F, whole
        return
    for b, f0 in enumerate(range(0, F, cols)):
        f1 = min(f0 + cols, F)
        with trace.span("sketch_block", block=b, cols=f1 - f0, what=what):
            with trace.stage("upload", cols=f1 - f0, what=what):
                blk = X[:, f0:f1] if take is None else take(f0, f1)
                if isinstance(blk, np.ndarray):
                    blk = np.ascontiguousarray(blk, dtype=np.float32)
                blk = jax.block_until_ready(
                    jnp.asarray(blk, dtype=jnp.float32))
            yield f0, f1, blk
        REGISTRY.counter(
            "sketch_blocks_total",
            "Column blocks the sketch and the binning took one at a time",
        ).inc()


def _cuts_by_blocks(X, weights: jax.Array, max_bin: int,
                    cols: Optional[int] = None):
    """(values ``[F, max_bin]``, min_vals ``[F]``) of ``X``'s columns, a
    block at a time (``_column_blocks``): a column's cuts depend on that
    column alone, so they are the whole-matrix program's to the bit."""
    F = int(X.shape[1])
    values = np.empty((F, max_bin), np.float32)
    min_vals = np.empty((F,), np.float32)
    for f0, f1, Xb in _column_blocks(X, "cuts", cols):
        v, m = _cuts_dispatch(Xb, weights, max_bin)
        values[f0:f1] = np.asarray(v)
        min_vals[f0:f1] = np.asarray(m)
    return values, min_vals


def _bins_by_blocks(X, cuts: "HistogramCuts", cols: Optional[int] = None):
    """The narrow bins of ``X``'s columns against ``cuts``, a block at a
    time: yields ``(f0, f1, bins block)`` on the device."""
    cut_j = jnp.asarray(cuts.values)
    dtype = storage_dtype(cuts.max_bin)
    for f0, f1, Xb in _column_blocks(X, "bins", cols):
        yield f0, f1, _bins_dispatch(Xb, cut_j[f0:f1], dtype)


def compute_cuts(
    X: np.ndarray | jax.Array,
    max_bin: int = 256,
    weights: Optional[np.ndarray | jax.Array] = None,
    categorical: Optional[Sequence[int]] = None,
) -> HistogramCuts:
    """Entry point, analog of ``SketchOnDMatrix`` (``hist_util.cc:132``).

    Categorical features get IDENTITY cuts ``[1, 2, ..., max_bin]`` so a
    category code ``c`` lands in bin ``c`` — one bin per category, the same
    one-bin-per-category layout the reference builds for categorical data
    (``hist_util.cc`` AddCutPoint categorical path). A matrix too large
    for the device to sketch at once goes a block of columns at a time
    (``sketch_block_cols``); the cuts are the same to the bit."""
    from ..observability import flight, trace

    if not hasattr(X, "shape"):
        X = np.asarray(X, dtype=np.float32)
    n, F = int(X.shape[0]), int(X.shape[1])
    if weights is None or (hasattr(weights, "size") and weights.size == 0):
        weights = jnp.ones((n,), dtype=jnp.float32)
    else:
        weights = jnp.asarray(weights, dtype=jnp.float32)
    # closed on the cuts on the host; the blocks' uploads are a stage of
    # their own inside it
    with trace.stage("sketch", rows=n, features=F, max_bin=max_bin) as st:
        values, min_vals = _cuts_by_blocks(X, weights, max_bin)
    flight.note("sketch", st.seconds)
    if categorical:
        apply_categorical_identity(values, min_vals, categorical)
    return HistogramCuts(values=values, min_vals=min_vals)


def compute_exact_cuts(
    X: np.ndarray,
    cap: int = 16384,
    categorical: Optional[Sequence[int]] = None,
) -> HistogramCuts:
    """Cuts at EVERY distinct finite value per feature — the exact-greedy
    candidate set. With these cuts the hist grower enumerates precisely the
    splits ``grow_colmaker`` (reference ``src/tree/updater_colmaker.cc:367``:
    sorted column scan over all value boundaries) enumerates, so
    ``tree_method='exact'`` is realized as exact binning + the same
    fixed-shape level program instead of a data-dependent column scan (which
    cannot map to XLA). Split conditions are the boundary values themselves
    rather than colmaker's midpoints — both classify every finite input
    identically; the reference's own hist family makes the same choice.

    ``cap`` bounds the bin width (the [F, B] cuts tensor and the level
    histograms scale with B); truly continuous features exceed it and the
    caller should use a quantile method instead — the reference likewise
    steers large data away from exact (``gbtree.cc:133-155`` auto
    selection).
    """
    Xn = np.asarray(X, np.float32)
    cat_set = frozenset(categorical or ())
    uniques = []
    widest = 0
    for f in range(Xn.shape[1]):
        col = Xn[:, f]
        u = np.unique(col[~np.isnan(col)])  # sorted, NaN dropped
        if len(u) > cap:
            raise ValueError(
                f"tree_method='exact': feature {f} has {len(u)} distinct "
                f"values (> cap {cap}); use tree_method='tpu_hist' for "
                "high-cardinality continuous data"
            )
        if f in cat_set and len(u):
            # identity cuts need B > max category code, even when codes are
            # sparse (distinct count alone would undersize the width)
            widest = max(widest, int(u[-1]) + 1)
        else:
            widest = max(widest, len(u))
        uniques.append(u)
    B = max(widest + 1, 2)
    values = np.empty((Xn.shape[1], B), np.float32)
    min_vals = np.zeros((Xn.shape[1],), np.float32)
    for f, u in enumerate(uniques):
        if len(u) == 0:
            values[f] = np.arange(1, B + 1, dtype=np.float32)
            continue
        sentinel = u[-1] + max(1.0, abs(float(u[-1])))
        values[f, : len(u)] = u
        values[f, len(u):] = sentinel  # duplicate padding: empty bins
        min_vals[f] = u[0]
    if categorical:
        apply_categorical_identity(values, min_vals, list(categorical))
    return HistogramCuts(values=values, min_vals=min_vals)


@jax.jit
def _bin_kernel(X: jax.Array, cut_values: jax.Array) -> jax.Array:
    """[n, F] float + [F, B] cuts -> [n, F] int32 bins (missing_bin == B)."""
    B = cut_values.shape[1]

    def one_feature(cuts_f: jax.Array, col: jax.Array) -> jax.Array:
        b = jnp.searchsorted(cuts_f, col, side="right").astype(jnp.int32)
        b = jnp.clip(b, 0, B - 1)
        return jnp.where(jnp.isnan(col), jnp.int32(B), b)

    return jax.vmap(one_feature, in_axes=(0, 1), out_axes=1)(cut_values, X)


def storage_dtype(max_bin: int):
    """Pick the narrowest storage dtype (reference: runtime-selected
    uint8/16/32 bin storage, ``hist_util.h:180``)."""
    if max_bin + 1 <= 255:
        return jnp.uint8
    if max_bin + 1 <= 65535:
        return jnp.uint16
    return jnp.int32


def _bins_dispatch(Xj: jax.Array, cut_values: jax.Array, dtype) -> jax.Array:
    """Quantize one dense block to the narrow storage dtype, routed
    through the ``bin_matrix`` dispatch op. The native impl writes the
    narrow u8/u16 ids directly (no int32 intermediate); the XLA impl is
    the original searchsorted kernel plus the cast."""
    from ..dispatch import Ctx, resolve

    n, F = int(Xj.shape[0]), int(Xj.shape[1])
    B = int(cut_values.shape[1])
    name = np.dtype(dtype).name
    dec = resolve("bin_matrix", Ctx(
        platform=jax.default_backend(), rows=n, features=F, bins=B,
        bins_dtype=name))
    if dec.impl == "native":
        return _native_bins_prog(n, F, B, name)(Xj, cut_values)
    return _bin_kernel(Xj, cut_values).astype(dtype)


def bin_matrix(X: np.ndarray | jax.Array, cuts: HistogramCuts) -> jax.Array:
    """Quantize a dense matrix against cuts. Analog of
    ``GHistIndexMatrix::Init`` / ELLPACK packing (``gradient_index.cc:199``).
    By the column blocks of the sketch where the matrix is too large for
    one program (``sketch_block_cols``): the narrow bins of the blocks are
    joined on the device."""
    from ..observability import trace

    if not hasattr(X, "shape"):
        X = np.asarray(X, dtype=np.float32)
    # closed on the joined bins, which the next step needs whole
    with trace.stage("bins", rows=int(X.shape[0]), max_bin=cuts.max_bin):
        parts = [b for _, _, b in _bins_by_blocks(X, cuts)]
        return jax.block_until_ready(
            parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1))


@dataclasses.dataclass
class BinnedMatrix:
    """The quantized training matrix: TPU analog of GHistIndexMatrix /
    EllpackPage. Dense [n_rows, n_features] narrow-int bin ids on device,
    missing encoded as ``cuts.max_bin``."""

    cuts: HistogramCuts
    bins: jax.Array  # [n_rows, n_features] narrow int

    @property
    def n_rows(self) -> int:
        return int(self.bins.shape[0])

    @property
    def n_features(self) -> int:
        return int(self.bins.shape[1])

    # feature ids binned as categorical (identity cuts)
    categorical: Tuple[int, ...] = ()
    # number of categories per categorical feature (aligned with
    # ``categorical``): max observed code + 1. Drives the
    # max_cat_to_onehot one-hot/partition decision (evaluate_splits.h
    # UseOneHot gate).
    cat_counts: Tuple[int, ...] = ()
    # cached row-sharded copy (rows padded to the mesh size with the
    # missing bin so padded rows are inert), keyed by the mesh object
    _sharded: Optional[Tuple[int, jax.Array, int]] = None
    # cached int32 copy padded to the fused kernel's row tile (pad rows
    # all-missing + zero gradients => inert, same trick as ``sharded``)
    _fused: Optional[Tuple[jax.Array, int]] = None
    _fused_mesh: Optional[Tuple[int, jax.Array, int]] = None
    # cached HBM-resident [n_pad, F*B] int8 one-hot for the hoisted level
    # kernel (training-invariant; built once per fit — tree/hist_kernel.py)
    _onehot: Optional[jax.Array] = None
    # the first ``fused_onehot`` of this matrix has run (the set-up stage
    # ``onehot`` is that call and no later one)
    _onehot_staged: bool = False
    # mesh twin: row-sharded one-hot, keyed by mesh id — built once per
    # (fit, mesh), NOT once per tree (review r4 weak #5). Build failures
    # degrade the process-wide ``onehot_build`` capability (module above)
    # instead of latching on this object.
    _onehot_mesh: Optional[Tuple[int, Optional[jax.Array]]] = None
    # frozen process-synced hoist plan, keyed by mesh id: ONE allgather
    # per (fit, mesh), never per chunk — and immune to free-HBM drift
    # flipping a jit static arg mid-fit
    _hoist_plan_mesh: Optional[Tuple[int, int]] = None

    def fused_bins(self) -> Tuple[jax.Array, int]:
        """(bins padded to the kernel row tile, padded row count) for the
        fused grower. Kept in the narrow storage dtype — the int32 widening
        the kernels want happens transiently inside the jit program, so no
        persistent 2-4x copy of the bin matrix is held in HBM."""
        if self._fused is None:
            from ..tree.grow_fused import pad_rows

            n_pad = pad_rows(self.n_rows)
            self._fused = (self._pad_narrow(n_pad), n_pad)
        return self._fused

    def _pad_narrow(self, n_pad: int) -> jax.Array:
        b = self.bins
        if n_pad != self.n_rows:
            pad = jnp.full((n_pad - self.n_rows, self.n_features),
                           self.cuts.missing_bin, self.bins.dtype)
            b = jnp.concatenate([b, pad])
        return b

    def fused_onehot(self, max_depth: int = 6) -> Optional[jax.Array]:
        """The hoisted [n_pad, Fh*B] int8 one-hot of the (first Fh features
        of the) bin matrix, or None when the pallas path is off or no
        worthwhile prefix fits the HBM/VMEM budgets
        (tree/hist_kernel.py:hoist_plan — the build and dispatch gates
        share one VMEM model). ``Fh < F`` is the partial hoist: the kernel
        streams these features and constructs the rest in-kernel. Cached
        once built: the expansion is training-invariant, so every tree of
        every round streams the same resident array. The build itself
        routes through the kernel dispatch registry
        (``dispatch.resolve("onehot_build", ...)`` inside
        ``build_onehot`` — docs/perf.md, "Choosing a kernel"), so pins
        and the ``onehot_build`` capability state apply there too.

        A matrix's first call is the set-up stage ``onehot``: the bins'
        padding, the plan and the build, closed on the resident array (on
        the padded bins where the plan hoists nothing). Every later call
        is a cached read, or the plan asked again, and no stage."""
        # The plan is FROZEN at first build: a live free-HBM budget would
        # otherwise count the resident one-hot itself next round, shrink
        # the plan, and rebuild every round (thrash + transient 2x HBM).
        if self._onehot is not None:
            return self._onehot
        if self._onehot_staged:
            return self._plan_and_build_onehot(max_depth)
        from ..observability import trace

        self._onehot_staged = True
        with trace.stage("onehot", rows=self.n_rows,
                         features=self.n_features, max_depth=max_depth):
            oh = self._plan_and_build_onehot(max_depth)
            if oh is None:
                jax.block_until_ready(self.fused_bins()[0])
        return oh

    def _plan_and_build_onehot(self, max_depth: int) -> Optional[jax.Array]:
        from ..tree.hist_kernel import build_onehot, hoist_plan

        bins, n_pad = self.fused_bins()
        B = self.cuts.max_bin
        if not _onehot_health.allowed():
            return None
        fh = hoist_plan(n_pad, self.n_features, B, max_depth)
        if fh == 0:
            return None
        from ..utils import console_logger

        gb = n_pad * fh * B / 1e9
        part = ("" if fh == self.n_features
                else f" (partial: {fh}/{self.n_features} features"
                     " stream, rest construct in-kernel)")
        console_logger.info(
            f"tpu_hist: hoisted one-hot active — {gb:.2f} GB "
            f"HBM-resident ({n_pad}x{fh}x{B} int8){part}; "
            "levels stream it through the MXU")
        try:
            _chaos.hit("pallas")
            # waited for: a fault of the build's run, not only of its
            # dispatch, degrades here
            self._onehot = jax.block_until_ready(
                build_onehot(bins[:, :fh], B=B))
        except Exception as e:
            # e.g. a Mosaic compile reject of the tile build on this
            # runtime: degrade to the in-kernel construct path rather
            # than failing the fit. Non-transient kinds DISABLE the
            # capability (never re-tried per call); transients fall back
            # for this fit only.
            kind = _onehot_health.failure(e)
            console_logger.warning(
                f"tpu_hist: hoisted one-hot build failed ({kind}; "
                f"{type(e).__name__}: {e}); training on the in-kernel "
                "construction path instead")
            return None
        _onehot_health.success()
        return self._onehot

    def fused_onehot_mesh(self, mesh, max_depth: int = 6
                          ) -> Optional[jax.Array]:
        """Row-sharded hoisted one-hot for the per-round mesh path, built
        ONCE per (fit, mesh) and cached — the per-tree shard_map then
        streams it instead of reconstructing the expansion every tree
        (review r4 weak #5). The hoist plan is evaluated per SHARD (each
        device resides its own rows' expansion); the build itself runs
        under ``shard_map`` — the Pallas tile build is an opaque custom
        call GSPMD cannot partition, so a plain jit on the sharded bins
        would gather/replicate the multi-GB expansion onto every device."""
        from jax.sharding import PartitionSpec as P

        from ..parallel.mesh import ROW_AXIS
        from ..tree.hist_kernel import build_onehot

        if self._onehot_mesh is not None and self._onehot_mesh[0] == id(mesh):
            return self._onehot_mesh[1]
        if not _onehot_health.allowed():
            return None
        binsf, n_pad = self.fused_bins_mesh(mesh)
        B = self.cuts.max_bin
        fh = self.hoist_plan_mesh(mesh, max_depth)
        if fh:
            try:
                _chaos.hit("pallas")
                oh = jax.shard_map(
                    lambda b: build_onehot(b[:, :fh], B=B, vma=(ROW_AXIS,)),
                    mesh=mesh, in_specs=P(ROW_AXIS, None),
                    out_specs=P(ROW_AXIS, None))(binsf)
                _onehot_health.success()
            except Exception as e:
                # same degrade as fused_onehot: a build failure must not
                # fail the fit
                kind = _onehot_health.failure(e)
                from ..utils import console_logger

                console_logger.warning(
                    f"tpu_hist: mesh hoisted one-hot build failed "
                    f"({kind}; {type(e).__name__}: {e}); training on the "
                    "in-kernel construction path instead")
                oh = None
            if jax.process_count() > 1:
                # ranks must AGREE on whether the expansion exists (it
                # shapes the SPMD program): if any rank's build failed
                # (e.g. an asymmetric OOM), all ranks drop to construct
                import numpy as _np

                from .. import collective

                ok_all = collective.process_allgather(
                    _np.asarray(0 if oh is None else 1, _np.int64),
                    site="onehot_agree")
                if int(ok_all.min()) == 0 and oh is not None:
                    # a peer rank's asymmetric failure is a resource
                    # problem for the whole SPMD program: disable here too
                    _onehot_health.failure(kind=_policy.RESOURCE)
                    oh = None
        else:
            oh = None
        self._onehot_mesh = (id(mesh), oh)
        return oh

    def hoist_plan_mesh(self, mesh, max_depth: int = 6) -> int:
        """The process-synced per-shard hoist plan for this (fit, mesh),
        FROZEN at first evaluation: the plan is a jit static arg of the
        SPMD programs, and ``hoist_plan`` reads live free HBM — replanning
        per chunk would both re-allgather every round (train() routes
        multi-process rounds as chunk=1 scans) and risk a mid-fit
        recompile when free memory drifts across a feature boundary."""
        from ..tree.hist_kernel import hoist_plan_synced

        if _onehot_health.state() == _degrade.DISABLED:
            # disabled means the expansion cannot exist on this runtime:
            # a nonzero plan here would send the chunk scans back to the
            # failed hoisted build every round (ADVICE r5)
            return 0
        if (self._hoist_plan_mesh is not None
                and self._hoist_plan_mesh[0] == id(mesh)):
            return self._hoist_plan_mesh[1]
        binsf, _ = self.fused_bins_mesh(mesh)
        # per-device rows: the global padded count over all mesh devices
        shard_rows_n = binsf.shape[0] // mesh.devices.size
        fh = hoist_plan_synced(shard_rows_n, self.n_features,
                               self.cuts.max_bin, max_depth)
        self._hoist_plan_mesh = (id(mesh), fh)
        return fh

    def fused_bins_mesh(self, mesh) -> Tuple[jax.Array, int]:
        """Row-sharded bins for the fused grower under a mesh: rows padded
        (all-missing, inert) to a multiple of tile x devices."""
        if self._fused_mesh is not None and self._fused_mesh[0] == id(mesh):
            return self._fused_mesh[1], self._fused_mesh[2]
        from ..parallel.mesh import (global_pad_rows, local_device_count,
                                     shard_rows)
        from ..tree.grow_fused import TR

        # pad THIS process's rows to the block size all processes agree on
        # (max over processes of their own tile-padded count): every
        # process's local block is then the same fraction of the global
        # array even when load_row_split handed out ragged slices
        unit = TR * local_device_count(mesh)
        n_pad = global_pad_rows(self.n_rows, unit)
        shards = shard_rows(self._pad_narrow(n_pad), mesh)
        self._fused_mesh = (id(mesh), shards, n_pad)
        return shards, n_pad

    def sharded(self, mesh) -> Tuple[jax.Array, int]:
        """(padded row-sharded bins, n_padded). Padding rows are all-missing
        (bin id == max_bin) and carry zero gradients at use sites — the
        fixed-shape analog of the reference's empty-worker handling
        (dask.py:914)."""
        from ..parallel.mesh import (
            local_device_count,
            pad_to_multiple,
            shard_rows,
        )

        if self._sharded is not None and self._sharded[0] == id(mesh):
            return self._sharded[1], self._sharded[2]
        n = self.n_rows
        n_pad = pad_to_multiple(n, local_device_count(mesh))
        bins = self.bins
        if n_pad != n:
            pad = jnp.full((n_pad - n, self.n_features), self.cuts.missing_bin,
                           dtype=self.bins.dtype)
            bins = jnp.concatenate([self.bins, pad], axis=0)
        shards = shard_rows(bins, mesh)
        self._sharded = (id(mesh), shards, n_pad)
        return shards, n_pad

    @classmethod
    def from_sparse(
        cls,
        storage,  # sparse.CSRStorage
        max_bin: int = 256,
        weights: Optional[np.ndarray] = None,
        cuts: Optional[HistogramCuts] = None,
        categorical: Optional[Sequence[int]] = None,
        col_block: int = 16,
    ) -> "BinnedMatrix":
        """Quantize CSR input WITHOUT a dense float detour: NaN-filled
        column blocks stream through the same ``_cuts_kernel``/``_bin_kernel``
        the dense path uses (bit-identical cuts and bins), so peak extra
        host memory is ``n x col_block`` floats. The quantized result is the
        usual dense narrow-int ELLPACK layout (reference sparse inputs
        likewise quantize into GHistIndex/Ellpack pages,
        ``gradient_index.cc:199``)."""
        import time

        from ..observability import flight, trace

        t_ing = time.perf_counter()
        n, F = storage.shape
        cat = tuple(categorical) if categorical else ()
        if weights is None or (hasattr(weights, "size") and weights.size == 0):
            w = jnp.ones((n,), dtype=jnp.float32)
        else:
            w = jnp.asarray(weights, dtype=jnp.float32)

        if cuts is None:
            with trace.stage("sketch", rows=n, features=F, max_bin=max_bin):
                vals, mins = _cuts_by_blocks(storage, w, max_bin, col_block)
            cuts = HistogramCuts(values=vals, min_vals=mins)
            if cat:
                apply_categorical_identity(cuts.values, cuts.min_vals, list(cat))
        bins = np.empty((n, F), dtype=np.dtype(storage_dtype(cuts.max_bin)))
        with trace.stage("bins", rows=n, max_bin=cuts.max_bin):
            for f0, f1, bb in _bins_by_blocks(storage, cuts, col_block):
                bins[:, f0:f1] = np.asarray(bb)
        counts: Tuple[int, ...] = ()
        if cat:
            maxes = []
            for f in cat:
                cv = storage.column_values(f)
                cv = cv[~np.isnan(cv)]
                maxes.append(float(cv.max()) if cv.size else np.nan)
            counts = tuple(int(m) + 1 if np.isfinite(m) else 1 for m in maxes)
        out = cls(cuts=cuts, bins=jnp.asarray(bins), categorical=cat,
                  cat_counts=counts)
        # DMatrix-construction wall time: the data plane's 'ingest' flight
        # stage (sketch + quantize + conversion — docs/observability.md)
        flight.note("ingest", time.perf_counter() - t_ing)
        return out

    @classmethod
    def from_dense(
        cls,
        X: np.ndarray | jax.Array,
        max_bin: int = 256,
        weights: Optional[np.ndarray] = None,
        cuts: Optional[HistogramCuts] = None,
        categorical: Optional[Sequence[int]] = None,
    ) -> "BinnedMatrix":
        import time

        from ..observability import flight

        t_ing = time.perf_counter()
        cat = tuple(categorical) if categorical else ()
        counts: Tuple[int, ...] = ()
        if cat:
            Xn = np.asarray(X)
            maxes = [
                np.nanmax(Xn[:, f]) if np.isfinite(Xn[:, f]).any() else np.nan
                for f in cat
            ]
            counts = tuple(
                int(m) + 1 if np.isfinite(m) else 1 for m in maxes
            )
        if cuts is None:
            cuts = compute_cuts(X, max_bin=max_bin, weights=weights, categorical=cat)
        out = cls(cuts=cuts, bins=bin_matrix(X, cuts), categorical=cat,
                  cat_counts=counts)
        flight.note("ingest", time.perf_counter() - t_ing)
        return out
