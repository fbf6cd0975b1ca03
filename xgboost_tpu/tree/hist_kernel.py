"""Fused partition + level-histogram kernels for tpu_hist.

Reference equivalents: the histogram kernel ``gpu_hist/histogram.cu:127-177``
(shared-memory atomic scatter-add per feature group) and the row partitioner
``gpu_hist/row_partitioner.cu``. TPUs have no fast scatter, so the TPU-native
formulation turns the histogram into MXU work: for every feature a one-hot
``[rows, n_bins]`` matrix is generated **in VMEM** (never touching HBM) and
contracted against per-node gradient columns on the systolic array. Gradient
precision comes from a hi/lo bfloat16 split (bitcast-masked so the compiler
cannot simplify it away): two bf16 terms carry ~16 significand bits, so
histogram sums land within ~2^-16 relative of exact f32 — the same error
class as the reference's single-precision accumulation, but deterministic
(its GPU kernel needs fixed-point atomics for that,
``gpu_hist/histogram.cu:81-120``). Near-tie splits may therefore resolve
differently than the f32 segment_sum fallback used on non-TPU backends.

The partition step (route every row through its node's split decision) is
fused into the same kernel: node decision tables are tiny, so the lookup is
a one-hot matmul against a ``[nodes, 4]`` table, and the per-row feature
value is selected by a second small matmul (every node's split feature out
of the bins tile) and the row's node one-hot — no gathers anywhere
(XLA/Mosaic gathers serialize on TPU).

Per-row scalars have the ROWS ON THE LANE AXIS everywhere in this module:
positions are ``[1, n]`` int32 and gradients ``[2, n]`` float32 (row 0 g,
row 1 h), from the grower's root to ``leaf_delta``. A ``[n, 1]`` or
``[n, 2]`` array is padded to 128 lanes in HBM and VMEM on the TPU (512
bytes a row for 4 or 8) and every VPU op on it works one lane in 128; the
lane-dense form is laid out one and two sublanes deep in HBM (``T(1,128)``,
``T(2,128)``: 4 and 8 bytes a row), a ``(k, tr)`` block of it takes at most
8 sublanes of VMEM (32 bytes a row) and is whole vregs. The XLA and native impls take the
same form and reshape at their own edge (the C ABI keeps rows major).
Every Mosaic call of a tree reads the bins FEATURE-MAJOR, ``[Fp, n]`` i32
with the rows on the lanes too (``_feature_major``: padded with the missing
bin to whole sublanes for the untiled kernels, to whole feature tiles for
the tiled one), and builds a column's one-hot ``[B, tr]`` by a sublane
broadcast of its row (``_construct_columns``). The XLA and native impls
read the narrow ``[n, F]`` storage.

Missing values: the quantized matrix encodes missing as bin id ``B``; the
one-hot over ``[0, B)`` is then all-zero, so missing rows simply drop out of
the histogram. Their per-feature sums are recovered as
``node_total - sum(bins)`` (the ELLPACK null-symbol trick inverted), keeping
the matmul lane count at exactly ``B`` — no padding waste.

A pure-XLA fallback (`fused_level_xla`) with identical semantics serves
non-TPU backends (CPU tests, virtual-device dryruns) via segment_sum.
"""

from __future__ import annotations

import functools
import threading
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis.retrace import guard_jit

__all__ = [
    "fused_level", "fused_level_xla", "fused_level_native",
    "fused_level_trees", "level_trees", "derive_siblings",
    "partition_apply", "partition_apply_xla", "leaf_delta",
    "TR", "use_pallas", "use_native_hist", "build_onehot",
    "pallas_level_fits", "pallas_route_fits", "level_plan", "LevelPlan",
    "feature_tile",
    "hoist_budget_bytes", "can_hoist", "hoist_plan", "device_free_bytes",
]

TR = 1024  # rows per kernel grid step
TR_HOIST = 512  # rows per grid step for the hoisted-one-hot kernel

# test hook: run pallas_calls in interpret mode (lets the CPU suite
# execute the REAL kernel bodies, including under shard_map)
_INTERPRET = False

# 0xFFFF0000 as int32: masks an f32 down to its bf16-representable prefix
_MASK_HI = np.int32(np.uint32(0xFFFF0000).view(np.int32))

# The UNTILED level kernels unroll the feature loop over every column and
# keep the whole ``[2K, F*B]`` accumulator in VMEM: they take a matrix up to
# this width. A wider one (or a narrower one whose accumulator outgrows
# VMEM) goes to the TILED kernel below them, whose accumulator and
# unrolled loop cover ``_FEATURE_TILE`` columns whatever ``F`` is.
_MAX_KERNEL_FEATURES = 512

# Columns of a feature tile of the tiled level kernel: the sublanes of the
# ``(ft, tr)`` i32 block of the feature-major bins (``_feature_major``) and
# the leading dimension of the accumulator block. The bins are padded to
# whole tiles with the missing bin, whose one-hot is all zero.
_FEATURE_TILE = 128

# The untiled kernels' feature-major bins are padded to whole sublanes of
# an i32 vreg (the ``(Fp, tr)`` block is then whole tiles); their loops and
# accumulators stop at the real ``F``.
_SUBLANES = 8

# test hook: a feature tile forced on the tiled kernel, and every level sent
# to it, so that the CPU suite can hold a narrow matrix's tiles against the
# untiled kernels (the interpreter takes any block width)
_FORCE_TILE: Optional[int] = None


def use_pallas() -> bool:
    """Whether the fused TPU kernel path is usable on the default backend."""
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# Native CPU histogram: XLA:CPU lowers segment_sum to a serialized scatter
# measured at ~68ns per (row, feature) update — table size, update width and
# index order do not move it, so at the bench shape the histogram IS the
# round (6 levels x ~345ms of a ~2s round). hist_build.cpp does the same
# f32 additions in the same row order in ~7ms (the reference's GHistBuilder
# tier, hist_util.h:323), reading bins in their NARROW storage dtype
# (uint8/uint16 — no widened int32 copy of the bin matrix anywhere), and is
# bit-identical to a standalone segment_sum. It is wired in as an XLA FFI
# custom call (NOT jax.pure_callback: on a single-core CPU client the
# callback machinery's async operand copies queue behind the very program
# being executed — np.asarray deadlocks, raw buffer reads race the copy;
# the FFI handler runs synchronously inside the thunk with materialized
# buffers), so the host round loop stays non-blocking and the scan/pipeline
# structure above it is unchanged. Route selection lives in the dispatch
# registry (dispatch/ops.py); the legacy XGBTPU_NATIVE_HIST=0 kill switch
# maps to a `level_hist=!native` pin there.
# ---------------------------------------------------------------------------

_ffi_lock = threading.Lock()
_ffi_state = {"registered": None}  # None = not tried, True/False = result


def _ensure_ffi() -> bool:
    """Build/load the native library and register its FFI handlers with
    XLA (once per process). False when the library is unavailable (no
    toolchain, failed build, canary refusal); a JAX API error propagates —
    it is not a build problem."""
    with _ffi_lock:
        if _ffi_state["registered"] is None:
            from ..native import get_hist_lib

            lib = get_hist_lib()
            if lib is not None:
                jax.ffi.register_ffi_target(
                    "xgbtpu_hb_level", jax.ffi.pycapsule(lib.XgbtpuHbLevel),
                    platform="cpu")
                jax.ffi.register_ffi_target(
                    "xgbtpu_hb_partition",
                    jax.ffi.pycapsule(lib.XgbtpuHbPartition), platform="cpu")
            _ffi_state["registered"] = lib is not None
        return _ffi_state["registered"]


def use_native_hist() -> bool:
    """Whether the native (FFI custom call) histogram path is usable:
    CPU backend, kernel tests not forcing interpret mode, the dispatch
    layer not pinning it off (the legacy ``XGBTPU_NATIVE_HIST=0`` kill
    switch maps to a ``level_hist=!native`` pin there), and the on-demand
    library builds/loads/registers."""
    from ..dispatch import pinned_off

    if pinned_off("level_hist", "native"):
        return False
    if _INTERPRET or jax.default_backend() != "cpu":
        return False
    return _ensure_ffi()


def fused_level_native(bins, pos, gh, ptab, *, K, Kp, B, d=None,
                       prev_offset=None, offset=None):
    """Same contract as ``fused_level_xla`` — ``pos`` [1, n] i32 and
    ``gh`` [2, n] f32 in, (new pos [1, n] i32, hist [F, 2K, B] f32, missing
    excluded) out — via the native FFI kernel, whose C ABI keeps the rows
    major (``[n, 1]``, ``[n, 2]``: the same bytes for ``pos``, one small
    transpose for ``gh``). Only valid for numerical decision tables
    (W == 4) on narrow-int bins. The heap offsets derive from static
    ``d``, or arrive as traced scalars from the depth-scanned driver (one
    call site for the kernel ABI)."""
    from ..native import boundary

    n, F = bins.shape
    if prev_offset is None:
        prev_offset = jnp.int32((1 << (d - 1)) - 1 if d > 0 else 0)
        offset = jnp.int32((1 << d) - 1)
    pos_new, hist = boundary.ffi_call(
        "xgbtpu_hb_level",
        (jax.ShapeDtypeStruct((n, 1), jnp.int32),
         jax.ShapeDtypeStruct((F, 2 * K, B), jnp.float32)),
        bins, pos.reshape(n, 1), gh.T, ptab,
        prev_offset.astype(jnp.int32), offset.astype(jnp.int32),
        K=K, Kp=Kp, B=B)
    return pos_new.reshape(1, n), hist


def _fell_off_mosaic(dec) -> bool:
    """Whether a TPU job's level or last routing resolved to XLA with
    nobody asking: a route the user pinned (``XGBTPU_DISPATCH``) is a
    choice, not a fall-back."""
    return (dec.impl == "xla" and dec.reason != "pinned"
            and jax.default_backend() == "tpu")


def _warn_off_mosaic(dec, what: str, n: int, F: int, K: int, B: int) -> None:
    """One ``console_logger.warning`` a shape where a TPU job's level or
    last routing falls off the Mosaic kernels to XLA (a ``segment_sum``
    scatter, a gather: a few GB/s on this chip), so that a matrix no
    kernel takes does not crawl unseen (``_fell_off_mosaic``)."""
    from ..dispatch.core import _warn_once

    _warn_once(
        f"offmosaic:{dec.op}:{n}:{F}:{B}",
        f"tpu_hist: {what} of a {n} x {F} matrix ({B} bins, {K} nodes) fits "
        f"no Mosaic kernel and runs in XLA ({dec.reason}: {dec.detail}); "
        "expect it to be slow")


def partition_apply(bins, pos, ptab, *, Kp: int, B: int, d: int,
                    pallas: bool = False, axis_name=None):
    """Route rows (``pos`` [1, n] i32 in, [1, n] i32 out) through level
    ``d-1``'s decisions, by the impl the dispatch registry resolves
    ``level_partition`` to: the Mosaic routing kernel where the call
    site's ``pallas`` flag is set and a row tile fits at this width
    (``_route_tr``; TPU: a gather streams at a few GB/s there), the native
    FFI kernel on the CPU path, XLA everywhere else (identical integer
    decisions)."""
    from ..dispatch import Ctx, resolve

    n, F = bins.shape
    dec = resolve("level_partition", Ctx(
        platform=jax.default_backend(), pallas=bool(pallas),
        interpret=bool(_INTERPRET), rows=int(n), features=int(F),
        nodes=int(Kp), table_width=int(ptab.shape[-1]),
        bins_dtype=str(bins.dtype), sharded=axis_name is not None))
    if pallas and _fell_off_mosaic(dec):
        _warn_off_mosaic(dec, "the last routing", n, F, Kp, B)
    if dec.impl == "pallas":
        vma = ()
        if axis_name is not None:
            # replication-proven table, uniformly varying operands: as in
            # ``fused_level``
            vma = (axis_name,)
            ptab = jax.lax.pcast(ptab, vma, to="varying")
        # the feature-major array the tree's root level read (padded to
        # whole feature tiles where even the root ran the tiled kernel, to
        # whole sublanes where an untiled kernel took it): the same
        # expression, so the tree keeps one widened copy. (A matrix whose
        # deep levels alone are tiled keeps the tiles' array beside it.)
        bins = _feature_major(bins, _tile_at(F, B, 1) or _SUBLANES, B)
        return _route_rows_pallas(
            bins, pos, ptab, Kp=Kp, B=B, d=d, vma=vma,
            tr=_route_tr(n, bins.shape[0], Kp, ptab.shape[-1]) or TR)
    if dec.impl == "native":
        from ..native import boundary

        prev_offset = (1 << (d - 1)) - 1 if d > 0 else 0
        return boundary.ffi_call(  # the C ABI's [n, 1]: the same bytes
            "xgbtpu_hb_partition",
            jax.ShapeDtypeStruct((n, 1), jnp.int32),
            bins, pos.reshape(n, 1), ptab, Kp=Kp, B=B,
            prev_offset=prev_offset).reshape(1, n)
    return partition_apply_xla(bins, pos, ptab, Kp=Kp, B=B, d=d)


# ---------------------------------------------------------------------------
# Hoisted one-hot: the quantized matrix's one-hot expansion is TRAINING-
# INVARIANT, yet the in-kernel construction (n x F x B int32 compares on the
# VPU) was measured as the per-level floor (~22 ms/level at 256 bins,
# docs/perf.md) and is re-done 6 levels x 500 rounds a run. Precomputing it
# ONCE per fit as an HBM-resident [n, F*B] int8 turns every level into pure
# MXU streaming: the level cost drops to the HBM read of the one-hot
# (~n*F*B bytes) overlapped with the matmuls. At max_bin=64 on the headline
# 1M x 50 workload that is 3.2 GB resident / ~4 ms/level streamed vs the
# ~22 ms construction floor. Reference analog: gpu_hist keeps the compressed
# ELLPACK resident and re-reads it per level (gpu_hist/histogram.cu:127) —
# this is the same trade with the TPU's preferred operand layout.
# ---------------------------------------------------------------------------

_HOIST_BUDGET_ENV = "XGBTPU_HOIST_BUDGET_MB"

# Below this many streamed features a partial hoist is not worth the
# resident HBM: the construct loop dominates either way.
_MIN_HOIST_FEATURES = 4


def device_free_bytes() -> Optional[int]:
    """Free HBM on this process's OWN first device per the runtime's
    allocator stats, or None where the backend keeps none (the CPU
    backend). local_devices (not devices) because on multi-process rank>0
    ``jax.devices()[0]`` is a remote, non-addressable device."""
    s = jax.local_devices()[0].memory_stats()
    if not s:
        return None
    return int(s["bytes_limit"]) - int(s["bytes_in_use"])


def hoist_plan_synced(n_pad: int, F: int, B: int, max_depth: int = 6) -> int:
    """``hoist_plan`` agreed across processes (min over ranks): the plan is
    baked statically into traced SPMD programs, so ranks with different
    free HBM must not compile different programs."""
    fh = hoist_plan(n_pad, F, B, max_depth)
    if jax.process_count() > 1:
        import numpy as _np

        from .. import collective

        all_fh = collective.process_allgather(
            _np.asarray(fh, _np.int64), site="hoist_plan")
        fh = int(all_fh.min())
    return fh


def hoist_budget_bytes() -> int:
    """HBM budget for the resident one-hot. XGBTPU_HOIST_BUDGET_MB wins
    when set (0 disables hoisting); otherwise 8 GiB clamped to 60% of the
    device's free HBM per ``memory_stats``. A TPU runtime that reports no
    ``memory_stats`` raises rather than guessing: an overshoot is an OOM
    minutes into a fit."""
    import os

    env = os.environ.get(_HOIST_BUDGET_ENV)
    if env is not None:
        try:
            return int(env) * 1024 * 1024
        except ValueError:
            pass
    budget = 8192 * 1024 * 1024
    free = device_free_bytes()
    if free is None:
        if jax.default_backend() == "tpu":
            raise RuntimeError(
                "the TPU runtime reports no memory_stats(), so the hoisted "
                f"one-hot cannot be budgeted; set {_HOIST_BUDGET_ENV} "
                "(0 disables hoisting)")
        return budget
    return min(budget, int(free * 0.6))


def hoist_plan(n_pad: int, F: int, B: int, max_depth: int = 6) -> int:
    """How many (leading) features to keep HBM-resident as a one-hot:
    the largest ``Fh <= F`` whose [n_pad, Fh*B] int8 expansion fits the
    HBM budget AND whose streaming working set fits VMEM at every level of
    the configured depth (``_hoist_tr`` — build and dispatch share one
    model). ``Fh == F`` is the full hoist; ``0 < Fh < F`` streams the
    first Fh features and constructs the rest in-kernel (the
    feature-group partitioning idea of the reference's
    gpu_hist/histogram.cu:127-177 applied to the resident expansion);
    0 means construct everything. The streaming kernel is untiled (its
    ``[2K, F*B]`` accumulator is counted whole), so a matrix only the
    tiled kernel takes gets 0 here: every column's one-hot is built in
    VMEM."""
    if not use_pallas() or B <= 0 or n_pad <= 0:
        return 0
    budget = hoist_budget_bytes()
    fh = min(F, budget // (n_pad * B))
    deepest_K = 1 << max(max_depth - 1, 0)
    while fh > 0 and _hoist_tr(fh * B, deepest_K, F, B) == 0:
        fh -= 1
    # the "not worth the resident HBM" floor applies only to PARTIAL
    # hoists — a full hoist of a narrow matrix (F < 4) is still a win
    if fh < F and fh < _MIN_HOIST_FEATURES:
        return 0
    return int(fh)


def can_hoist(n_pad: int, F: int, B: int, max_depth: int = 6) -> bool:
    """Whether the FULL one-hot can be hoisted (see ``hoist_plan``)."""
    return hoist_plan(n_pad, F, B, max_depth) == F


_BUILD_VMEM_BUDGET = 10 * 1024 * 1024  # double-buffered out tile + bins


def _build_tr(n: int, F: int, B: int) -> int:
    """Largest row tile (multiple of 256, dividing ``n``) whose build
    working set — the double-buffered ``[tr, F*B]`` int8 out tile plus the
    i32 bins tile — fits the VMEM budget. 0 when none does."""
    for tr in (1024, 512, 256):
        if n % tr == 0 and tr * F * B * 2 + tr * F * 4 <= _BUILD_VMEM_BUDGET:
            return tr
    return 0


def _build_onehot_body(bins_ref, out_ref, *, F: int, B: int):
    binsb = bins_ref[:, :]  # [tr, F] i32
    iota_b = jax.lax.broadcasted_iota(jnp.int32, (binsb.shape[0], B), 1)
    for f in range(F):
        col = binsb[:, f:f + 1]
        out_ref[:, f * B:(f + 1) * B] = (col == iota_b).astype(jnp.int8)


@guard_jit(name="onehot_build_pallas", static_argnames=("B", "tr", "vma"))
def _build_onehot_pallas(bins: jax.Array, *, B: int, tr: int,
                         vma=()) -> jax.Array:
    """Tile-local build: each row-tile grid step compares its i32 bins
    columns against an iota entirely in VMEM and stores the int8 tile, so
    peak HBM is the int8 output itself. The XLA broadcast build instead
    materializes the ``[n, F, B]`` *s32 compare intermediate* (4
    bytes/entry, 4x the output) — at the headline 1M x 34 x 256
    partial-hoist shape a 26 GB allocation that cannot fit any chip."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, F = bins.shape
    # the scope sits inside the jitted builders: a caller outside any
    # program (BinnedMatrix.fused_onehot) could not put it in the HLO. The
    # TPU compiler names a Mosaic call after the last component of its
    # path, so a second scope keeps the kernel's name in a profile.
    with jax.named_scope("xgb.onehot_build"), \
            jax.named_scope("_build_onehot_pallas"):
        return pl.pallas_call(
            functools.partial(_build_onehot_body, F=F, B=B),
            grid=(n // tr,),
            in_specs=[
                pl.BlockSpec((tr, F), lambda i: (i, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((tr, F * B), lambda i: (i, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=_vma_struct((n, F * B), jnp.int8, vma),
            interpret=_INTERPRET,
        )(bins.astype(jnp.int32))


@guard_jit(name="onehot_build_xla", static_argnames=("B",))
def _build_onehot_xla(bins: jax.Array, *, B: int) -> jax.Array:
    n, F = bins.shape
    with jax.named_scope("xgb.onehot_build"):
        iota = jnp.arange(B, dtype=jnp.int32)
        oh = (bins.astype(jnp.int32)[:, :, None] == iota[None, None, :])
        return oh.astype(jnp.int8).reshape(n, F * B)


def build_onehot(bins: jax.Array, *, B: int, vma=()) -> jax.Array:
    """[n, F] narrow-int bins -> [n, F*B] int8 one-hot (missing bin ``B``
    maps to an all-zero row, so missing rows drop out of histograms exactly
    like the in-kernel construction). Built once per training run; on TPU
    via a Pallas tile kernel whose peak HBM footprint is the output alone
    (see ``_build_onehot_pallas``), elsewhere by XLA broadcast-compare
    (small shapes only — tests, narrow matrices). ``vma`` annotates the
    output's varying axes when building inside ``shard_map``."""
    from ..dispatch import Ctx, resolve
    from ..observability import trace

    n, F = bins.shape
    with trace.span("onehot_build", rows=int(n), features=int(F), B=B):
        dec = resolve("onehot_build", Ctx(
            platform=jax.default_backend(),
            pallas=bool(use_pallas() or _INTERPRET),
            rows=int(n), features=int(F), bins=int(B)))
        if dec.impl == "pallas":
            return _build_onehot_pallas(bins, B=B, tr=_build_tr(n, F, B),
                                        vma=vma)
        return _build_onehot_xla(bins, B=B)


def _split_hilo(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Exact f32 = hi + lo with both parts bf16-representable. Done with a
    bitcast mask (not a dtype round-trip) so XLA/Mosaic cannot fold
    ``convert(convert(x))`` back into ``x`` and silently drop the lo term."""
    from jax.experimental.pallas import tpu as pltpu

    hi = pltpu.bitcast(pltpu.bitcast(x, jnp.int32) & _MASK_HI, jnp.float32)
    return hi, x - hi


def _bins_operand(binsb, B: int):
    """The bins tile as the feature pick's matmul operand: bins 0..B and a
    0/1 one-hot are exact in bf16 up to B = 256 (one MXU pass there), f32
    at full precision beyond."""
    return binsb.astype(jnp.float32).astype(
        jnp.bfloat16 if B <= 256 else jnp.float32)


def _partition_tile(pos, binsT, ptab_ref, *, Kp: int, B: int,
                    prev_offset: int, tree=None, bins_op=None):
    """Route a tile's rows through the previous level's decision table
    (shared by the level kernels and the routing kernel). ``pos`` is
    ``[1, Tr]`` i32 (rows on the lanes) and so is the result; ``binsT`` is
    the ``[F, Tr]`` i32 tile of the tree's feature-major bins
    (``_feature_major``; ``F`` counts the padding), a value in VMEM.
    Table layout: ``[Kp, 4]``
    numerical (is_split, feature, bin, default_left), or ``[Kp, 5 + B]``
    when categorical features exist — column 4 flags a categorical node and
    columns 5: carry its RIGHT-going category set (evaluate_splits.h
    Decision: stored sets go right). ``is_split`` is 0 (no split), 1, or 2:
    a split whose RIGHT child a sibling-subtracting level builds (1: the
    left), see ``_level_update``; routing reads it as ``> 0.5``.

    Every per-row quantity is a ``[1, Tr]`` row or a ``[k, Tr]`` stack of
    them: the node one-hot is ``[Kp, Tr]``, the decisions ``ptab^T [W, Kp]
    @ [Kp, Tr]``. The row's bin of its node's split feature: a plain
    ``[Kp, F] @ [F, Tr]`` gives every node's split feature for every row,
    ``[Kp, Tr]`` (the padded columns are no node's feature), and the node
    one-hot picks the row's own.

    ``tree`` (a level call that carries several trees): ``ptab_ref`` is
    ``[T, Kp, W]`` and this tree's table its ``tree``-th; ``bins_op`` is
    then the bins tile already cast for the pick (``_bins_operand``), made
    once for all T."""
    W = ptab_ref.shape[-1]
    Tr = pos.shape[1]
    ptab = ptab_ref[:, :] if tree is None else ptab_ref[tree]  # [Kp, W]
    lp = pos - prev_offset  # [1, Tr]
    iota_kp = jax.lax.broadcasted_iota(jnp.int32, (Kp, Tr), 0)
    ohp = (lp == iota_kp).astype(jnp.float32)  # [Kp, Tr]
    # f32 table matmul: exact for feature ids / bin ids up to 2^24
    dec = jax.lax.dot_general(
        ptab, ohp, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )  # [W, Tr]
    isp_of = dec[0:1, :]
    b_of = dec[2:3, :]
    dl_of = dec[3:4, :]
    iota_f = jax.lax.broadcasted_iota(jnp.int32, (Kp, binsT.shape[0]), 1)
    ohf = ptab[:, 1:2].astype(jnp.int32) == iota_f  # [Kp, F]
    narrow = B <= 256  # see _bins_operand
    ohf = ohf.astype(jnp.float32).astype(
        jnp.bfloat16 if narrow else jnp.float32)
    if bins_op is None:
        bins_op = _bins_operand(binsT, B)
    node_bv = jax.lax.dot_general(
        ohf, bins_op, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=None if narrow else jax.lax.Precision.HIGHEST,
    )  # [Kp, Tr]: bins[row, feature of node]
    bv = jnp.sum(ohp * node_bv, axis=0, keepdims=True)  # [1, Tr]
    # arithmetic (not boolean) masks: the numerical and the categorical
    # decision are one expression, with no select between i1 vectors
    missing = (bv >= B).astype(jnp.float32)
    leq = (bv <= b_of).astype(jnp.float32)
    if W > 4:
        isc_of = dec[4:5, :]
        setrow = dec[5:, :]  # [B, Tr] the node's right-going set
        iota_b = jax.lax.broadcasted_iota(jnp.int32, (W - 5, Tr), 0)
        member = jnp.sum(
            (bv == iota_b.astype(jnp.float32)).astype(jnp.float32) * setrow,
            axis=0, keepdims=True)
        present_left = isc_of * (1.0 - member) + (1.0 - isc_of) * leq
    else:
        present_left = leq
    goleft = missing * dl_of + (1.0 - missing) * present_left
    inb = (lp >= 0).astype(jnp.float32) * (lp < Kp).astype(jnp.float32)
    goes = inb * isp_of
    child = 2 * pos + 1 + (goleft < 0.5).astype(jnp.int32)
    return pos + (goes > 0.5).astype(jnp.int32) * (child - pos)


def _grad_terms(node, ids, gh_ref, row: int = 0):
    """The four f32 ``[K, Tr]`` terms of a tree's gradient channels, in the
    order [g_hi, h_hi, g_lo, h_lo]: a row of the data (a lane here)
    contributes to the channel group whose id (``ids``: ``[K, Tr]`` or
    ``[K, 1]`` i32) its ``node`` (``[1, Tr]`` i32) is, or to none.
    ``gh_ref`` is the ``(2T, Tr)`` block, this tree's g over h at rows
    ``row``, ``row + 1``."""
    ohseg = (node == ids).astype(jnp.float32)  # [K, Tr]
    g = gh_ref[row:row + 1, :]
    h = gh_ref[row + 1:row + 2, :]
    g_hi, g_lo = _split_hilo(g)
    h_hi, h_lo = _split_hilo(h)
    return [ohseg * g_hi, ohseg * h_hi, ohseg * g_lo, ohseg * h_lo]


def _route_and_terms(pos, binsT, gh_ref, ptab_ref, built_ref, *, K: int,
                     Kp: int, B: int, prev_offset: int, offset: int,
                     tree=None, bins_op=None):
    """The level kernels' shared head, for one tree: route the tile's rows
    (``pos`` ``[1, Tr]``) through the previous level's decisions, then form
    the gradient channels' terms (``_grad_terms``). Direct build
    (``built_ref`` None): one channel group a node of this level. Sibling
    subtraction: one a PARENT, for the rows now at the child that parent
    marked, whose heap index ``built_ref`` ``[Kp, 1]`` holds (-1: the
    parent did not split; a row that stayed above this level is at no
    child). The sibling is ``parent - built``, taken outside
    (``derive_siblings``). ``tree``: this tree's index in a call that
    carries several (tables ``[T, Kp, W]``, built ids ``[T, Kp, 1]``, g
    and h at rows ``2 tree``, ``2 tree + 1``)."""
    row = 0 if tree is None else 2 * tree
    if Kp > 0:
        pos = _partition_tile(pos, binsT, ptab_ref, Kp=Kp, B=B,
                              prev_offset=prev_offset, tree=tree,
                              bins_op=bins_op)
    if built_ref is None:
        iota_k = jax.lax.broadcasted_iota(jnp.int32, (K, pos.shape[1]), 0)
        return pos, _grad_terms(pos - offset, iota_k, gh_ref, row)
    built = built_ref[:, :] if tree is None else built_ref[tree]
    return pos, _grad_terms(pos, built, gh_ref, row)


def _route_and_channels(pos_ref, binsT, gh_ref, ptab_ref, built_ref, pos_out,
                        *, T, Kp: int, B: int, **kw):
    """Route the block's rows and return the gradient channels the one-hot
    meets, bf16. One tree (``T`` None; ``pos_ref`` ``(1, Tr)``): ``[4Kc,
    Tr]`` in the row order [g_hi | h_hi | g_lo | h_lo], so ``out[:2Kc] +
    out[2Kc:] = [g, h]``. ``T`` trees of one round (``pos_ref`` ``(T,
    Tr)``): each routes its own positions through its own table, over the
    one bins tile, and the T channel groups are stacked ``[4 T Kc, Tr]``,
    every tree's hi terms above every tree's lo terms, so that the same
    ``out[:half] + out[half:]`` leaves tree t's ``[g, h]`` at rows ``2Kc t
    .. 2Kc (t + 1)``: per tree the one-tree kernel's arithmetic, row for
    row. Writes the routed positions to ``pos_out``; None where the rows
    came routed already (the tiled kernel: ``Kp`` 0, no bins, no table)."""
    if T is None:
        pos, terms = _route_and_terms(pos_ref[:, :], binsT, gh_ref, ptab_ref,
                                      built_ref, Kp=Kp, B=B, **kw)
        chans = jnp.concatenate(terms, axis=0).astype(jnp.bfloat16)
        if pos_out is not None:
            pos_out[:, :] = pos
        return chans
    bins_op = _bins_operand(binsT, B) if Kp > 0 else None
    hi, lo = [], []
    for t in range(T):
        pos, terms = _route_and_terms(
            pos_ref[t:t + 1, :], binsT, gh_ref, ptab_ref, built_ref, Kp=Kp,
            B=B, tree=t, bins_op=bins_op, **kw)
        if pos_out is not None:
            pos_out[t:t + 1, :] = pos
        hi += terms[:2]
        lo += terms[2:]
    return jnp.concatenate(hi + lo, axis=0).astype(jnp.bfloat16)


def _level_kernel(bins_ref, pos_ref, gh_ref, ptab_ref, *rest,
                  K: int, Kp: int, F: int, B: int,
                  prev_offset: int, offset: int, T=None):
    """One grid step: partition `Tr` rows through the previous level's
    decisions, then accumulate their (g, h) into this level's histogram.
    ``bins_ref`` is the ``(Fp, Tr)`` block of the feature-major bins,
    ``pos_ref`` and ``gh_ref`` the ``(1, Tr)`` and ``(2, Tr)`` blocks of
    the lane-dense arrays. ``rest``: the outputs ``pos_out, hist_ref``,
    behind ``built_ref`` where siblings are subtracted (the histogram is
    then the built children's, ``Kc = Kp`` nodes wide). ``T`` trees of one
    round (``_route_and_channels``): blocks ``(T, Tr)`` and ``(2T, Tr)``,
    the histogram ``2 T Kc`` rows, and a column's one-hot is built once
    for all of them."""
    from jax.experimental import pallas as pl

    *built_ref, pos_out, hist_ref = rest
    built_ref = built_ref[0] if built_ref else None

    c = pl.program_id(0)

    @pl.when(c == 0)
    def _():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    binsT = bins_ref[:, :]  # [Fp, Tr] i32
    ghs4 = _route_and_channels(  # [2M, Tr]
        pos_ref, binsT, gh_ref, ptab_ref, built_ref, pos_out, T=T, K=K,
        Kp=Kp, B=B, prev_offset=prev_offset, offset=offset)

    _construct_columns(hist_ref, binsT, ghs4, B, range(F))


def _construct_columns(hist_ref, binsT, ghs4, B: int, cols):
    """The construct loop of every level kernel: for each column ``f`` of
    ``cols`` (rows of the ``[Fp, Tr]`` feature-major bins tile), its
    one-hot ``[B, Tr]`` built in VMEM by a SUBLANE broadcast of the row
    ``binsT[f]`` against an iota over the sublanes (one broadcast a
    128-row lane group, shared by its ``B / 8`` vregs: PERF.md section 6,
    PR 36), met with the channels ``[2M, Tr]`` with both minor dimensions
    contracted (the MXU latches the weights transposed), and added into
    the accumulator: ``hist_ref[f]`` where it is ``[columns, M, B]``, lanes
    ``f B .. (f + 1) B`` of the streaming kernel's ``[M, F B]``."""
    M = ghs4.shape[0] // 2
    Tr = binsT.shape[1]
    iota_b = jax.lax.broadcasted_iota(jnp.int32, (B, Tr), 0)
    for f in cols:
        # missing (== B) -> a zero column
        oh_t = (binsT[f:f + 1, :] == iota_b).astype(jnp.bfloat16)
        out = jax.lax.dot_general(
            ghs4, oh_t, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [2M, Tr] x [B, Tr] -> [2M, B]
        if len(hist_ref.shape) == 3:
            hist_ref[f, :, :] += out[:M] + out[M:]
        else:
            hist_ref[:, f * B:(f + 1) * B] += out[:M] + out[M:]


def _in_hbm(binsT):
    """The untiled kernels' feature-major bins, held in HBM for the call.
    Without the constraint XLA may keep an array that fits the chip's
    VMEM there for a whole round (Cover Type's ``s32[56, 436224]``, 98
    MB), and the split evaluation's loops, spilled to HBM, lose more than
    the kernels gain."""
    if _INTERPRET:  # a placement for the chip's compiler alone
        return binsT
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.with_memory_space_constraint(binsT, pltpu.HBM)


def _vma_struct(shape, dtype, axes):
    """ShapeDtypeStruct with the varying-manual-axes annotation shard_map's
    check_vma demands of pallas_call outputs (per-shard kernel results vary
    over the row axis; the psum above the kernel restores invariance)."""
    if axes:
        return jax.ShapeDtypeStruct(shape, dtype, vma=frozenset(axes))
    return jax.ShapeDtypeStruct(shape, dtype)


def _built_children(ptab, *, Kp: int, d: int, sub: bool):
    """The extra operand of a sibling-subtracting level kernel: ``[Kp, 1]``
    i32 (a column: the channels compare it with the rows' ``[1, Tr]``
    positions), for every parent the heap index of the child its decision
    table marks to be built (``is_split`` 1: the left, 2: the right), -1
    where it does not split. Returns (arrays, block specs): empty for the
    direct build, whose kernels take no such operand. ``[T, Kp, 1]`` from
    the ``[T, Kp, W]`` tables of a call that carries T trees."""
    if not sub:
        return [], []
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    assert Kp > 0, "the root has no parent to subtract from"
    mark = ptab[..., 0].astype(jnp.int32)  # [Kp], or [T, Kp]
    parent = ((1 << (d - 1)) - 1) + jnp.arange(Kp, dtype=jnp.int32)
    built = jnp.where(mark > 0, 2 * parent + mark, -1)[..., None]
    return [built], [pl.BlockSpec(built.shape, lambda *_: (0,) * built.ndim,
                                  memory_space=pltpu.VMEM)]


def _tree_axis(ptab, Kp: int):
    """What a tree axis on the decision tables (``[T, Kp, W]``: the call
    carries T trees of one round) changes of a level call's operands: (T or
    None, the rows of the positions block, the tables' block spec)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    W = ptab.shape[-1]
    if ptab.ndim == 3:
        T = ptab.shape[0]
        return T, T, pl.BlockSpec((T, max(Kp, 1), W), lambda c: (0, 0, 0),
                                  memory_space=pltpu.VMEM)
    return None, 1, pl.BlockSpec((max(Kp, 1), W), lambda c: (0, 0),
                                 memory_space=pltpu.VMEM)


@guard_jit(name="fused_level_pallas",
           static_argnames=("F", "K", "Kp", "B", "d", "tr", "vma", "sub"))
def _fused_level_pallas(binsT, pos, gh, ptab, *, F, K, Kp, B, d, tr=TR,
                        vma=(), sub=False):
    """The in-kernel construction of a level: the feature-major i32 bins
    ``[Fp, n]`` (``_feature_major``; ``F`` of its rows real) by ``(Fp,
    tr)`` blocks, positions ``[R, n]`` and gradients ``[2R, n]`` in; the
    routed positions and ``[F, 2Kc, B]`` out."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    Fp, n = binsT.shape
    assert n % tr == 0, f"rows {n} not padded to {tr}"
    prev_offset = (1 << (d - 1)) - 1 if d > 0 else 0
    offset = (1 << d) - 1
    Kc = Kp if sub else K  # nodes built: one child of every parent
    T, R, ptab_spec = _tree_axis(ptab, Kp)
    built, built_specs = _built_children(ptab, Kp=Kp, d=d, sub=sub)
    kern = functools.partial(
        _level_kernel, K=K, Kp=Kp, F=F, B=B,
        prev_offset=prev_offset, offset=offset, T=T,
    )
    pos_new, hist = pl.pallas_call(
        kern,
        grid=(n // tr,),
        in_specs=[
            pl.BlockSpec((Fp, tr), lambda c: (0, c), memory_space=pltpu.VMEM),
            pl.BlockSpec((R, tr), lambda c: (0, c), memory_space=pltpu.VMEM),
            pl.BlockSpec((2 * R, tr), lambda c: (0, c),
                         memory_space=pltpu.VMEM),
            ptab_spec,
        ] + built_specs,
        out_specs=[
            pl.BlockSpec((R, tr), lambda c: (0, c), memory_space=pltpu.VMEM),
            pl.BlockSpec((F, 2 * R * Kc, B), lambda c: (0, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            _vma_struct((R, n), jnp.int32, vma),
            _vma_struct((F, 2 * R * Kc, B), jnp.float32, vma),
        ],
        interpret=_INTERPRET,
    )(_in_hbm(binsT), pos, gh, ptab, *built)
    if T is None:
        return pos_new, hist
    # [F, T * 2Kc, B] -> a tree's [F, 2Kc, B] each
    return pos_new, jnp.transpose(hist.reshape(F, T, 2 * Kc, B), (1, 0, 2, 3))


def _hoisted_kernel(bins_ref, oh_ref, pos_ref, gh_ref, ptab_ref, *rest,
                    K: int, Kp: int, F: int, Fh: int, B: int,
                    prev_offset: int, offset: int, T=None):
    """Hoisted-one-hot grid step: partition + grad channels (cheap VPU, on
    whole vregs: rows on the lanes), ONE [4Kc, Tr] x [Tr, Fh*B] MXU matmul
    streaming the resident one-hot for the first ``Fh`` features, and the
    construct loop (``_construct_columns``, on the ``(Fp, Tr)`` block of
    the feature-major bins) for the remaining ``F - Fh`` (empty when the
    full expansion fit HBM). ``pos_ref``, ``gh_ref``, ``rest``, ``Kc`` and
    ``T`` as in ``_level_kernel``: with T trees the one-hot tile is read
    once and meets all their channels, ``[4 T Kc, Tr]``, in that one
    matmul."""
    from jax.experimental import pallas as pl

    *built_ref, pos_out, hist_ref = rest
    built_ref = built_ref[0] if built_ref else None

    c = pl.program_id(0)

    @pl.when(c == 0)
    def _():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    binsT = bins_ref[:, :]  # [Fp, Tr] i32
    M = 2 * (K if built_ref is None else Kp) * (T or 1)  # accumulator rows
    ghs4 = _route_and_channels(  # [2M, Tr]
        pos_ref, binsT, gh_ref, ptab_ref, built_ref, pos_out, T=T, K=K,
        Kp=Kp, B=B, prev_offset=prev_offset, offset=offset)

    oh = oh_ref[:, :].astype(jnp.bfloat16)  # [Tr, Fh*B] int8 -> bf16
    out = jax.lax.dot_general(
        ghs4, oh, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [2M, Fh*B]
    hist_ref[:, : Fh * B] += out[:M] + out[M:]
    _construct_columns(hist_ref, binsT, ghs4, B, range(Fh, F))


@guard_jit(name="hoisted_level_pallas",
           static_argnames=("F", "K", "Kp", "B", "d", "tr", "vma", "sub"))
def _hoisted_level_pallas(binsT, onehot, pos, gh, ptab, *, F, K, Kp, B, d,
                          tr=TR_HOIST, vma=(), sub=False):
    """A level on the resident one-hot ``[n, Fh*B]`` (rows-major, as the
    streaming matmul takes it) and the feature-major i32 bins ``[Fp, n]``
    (``F`` of its rows real: the routing and the construct loop's), by
    ``(tr, Fh*B)`` and ``(Fp, tr)`` blocks; the contract of
    ``_fused_level_pallas``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    Fp, n = binsT.shape
    Q = F * B
    Qh = onehot.shape[1]
    Fh = Qh // B  # the onehot's width IS the partial-hoist plan
    assert onehot.shape == (n, Qh) and Qh == Fh * B and Fh <= F <= Fp, (
        onehot.shape, F, Fp, B)
    assert n % tr == 0, f"rows {n} not padded to {tr}"
    prev_offset = (1 << (d - 1)) - 1 if d > 0 else 0
    offset = (1 << d) - 1
    Kc = Kp if sub else K  # nodes built: one child of every parent
    T, R, ptab_spec = _tree_axis(ptab, Kp)
    built, built_specs = _built_children(ptab, Kp=Kp, d=d, sub=sub)
    kern = functools.partial(
        _hoisted_kernel, K=K, Kp=Kp, F=F, Fh=Fh, B=B,
        prev_offset=prev_offset, offset=offset, T=T,
    )
    pos_new, hist2 = pl.pallas_call(
        kern,
        grid=(n // tr,),
        in_specs=[
            pl.BlockSpec((Fp, tr), lambda c: (0, c), memory_space=pltpu.VMEM),
            pl.BlockSpec((tr, Qh), lambda c: (c, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((R, tr), lambda c: (0, c), memory_space=pltpu.VMEM),
            pl.BlockSpec((2 * R, tr), lambda c: (0, c),
                         memory_space=pltpu.VMEM),
            ptab_spec,
        ] + built_specs,
        out_specs=[
            pl.BlockSpec((R, tr), lambda c: (0, c), memory_space=pltpu.VMEM),
            pl.BlockSpec((2 * R * Kc, Q), lambda c: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            _vma_struct((R, n), jnp.int32, vma),
            _vma_struct((2 * R * Kc, Q), jnp.float32, vma),
        ],
        interpret=_INTERPRET,
    )(_in_hbm(binsT), onehot, pos, gh, ptab, *built)
    if T is None:
        # [2Kc, F*B] -> the dispatcher contract [F, 2Kc, B]
        hist = jnp.transpose(hist2.reshape(2 * Kc, F, B), (1, 0, 2))
        return pos_new, hist
    # [T * 2Kc, F*B] -> a tree's [F, 2Kc, B] each
    return pos_new, jnp.transpose(hist2.reshape(T, 2 * Kc, F, B),
                                  (0, 2, 1, 3))


# ---------------------------------------------------------------------------
# The TILED level kernel: an accumulator over a tile of columns. The untiled
# kernels above keep ``[2K, F*B]`` f32 in VMEM and unroll their construct
# loop over every column, which stops them near a hundred columns at 256
# bins and at ``_MAX_KERNEL_FEATURES`` outright. Here the grid has a second,
# OUTER axis over feature tiles: the accumulator block is the tile's
# ``[ft, 2K, B]``, the unrolled loop runs over ``ft`` columns whatever ``F``
# is, and the row tiles are swept once a feature tile, inner, so a block of
# the accumulator stays in VMEM for its whole sweep. A row's route depends on
# its whole bins row (``_partition_tile``), so it is NOT redone a feature
# tile (that would read the i32 bins F / ft times a level): the rows are
# routed ONCE a level by the routing kernel (``_route_rows_pallas``, under
# ``xgb.partition``), and the tiles read the routed positions and their own
# columns. Every column's one-hot is built in VMEM (nothing resident is
# streamed: ``hoist_plan``), and a tile's step stays inside the budget the
# untiled streaming step has (``_tile_tr``).
# The tiles read the tree's feature-major bins padded to whole tiles
# (``_feature_major``), a tile's block ``(ft, tr)``, by the construct loop
# every level kernel shares (``_construct_columns``); the routing beside
# them reads the same array. Per histogram cell the sums are the untiled
# kernel's, row tile by row tile in the same order: at the same row tile the
# result is the untiled kernel's bit for bit (tests/test_feature_tiles.py).
# ---------------------------------------------------------------------------


def _tiled_level_kernel(bins_ref, pos_ref, gh_ref, *rest, K: int, B: int,
                        offset: int, T=None):
    """Grid step (feature tile j, row tile c): the ``ft`` columns of the
    ``(ft, Tr)`` block of the feature-major bins, each one's one-hot
    ``[B, Tr]`` met with the channels ``[2M, Tr]`` into the tile's ``[ft,
    M, B]`` accumulator (``M = 2 T Kc``), zeroed at the tile's first row
    tile. ``rest``: the output ``hist_ref``, behind ``built_ref`` where
    siblings are subtracted."""
    from jax.experimental import pallas as pl

    *built_ref, hist_ref = rest
    built_ref = built_ref[0] if built_ref else None

    @pl.when(pl.program_id(1) == 0)
    def _():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    # rows ALREADY at this level: the untiled kernels' channel stack, in
    # the same row order, with no routing and nothing written
    ghs4 = _route_and_channels(pos_ref, None, gh_ref, None, built_ref, None,
                               T=T, K=K, Kp=0, B=0, prev_offset=0,
                               offset=offset)
    binsT = bins_ref[:, :]  # [ft, Tr] i32
    _construct_columns(hist_ref, binsT, ghs4, B, range(binsT.shape[0]))


# "level" in the name: the benchmark books a Mosaic call so named to the
# level histogram (reduce/summary.py)
@guard_jit(name="tiled_level_pallas",
           static_argnames=("K", "Kp", "B", "d", "tr", "ft", "vma", "sub"))
def _tiled_level_pallas(binsT, pos, gh, ptab, *, K, Kp, B, d, tr, ft, vma=(),
                        sub=False):
    """One level's histogram by feature tiles: routed ``pos`` ``[R, n]``,
    ``gh`` ``[2R, n]`` and the feature-major i32 bins ``[Fp, n]`` in whole
    tiles in, ``[Fp, 2 R Kc, B]`` out. Grid (feature tiles, row tiles);
    bins ``(ft, tr)``, positions ``(R, tr)`` and gradients ``(2R, tr)`` by
    row tile, the built children's ids where siblings are subtracted."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    Fp, n = binsT.shape
    assert Fp % ft == 0, (Fp, ft)
    assert n % tr == 0, f"rows {n} not padded to {tr}"
    R = pos.shape[0]
    M = 2 * R * (Kp if sub else K)
    built, built_specs = _built_children(ptab, Kp=Kp, d=d, sub=sub)
    kern = functools.partial(_tiled_level_kernel, K=K, B=B,
                             offset=(1 << d) - 1,
                             T=R if ptab.ndim == 3 else None)
    return pl.pallas_call(
        kern,
        grid=(Fp // ft, n // tr),
        in_specs=[
            pl.BlockSpec((ft, tr), lambda j, c: (j, c),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((R, tr), lambda j, c: (0, c),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((2 * R, tr), lambda j, c: (0, c),
                         memory_space=pltpu.VMEM),
        ] + built_specs,
        out_specs=pl.BlockSpec((ft, M, B), lambda j, c: (j, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=_vma_struct((Fp, M, B), jnp.float32, vma),
        interpret=_INTERPRET,
    )(binsT, pos, gh, *built)


def _feature_major(bins, ft: int, B: int):
    """A tree's i32 bins ``[n, F]`` as its Mosaic calls read them:
    FEATURE-MAJOR ``[Fp, n]`` (rows on the lanes), padded to a multiple of
    ``ft`` columns (whole feature tiles for the tiled kernel, whole
    sublanes, ``_SUBLANES``, for the untiled ones) with the missing bin
    (an all-zero one-hot: a padded column is no node's feature, and the
    tiled kernel's histogram of it is zero and cut off). The same
    expression wherever a tree's program asks for it, so XLA keeps one
    such array a tree (it folds the pad and the transpose into the
    widening), read by every level call and routing."""
    pad = -bins.shape[1] % ft
    if pad:
        bins = jnp.pad(bins, ((0, 0), (0, pad)), constant_values=B)
    return bins.T


def _tiled_level(bins, pos, gh, ptab, *, K, Kp, B, d, plan, vma, sub):
    """One level through the tiled kernel (``plan.kernel == "tiled"``): the
    rows routed once, by the routing kernel a tree (its Mosaic time is
    ``xgb.partition``'s, not the level histogram's), then the feature
    tiles, both on the feature-major bins; the contract of the untiled
    calls."""
    n, F = bins.shape
    T = ptab.shape[0] if ptab.ndim == 3 else None
    binsT = _feature_major(bins, plan.ft, B)
    if Kp > 0:
        tr_r = _route_tr(n, binsT.shape[0], Kp, ptab.shape[-1])
        with jax.named_scope("xgb.partition"):
            if T is None:
                pos = _route_rows_pallas(binsT, pos, ptab, Kp=Kp, B=B, d=d,
                                         tr=tr_r, vma=vma)
            else:
                pos = jnp.concatenate([
                    _route_rows_pallas(binsT, pos[t:t + 1], ptab[t], Kp=Kp,
                                       B=B, d=d, tr=tr_r, vma=vma)
                    for t in range(T)])
    hist = _tiled_level_pallas(binsT, pos, gh, ptab, K=K, Kp=Kp, B=B, d=d,
                               tr=plan.tr, ft=plan.ft, vma=vma, sub=sub)[:F]
    if T is None:
        return pos, hist  # [F, 2Kc, B]
    # [F, T * 2Kc, B] -> a tree's [F, 2Kc, B] each
    return pos, jnp.transpose(hist.reshape(F, T, -1, B), (1, 0, 2, 3))


def _route_kernel(bins_ref, pos_ref, ptab_ref, pos_out, *, Kp: int, B: int,
                  prev_offset: int):
    """One grid step of a routing: ``Tr`` rows (a ``(1, Tr)`` block of
    positions in and out) through a level's decisions, and nothing else."""
    pos_out[:, :] = _partition_tile(pos_ref[:, :], bins_ref[:, :], ptab_ref,
                                    Kp=Kp, B=B, prev_offset=prev_offset)


# no "level" in this name: the TPU compiler names the Mosaic call after the
# function, and the benchmark books calls so named to the level histogram
@guard_jit(name="route_rows_pallas",
           static_argnames=("Kp", "B", "d", "tr", "vma"))
def _route_rows_pallas(binsT, pos, ptab, *, Kp, B, d, tr=TR, vma=()):
    """The routing kernel over a tree's feature-major i32 bins ``[Fp, n]``
    (``_feature_major``), block ``(Fp, tr)``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    Fp, n = binsT.shape
    assert n % tr == 0, f"rows {n} not padded to {tr}"
    prev_offset = (1 << (d - 1)) - 1 if d > 0 else 0
    W = ptab.shape[1]
    kern = functools.partial(_route_kernel, Kp=Kp, B=B,
                             prev_offset=prev_offset)
    return pl.pallas_call(
        kern,
        grid=(n // tr,),
        in_specs=[
            pl.BlockSpec((Fp, tr), lambda c: (0, c), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tr), lambda c: (0, c), memory_space=pltpu.VMEM),
            pl.BlockSpec((Kp, W), lambda c: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, tr), lambda c: (0, c),
                               memory_space=pltpu.VMEM),
        out_shape=_vma_struct((1, n), jnp.int32, vma),
        interpret=_INTERPRET,
    )(binsT, pos, ptab)


def partition_apply_xla(bins, pos, ptab, *, Kp: int, B: int, d: int,
                        prev_offset=None):
    """Route rows through level ``d-1``'s decisions (XLA, gather-free where
    it matters: the per-node table lookup is a one-hot matmul). Handles
    both table layouts — see ``_partition_tile``. ``prev_offset`` may be a
    TRACED scalar (the depth-scanned grow passes ``2^(d-1) - 1`` computed
    inside the scan body); when None it is derived statically from ``d``.
    ``pos`` is ``[1, n]`` in and out; the body works on its one row."""
    if prev_offset is None:
        prev_offset = (1 << (d - 1)) - 1 if d > 0 else 0
    W = ptab.shape[1]
    p = pos[0]
    lp = p - prev_offset  # [n]
    ohp = jax.nn.one_hot(jnp.where((lp >= 0) & (lp < Kp), lp, Kp),
                         Kp + 1, dtype=jnp.float32)[:, :Kp]  # [n, Kp]
    dec = jax.lax.dot_general(ohp, ptab, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32,
                              precision=jax.lax.Precision.HIGHEST)  # [n, W]
    isp_of = dec[:, 0]
    f_of = dec[:, 1].astype(jnp.int32)
    b_of = dec[:, 2]
    dl_of = dec[:, 3]
    bv = jnp.take_along_axis(bins, f_of[:, None], axis=1)[:, 0].astype(jnp.float32)
    missing = bv >= B
    present_left = bv <= b_of
    if W > 4:
        isc_of = dec[:, 4] > 0.5
        setrow = dec[:, 5:]  # [n, B]
        member = jnp.take_along_axis(
            setrow, jnp.minimum(bv, float(B - 1)).astype(jnp.int32)[:, None],
            axis=1)[:, 0] > 0.5
        present_left = jnp.where(isc_of, ~member, present_left)
    goleft = jnp.where(missing, dl_of > 0.5, present_left)
    inb = (lp >= 0) & (lp < Kp)
    goes = inb & (isp_of > 0.5)
    p = jnp.where(goes, jnp.where(goleft, 2 * p + 1, 2 * p + 2), p)
    return p[None, :]


@guard_jit(name="fused_level_xla", static_argnames=("K", "Kp", "B", "d"))
def fused_level_xla(bins, pos, gh, ptab, *, K, Kp, B, d):
    """Same contract as the pallas kernel (``pos`` [1, n], ``gh`` [2, n]),
    for non-TPU backends: partition via (cheap on CPU) gathers, histogram
    via segment_sum scatter-add over the rows-major ``gh.T``."""
    if Kp > 0:
        pos = partition_apply_xla(bins, pos, ptab, Kp=Kp, B=B, d=d)
    offset = (1 << d) - 1
    local = pos[0] - offset
    n, F = bins.shape
    seg = jnp.where((local >= 0) & (local < K), local, -1)
    MB = B + 1
    from .grow import blocked_histogram

    hist = blocked_histogram(bins, gh.T, seg, K, MB)  # [K, F, MB, 2]
    # -> kernel layout [F, 2K, B] (drop the missing bin: recovered by caller)
    hg = jnp.transpose(hist[:, :, :B, 0], (1, 0, 2))  # [F, K, B]
    hh = jnp.transpose(hist[:, :, :B, 1], (1, 0, 2))
    return pos, jnp.concatenate([hg, hh], axis=1)  # [F, 2K, B]


def fused_level_scanned(bins, pos, gh, ptab, prev_offset, offset, *,
                        K: int, B: int, native: bool):
    """One FIXED-WIDTH level step for the depth-scanned grow: partition
    rows through the previous level's decisions, then histogram, with the
    heap offsets as traced scalars and the node width pinned to ``K`` (the
    deepest level's ``2^(max_depth-1)``) at every iteration. Lanes beyond
    a shallow level's real width are self-masking: no row occupies them
    (histogram zero) and their heap stats are zero, so ``eval_splits``
    can never split them. Same output contract as ``fused_level_xla``."""
    if native:
        return fused_level_native(bins, pos, gh, ptab, K=K, Kp=K, B=B,
                                  prev_offset=prev_offset, offset=offset)
    pos = partition_apply_xla(bins, pos, ptab, Kp=K, B=B, d=-1,
                              prev_offset=prev_offset)
    local = pos[0] - offset
    n, F = bins.shape
    seg = jnp.where((local >= 0) & (local < K), local, -1)
    MB = B + 1
    from .grow import blocked_histogram

    hist = blocked_histogram(bins, gh.T, seg, K, MB)  # [K, F, MB, 2]
    hg = jnp.transpose(hist[:, :, :B, 0], (1, 0, 2))  # [F, K, B]
    hh = jnp.transpose(hist[:, :, :B, 1], (1, 0, 2))
    return pos, jnp.concatenate([hg, hh], axis=1)  # [F, 2K, B]


_VMEM_ACC_BUDGET = 6 * 1024 * 1024  # bytes for the [F, 2K, B] accumulator
_VMEM_HOIST_BUDGET = 12 * 1024 * 1024  # total working set of the hoisted step


def _hoist_vmem_bytes(tr: int, Qh: int, K: int, F: int,
                      B: Optional[int] = None) -> int:
    """Working-set estimate for one grid step of the UNTILED hoisted
    kernel: double-buffered int8 one-hot tile + its bf16 cast + the
    [4K, Qh] dot output + the [2K, F*B] f32 accumulator (full-width in
    this kernel: the construct loop for unhoisted features writes into it;
    the tiled kernel keeps ``_FEATURE_TILE`` columns of it,
    ``_tile_vmem_bytes``) + the bins tile + per-feature construct scratch.
    The bins tile is counted ``tr x F`` i32, as the parent's plans had it:
    the feature-major ``(Fp, tr)`` block (ISSUE 38) is whole sublanes of
    ``tr`` lanes, under the lane-padded ``(tr, F)`` block it replaced, and
    the VMEM that frees is not spent (every plan stays the parent's).
    ``B=None`` (legacy 3-arg callers) means full hoist: Qh==F*B."""
    if B is None:
        B = Qh // F
    Q = F * B
    construct = (tr * B * 2 + 4 * K * B * 4) if Qh < Q else 0
    return (2 * tr * Qh + 2 * tr * Qh + 4 * K * Qh * 4
            + 2 * K * Q * 4 + tr * F * 4 + construct)


def _hoist_tr(Qh: int, K: int, F: int, B: Optional[int] = None) -> int:
    """Largest workable row tile for the untiled hoisted kernel at this
    level's node count, or 0 if no tile fits VMEM. Single source of truth
    for both the build-side gate (``hoist_plan``) and the dispatch
    (``level_plan``) so they cannot disagree."""
    for tr in (TR_HOIST, TR_HOIST // 2, TR_HOIST // 4):
        if _hoist_vmem_bytes(tr, Qh, K, F, B) <= _VMEM_HOIST_BUDGET:
            return tr
    return 0


def _construct_fits(F: int, K: int, B: int) -> bool:
    """Whether the untiled construct-only kernel takes this level: a width
    its unrolled loop was compiled at, and the whole accumulator in its
    share of VMEM."""
    return F <= _MAX_KERNEL_FEATURES and F * 2 * K * B * 4 <= _VMEM_ACC_BUDGET


def _up(x: int, m: int) -> int:
    return -(-x // m) * m


def _tile_vmem_bytes(tr: int, ft: int, K: int, B: int) -> int:
    """Working set of one grid step of the tiled kernel, at the tiles it
    occupies (8 sublanes, 128 lanes), ``K`` the nodes of every tree the
    call carries. Double-buffered blocks: the ``(ft, tr)`` i32 tile of the
    feature-major bins (``ft`` sublanes of ``tr`` lanes), positions and
    gradients (8 and 16 sublanes of ``tr`` lanes), and the ``[ft, 2K, B]``
    f32 accumulator, whose block index moves with the tile. Values: the
    ``[4K, tr]`` channel terms in f32 and bf16, a column's ``[B, tr]``
    compare and one-hot (``B`` sublanes of ``tr`` lanes), the dot's
    ``[4K, B]``."""
    lanes_b = _up(B, 128)
    return (2 * 4 * (_up(ft, 8) * tr + 24 * tr)
            + 2 * 4 * ft * _up(2 * K, 8) * lanes_b
            + 4 * K * tr * (4 + 2)
            + _up(B, 8) * tr * (4 + 2) + 4 * K * lanes_b * 4)


def _tile_tr(ft: int, K: int, B: int) -> int:
    """Largest row tile of the tiled kernel under the budget the hoisted
    step has; 0 where none fits (64 built nodes at 128 bins: the tile's
    accumulator alone, double-buffered, is past it)."""
    for tr in (TR, TR // 2, TR // 4, TR // 8):
        if _tile_vmem_bytes(tr, ft, K, B) <= _VMEM_HOIST_BUDGET:
            return tr
    return 0


class LevelPlan(NamedTuple):
    """How one Mosaic level call runs: the VMEM model's answer for (rows,
    columns, nodes, bins, resident one-hot). ``kernel`` ``"hoisted"`` and
    ``"construct"`` are the untiled kernels at row tile ``tr``;
    ``"tiled"`` sweeps ``tiles`` feature tiles of ``ft`` columns at row
    tile ``tr``."""

    kernel: str
    tr: int
    tiles: int = 1
    ft: int = 0


def level_plan(rows: int, F: int, K: int, B: int, onehot_width: int = 0,
               table_width: int = 4) -> Optional[LevelPlan]:
    """The ONE VMEM model of the level kernels, shared by the registry
    predicate (``pallas_level_fits``) and the dispatch (``fused_level``,
    ``fused_level_trees``, ``level_trees``); the build-side gate
    (``hoist_plan``) asks the streaming kernel's part of it
    (``_hoist_tr``). In order: the untiled streaming kernel (a resident
    one-hot of ``onehot_width`` lanes, a row tile that divides ``rows``),
    the untiled in-kernel construction (feature and accumulator gates),
    then the tiled kernel, whose accumulator covers a tile of columns and
    which needs the level's routing beside it (a decision table of
    ``table_width`` columns); None where none fits. A shape either untiled
    kernel takes never reaches the tiles."""
    if _FORCE_TILE is None:
        if onehot_width:
            tr = _hoist_tr(onehot_width, K, F, B)
            if tr and rows % tr == 0:
                return LevelPlan("hoisted", tr)
        if _construct_fits(F, K, B):
            return LevelPlan("construct", TR)
    ft = _FEATURE_TILE if _FORCE_TILE is None else _FORCE_TILE
    Fp = _up(F, ft)
    tr = _tile_tr(ft, K, B)
    # rows come padded to ``TR`` (``pad_rows``), which every tile divides:
    # like the untiled construction's, this gate reads the shape alone. The
    # routing has at most K parents.
    if not tr or not _route_tr(TR, Fp, K, table_width):
        return None
    return LevelPlan("tiled", tr, Fp // ft, ft)


def pallas_level_fits(rows: int, F: int, K: int, B: int,
                      onehot_width: int = 0, table_width: int = 4) -> bool:
    """Whether SOME pallas level kernel fits this level's working set
    (``level_plan``): the ``level_hist`` registry predicate
    (dispatch/ops.py) and the kernel branch below share that single model
    so they cannot disagree."""
    return level_plan(rows, F, K, B, onehot_width, table_width) is not None


def _tile_at(F: int, B: int, K: int) -> int:
    """The feature tile where a level of ``K`` built nodes over this matrix
    runs the tiled kernel with nothing resident, 0 where an untiled kernel
    takes it or none does. Shape-only: the row count moves the row tile,
    not the tile's width."""
    plan = level_plan(TR, F, K, B)
    return plan.ft if plan is not None and plan.kernel == "tiled" else 0


def feature_tile(F: int, B: int, max_depth: int) -> int:
    """Columns of a feature tile where the deepest level of a tree over
    this matrix runs the tiled kernel, else 0 (``xgb.scan_chunk``'s
    ``feature_tile``, where the Mosaic kernels run)."""
    return _tile_at(F, B, 1 << max(max_depth - 2, 0))


def _route_vmem_bytes(tr: int, F: int, Kp: int, W: int) -> int:
    """One grid step of the routing kernel, counted at the tiles it
    occupies (8 sublanes, 128 lanes). Blocks, double-buffered: the i32
    bins tile, counted as the ``(tr, F)`` block it was before ISSUE 38
    (``tr`` sublanes of ``_up(F, 128)`` lanes; the feature-major ``(F,
    tr)`` block every routing reads is ``_up(F, 8)`` sublanes of ``tr``
    lanes: never more, and the same at whole feature tiles of 128, so the
    tiles stay the parent's), positions in and out as ``(1, tr)`` rows
    (8 sublanes each, 32 bytes a row of data where the ``(tr, 1)`` columns
    took 512), the ``(Kp, W)`` decision table. Values of
    ``_partition_tile``: the bins tile as loaded with its f32 and bf16
    casts, every node's ``[Kp, F]`` feature one-hot, three ``[Kp, tr]``
    (node one-hot, the nodes' bins, their product), the ``[W, tr]``
    decisions, two ``[B, tr]`` for a categorical table's set lookup, and
    sixteen ``[1, tr]`` rows."""
    lanes_f, kp8 = _up(F, 128), _up(Kp, 8)
    blocks = 2 * 4 * (tr * lanes_f + 2 * 8 * tr + kp8 * _up(W, 128))
    values = (tr * lanes_f * (4 + 4 + 2) + kp8 * lanes_f * (4 + 2)
              + 4 * tr * (3 * kp8 + _up(W, 8) + 16 * 8
                          + (2 * _up(W - 5, 8) if W > 4 else 0)))
    return blocks + values


def _route_tr(rows: int, F: int, Kp: int, W: int) -> int:
    """The routing kernel's row tile, chosen from the width: the largest
    of ``TR`` down to ``TR / 8`` whose working set is inside the budget
    the hoisted step has (``TR`` up to 512 columns, 256 rows at 2,000),
    or 0; rows come in whole ``TR`` tiles (``pad_rows``), as they
    always had to."""
    if rows % TR:
        return 0
    for tr in (TR, TR // 2, TR // 4, TR // 8):
        if _route_vmem_bytes(tr, F, Kp, W) <= _VMEM_HOIST_BUDGET:
            return tr
    return 0


def pallas_route_fits(rows: int, F: int, Kp: int, W: int) -> bool:
    """Whether the routing kernel (``_route_rows_pallas``) fits: rows in
    whole tiles of a size its working set allows at this width
    (``_route_tr``; the bins tile is the whole row, so the tile shrinks as
    the matrix widens). The ``level_partition`` registry predicate is its
    one caller, as ``pallas_level_fits`` is ``level_hist``'s."""
    return rows > 0 and F > 0 and Kp > 0 and _route_tr(rows, F, Kp, W) > 0


def derive_siblings(parent_hist, built, ptab):
    """The level's full histogram ``[F, 2K, B]`` from the previous level's
    ``parent_hist`` ``[F, 2Kp, B]`` and the ``built`` children's
    ``[F, 2Kp, B]`` (``fused_level`` under ``sibling_sub``): the sibling of
    a built child is ``parent - built`` in f32, zero where the parent did
    not split (it has no children and no row at this level; unmasked, its
    histogram would come back as a child). ``ptab`` is the parents'
    decision table: column 0 says which child was built
    (``_partition_tile``). The reference's SubtractionTrick
    (``updater_gpu_hist.cu``), with the smaller child built."""
    F, Kp2, B = built.shape
    Kp = Kp2 // 2
    mark = ptab[:, 0][None, None, :, None]  # over [F, (g, h), Kp, B]
    built = built.reshape(F, 2, Kp, B)
    derived = jnp.where(mark > 0.5,
                        parent_hist.reshape(F, 2, Kp, B) - built, 0.0)
    built_right = mark > 1.5
    children = jnp.stack([jnp.where(built_right, derived, built),
                          jnp.where(built_right, built, derived)], axis=3)
    return children.reshape(F, 4 * Kp, B)  # node 2*lp + side, g rows first


def _level_call(bins, onehot, pos, gh, ptab, *, K, Kp, B, d, vma, sub):
    """The Mosaic call(s) of one level, as ``level_plan`` says for the
    nodes of every tree ``ptab`` carries: the streaming kernel, the
    in-kernel construction, or the tiled kernel behind one routing
    (``_tiled_level``; the printed routes then say how many feature tiles
    the call swept). Each reads the tree's widened ``bins`` feature-major
    (``_feature_major``)."""
    n, F = bins.shape
    trees = ptab.shape[0] if ptab.ndim == 3 else 1
    nodes = trees * (Kp if sub else K)
    W = ptab.shape[-1]
    # two calls, not ``0 if onehot is None else ...``: the package's lint
    # (TS103) reads that expression as a branch on a traced value
    if onehot is None:
        plan = level_plan(n, F, nodes, B, 0, W)
    else:
        plan = level_plan(n, F, nodes, B, onehot.shape[1], W)
    if plan is not None and plan.kernel == "tiled":
        from ..dispatch import note

        note("feature_tiles", plan.tiles)
        return _tiled_level(bins, pos, gh, ptab, K=K, Kp=Kp, B=B, d=d,
                            plan=plan, vma=vma, sub=sub)
    binsT = _feature_major(bins, _SUBLANES, B)
    if plan is not None and plan.kernel == "hoisted":
        return _hoisted_level_pallas(binsT, onehot, pos, gh, ptab, F=F, K=K,
                                     Kp=Kp, B=B, d=d, tr=plan.tr, vma=vma,
                                     sub=sub)
    # the in-kernel construction; also what a pin to ``pallas`` gets where
    # the model says nothing fits
    return _fused_level_pallas(binsT, pos, gh, ptab, F=F, K=K, Kp=Kp, B=B,
                               d=d, vma=vma, sub=sub)


def fused_level(bins, pos, gh, ptab, *, K, Kp, B, d, pallas: bool,
                onehot: Optional[jax.Array] = None,
                axis_name: Optional[str] = None,
                sibling_sub: bool = False):
    """Dispatch: ``pos`` [1, n] i32 and ``gh`` [2, n] f32 (row 0 g, row 1
    h) in, (new pos [1, n] i32, hist [F, 2K, B] f32) out: rows on the lane
    axis, for every impl. ``hist`` excludes the missing bin (derive
    per-feature missing sums as total - sum).
    The impl is resolved through the kernel dispatch registry
    (``dispatch.resolve("level_hist", ...)`` — pins, degrade state and
    platform preference in one lookup). ``onehot`` (the HBM-resident
    [n, F*B] int8 expansion) selects the streaming kernel inside the
    pallas impl; deep levels whose accumulators outgrow VMEM fall back to
    the in-kernel construction, then to the tiled kernel (``level_plan``),
    then to native/XLA.

    ``sibling_sub`` (a caller that holds the previous level's histogram and
    whose ``ptab`` marks a child of every split, ``d >= 1``): the pallas
    impl builds that child alone and ``hist`` is ``[F, 2Kp, B]``, for
    ``derive_siblings``; its VMEM gates see ``Kp`` nodes, so a deep level's
    row tile is the one the level above has. Every other impl builds the
    level directly, whatever the flag: the caller tells by the shape."""
    from ..dispatch import Ctx, resolve

    n, F = bins.shape
    Kc = Kp if sibling_sub else K  # the nodes a pallas impl would build
    oh_width = 0
    if onehot is not None:
        oh_width = int(onehot.shape[1])
    dec = resolve("level_hist", Ctx(
        platform=jax.default_backend(), pallas=bool(pallas),
        interpret=bool(_INTERPRET), rows=int(n), features=int(F),
        nodes=int(Kc), bins=int(B), table_width=int(ptab.shape[-1]),
        bins_dtype=str(bins.dtype), sharded=axis_name is not None,
        onehot_width=oh_width))
    if pallas and _fell_off_mosaic(dec):
        _warn_off_mosaic(dec, f"level {d}", n, F, Kc, B)
    vma = (axis_name,) if axis_name is not None else ()
    if dec.impl == "pallas":
        if axis_name is not None:
            # the decision table is replication-proven (it derives from
            # the psum'd histogram); the pallas boundary wants operands
            # uniformly varying, so relax it — a no-op on device
            ptab = jax.lax.pcast(ptab, (axis_name,), to="varying")
        return _level_call(bins, onehot, pos, gh, ptab, K=K, Kp=Kp, B=B,
                           d=d, vma=vma, sub=sibling_sub)
    if dec.impl == "native":
        return fused_level_native(bins, pos, gh, ptab, K=K, Kp=Kp, B=B, d=d)
    return fused_level_xla(bins, pos, gh, ptab, K=K, Kp=Kp, B=B, d=d)


def level_trees(rows: int, F: int, Kc: int, B: int, trees: int,
                onehot_width: int = 0) -> int:
    """How many of a round's ``trees`` (class trees x ``num_parallel_tree``)
    one Mosaic level call carries when each builds ``Kc`` nodes: the
    largest T dividing ``trees`` whose ``T x Kc`` nodes fit the kernel's
    VMEM model (``level_plan``), the streaming kernel's (at the resident
    one-hot's width as planned: the plan is not asked again) or, with no
    resident one-hot, the construct-only kernel's accumulator gate; where
    one tree's level already runs the tiled kernel, its. The level
    then runs ``trees / T`` calls; 1 means a call a tree through
    ``fused_level``. Read from the shapes alone: nothing pins it."""
    one = level_plan(rows, F, Kc, B, onehot_width)
    tiled = one is not None and one.kernel == "tiled"
    for T in range(trees, 1, -1):
        if trees % T:
            continue
        plan = level_plan(rows, F, T * Kc, B, onehot_width)
        if plan is None:
            continue
        if tiled:  # a level the untiled kernels take stays with them
            fits = plan.kernel == "tiled"
        elif onehot_width:
            fits = plan.kernel == "hoisted"
        else:
            fits = plan.kernel == "construct" and rows % TR == 0
        if fits:
            return T
    return 1


def fused_level_trees(bins, pos, gh, ptab, *, K, Kp, B, d,
                      onehot: Optional[jax.Array] = None,
                      sibling_sub: bool = False):
    """One level of T trees of the same round in ONE pass over the rows
    (T from ``level_trees``; Mosaic kernels only, one chip): ``pos`` [T, n]
    i32, ``gh`` [2T, n] f32 (tree t's g over h at rows 2t, 2t + 1) and the
    trees' decision tables ``ptab`` [T, Kp, W] in, (new pos [T, n], hist
    [T, F, 2K, B]) out, or [T, F, 2Kp, B], the built children's, under
    ``sibling_sub``: per tree what ``fused_level`` returns, bit for bit at
    the same row tile (a tile's sums are the one-tree kernel's, row for
    row; where ``T x Kc`` nodes take a smaller tile than ``Kc`` do, the
    f32 accumulator adds the tiles' sums in another grouping and a cell
    may differ in its last bit, as a level's does from the next's today).
    The bins tile and the one-hot tile are read once a grid step and meet
    all T trees' gradient channels in one matmul."""
    return _level_call(bins, onehot, pos, gh, ptab, K=K, Kp=Kp, B=B, d=d,
                       vma=(), sub=sibling_sub)


def leaf_delta(pos, leaf_values, max_nodes_pad: int, pallas: bool):
    """Prediction-cache delta ``[n]``: ``leaf_values[pos]`` for every row
    of ``pos`` ``[1, n]``, as an exact one-hot matmul (TPU) or a plain
    gather (CPU). Leaf values are split into THREE bf16 terms (24
    significand bits = exact f32) so the cache never drifts from the
    materialized model; the dot is ``tab^T [3, P] @ onehot [P, n]``, its
    result ``[3, n]`` (rows on the lanes, no padded ``[n, 3]``). This is
    the UpdatePredictionCache fast path (reference ``gbtree.cc:219``).
    ``pallas`` is the caller's platform flag; the impl resolves through
    the ``leaf_delta`` registry row, so pins apply and the route is
    counted for every grower."""
    from ..dispatch import Ctx, resolve

    dec = resolve("leaf_delta", Ctx(platform=jax.default_backend(),
                                    pallas=bool(pallas)))
    if dec.impl != "pallas":
        return leaf_values[jnp.clip(pos[0], 0, leaf_values.shape[0] - 1)]
    lv = jnp.zeros((max_nodes_pad,), jnp.float32).at[:leaf_values.shape[0]].set(leaf_values)

    def bf_mask(x):
        return jax.lax.bitcast_convert_type(
            jax.lax.bitcast_convert_type(x, jnp.int32) & _MASK_HI, jnp.float32)

    hi = bf_mask(lv)
    r = lv - hi
    mid = bf_mask(r)
    lo = r - mid
    tab = jnp.stack([hi, mid, lo]).astype(jnp.bfloat16)  # [3, P]
    oh = jax.nn.one_hot(pos[0], max_nodes_pad, dtype=jnp.bfloat16, axis=0)
    out = jax.lax.dot_general(tab, oh, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # [3, n]
    return out[0] + out[1] + out[2]
