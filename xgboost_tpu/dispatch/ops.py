"""The default kernel-op table: every backend a registry entry.

Each op's impls, applicability predicates and per-platform preference
live HERE — a new backend (a GPU tier, a second native kernel) is a
``register`` call, not a rewrite of the call sites. Predicates read only
the :class:`~xgboost_tpu.dispatch.core.Ctx` the call site passed (shape,
dtype, platform flags) plus the owning module's probe helpers; they are
imported lazily so importing the dispatch layer never drags in jax or
builds a native library.

Op reference (see docs/perf.md, "Choosing a kernel"):

====================  =========================================  =============
op                    implementations (preference order)         capability
====================  =========================================  =============
``tree_grow``         native (CPU, whole-round kernel) > level   native_tree
``sibling_sub``       on > off (histogram subtraction trick:     —
                      whole-tree kernel, Pallas level loop)
``hist_acc``          CPU: quant > float (integer histogram      —
                      accumulation inside the whole-tree kernel)
``level_hist``        pallas > native (CPU) > xla                native_hist
``level_partition``   pallas (TPU, fits) > native (CPU) > xla    native_hist
``level_update``      xla (single impl: shared split eval)       —
``depth_scan``        scanned > unrolled                         —
``onehot_build``      pallas > xla                               —
``leaf_delta``        pallas > xla                               —
``lossguide_step``    pallas (TPU, numerical, one device) > xla  —
``predict_walk``      TPU: pallas > xla > native;                pallas_predict
                      CPU: native > xla                          / native_serving
``sketch_cuts``       CPU: native > xla; TPU: xla                native_sketch
``bin_matrix``        CPU: native > xla; TPU: xla                native_sketch
====================  =========================================  =============
"""

from __future__ import annotations

from .core import Ctx, register, set_report_ctx

_NARROW_BINS = ("uint8", "uint16")


def _platform() -> str:
    import jax

    return jax.default_backend()


def _native_level_applicable(ctx: Ctx) -> bool:
    """The FFI level kernel's trace-time envelope: CPU backend, in-process
    (no mesh axis), numerical 4-wide decision tables, narrow-int bins,
    and not the interpret-mode kernel tests."""
    return (ctx.get("platform") == "cpu"
            and not ctx.get("interpret", False)
            and not ctx.get("sharded", False)
            and ctx.get("table_width", 4) == 4
            and ctx.get("bins_dtype") in _NARROW_BINS)


def _native_level_available(ctx: Ctx) -> bool:
    from ..tree import hist_kernel

    return hist_kernel._ensure_ffi()


def _tree_grow_native_applicable(ctx: Ctx) -> bool:
    """The whole-tree kernel's trace-time envelope (ISSUE 17 tentpole):
    everything the per-level native kernel needs, PLUS the features whose
    eval the C++ port replicates bitwise. Per-level colsample draws
    (bylevel/bynode < 1) stay on the per-level path — their PRNG folds
    cannot be mirrored in C++ — as does max_delta_step > 0, whose gain
    expression XLA:CPU contracts into an FMA the kernel must not emit
    (see tree_build.cpp). Monotone/interaction constraints and
    categorical tables keep the XLA evaluator."""
    return (ctx.get("platform") == "cpu"
            and not ctx.get("interpret", False)
            and not ctx.get("sharded", False)
            and not ctx.get("pallas", False)
            and not ctx.get("has_cats", False)
            and ctx.get("bins_dtype") in _NARROW_BINS
            and int(ctx.get("depth", 0)) >= 1
            and not ctx.get("monotone", False)
            and not ctx.get("interaction", False)
            and float(ctx.get("colsample_level", 1.0)) >= 1.0
            and float(ctx.get("colsample_node", 1.0)) >= 1.0
            and float(ctx.get("max_delta_step", 0.0)) == 0.0)


def _tree_grow_native_available(ctx: Ctx) -> bool:
    from ..tree import tree_kernel

    return tree_kernel.tree_ffi_ready()


# The whole-round grow kernel (native/tree_build.cpp): ONE custom call per
# boosting round on CPU; the ``level`` impl is the existing per-level path
# (depth scan / unrolled / pallas / mesh), which every other platform and
# every out-of-envelope config keeps.
register("tree_grow", "native", pref=(("cpu", 0), ("*", 2)),
         applicable=_tree_grow_native_applicable,
         available=_tree_grow_native_available,
         capability="native_tree")
register("tree_grow", "level", pref=(("*", 1),))
set_report_ctx("tree_grow", lambda: Ctx(
    platform=_platform(), pallas=_platform() == "tpu", interpret=False,
    sharded=False, has_cats=False, bins_dtype="uint8", depth=6,
    monotone=False, interaction=False, colsample_level=1.0,
    colsample_node=1.0, max_delta_step=0.0))


# Sibling subtraction: build only the smaller child's histogram, derive the
# other as parent - child. Two routes obey the row: the whole-tree kernel
# (CPU; smaller by row count), and the unrolled level loop where the Mosaic
# level kernels run (TPU, ``grow_fused``: smaller by hessian sum, resolved
# once a level below the root; the kernels then run half the gradient
# channels and a mesh's psum carries half the bytes). ``off`` pins the
# whole-tree kernel bit-identical to the per-level native path and the
# level loop to the direct build of every node (the legacy
# ``XGBTPU_SIBLING_SUB=0`` kill switch maps here). The other routes (paged,
# depth-scanned, per-level native, the lossguide plain reference) build
# every node directly; the lossguide chip path builds the smaller child by
# rows (``lossguide_step``).
register("sibling_sub", "on", pref=(("*", 0),))
register("sibling_sub", "off", pref=(("*", 1),))
set_report_ctx("sibling_sub", lambda: Ctx(platform=_platform()))


# Histogram accumulation inside the whole-tree kernel (ISSUE 19): the
# fixed-point integer engine (per-node row lists, packed int32 gradient
# lanes, int64 merge — thread-count invariant by construction) leads on
# CPU; ``float`` is the r17 f32 core and the bit-identity kill switch —
# pinning BOTH ``hist_acc=float`` and ``sibling_sub=off`` makes the
# whole-tree kernel byte-identical to the per-level native path.
register("hist_acc", "quant", pref=(("cpu", 0), ("*", 2)))
register("hist_acc", "float", pref=(("*", 1),))
set_report_ctx("hist_acc", lambda: Ctx(platform=_platform()))


def _pallas_level_applicable(ctx: Ctx) -> bool:
    from ..tree import hist_kernel

    return bool(ctx.get("pallas")) and hist_kernel.pallas_level_fits(
        int(ctx.get("rows", 0)), int(ctx.get("features", 0)),
        int(ctx.get("nodes", 1)), int(ctx.get("bins", 0)),
        int(ctx.get("onehot_width", 0)), int(ctx.get("table_width", 4)))


register("level_hist", "pallas", pref=(("*", 0),),
         applicable=_pallas_level_applicable)
register("level_hist", "native", pref=(("*", 1),),
         applicable=_native_level_applicable,
         available=_native_level_available,
         capability="native_hist")
register("level_hist", "xla", pref=(("*", 2),))
set_report_ctx("level_hist", lambda: Ctx(
    platform=_platform(), pallas=_platform() == "tpu", interpret=False,
    rows=8192, features=50, nodes=32, bins=64, table_width=4,
    bins_dtype="uint8", sharded=False, onehot_width=0))


def _pallas_partition_applicable(ctx: Ctx) -> bool:
    from ..tree import hist_kernel

    return bool(ctx.get("pallas")) and hist_kernel.pallas_route_fits(
        int(ctx.get("rows", 0)), int(ctx.get("features", 0)),
        int(ctx.get("nodes", 1)), int(ctx.get("table_width", 4)))


# The standalone routing step (a tree's last level, the paged deltas). On
# the TPU the gather of the XLA form streams at a few GB/s, so the Mosaic
# tile the level kernels share leads; on the CPU a gather is the cheap form
# and a one-hot select over F is F times the work.
register("level_partition", "pallas", pref=(("*", 0),),
         applicable=_pallas_partition_applicable)
register("level_partition", "native", pref=(("*", 1),),
         applicable=_native_level_applicable,
         available=_native_level_available,
         capability="native_hist")
register("level_partition", "xla", pref=(("*", 2),))
set_report_ctx("level_partition", lambda: Ctx(
    platform=_platform(), pallas=_platform() == "tpu", interpret=False,
    rows=8192, features=50, nodes=32, table_width=4,
    bins_dtype="uint8", sharded=False))


# split evaluation / heap writes are one shared pure-XLA body on every
# backend (tree/grow_fused.py:_level_update) — registered so the table is
# complete and a future backend-specific evaluator is a row, not a branch
register("level_update", "xla", pref=(("*", 0),))
set_report_ctx("level_update", lambda: Ctx(platform=_platform()))


def _scanned_applicable(ctx: Ctx) -> bool:
    """The fused depth scan runs where its fixed-width trick is sound:
    off the pallas path (Mosaic kernels specialize per level width by
    design), in-process (the unrolled loop is the proven shard_map path),
    depth >= 1. A categorical job's table is carried at the deepest
    level's width like a numerical one's, its category sets beside the
    decisions (``grow_fused._init_state``)."""
    return (not ctx.get("pallas", False)
            and not ctx.get("sharded", False)
            and int(ctx.get("depth", 0)) >= 1)


register("depth_scan", "scanned", pref=(("*", 0),),
         applicable=_scanned_applicable)
register("depth_scan", "unrolled", pref=(("*", 1),))
set_report_ctx("depth_scan", lambda: Ctx(
    platform=_platform(), pallas=_platform() == "tpu", has_cats=False,
    sharded=False, depth=6))


def _onehot_pallas_applicable(ctx: Ctx) -> bool:
    from ..tree import hist_kernel

    return (bool(ctx.get("pallas"))
            and int(ctx.get("features", 0)) > 0
            and hist_kernel._build_tr(int(ctx.get("rows", 0)),
                                      int(ctx.get("features", 0)),
                                      int(ctx.get("bins", 0))) != 0)


register("onehot_build", "pallas", pref=(("*", 0),),
         applicable=_onehot_pallas_applicable)
register("onehot_build", "xla", pref=(("*", 1),))
set_report_ctx("onehot_build", lambda: Ctx(
    platform=_platform(), pallas=_platform() == "tpu", rows=8192,
    features=50, bins=64))


def _lossguide_pallas_applicable(ctx: Ctx) -> bool:
    from ..tree.node_hist import node_hist_fits

    return (bool(ctx.get("pallas")) and not ctx.get("sharded", False)
            and not ctx.get("has_cats", False)
            and not ctx.get("interaction", False)
            and node_hist_fits(int(ctx.get("features", 0)),
                               int(ctx.get("bins", 0))))


# A leaf-wise (lossguide) tree's step: ``pallas`` keeps the rows grouped by
# leaf and builds the smaller child's histogram over its own rows in the
# Mosaic node kernel (tree/grow_lossguide.py, grow_tree_lossguide_rows);
# ``xla`` is the plain reference, a masked segment_sum over every row a
# step (the CPU, the mesh, categorical and interaction-constrained jobs).
register("lossguide_step", "pallas", pref=(("*", 0),),
         applicable=_lossguide_pallas_applicable)
register("lossguide_step", "xla", pref=(("*", 1),))
set_report_ctx("lossguide_step", lambda: Ctx(
    platform=_platform(), pallas=_platform() == "tpu", sharded=False,
    has_cats=False, interaction=False, features=50, bins=256))


register("leaf_delta", "pallas", pref=(("*", 0),),
         applicable=lambda ctx: bool(ctx.get("pallas")))
register("leaf_delta", "xla", pref=(("*", 1),))
set_report_ctx("leaf_delta", lambda: Ctx(
    platform=_platform(), pallas=_platform() == "tpu"))


def _walk_native_applicable(ctx: Ctx) -> bool:
    return not ctx.get("has_cats", False)


def _walk_native_available(ctx: Ctx) -> bool:
    from ..native import serving_lib_available

    return serving_lib_available()


def _walk_pallas_applicable(ctx: Ctx) -> bool:
    """Same envelope as ``predictor.predict_margin``'s own gate: a TPU
    (or the interpret-mode test hook), a heap-layout numerical forest,
    and a node table that fits VMEM."""
    from ..predictor import pallas_walk_fits

    return ((ctx.get("platform") == "tpu" or ctx.get("interpret", False))
            and bool(ctx.get("heap_layout", False))
            and not ctx.get("has_cats", False)
            and pallas_walk_fits(int(ctx.get("trees", 1)),
                                 int(ctx.get("nodes", 1))))


# Preference: on TPU the device walk (pallas, else the bucketed XLA
# program) owns the route and the native walker is the degrade fallback;
# on CPU the native walker leads and XLA backstops categorical forests /
# missing toolchains. Both device impls carry the ``pallas_predict``
# capability ON DEVICE PLATFORMS ONLY, so a degraded device path routes
# to native with reason="degraded" — the lookup that replaced the
# serving_context(force_native=) thread-local.
register("predict_walk", "pallas", pref=(("*", 0),),
         applicable=_walk_pallas_applicable,
         capability="pallas_predict", cap_platforms=("tpu",))
register("predict_walk", "xla", pref=(("*", 1),),
         capability="pallas_predict", cap_platforms=("tpu",))
register("predict_walk", "native", pref=(("cpu", 0), ("*", 2)),
         applicable=_walk_native_applicable,
         available=_walk_native_available,
         capability="native_serving")
set_report_ctx("predict_walk", lambda: Ctx(
    platform=_platform(), has_cats=False, heap_layout=True))


# The data-plane ops (ISSUE 15): DMatrix-construction sketch + binning.
# The native impls are XLA FFI custom calls (native/sketch_bin.cpp) doing
# the same float ops in the same order as the XLA kernels — bit-identical
# cuts/bins, ~an order of magnitude faster on XLA:CPU. On device backends
# the XLA route leads (the sort/searchsorted pipeline parallelizes there
# and the data is already device-resident).


def _native_sketch_applicable(ctx: Ctx) -> bool:
    return ctx.get("platform") == "cpu" and int(ctx.get("rows", 0)) >= 1


def _native_sketch_available(ctx: Ctx) -> bool:
    from ..data import quantile

    return quantile._ensure_sketch_ffi()


def _native_bin_applicable(ctx: Ctx) -> bool:
    """The native binning kernel writes the narrow storage dtype directly;
    int32-wide tables (max_bin >= 65535) stay on the XLA route."""
    return (ctx.get("platform") == "cpu"
            and int(ctx.get("rows", 0)) >= 1
            and ctx.get("bins_dtype") in _NARROW_BINS)


register("sketch_cuts", "native", pref=(("cpu", 0), ("*", 2)),
         applicable=_native_sketch_applicable,
         available=_native_sketch_available,
         capability="native_sketch")
register("sketch_cuts", "xla", pref=(("*", 1),))
set_report_ctx("sketch_cuts", lambda: Ctx(
    platform=_platform(), rows=8192, features=50, bins=64))


register("bin_matrix", "native", pref=(("cpu", 0), ("*", 2)),
         applicable=_native_bin_applicable,
         available=_native_sketch_available,
         capability="native_sketch")
register("bin_matrix", "xla", pref=(("*", 1),))
set_report_ctx("bin_matrix", lambda: Ctx(
    platform=_platform(), rows=8192, features=50, bins=64,
    bins_dtype="uint8"))
