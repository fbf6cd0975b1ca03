"""Fast-path tree grower: per-level fused pallas kernels, zero host syncs.

This is the production ``tpu_hist`` grower (reference:
``src/tree/updater_gpu_hist.cu`` UpdateTree loop, :667). Differences from
``grow.py``'s original fori_loop design, all driven by TPU/runtime realities:

- levels are **unrolled** (max_depth is static) so each level's histogram
  kernel is specialized to its real node count ``K = 2^d`` instead of the
  padded max width — the matmul M-dim grows with the level;
- histogram + partition run as one fused Pallas kernel per level
  (``hist_kernel.py``) — no scatters, no gathers, no HBM one-hot traffic;
- gamma pruning (``updater_prune.cc``), leaf-value resolution and the
  prediction-cache delta (``UpdatePredictionCache``, gbtree.cc:219) are
  computed **on device inside the same jit program**, so a boosting round
  performs zero device->host transfers (each sync through the runtime
  costs ~60ms — more than the whole tree build);
- learning rate (eta) and gamma are traced scalars, so LearningRateScheduler
  callbacks never force a recompile.

The tree comes back as a ``GrownTree`` of small [max_nodes] device arrays
(the heap layout: children of ``i`` at ``2i+1/2i+2``); host RegTree
materialization is deferred until model IO actually needs it.

Distributed: pass ``cfg.axis_name`` — the per-level fixed-size histogram and
the root gradient totals are psum'd (the reference's two collective sites:
``hist/histogram.h:201``, root InitRoot AllReduce), everything else is
replicated arithmetic on identical inputs.
"""

from __future__ import annotations

from typing import List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis.retrace import guard_jit
from .grow import (
    GrowParams,
    _sample_features_exact,
    apply_row_sampling,
    child_bounds_and_weights,
    eval_splits,
    exact_k_subset,
    interaction_allowed,
    seq_cumsum,
)
from .hist_kernel import (
    TR,
    derive_siblings,
    fused_level,
    fused_level_trees,
    leaf_delta,
    level_trees,
    partition_apply,
    partition_apply_xla,
)
from .param import RT_EPS, calc_weight

__all__ = ["GrownTree", "grow_tree_fused", "grow_trees_one_pass", "pad_rows"]

_INF = float(np.inf)


class GrownTree(NamedTuple):
    """Heap-layout tree (all [max_nodes]) + the round's cache delta [n]."""

    keep: jax.Array  # bool — is_split after gamma pruning
    feature: jax.Array  # int32
    split_bin: jax.Array  # int32
    split_cond: jax.Array  # f32
    default_left: jax.Array  # bool
    node_g: jax.Array  # f32
    node_h: jax.Array  # f32
    node_weight: jax.Array  # f32 (pre-eta)
    loss_chg: jax.Array  # f32
    leaf_value: jax.Array  # f32 — eta-applied governing leaf value per node
    delta: jax.Array  # f32 [n_padded] margin increment (training rows)
    cat_set: jax.Array  # bool [max_nodes, B] right-going sets ([1,1] if none)


class _HeapState(NamedTuple):
    """Per-tree heap arrays threaded through the level loop (all
    [max_nodes] except the constraint extras)."""

    is_split: jax.Array
    feature: jax.Array
    split_bin: jax.Array
    split_cond: jax.Array
    default_left: jax.Array
    node_g: jax.Array
    node_h: jax.Array
    node_w: jax.Array
    loss_chg: jax.Array
    lo_b: jax.Array  # [max_nodes] or [1] when unconstrained
    up_b: jax.Array
    used: jax.Array  # [max_nodes, F] or [1, F]
    ptab: jax.Array  # [K, 4] (or [K, 5+B] with categoricals) decisions
    cat_set: jax.Array  # [max_nodes, B] right-going sets, or [1, 1]


def pad_rows(n: int) -> int:
    """Rows padded to the kernel tile size."""
    return -(-n // TR) * TR


def _constraint_consts(cfg: GrowParams, F: int):
    mono_j = gmask = None
    if cfg.has_monotone:
        mono_np = np.zeros(F, np.int32)
        mono_np[: len(cfg.monotone)] = cfg.monotone[:F]
        mono_j = jnp.asarray(mono_np)
    if cfg.has_interaction:
        gmask_np = np.zeros((len(cfg.interaction), F), bool)
        for gi, grp in enumerate(cfg.interaction):
            for f in grp:
                if f < F:
                    gmask_np[gi, f] = True
        gmask = jnp.asarray(gmask_np)
    return mono_j, gmask


def _init_state(cfg: GrowParams, F: int, G0, H0, B: int = 0,
                ptab_rows: int = 1) -> _HeapState:
    max_nodes = cfg.max_nodes
    p = cfg.split
    z = lambda dt: jnp.zeros((max_nodes,), dt)  # noqa: E731
    nb = max_nodes if cfg.has_monotone else 1
    nu = max_nodes if cfg.has_interaction else 1
    cat = cfg.has_categorical
    return _HeapState(
        is_split=z(bool), feature=z(jnp.int32), split_bin=z(jnp.int32),
        split_cond=z(jnp.float32), default_left=z(bool),
        node_g=z(jnp.float32).at[0].set(G0),
        node_h=z(jnp.float32).at[0].set(H0),
        node_w=z(jnp.float32).at[0].set(calc_weight(G0, H0, p)),
        loss_chg=z(jnp.float32),
        lo_b=jnp.full((nb,), -_INF), up_b=jnp.full((nb,), _INF),
        used=jnp.zeros((nu, F), bool),
        # ptab_rows > 1: the depth-scanned driver carries a FIXED-width
        # decision table (the deepest level's width) through lax.scan
        ptab=jnp.zeros((ptab_rows, 5 + B if cat else 4), jnp.float32),
        cat_set=jnp.zeros((max_nodes if cat else 1, B if cat else 1), bool),
    )


def _level_update(
    st: _HeapState,
    histC: jax.Array,  # [F, 2K, B] (missing excluded)
    cut_values: jax.Array,
    tree_mask: jax.Array,  # [F] colsample_bytree mask
    k_level: jax.Array,  # PRNG key for bylevel/bynode draws
    cfg: GrowParams,
    d,  # python int (unrolled/paged) or traced scalar (depth scan)
    Kw: Optional[int] = None,
    mark_built: bool = False,
) -> _HeapState:
    """Evaluate level ``d``'s splits from its histogram and write the heap
    arrays + the next partition table. Shared by the in-core single-program
    grower, the depth-scanned driver and the external-memory paged driver.

    ``mark_built`` (the next level subtracts siblings): the table's
    ``is_split`` column reads 2 at a split whose RIGHT child has the
    smaller hessian sum, and the next level kernel builds that child alone
    (1: the left). The derived child is then the larger, so
    ``parent - built`` never cancels to a small number. (The native core
    picks by row count, ``tree_build.cpp``; this loop has no counts and
    needs none.) Routing reads the column as ``> 0.5`` either way.

    ``Kw`` is the FIXED node width of the depth-scanned driver (the
    deepest level's ``2^(max_depth-1)``); ``d`` is then a traced scan
    counter and the heap offset is computed in-program. Lanes beyond a
    shallow level's true width carry zero G/H (no row occupies them), so
    ``can_split`` masks them out and their (transient) heap writes are
    overwritten by the deeper levels' own slot writes before anything
    reads them — the padding is self-masking."""
    F = tree_mask.shape[0]
    B = cut_values.shape[1]
    p = cfg.split
    max_nodes = cfg.max_nodes
    if Kw is None:
        K = 1 << d
        off = K - 1
    else:
        K = Kw
        off = jnp.left_shift(jnp.int32(1), d) - 1
    mono_j, gmask = _constraint_consts(cfg, F)

    Gtot = jax.lax.dynamic_slice_in_dim(st.node_g, off, K)
    Htot = jax.lax.dynamic_slice_in_dim(st.node_h, off, K)

    hg = jnp.transpose(histC[:, :K, :], (1, 0, 2))  # [K, F, B]
    hh = jnp.transpose(histC[:, K:, :], (1, 0, 2))
    # Present-value totals via the same strict left-to-right association
    # eval_splits' seq_cumsum uses, so the native tree_grow kernel can
    # reproduce g_miss/h_miss exactly (a single C loop over bins).
    g_miss = Gtot[:, None] - seq_cumsum(hg)[..., -1]
    h_miss = Htot[:, None] - seq_cumsum(hh)[..., -1]
    hist = jnp.stack(
        [
            jnp.concatenate([hg, g_miss[..., None]], axis=-1),
            jnp.concatenate([hh, h_miss[..., None]], axis=-1),
        ],
        axis=-1,
    )  # [K, F, B+1, 2]

    if cfg.has_monotone:
        node_lo = jax.lax.dynamic_slice_in_dim(st.lo_b, off, K)
        node_up = jax.lax.dynamic_slice_in_dim(st.up_b, off, K)

    k_tree = max(1, int(round(cfg.colsample_bytree * F))) \
        if cfg.colsample_bytree < 1.0 else F
    fmask = tree_mask
    if cfg.colsample_bylevel < 1.0:
        k_lvl = max(1, int(round(cfg.colsample_bylevel * k_tree)))
        fmask = exact_k_subset(jax.random.fold_in(k_level, d), fmask, k_lvl)
    else:
        k_lvl = k_tree
    if cfg.colsample_bynode < 1.0:
        k_nd = max(1, int(round(cfg.colsample_bynode * k_lvl)))
        kn = jax.random.fold_in(jax.random.fold_in(k_level, d), 1)
        node_fmask = exact_k_subset(
            kn, jnp.broadcast_to(fmask[None, :], (K, F)), k_nd
        )
    else:
        node_fmask = jnp.broadcast_to(fmask[None, :], (K, F))
    if cfg.has_interaction:
        node_used = jax.lax.dynamic_slice_in_dim(st.used, off, K, axis=0)
        node_fmask = node_fmask & interaction_allowed(node_used, gmask)

    if cfg.has_categorical:
        _, cat_j, catp_j = cfg.cat_masks_jnp(F)
    else:
        cat_j = catp_j = None
    dec = eval_splits(
        hist, Gtot, Htot, p, node_fmask, B,
        mono=mono_j if cfg.has_monotone else None,
        node_lo=node_lo if cfg.has_monotone else None,
        node_up=node_up if cfg.has_monotone else None,
        cat_feats=cat_j, cat_part=catp_j,
    )
    can_split = (dec.loss > RT_EPS) & (Htot > 0.0)
    GLb, HLb = dec.GL, dec.HL
    GRb, HRb = Gtot - GLb, Htot - HLb
    cond = cut_values[dec.f, dec.b]

    slots = off + jnp.arange(K)
    is_split = st.is_split.at[slots].set(can_split)
    feature = st.feature.at[slots].set(dec.f)
    split_bin = st.split_bin.at[slots].set(dec.b)
    split_cond = st.split_cond.at[slots].set(cond)
    default_left = st.default_left.at[slots].set(dec.dir == 1)
    node_w = st.node_w.at[slots].set(dec.w_node)
    loss_chg = st.loss_chg.at[slots].set(jnp.where(can_split, dec.loss, 0.0))

    if cfg.has_monotone:
        l_lo, l_up, r_lo, r_up, wl_c, wr_c = child_bounds_and_weights(
            p, mono_j[dec.f], GLb, HLb, GRb, HRb, node_lo, node_up
        )
    else:
        wl_c = calc_weight(GLb, HLb, p)
        wr_c = calc_weight(GRb, HRb, p)

    lidx = jnp.where(can_split, 2 * slots + 1, max_nodes)
    ridx = jnp.where(can_split, 2 * slots + 2, max_nodes)
    node_g = st.node_g.at[lidx].set(GLb, mode="drop").at[ridx].set(GRb, mode="drop")
    node_h = st.node_h.at[lidx].set(HLb, mode="drop").at[ridx].set(HRb, mode="drop")
    node_w = node_w.at[lidx].set(wl_c, mode="drop").at[ridx].set(wr_c, mode="drop")
    lo_b, up_b, used = st.lo_b, st.up_b, st.used
    if cfg.has_monotone:
        lo_b = lo_b.at[lidx].set(l_lo, mode="drop").at[ridx].set(r_lo, mode="drop")
        up_b = up_b.at[lidx].set(l_up, mode="drop").at[ridx].set(r_up, mode="drop")
    if cfg.has_interaction:
        child_used = jax.lax.dynamic_slice_in_dim(used, off, K, axis=0) | (
            jax.nn.one_hot(dec.f, F, dtype=bool)
        )
        used = used.at[lidx].set(child_used, mode="drop")
        used = used.at[ridx].set(child_used, mode="drop")

    if mark_built:
        split_col = jnp.where(can_split, jnp.where(HLb <= HRb, 1.0, 2.0), 0.0)
    else:
        split_col = can_split.astype(jnp.float32)
    ptab = jnp.stack(
        [
            split_col,
            dec.f.astype(jnp.float32),
            dec.b.astype(jnp.float32),
            (dec.dir == 1).astype(jnp.float32),
        ],
        axis=1,
    )  # [K, 4]
    cat_set = st.cat_set
    if cfg.has_categorical:
        any_mask = jnp.asarray(cfg.cat_mask_np(F))
        is_cat = any_mask[dec.f] & can_split  # [K]
        win_set = dec.cat_set & is_cat[:, None]  # [K, B]
        cat_set = cat_set.at[slots].set(win_set)
        # widen the decision table: col 4 = is_cat, cols 5: = right set
        ptab = jnp.concatenate(
            [ptab, is_cat.astype(jnp.float32)[:, None],
             win_set.astype(jnp.float32)], axis=1)  # [K, 5 + B]
    return _HeapState(
        is_split=is_split, feature=feature, split_bin=split_bin,
        split_cond=split_cond, default_left=default_left,
        node_g=node_g, node_h=node_h, node_w=node_w, loss_chg=loss_chg,
        lo_b=lo_b, up_b=up_b, used=used, ptab=ptab, cat_set=cat_set,
    )


def _finalize(st: _HeapState, eta, gamma, cfg: GrowParams):
    """Gamma pruning (bottom-up, updater_prune.cc) + governing leaf value
    per heap node; shared by both drivers."""
    max_depth = cfg.max_depth
    max_nodes = cfg.max_nodes
    keep = st.is_split
    child_keep = jnp.zeros((1 << max_depth,), bool)
    for d in range(max_depth - 1, -1, -1):
        w = 1 << d
        off = w - 1
        isl = jax.lax.dynamic_slice_in_dim(st.is_split, off, w)
        lcl = jax.lax.dynamic_slice_in_dim(st.loss_chg, off, w)
        child_any = child_keep[0::2] | child_keep[1::2]
        keep_l = isl & ((lcl >= gamma) | child_any)
        keep = jax.lax.dynamic_update_slice_in_dim(keep, keep_l, off, axis=0)
        child_keep = keep_l

    leaf_value = jnp.zeros((max_nodes,), jnp.float32)
    root_open = keep[0]
    gov = jnp.where(root_open, 0.0, eta * st.node_w[0])[None]
    gov_open = root_open[None]
    leaf_value = leaf_value.at[0].set(gov[0])
    for d in range(1, max_depth + 1):
        w = 1 << d
        off = w - 1
        parent_gov = jnp.repeat(gov, 2)
        parent_open = jnp.repeat(gov_open, 2)
        own_w = jax.lax.dynamic_slice_in_dim(st.node_w, off, w)
        if d < max_depth:
            node_keep = jax.lax.dynamic_slice_in_dim(keep, off, w)
        else:
            node_keep = jnp.zeros((w,), bool)
        gov = jnp.where(parent_open,
                        jnp.where(node_keep, 0.0, eta * own_w), parent_gov)
        gov_open = parent_open & node_keep
        leaf_value = jax.lax.dynamic_update_slice_in_dim(
            leaf_value, gov, off, axis=0
        )
    return keep, leaf_value


def grow_tree_fused(
    bins: jax.Array,  # [n_pad, F] narrow-int bins (missing == B; pads all-B)
    grad: jax.Array,  # [n_pad] f32 (pad rows zero)
    hess: jax.Array,  # [n_pad] f32
    cut_values: jax.Array,  # [F, B] f32
    key: jax.Array,
    eta: jax.Array,  # traced scalar
    gamma: jax.Array,  # traced scalar (min_split_loss for pruning)
    cfg: GrowParams,
    feature_weights: Optional[jax.Array] = None,
    onehot: Optional[jax.Array] = None,  # [n_pad, F*B] int8 (hoisted)
) -> GrownTree:
    """Host entry point: times the compiled whole-tree dispatch as a
    ``grow_tree`` span. Suppressed while a larger program (scan chunk /
    shard_map) is being staged around it — telemetry is host-side only."""
    from ..observability import trace

    with trace.span("grow_tree", fused=True, depth=cfg.max_depth,
                    features=int(bins.shape[1])):
        return _grow_tree_fused_impl(bins, grad, hess, cut_values, key,
                                     eta, gamma, cfg, feature_weights,
                                     onehot)


# hess is donated (the grow program has exactly one [n]-shaped output — the
# prediction-cache delta — so exactly one [n] input buffer can be reused in
# place; donating grad too just trips XLA's "not usable" warning)
@guard_jit(name="grow_tree_fused", static_argnames=("cfg",),
           donate_argnames=("hess",))
def _grow_tree_fused_impl(
    bins: jax.Array,
    grad: jax.Array,
    hess: jax.Array,
    cut_values: jax.Array,
    key: jax.Array,
    eta: jax.Array,
    gamma: jax.Array,
    cfg: GrowParams,
    feature_weights: Optional[jax.Array] = None,
    onehot: Optional[jax.Array] = None,
) -> GrownTree:
    # the ``xgb.<phase>`` scopes below name the round's device phases in a
    # profile: each op's scope path is in the trace (docs/observability.md,
    # "Reading a device profile"). They change HLO metadata only.
    pallas = _pallas_flag(cfg)
    if pallas:
        # transient in-program widening for the Mosaic kernels, which read
        # it feature-major (``hist_kernel._feature_major``: XLA folds the
        # pad and the transpose into this widening, one array a tree); the
        # XLA and native paths read the NARROW storage dtype directly (the
        # int8-packing half of the ISSUE 13 tentpole: no 4x int32 copy of
        # the bin matrix on the CPU path)
        with jax.named_scope("xgb.level_hist"):
            bins = bins.astype(jnp.int32)
    n, F = bins.shape
    B = cut_values.shape[1]
    p = cfg.split
    max_depth = cfg.max_depth

    gh, tree_mask, k_level, G0, H0, st = _tree_start(
        grad, hess, key, feature_weights, cfg, F, B)
    with jax.named_scope("xgb.root"):
        pos = jnp.zeros((1, n), jnp.int32)  # every row starts at the root

    tree_grow_native_route = _use_tree_grow(cfg, pallas, max_depth,
                                            str(bins.dtype))
    if tree_grow_native_route:
        # whole-round kernel (ISSUE 17 tentpole): the ENTIRE depth loop —
        # per-level partition, histogram (with sibling subtraction), split
        # eval and heap update, plus the final leaf routing — runs as ONE
        # native custom call per round instead of ~2 dispatches per level.
        # The kernel's outputs satisfy _level_update's state contract
        # bit-for-bit (subtraction off + hist_acc float), so _finalize
        # consumes them unchanged. Sibling subtraction and the histogram
        # accumulation core resolve through their own table rows
        # (XGBTPU_SIBLING_SUB=0 -> sibling_sub=off pin; hist_acc=quant
        # is the fixed-point integer engine, hist_acc=float the r17
        # core).
        from ..dispatch import Ctx, resolve
        from .tree_kernel import tree_grow_native

        plat = jax.default_backend()
        sub_on = resolve("sibling_sub", Ctx(platform=plat)).impl == "on"
        hist_acc = resolve("hist_acc", Ctx(platform=plat)).impl
        (pos, isl, feat, sbin, scond, dleft, ng, nh, nw, lchg) = \
            tree_grow_native(bins, gh, cut_values, tree_mask, G0, H0,
                             max_depth=max_depth, B=B, sibling_sub=sub_on,
                             hist_acc=hist_acc, split=p)
        st = st._replace(is_split=isl, feature=feat, split_bin=sbin,
                         split_cond=scond, default_left=dleft, node_g=ng,
                         node_h=nh, node_w=nw, loss_chg=lchg)
    elif _use_depth_scan(cfg, pallas, max_depth):
        # fused depth scan (ISSUE 13 tentpole): the per-level bodies
        # collapse into ONE lax.scan over the depth counter at the
        # deepest level's fixed node width — a depth-6 tree stages one
        # level program instead of six specialized ones (compile time and
        # program size drop ~proportionally), and the scan carry gives
        # the per-level node-state tensors in-place reuse for free. The
        # pallas path keeps the unrolled loop: its Mosaic kernels
        # specialize the matmul M-dim to the level's true width (the
        # whole point of unrolling on TPU) and bake heap offsets into the
        # kernel grid.
        from ..dispatch import Ctx, resolve
        from . import hist_kernel as _hk
        from .hist_kernel import fused_level_scanned

        Km = 1 << (max_depth - 1)
        st = _init_state(cfg, F, G0, H0, B, ptab_rows=Km)
        # the per-level kernel inside the scan resolves through the same
        # level_hist table as the unrolled loop (pins, degrade state and
        # the FFI availability probe apply identically); `native` is a
        # static flag because the scan body stages ONE program
        native = resolve("level_hist", Ctx(
            platform=jax.default_backend(), pallas=False,
            interpret=bool(_hk._INTERPRET), rows=int(n),
            features=int(F), nodes=int(Km),
            bins=int(B), table_width=int(st.ptab.shape[-1]),
            bins_dtype=str(bins.dtype),
            sharded=cfg.axis_name is not None,
            onehot_width=0)).impl == "native"

        def _level_body(carry, d):
            st, pos = carry
            prev_off = jnp.left_shift(
                jnp.int32(1), jnp.maximum(d - 1, 0)) - 1  # 0 at the root
            off = jnp.left_shift(jnp.int32(1), d) - 1
            with jax.named_scope("xgb.level_hist"):
                pos, histC = fused_level_scanned(
                    bins, pos, gh, st.ptab, prev_off, off, K=Km, B=B,
                    native=native)
            if cfg.axis_name is not None:
                from .. import collective

                with jax.named_scope("xgb.hist_psum"):
                    histC = collective.psum(histC, cfg.axis_name)
            with jax.named_scope("xgb.split_eval"):
                st = _level_update(st, histC, cut_values, tree_mask,
                                   k_level, cfg, d, Kw=Km)
            return (st, pos), None

        (st, pos), _ = jax.lax.scan(
            _level_body, (st, pos),
            jnp.arange(max_depth, dtype=jnp.int32))
    else:
        from ..dispatch import Ctx, resolve

        # Sibling subtraction where the Mosaic level kernels run (the
        # reference's SubtractionTrick, updater_gpu_hist.cu): below the
        # root a level builds one child of every split, under a mesh the
        # psum carries that half, and the sibling is the parent's
        # histogram, kept for one level, less the built child's.
        sub = False  # the root has no parent
        for d in range(max_depth):
            K = 1 << d
            Kp = K >> 1  # previous level width (0 at the root)
            with jax.named_scope("xgb.level_hist"):
                pos, histC = fused_level(
                    bins, pos, gh, st.ptab, K=K, Kp=Kp, B=B, d=d,
                    pallas=pallas, onehot=onehot, axis_name=cfg.axis_name,
                    sibling_sub=sub,
                )  # histC: [F, 2K, B], missing excluded; built: [F, 2Kp, B]
            if cfg.axis_name is not None:
                with jax.named_scope("xgb.hist_psum"):
                    histC = jax.lax.psum(histC, cfg.axis_name)
            if histC.shape[1] == 2 * Kp:  # the built half: pallas, sub
                with jax.named_scope("xgb.level_hist"):
                    histC = derive_siblings(parent_hist, histC, st.ptab)
            sub = (pallas and d + 1 < max_depth
                   and resolve("sibling_sub", Ctx(
                       platform=jax.default_backend(), pallas=True,
                       depth=d + 1)).impl == "on")
            with jax.named_scope("xgb.split_eval"):
                st = _level_update(st, histC, cut_values, tree_mask, k_level,
                                   cfg, d, mark_built=sub)
            parent_hist = histC

    # (the last routing is folded into the whole-tree kernel when that
    # route ran: its pos output is already at the leaf level)
    return _tree_end(bins, pos, st, eta, gamma, cfg, B, pallas,
                     route=not tree_grow_native_route)


def _tree_start(grad, hess, key, feature_weights, cfg: GrowParams, F: int,
                B: int):
    """A tree's start: the three keys, row and column sampling, the
    gradients ``[2, n]``, the root totals and the empty heap."""
    k_sub, k_ctree, k_level = jax.random.split(key, 3)
    if cfg.axis_name is not None:
        k_sub = jax.random.fold_in(k_sub, jax.lax.axis_index(cfg.axis_name))

    with jax.named_scope("xgb.root"):
        grad, hess = apply_row_sampling(cfg, k_sub, grad, hess)
        # per-row arrays of the tree have the rows on the LANE axis from
        # here to ``leaf_delta`` (hist_kernel's module docstring)
        gh = jnp.stack([grad, hess])  # [2, n]

        if cfg.colsample_bytree < 1.0:
            tree_mask = _sample_features_exact(
                k_ctree, F, cfg.colsample_bytree, feature_weights
            )
        else:
            tree_mask = jnp.ones((F,), bool)

        # root totals (the InitRoot AllReduce site)
        G0 = grad.sum()
        H0 = hess.sum()
        if cfg.axis_name is not None:
            G0 = jax.lax.psum(G0, cfg.axis_name)
            H0 = jax.lax.psum(H0, cfg.axis_name)
        st = _init_state(cfg, F, G0, H0, B)
    return gh, tree_mask, k_level, G0, H0, st


def _tree_end(bins, pos, st: _HeapState, eta, gamma, cfg: GrowParams, B: int,
              pallas: bool, route: bool = True) -> GrownTree:
    """A tree's end: rows routed through the last level's splits to their
    leaves (``pos`` ``[1, n]``; ``route`` False where they already are),
    gamma pruning, leaf values, the margin's delta."""
    if cfg.max_depth > 0 and route:
        with jax.named_scope("xgb.partition"):
            pos = partition_apply(
                bins, pos, st.ptab, Kp=1 << (cfg.max_depth - 1), B=B,
                d=cfg.max_depth, pallas=pallas, axis_name=cfg.axis_name,
            )

    with jax.named_scope("xgb.finalize"):
        keep, leaf_value = _finalize(st, eta, gamma, cfg)
    pad_nodes = max(128, 1 << (cfg.max_nodes - 1).bit_length())
    with jax.named_scope("xgb.leaf_delta"):
        delta = leaf_delta(pos, leaf_value, pad_nodes, pallas=pallas)

    return GrownTree(
        keep=keep, feature=st.feature, split_bin=st.split_bin,
        split_cond=st.split_cond, default_left=st.default_left,
        node_g=st.node_g, node_h=st.node_h, node_weight=st.node_w,
        loss_chg=st.loss_chg, leaf_value=leaf_value, delta=delta,
        cat_set=st.cat_set,
    )


# ---------------------------------------------------------------------------
# A round's trees in one pass over the rows (multiclass, num_parallel_tree).
# The trees of one round are independent: their gradients come from the
# round's margin before any of them is grown. Grown one after another, each
# tree's each level streams the same resident one-hot and rebuilds the same
# features' one-hot in VMEM; grown level by level together, a level call
# carries several trees' gradient channels over one read of the rows
# (``hist_kernel.fused_level_trees``). What a tree does with its histogram
# is ``_grow_tree_fused_impl``'s own steps, each a jitted computation traced
# once and called a tree, as ``jit(_grow_tree_fused_impl)`` is in the class
# loop. A job that grows one tree a round never comes here
# (``gbtree._scan_rounds_impl``).
# ---------------------------------------------------------------------------


_tree_root = guard_jit(_tree_start, name="tree_root",
                       static_argnames=("cfg", "F", "B"))


@guard_jit(name="tree_level", static_argnames=("cfg", "d", "mark_built"))
def _tree_level(st: _HeapState, histC, parent_hist, cut_values, tree_mask,
                k_level, *, cfg: GrowParams, d: int, mark_built: bool):
    """A tree's step at level ``d`` once its histogram is built: the
    siblings derived where the kernel built one child of every split
    (``histC`` is then ``[F, 2Kp, B]``), the splits evaluated, the heap and
    the next decision table written. Returns the state and the level's
    full histogram, the next level's ``parent_hist``."""
    if histC.shape[1] == (1 << d):  # the built half: 2 Kp = K rows
        with jax.named_scope("xgb.level_hist"):
            histC = derive_siblings(parent_hist, histC, st.ptab)
    with jax.named_scope("xgb.split_eval"):
        st = _level_update(st, histC, cut_values, tree_mask, k_level, cfg, d,
                           mark_built=mark_built)
    return st, histC


_tree_leaves = guard_jit(_tree_end, name="tree_leaves",
                         static_argnames=("cfg", "B", "pallas", "route"))


def grow_trees_one_pass(
    bins: jax.Array,  # [n_pad, F] narrow-int bins, as ``grow_tree_fused``
    grads: Sequence[jax.Array],  # a tree's [n_pad] f32 each
    hesss: Sequence[jax.Array],
    cut_values: jax.Array,
    keys: Sequence[jax.Array],
    eta: jax.Array,
    gamma: jax.Array,
    cfg: GrowParams,
    feature_weights: Optional[jax.Array] = None,
    onehot: Optional[jax.Array] = None,
) -> List[GrownTree]:
    """The trees of one round (two or more; one chip, the Mosaic level
    kernels), grown level by level together inside the caller's program:
    per level the shared kernel call(s) (``level_trees`` says how many
    trees a call carries, from the kernel's VMEM model at this level's
    node count), then per tree ``_grow_tree_fused_impl``'s own steps. Every
    tree is the one ``grow_tree_fused`` grows from the same gradients and
    key: the same arithmetic on the same rows in the same order, bit for
    bit wherever the level kernel's row tile is the same
    (``fused_level_trees``)."""
    from ..dispatch import Ctx, note, resolve
    from . import hist_kernel as _hk

    NT = len(keys)
    assert NT > 1 and cfg.axis_name is None and not cfg.has_categorical
    with jax.named_scope("xgb.level_hist"):
        # once a round, for every tree (read feature-major: as above)
        bins = bins.astype(jnp.int32)
    n, F = bins.shape
    B = cut_values.shape[1]
    ghs, masks, k_levels, _, _, sts = map(list, zip(*[
        _tree_root(g, h, key, feature_weights, cfg=cfg, F=F, B=B)
        for g, h, key in zip(grads, hesss, keys)]))
    with jax.named_scope("xgb.root"):
        gh = jnp.concatenate(ghs)  # [2 NT, n]: tree t's g over h
        pos = jnp.zeros((NT, n), jnp.int32)  # every row starts at the root
    hists: List[Optional[jax.Array]] = [None] * NT
    oh_width = 0 if onehot is None else int(onehot.shape[1])
    sub = False  # the root has no parent
    for d in range(cfg.max_depth):
        K = 1 << d
        Kp = K >> 1
        Kc = Kp if sub else K
        T = level_trees(n, F, Kc, B, NT, oh_width)
        if T > 1 and resolve("level_hist", Ctx(
                platform=jax.default_backend(), pallas=True,
                interpret=bool(_hk._INTERPRET), rows=int(n), features=int(F),
                nodes=int(T * Kc), bins=int(B),
                table_width=int(sts[0].ptab.shape[-1]),
                bins_dtype=str(bins.dtype), sharded=False,
                onehot_width=oh_width)).impl != "pallas":
            T = 1  # pinned or degraded away from the Mosaic kernels
        new_pos, built = [], []
        for lo in range(0, NT, T):
            note("level_trees", T)
            with jax.named_scope("xgb.level_hist"):
                if T == 1:
                    p, h = fused_level(
                        bins, pos[lo:lo + 1], gh[2 * lo:2 * lo + 2],
                        sts[lo].ptab, K=K, Kp=Kp, B=B, d=d, pallas=True,
                        onehot=onehot, sibling_sub=sub)
                    built.append(h)
                else:
                    p, h = fused_level_trees(
                        bins, pos[lo:lo + T], gh[2 * lo:2 * (lo + T)],
                        jnp.stack([st.ptab for st in sts[lo:lo + T]]),
                        K=K, Kp=Kp, B=B, d=d, onehot=onehot,
                        sibling_sub=sub)
                    built += [h[t] for t in range(T)]
            new_pos.append(p)
        pos = new_pos[0] if len(new_pos) == 1 else jnp.concatenate(new_pos)
        sub = (d + 1 < cfg.max_depth
               and resolve("sibling_sub", Ctx(
                   platform=jax.default_backend(), pallas=True,
                   depth=d + 1)).impl == "on")
        for t in range(NT):
            sts[t], hists[t] = _tree_level(
                sts[t], built[t], hists[t], cut_values, masks[t],
                k_levels[t], cfg=cfg, d=d, mark_built=sub)
    return [_tree_leaves(bins, pos[t:t + 1], sts[t], eta, gamma, cfg=cfg,
                         B=B, pallas=True) for t in range(NT)]


def _use_tree_grow(cfg: GrowParams, pallas: bool, max_depth: int,
                   bins_dtype: str) -> bool:
    """Whether the round runs as ONE native whole-tree custom call —
    resolved through the dispatch registry (``tree_grow``: native >
    level). The native impl's envelope (``dispatch/ops.py``) is the
    per-level native kernel's plus the eval features the C++ port
    replicates bitwise: no per-level/per-node colsample draws, no
    monotone/interaction constraints, no categorical tables and
    ``max_delta_step == 0``. Everything else keeps the per-level path
    (``level``), including all of pallas/mesh/paged."""
    from ..dispatch import Ctx, resolve
    from . import hist_kernel as _hk

    return resolve("tree_grow", Ctx(
        platform=jax.default_backend(), pallas=bool(pallas),
        interpret=bool(_hk._INTERPRET),
        sharded=cfg.axis_name is not None,
        has_cats=bool(cfg.has_categorical), bins_dtype=bins_dtype,
        depth=int(max_depth), monotone=bool(cfg.has_monotone),
        interaction=bool(cfg.has_interaction),
        colsample_level=float(cfg.colsample_bylevel),
        colsample_node=float(cfg.colsample_bynode),
        max_delta_step=float(cfg.split.max_delta_step))).impl == "native"


def _use_depth_scan(cfg: GrowParams, pallas: bool, max_depth: int) -> bool:
    """Whether the level loop runs as one lax.scan (the fused depth scan)
    instead of unrolled per-level bodies — resolved through the dispatch
    registry (``depth_scan``: scanned > unrolled). The scanned driver is
    inapplicable on the pallas path (Mosaic kernels specialize per level
    width by design), for categorical trees (the widened decision table
    is level-shaped) and under meshes (the unrolled loop is the proven
    shard_map path); the legacy ``XGBTPU_DEPTH_SCAN=0`` escape hatch maps
    to a ``depth_scan=unrolled`` pin."""
    from ..dispatch import Ctx, resolve

    return resolve("depth_scan", Ctx(
        platform=jax.default_backend(), pallas=bool(pallas),
        has_cats=bool(cfg.has_categorical),
        sharded=cfg.axis_name is not None,
        depth=int(max_depth))).impl == "scanned"


def _pallas_flag(cfg: GrowParams) -> bool:
    """The fused Mosaic kernels run under shard_map too: they are pure
    per-shard local work (the histogram psum sits OUTSIDE fused_level, at
    grow_tree_fused's collective site), so the distributed path executes
    the SAME kernel the single-chip bench measures — the reference's
    AllReduceHist design (updater_gpu_hist.cu:526). Round 3 gated this off
    under a mesh, which silently sent every distributed run to the slow
    XLA fallback (review Weak #6)."""
    from .hist_kernel import use_pallas

    return use_pallas()


# jitted views of the shared level machinery for the paged (out-of-core)
# driver, which runs the level loop in Python so pages can stream from disk.
# Retrace-guarded: these recompile per level width by design (K is static),
# so their budget is the level count, not 1 — the guard makes any EXTRA
# recompile (e.g. a non-static scalar sneaking in) visible and budgetable.
# The heap state is DONATED: the per-level node-state tensors are updated
# in place across the level loop instead of re-allocated (ISSUE 13).
_level_update_jit = guard_jit(_level_update, name="level_update",
                              static_argnames=("cfg", "d", "mark_built"),
                              donate_argnames=("st",))
_finalize_jit = guard_jit(_finalize, name="finalize",
                          static_argnames=("cfg",))


@guard_jit(name="page_delta", static_argnames=("Kp", "B", "d", "pallas",
                                               "pad_nodes"))
def _page_delta(bins, pos, ptab, leaf_value, *, Kp, B, d, pallas, pad_nodes):
    pos = partition_apply(bins, pos, ptab, Kp=Kp, B=B, d=d, pallas=pallas)
    return leaf_delta(pos, leaf_value, pad_nodes, pallas=pallas)


def grow_tree_fused_paged(
    paged,  # data.external.PagedBins
    grad: np.ndarray,  # [n] host or device
    hess: np.ndarray,
    cut_values: jax.Array,
    key: jax.Array,
    eta: float,
    gamma: float,
    cfg: GrowParams,
    feature_weights: Optional[jax.Array] = None,
) -> GrownTree:
    """Out-of-core variant of ``grow_tree_fused``: the level loop runs in
    Python, streaming quantized pages from the disk cache (prefetched by the
    native pager) and accumulating the fixed-size level histogram across
    pages — the reference's external-memory training loop
    (``sparse_page_source.h``: re-stream pages every iteration, window
    prefetched). Device memory holds ONE page of bins plus per-page row
    positions/gradients; the histogram/eval machinery is byte-identical to
    the in-core path (shared ``_level_update``/``_finalize``)."""
    assert cfg.axis_name is None, (
        "paged + mesh is not supported inside one process; compose them "
        "ACROSS processes instead — shard rows across processes (dsplit="
        "row), page within each, elastically if workers may die. Recipe: "
        "docs/distributed.md, 'Composing external memory with a mesh "
        "(paged + sharded rows)'.")
    assert not cfg.has_categorical
    from ..observability import trace as _trace

    with _trace.span("grow_tree_paged", depth=cfg.max_depth,
                     pages=paged.n_pages):
        return _grow_tree_fused_paged(paged, grad, hess, cut_values, key,
                                      eta, gamma, cfg, feature_weights)


def _grow_tree_fused_paged(
    paged,
    grad: np.ndarray,
    hess: np.ndarray,
    cut_values: jax.Array,
    key: jax.Array,
    eta: float,
    gamma: float,
    cfg: GrowParams,
    feature_weights: Optional[jax.Array] = None,
) -> GrownTree:
    B = cut_values.shape[1]
    F = paged.n_features
    n = paged.n_rows
    P = paged.n_pages
    pr_pad = pad_rows(paged.page_rows)
    pallas = _pallas_flag(cfg)
    missing_bin = B

    k_sub, k_ctree, k_level = jax.random.split(key, 3)
    grad = jnp.asarray(grad, jnp.float32)
    hess = jnp.asarray(hess, jnp.float32)

    gh_pages = []
    for k in range(P):
        lo = k * paged.page_rows
        r = paged.rows_of(k)
        g = jax.lax.dynamic_slice_in_dim(grad, lo, r) if r == paged.page_rows \
            else grad[lo:lo + r]
        h = jax.lax.dynamic_slice_in_dim(hess, lo, r) if r == paged.page_rows \
            else hess[lo:lo + r]
        g, h = apply_row_sampling(cfg, jax.random.fold_in(k_sub, k), g, h)
        if r != pr_pad:
            pad = jnp.zeros((pr_pad - r,), jnp.float32)
            g = jnp.concatenate([g, pad])
            h = jnp.concatenate([h, pad])
        gh_pages.append(jnp.stack([g, h]))  # [2, pr_pad]

    if cfg.colsample_bytree < 1.0:
        tree_mask = _sample_features_exact(
            k_ctree, F, cfg.colsample_bytree, feature_weights
        )
    else:
        tree_mask = jnp.ones((F,), bool)

    G0 = sum(gh[0].sum() for gh in gh_pages)
    H0 = sum(gh[1].sum() for gh in gh_pages)
    st = _init_state(cfg, F, G0, H0)
    pos_pages = [jnp.zeros((1, pr_pad), jnp.int32) for _ in range(P)]

    def page_bins(k: int) -> jax.Array:
        arr = paged.read_page(k)
        if arr.shape[0] != pr_pad:
            pad = np.full((pr_pad - arr.shape[0], F), missing_bin, arr.dtype)
            arr = np.concatenate([arr, pad])
        # narrow dtype preserved off-TPU (native/XLA paths read it as-is)
        return jnp.asarray(arr.astype(np.int32) if pallas else arr)

    # prefetch-overlapped paging (ISSUE 15): right after page k's level
    # work is DISPATCHED (jax dispatch is async — the host returns while
    # the device chews), admit the background decode of the next page the
    # sweep will read, so disk read + symbol unpack overlap the in-flight
    # compute. k wraps to 0 at the sweep end: the next consumer is the
    # following level's (or the delta pass's / the NEXT ROUND'S) page-0
    # read. The very first page-0 read of a tree with no wrapped
    # prefetch in flight stays SYNCHRONOUS on purpose (charged to
    # `ingest`): prefetching it here would just move the same blocking
    # read onto the worker and charge it to `prefetch_wait`, making the
    # overlap stage read as wait it never hid. Bit-identical to
    # synchronous reads by construction (same bytes, same order — pinned
    # by tests/test_data_plane.py).
    prefetch = getattr(paged, "start_prefetch", lambda k: None)

    for d in range(cfg.max_depth):
        K = 1 << d
        Kp = K >> 1
        hist = jnp.zeros((F, 2 * K, B), jnp.float32)
        for k in range(P):
            pos_k, hist_k = fused_level(
                page_bins(k), pos_pages[k], gh_pages[k], st.ptab,
                K=K, Kp=Kp, B=B, d=d, pallas=pallas,
            )
            prefetch(k + 1 if k + 1 < P else 0)
            pos_pages[k] = pos_k
            hist = hist + hist_k
        st = _level_update_jit(st, hist, cut_values, tree_mask, k_level,
                               cfg=cfg, d=d)

    keep, leaf_value = _finalize_jit(st, jnp.float32(eta), jnp.float32(gamma),
                                     cfg=cfg)
    pad_nodes = max(128, 1 << (cfg.max_nodes - 1).bit_length())
    deltas = []
    for k in range(P):
        if cfg.max_depth > 0:
            dlt = _page_delta(
                page_bins(k), pos_pages[k], st.ptab, leaf_value,
                Kp=1 << (cfg.max_depth - 1), B=B, d=cfg.max_depth,
                pallas=pallas, pad_nodes=pad_nodes,
            )
            # wrap-around: page 0's next reader is the NEXT ROUND's first
            # level — the cross-round half of the prefetch overlap (the
            # RoundPipeline keeps round i+1's dispatch going while round
            # i's device work is still in flight)
            prefetch(k + 1 if k + 1 < P else 0)
        else:
            dlt = leaf_delta(pos_pages[k], leaf_value, pad_nodes,
                             pallas=pallas)
        deltas.append(dlt[: paged.rows_of(k)])
    delta = jnp.concatenate(deltas)

    return GrownTree(
        keep=keep, feature=st.feature, split_bin=st.split_bin,
        split_cond=st.split_cond, default_left=st.default_left,
        node_g=st.node_g, node_h=st.node_h, node_weight=st.node_w,
        loss_chg=st.loss_chg, leaf_value=leaf_value, delta=delta,
        cat_set=st.cat_set,
    )
