"""Loss-guide (best-first) tree growth, ``grow_policy='lossguide'``.

Reference: the Driver priority queue (``src/tree/driver.h:30-88`` — lossguide
pops the single best candidate; depthwise pops whole levels) combined with
the same histogram/evaluate machinery as ``updater_quantile_hist.cc``.
Split evaluation, monotone bound propagation, and interaction masking are
the SAME code as the depthwise grower (``grow.eval_splits`` et al.) — the
reference likewise shares one HistEvaluator between policies.

TPU-first shape: nodes are ALLOCATION-ordered (root=0, each split appends
two ids), not heap-ordered — lossguide trees can be deep chains, which would
overflow an implicit-heap id space. The whole growth runs in one
``lax.fori_loop`` over ``max_leaves-1`` split steps with fixed
``[2*max_leaves-1]`` tensors; each step:

1. argmax of cached candidate gains over open leaves (the priority queue,
   as a flat masked argmax — no heap needed at this scale),
2. partitions the chosen node's rows,
3. histograms BOTH new children in ONE masked segment_sum pass over the
   data (side bit folded into the segment id),
4. evaluates + caches their best candidate splits.

Step cost is one data pass, so lossguide costs ~max_leaves passes vs
depthwise's max_depth passes — same trade the reference makes (per-node
builds vs level builds).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .grow import (
    GrowParams,
    _sample_features_exact,
    blocked_histogram,
    child_bounds_and_weights,
    eval_splits,
    interaction_allowed,
)
from .param import RT_EPS, calc_weight

__all__ = ["AllocTree", "grow_tree_lossguide"]

_INF = float(np.inf)


class AllocTree(NamedTuple):
    """Allocation-ordered tree tensors (left/right = -1 for leaves)."""

    left: jax.Array  # int32 [M]
    right: jax.Array  # int32 [M]
    feature: jax.Array  # int32 [M]
    split_bin: jax.Array  # int32 [M]
    split_cond: jax.Array  # f32 [M]
    default_left: jax.Array  # bool [M]
    node_g: jax.Array  # f32 [M]
    node_h: jax.Array  # f32 [M]
    node_weight: jax.Array  # f32 [M]
    loss_chg: jax.Array  # f32 [M]
    n_nodes: jax.Array  # int32 scalar
    positions: jax.Array  # int32 [n]
    # [M, B] right-going category set per categorical split node
    # ([1, 1] placeholder when no categorical features)
    cat_set: jax.Array
    depth: jax.Array  # int32 [M] node depths (walk bound for the predictor)


@jax.jit
def finalize_alloc(alloc: AllocTree, eta, gamma):
    """On-device gamma pruning + governing leaf values + cache delta for an
    allocation-ordered tree — the device analog of ``RegTree.from_alloc``'s
    host passes, so a lossguide round performs no device->host syncs.
    Children always have larger ids, so ONE descending pass is the pruning
    fixpoint and ONE ascending pass propagates pruned-leaf values down.
    Returns (keep [M], leaf_value [M] (eta-applied, 0 at kept-internal),
    delta [n])."""
    left, right, loss = alloc.left, alloc.right, alloc.loss_chg
    M = left.shape[0]
    iota = jnp.arange(M)
    in_range = iota < alloc.n_nodes
    keep0 = (left != -1) & in_range

    def pbody(t, keep):
        i = M - 1 - t
        l = jnp.clip(left[i], 0, M - 1)
        r = jnp.clip(right[i], 0, M - 1)
        lk = jnp.where(left[i] >= 0, keep[l], False)
        rk = jnp.where(right[i] >= 0, keep[r], False)
        collapse = keep[i] & ~lk & ~rk & (loss[i] < gamma)
        return keep.at[i].set(keep[i] & ~collapse)

    keep = jax.lax.cond(
        gamma > 0.0,
        lambda k: jax.lax.fori_loop(0, M, pbody, k),
        lambda k: k,
        keep0,
    )

    nan = jnp.float32(jnp.nan)
    lv0 = jnp.full((M,), nan)

    def vbody(i, lv):
        own = jnp.isnan(lv[i]) & ~keep[i] & (i < alloc.n_nodes)
        lv = lv.at[i].set(jnp.where(own, eta * alloc.node_weight[i], lv[i]))
        li = jnp.clip(left[i], 0, M - 1)
        ri = jnp.clip(right[i], 0, M - 1)
        prop = (left[i] != -1) & ~jnp.isnan(lv[i])
        lv = lv.at[li].set(jnp.where(prop, lv[i], lv[li]))
        lv = lv.at[ri].set(jnp.where(prop, lv[i], lv[ri]))
        return lv

    lv = jax.lax.fori_loop(0, M, vbody, lv0)
    lv = jnp.nan_to_num(lv)

    from .hist_kernel import leaf_delta, use_pallas

    pad = max(128, 1 << (M - 1).bit_length())
    delta = leaf_delta(alloc.positions[None, :], lv, pad,
                       pallas=use_pallas())
    return keep, lv, delta


@partial(jax.jit, static_argnames=("cfg", "max_leaves"))
def grow_tree_lossguide(
    bins: jax.Array,  # [n, F]
    grad: jax.Array,
    hess: jax.Array,
    cut_values: jax.Array,  # [F, B]
    key: jax.Array,
    cfg: GrowParams,
    max_leaves: int,
    feature_weights: Optional[jax.Array] = None,  # [F] sampling weights
) -> AllocTree:
    n, F = bins.shape
    B = cut_values.shape[1]
    MB = B + 1
    p = cfg.split
    M = 2 * max_leaves - 1
    bins32 = bins.astype(jnp.int32)
    max_depth = cfg.max_depth  # 0 = unbounded (the lossguide default)

    k_sub, k_ctree, k_node = jax.random.split(key, 3)
    if cfg.axis_name is not None:
        # decorrelate row sampling across shards; feature sampling keys stay
        # shared (see grow.py — reference random.h:146 invariant)
        k_sub = jax.random.fold_in(k_sub, jax.lax.axis_index(cfg.axis_name))
    from .grow import apply_row_sampling

    grad, hess = apply_row_sampling(cfg, k_sub, grad, hess)
    if cfg.colsample_bytree < 1.0:
        tree_fmask = _sample_features_exact(
            k_ctree, F, cfg.colsample_bytree, feature_weights
        )
    else:
        tree_fmask = jnp.ones((F,), bool)

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
    cat_oh_j = None
    catp_j = None
    cat_any_j = None
    if cfg.has_categorical:
        cat_any_j, cat_oh_j, catp_j = cfg.cat_masks_jnp(F)

    gh = jnp.stack([grad, hess], axis=-1)

    def pair_hist(side):
        """Feature-block-scanned scatter-add for a +0/+1 side selector ->
        [2, F, MB, 2]. side[i] in {-1 (skip), 0 (left child), 1 (right)}."""
        return blocked_histogram(bins32, gh, side, 2, MB, cfg.axis_name)

    def node_masks(node_ids, depths, used_rows):
        """[K, F] feature mask for a batch of nodes: hierarchical EXACT-k
        column sampling (random.h:120 — bylevel keyed by depth, bynode by
        node id, each nested in its parent set), then interaction masks."""
        from .grow import exact_k_subset

        k_tree = max(1, int(round(cfg.colsample_bytree * F))) \
            if cfg.colsample_bytree < 1.0 else F
        fm = jnp.broadcast_to(tree_fmask[None, :], (node_ids.shape[0], F))
        if cfg.colsample_bylevel < 1.0:
            k_lvl = max(1, int(round(cfg.colsample_bylevel * k_tree)))
            keys = jax.vmap(lambda dd: jax.random.fold_in(k_node, dd))(depths)
            fm = jax.vmap(lambda kk, m: exact_k_subset(kk, m, k_lvl))(keys, fm)
        else:
            k_lvl = k_tree
        if cfg.colsample_bynode < 1.0:
            k_nd = max(1, int(round(cfg.colsample_bynode * k_lvl)))
            keys = jax.vmap(lambda nid: jax.random.fold_in(jax.random.fold_in(k_node, nid), 1))(node_ids)
            fm = jax.vmap(lambda kk, m: exact_k_subset(kk, m, k_nd))(keys, fm)
        if cfg.has_interaction:
            fm = fm & interaction_allowed(used_rows, gmask)
        return fm

    # ---- state tensors ----
    left = jnp.full((M,), -1, jnp.int32)
    right = jnp.full((M,), -1, jnp.int32)
    feature = jnp.zeros((M,), jnp.int32)
    split_bin = jnp.zeros((M,), jnp.int32)
    split_cond = jnp.zeros((M,), jnp.float32)
    default_left = jnp.zeros((M,), bool)
    node_g = jnp.zeros((M,), jnp.float32)
    node_h = jnp.zeros((M,), jnp.float32)
    node_w = jnp.zeros((M,), jnp.float32)
    loss_chg = jnp.zeros((M,), jnp.float32)
    depth = jnp.zeros((M,), jnp.int32)
    cand_gain = jnp.full((M,), -jnp.inf)
    cand_dir = jnp.zeros((M,), jnp.int32)
    cand_f = jnp.zeros((M,), jnp.int32)
    cand_b = jnp.zeros((M,), jnp.int32)
    cand_gl = jnp.zeros((M,), jnp.float32)
    cand_hl = jnp.zeros((M,), jnp.float32)
    n_mb = M if cfg.has_monotone else 1
    n_mu = M if cfg.has_interaction else 1
    lo_b = jnp.full((n_mb,), -_INF)
    up_b = jnp.full((n_mb,), _INF)
    used = jnp.zeros((n_mu, F), bool)
    n_cs, b_cs = (M, B) if cfg.has_categorical else (1, 1)
    cand_cat = jnp.zeros((n_cs, b_cs), bool)  # best candidate's category set
    cat_set = jnp.zeros((n_cs, b_cs), bool)  # committed split sets

    # ---- root ----
    pos = jnp.zeros((n,), jnp.int32)
    if cfg.axis_name is not None:
        # per-row positions are per-shard data: mark varying so the
        # expansion loop's carry types line up under check_vma
        pos = jax.lax.pcast(pos, (cfg.axis_name,), to="varying")
    h0 = pair_hist(jnp.zeros((n,), jnp.int32))[:1]  # all rows as "left"
    G0 = h0[0, 0, :, 0].sum()
    H0 = h0[0, 0, :, 1].sum()
    fm0 = node_masks(jnp.zeros((1,), jnp.int32), jnp.zeros((1,), jnp.int32), used[:1])
    dec0 = eval_splits(
        h0, G0[None], H0[None], p, fm0, B,
        mono=mono_j if cfg.has_monotone else None,
        node_lo=lo_b[:1] if cfg.has_monotone else None,
        node_up=up_b[:1] if cfg.has_monotone else None,
        cat_feats=cat_oh_j,
        cat_part=catp_j,
    )
    node_g = node_g.at[0].set(G0)
    node_h = node_h.at[0].set(H0)
    node_w = node_w.at[0].set(dec0.w_node[0])
    cand_gain = cand_gain.at[0].set(dec0.loss[0])
    cand_dir = cand_dir.at[0].set(dec0.dir[0])
    cand_f = cand_f.at[0].set(dec0.f[0])
    cand_b = cand_b.at[0].set(dec0.b[0])
    cand_gl = cand_gl.at[0].set(dec0.GL[0])
    cand_hl = cand_hl.at[0].set(dec0.HL[0])
    if cfg.has_categorical:
        cand_cat = cand_cat.at[0].set(dec0.cat_set[0])

    # ---- batched best-first expansion ----
    # K_EXP=1 reproduces the reference's one-pop-at-a-time queue exactly
    # (driver.h lossguide). For large leaf budgets the dominant cost is one
    # full-data histogram pass PER STEP (review r2 weak #6: 255 leaves =
    # 255 passes), so above 64 leaves the top-8 candidates are expanded per
    # pass — leaves are independent, children join the queue next step, and
    # a remaining-budget mask keeps the total expansion count identical.
    K_EXP = 1 if max_leaves <= 64 else 8
    kk = K_EXP

    def body(t, state):
        (pos, left, right, feature, split_bin, split_cond, default_left,
         node_g, node_h, node_w, loss_chg, depth,
         cand_gain, cand_dir, cand_f, cand_b, cand_gl, cand_hl, cand_cat,
         lo_b, up_b, used, cat_set, n_alloc) = state

        # ---- pop the top-k candidates (driver.h lossguide queue) ----
        vals, picks = jax.lax.top_k(cand_gain, kk)  # [k]
        remaining = (max_leaves - 1) - (n_alloc - 1) // 2
        do = (vals > RT_EPS) & (jnp.arange(kk) < remaining)

        inc = 2 * do.astype(jnp.int32)
        off = jnp.cumsum(inc) - inc  # exclusive prefix: packed child slots
        l_id = jnp.where(do, n_alloc + off, M)
        r_id = jnp.where(do, n_alloc + off + 1, M)

        f = cand_f[picks]
        b = cand_b[picks]
        dr = cand_dir[picks]
        GLb, HLb = cand_gl[picks], cand_hl[picks]
        GRb, HRb = node_g[picks] - GLb, node_h[picks] - HLb

        wp = jnp.where(do, picks, M)  # drop-write for masked pops
        left = left.at[wp].set(l_id, mode="drop")
        right = right.at[wp].set(r_id, mode="drop")
        feature = feature.at[wp].set(f, mode="drop")
        split_bin = split_bin.at[wp].set(b, mode="drop")
        split_cond = split_cond.at[wp].set(cut_values[f, b], mode="drop")
        default_left = default_left.at[wp].set(dr == 1, mode="drop")
        loss_chg = loss_chg.at[wp].set(vals, mode="drop")
        cand_gain = cand_gain.at[wp].set(-jnp.inf, mode="drop")
        if cfg.has_categorical:
            cat_set = cat_set.at[wp].set(cand_cat[picks], mode="drop")

        # children weights + monotone bounds via the shared helper (all [k])
        if cfg.has_monotone:
            plo, pup = lo_b[picks], up_b[picks]
            l_lo, l_up, r_lo, r_up, wl_c, wr_c = child_bounds_and_weights(
                p, mono_j[f], GLb, HLb, GRb, HRb, plo, pup,
            )
        else:
            wl_c = calc_weight(GLb, HLb, p)
            wr_c = calc_weight(GRb, HRb, p)

        node_g = node_g.at[l_id].set(GLb, mode="drop").at[r_id].set(GRb, mode="drop")
        node_h = node_h.at[l_id].set(HLb, mode="drop").at[r_id].set(HRb, mode="drop")
        node_w = node_w.at[l_id].set(wl_c, mode="drop").at[r_id].set(wr_c, mode="drop")
        child_depth = depth[picks] + 1  # [k]
        depth = depth.at[l_id].set(child_depth, mode="drop").at[r_id].set(child_depth, mode="drop")
        if cfg.has_monotone:
            lo_b = lo_b.at[l_id].set(l_lo, mode="drop").at[r_id].set(r_lo, mode="drop")
            up_b = up_b.at[l_id].set(l_up, mode="drop").at[r_id].set(r_up, mode="drop")
        if cfg.has_interaction:
            child_used = used[picks] | jax.nn.one_hot(f, F, dtype=bool)  # [k, F]
            used = used.at[l_id].set(child_used, mode="drop")
            used = used.at[r_id].set(child_used, mode="drop")

        # ---- partition the picked nodes' rows (each row belongs to at
        # most one pick: leaves are disjoint) ----
        ohm = (pos[:, None] == picks[None, :]) & do[None, :]  # [n, k]
        hit = ohm.any(axis=1)
        ohmi = ohm.astype(jnp.int32)
        f_of = (ohmi * f[None, :]).sum(axis=1)
        b_of = (ohmi * b[None, :]).sum(axis=1)
        dr_of = (ohmi * dr[None, :]).sum(axis=1)
        lid_of = (ohmi * l_id[None, :]).sum(axis=1)
        rid_of = (ohmi * r_id[None, :]).sum(axis=1)
        bv = jnp.take_along_axis(bins32, f_of[:, None], axis=1)[:, 0]
        present = bv <= b_of
        if cfg.has_categorical:
            # the stored category set goes RIGHT (categorical.h Decision)
            cc = cand_cat[picks]  # [k, B]
            inset_k = jax.vmap(lambda row: row[jnp.minimum(bv, B - 1)])(cc)
            in_set = (inset_k.T & ohm).any(axis=1)
            is_cat_row = (ohmi * cat_any_j[f][None, :].astype(jnp.int32)).sum(axis=1) > 0
            present = jnp.where(is_cat_row, ~in_set, present)
        goleft = jnp.where(bv == B, dr_of == 1, present)
        pos = jnp.where(hit, jnp.where(goleft, lid_of, rid_of), pos)

        # ---- histogram all 2k children in ONE pass, then evaluate ----
        seg = jnp.full((n,), -1, jnp.int32)
        eq_l = pos[:, None] == l_id[None, :]  # [n, k]
        eq_r = pos[:, None] == r_id[None, :]
        two_j = (2 * jnp.arange(kk, dtype=jnp.int32))[None, :]
        seg = jnp.where(eq_l.any(1),
                        (eq_l.astype(jnp.int32) * two_j).sum(1), seg)
        seg = jnp.where(eq_r.any(1),
                        (eq_r.astype(jnp.int32) * (two_j + 1)).sum(1), seg)
        hist = blocked_histogram(bins32, gh, seg, 2 * kk, MB, cfg.axis_name)

        def ilv(a_l, a_r):  # interleave left/right per pick -> [2k]
            return jnp.stack([a_l, a_r], axis=1).reshape(-1)

        G2 = ilv(GLb, GRb)
        H2 = ilv(HLb, HRb)
        ids2 = ilv(l_id, r_id)
        depth2 = jnp.repeat(child_depth, 2)
        used2 = (
            jnp.repeat(child_used, 2, axis=0)
            if cfg.has_interaction
            else used[:1].repeat(2 * kk, axis=0)
        )
        fm2 = node_masks(ids2, depth2, used2)
        dec = eval_splits(
            hist, G2, H2, p, fm2, B,
            mono=mono_j if cfg.has_monotone else None,
            node_lo=ilv(l_lo, r_lo) if cfg.has_monotone else None,
            node_up=ilv(l_up, r_up) if cfg.has_monotone else None,
            cat_feats=cat_oh_j,
            cat_part=catp_j,
        )
        bl = dec.loss
        if max_depth > 0:
            bl = jnp.where(depth2 >= max_depth, -jnp.inf, bl)
        cand_gain = cand_gain.at[ids2].set(bl, mode="drop")
        cand_dir = cand_dir.at[ids2].set(dec.dir, mode="drop")
        cand_f = cand_f.at[ids2].set(dec.f, mode="drop")
        cand_b = cand_b.at[ids2].set(dec.b, mode="drop")
        cand_gl = cand_gl.at[ids2].set(dec.GL, mode="drop")
        cand_hl = cand_hl.at[ids2].set(dec.HL, mode="drop")
        if cfg.has_categorical:
            cand_cat = cand_cat.at[ids2].set(dec.cat_set, mode="drop")

        n_alloc = n_alloc + inc.sum()
        return (pos, left, right, feature, split_bin, split_cond, default_left,
                node_g, node_h, node_w, loss_chg, depth,
                cand_gain, cand_dir, cand_f, cand_b, cand_gl, cand_hl, cand_cat,
                lo_b, up_b, used, cat_set, n_alloc)

    state = (pos, left, right, feature, split_bin, split_cond, default_left,
             node_g, node_h, node_w, loss_chg, depth,
             cand_gain, cand_dir, cand_f, cand_b, cand_gl, cand_hl, cand_cat,
             lo_b, up_b, used, cat_set, jnp.int32(1))
    # + ramp-up slack: the queue holds < K_EXP expandable leaves for the
    # first ~log2(K_EXP) steps, so a flat division would under-build trees
    ramp = max(0, (K_EXP - 1).bit_length())
    n_steps = -(-(max_leaves - 1) // K_EXP) + ramp
    state = jax.lax.fori_loop(0, n_steps, body, state)
    (pos, left, right, feature, split_bin, split_cond, default_left,
     node_g, node_h, node_w, loss_chg, depth, *_rest) = state
    n_alloc = state[-1]
    cat_set = state[-2]
    return AllocTree(
        left=left, right=right, feature=feature, split_bin=split_bin,
        split_cond=split_cond, default_left=default_left,
        node_g=node_g, node_h=node_h, node_weight=node_w,
        loss_chg=loss_chg, n_nodes=n_alloc, positions=pos, cat_set=cat_set,
        depth=depth,
    )
