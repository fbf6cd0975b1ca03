"""Loss-guide (best-first) tree growth, ``grow_policy='lossguide'``.

Reference: the Driver priority queue (``src/tree/driver.h:30-88``): for
loss-guide it pops the ONE open leaf with the largest candidate gain, splits
it, and pushes its two children; depthwise pops whole levels. Split
evaluation, monotone bound propagation, and interaction masking are the
SAME code as the depthwise grower (``grow.eval_splits`` et al.) — the
reference likewise shares one HistEvaluator between policies.

TPU-first shape: nodes are ALLOCATION-ordered (root=0, each split appends
two ids), not heap-ordered — lossguide trees can be deep chains, which would
overflow an implicit-heap id space. The growth is one ``lax.while_loop`` of
at most ``max_leaves - 1`` steps over fixed ``[2*max_leaves-1]`` tensors;
it stops where the budget is spent or no open leaf has a gain over
``RT_EPS``. Each step:

1. pops the open leaf with the largest cached candidate gain (a flat
   argmax over the node ids: at this scale no heap is needed). Equal gains
   go to the LOWER node id, which is the one pushed earlier;
2. records the split and its two children (weights, depth, monotone bounds,
   used features);
3. partitions the leaf's rows and builds the children's histograms;
4. evaluates both children (``eval_splits``) and caches their best
   candidates; a child at ``max_depth`` (when > 0) gets none.

Two growers share steps 1, 2 and 4 and differ in step 3:

- ``grow_tree_lossguide``, the plain reference (every backend off the chip,
  the mesh, and categorical or interaction-constrained jobs everywhere):
  every row carries its leaf id, and both children's histograms come from
  one ``segment_sum`` pass over all n rows, float32, rows outside the split
  leaf masked. It departs from ``driver.h`` in nothing.
- ``grow_tree_lossguide_rows``, the chip path (numerical, one device):
  the rows' data (packed bins, g, h, id) are kept grouped by open leaf in
  an arena (``node_hist``), a Mosaic kernel moves the split leaf's range
  into its two children's, the SMALLER child's histogram is built over its
  own rows by a second, and the sibling's is parent minus built. The
  partition and the histogram follow the split leaf's rows, not n.
  Besides, a step rewrites every row's leaf id (``positions``, which
  ``leaf_delta`` reads) by one elementwise pass over n rows that reads the
  split feature's row of the bins: 12 bytes a row, no gather and no
  scatter, where deriving the ids once a tree from the row order would
  take a scatter of n rows.

Gamma pruning, leaf values and the cache delta are ``finalize_alloc``'s, on
device, for both.
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
    apply_row_sampling,
    blocked_histogram,
    child_bounds_and_weights,
    eval_splits,
    exact_k_subset,
    interaction_allowed,
)
from .param import RT_EPS, calc_weight

__all__ = ["AllocTree", "chip_bins", "grow_tree_lossguide",
           "grow_tree_lossguide_rows", "node_work"]

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

    with jax.named_scope("xgb.finalize"):
        keep = jax.lax.cond(
            gamma > 0.0,
            lambda k: jax.lax.fori_loop(0, M, pbody, k),
            lambda k: k,
            keep0,
        )
        lv = jax.lax.fori_loop(0, M, vbody, lv0)
        lv = jnp.nan_to_num(lv)

    from .hist_kernel import leaf_delta, use_pallas

    pad = max(128, 1 << (M - 1).bit_length())
    with jax.named_scope("xgb.leaf_delta"):
        delta = leaf_delta(alloc.positions[None, :], lv, pad,
                           pallas=use_pallas())
    return keep, lv, delta


# ---------------------------------------------------------------------------
# what both growers share: the queue of open leaves and its bookkeeping
# ---------------------------------------------------------------------------


class _Queue(NamedTuple):
    """The tree so far and the cached best split of every open leaf (all
    ``[M]`` but the constraint extras: ``[1]`` or ``[1, F]`` where no
    constraint needs them)."""

    left: jax.Array
    right: jax.Array
    feature: jax.Array
    split_bin: jax.Array
    split_cond: jax.Array
    default_left: jax.Array
    node_g: jax.Array
    node_h: jax.Array
    node_w: jax.Array
    loss_chg: jax.Array
    depth: jax.Array
    cand_gain: jax.Array  # -inf: not an open leaf with a split
    cand_dir: jax.Array
    cand_f: jax.Array
    cand_b: jax.Array
    cand_gl: jax.Array
    cand_hl: jax.Array
    cand_cat: jax.Array
    lo_b: jax.Array
    up_b: jax.Array
    used: jax.Array
    cat_set: jax.Array
    n_alloc: jax.Array  # int32: nodes allocated, 1 + 2 x splits


class _Split(NamedTuple):
    """The popped leaf's split (scalars; ``used`` is ``[F]`` or ``[1]``)."""

    node: jax.Array
    f: jax.Array
    b: jax.Array
    default_left: jax.Array  # bool
    l_id: jax.Array
    r_id: jax.Array
    depth: jax.Array  # the children's
    lo: jax.Array  # the children's monotone bounds ([2]: left, right)
    up: jax.Array
    used: jax.Array  # the children's used features


class _Tree:
    """Per-tree constants of a grower: sampled features, constraint masks,
    and the keys of the per-node column draws."""

    def __init__(self, cfg: GrowParams, F: int, B: int, key,
                 feature_weights):
        self.cfg, self.F, self.B = cfg, F, B
        k_sub, k_ctree, self.k_node = jax.random.split(key, 3)
        if cfg.axis_name is not None:
            # decorrelate row sampling across shards; feature sampling keys
            # stay shared (see grow.py — reference random.h:146 invariant)
            k_sub = jax.random.fold_in(k_sub,
                                       jax.lax.axis_index(cfg.axis_name))
        self.k_sub = k_sub
        if cfg.colsample_bytree < 1.0:
            self.tree_fmask = _sample_features_exact(
                k_ctree, F, cfg.colsample_bytree, feature_weights)
        else:
            self.tree_fmask = jnp.ones((F,), bool)
        self.mono = self.gmask = None
        if cfg.has_monotone:
            mono_np = np.zeros(F, np.int32)
            mono_np[: len(cfg.monotone)] = cfg.monotone[:F]
            self.mono = jnp.asarray(mono_np)
        if cfg.has_interaction:
            gmask_np = np.zeros((len(cfg.interaction), F), bool)
            for gi, grp in enumerate(cfg.interaction):
                for f in grp:
                    if f < F:
                        gmask_np[gi, f] = True
            self.gmask = jnp.asarray(gmask_np)
        self.cat_any = self.cat_oh = self.cat_part = None
        if cfg.has_categorical:
            self.cat_any, self.cat_oh, self.cat_part = cfg.cat_masks_jnp(F)

    def node_masks(self, node_ids, depths, used_rows):
        """[K, F] feature mask for a batch of nodes: hierarchical EXACT-k
        column sampling (random.h:120 — bylevel keyed by depth, bynode by
        node id, each nested in its parent set), then interaction masks."""
        cfg, F = self.cfg, self.F
        k_tree = max(1, int(round(cfg.colsample_bytree * F))) \
            if cfg.colsample_bytree < 1.0 else F
        fm = jnp.broadcast_to(self.tree_fmask[None, :],
                              (node_ids.shape[0], F))
        if cfg.colsample_bylevel < 1.0:
            k_lvl = max(1, int(round(cfg.colsample_bylevel * k_tree)))
            keys = jax.vmap(lambda dd: jax.random.fold_in(self.k_node, dd))(
                depths)
            fm = jax.vmap(lambda kk, m: exact_k_subset(kk, m, k_lvl))(keys, fm)
        else:
            k_lvl = k_tree
        if cfg.colsample_bynode < 1.0:
            k_nd = max(1, int(round(cfg.colsample_bynode * k_lvl)))
            keys = jax.vmap(lambda nid: jax.random.fold_in(
                jax.random.fold_in(self.k_node, nid), 1))(node_ids)
            fm = jax.vmap(lambda kk, m: exact_k_subset(kk, m, k_nd))(keys, fm)
        if cfg.has_interaction:
            fm = fm & interaction_allowed(used_rows, self.gmask)
        return fm

    def empty_queue(self, M: int) -> _Queue:
        cfg, F, B = self.cfg, self.F, self.B
        n_mb = M if cfg.has_monotone else 1
        n_mu = M if cfg.has_interaction else 1
        n_cs, b_cs = (M, B) if cfg.has_categorical else (1, 1)
        z = lambda dt: jnp.zeros((M,), dt)  # noqa: E731
        return _Queue(
            left=jnp.full((M,), -1, jnp.int32),
            right=jnp.full((M,), -1, jnp.int32),
            feature=z(jnp.int32), split_bin=z(jnp.int32),
            split_cond=z(jnp.float32), default_left=z(bool),
            node_g=z(jnp.float32), node_h=z(jnp.float32),
            node_w=z(jnp.float32), loss_chg=z(jnp.float32),
            depth=z(jnp.int32), cand_gain=jnp.full((M,), -jnp.inf),
            cand_dir=z(jnp.int32), cand_f=z(jnp.int32), cand_b=z(jnp.int32),
            cand_gl=z(jnp.float32), cand_hl=z(jnp.float32),
            cand_cat=jnp.zeros((n_cs, b_cs), bool),
            lo_b=jnp.full((n_mb,), -_INF), up_b=jnp.full((n_mb,), _INF),
            used=jnp.zeros((n_mu, F), bool),
            cat_set=jnp.zeros((n_cs, b_cs), bool),
            n_alloc=jnp.int32(1))

    def push(self, q: _Queue, hist, ids, G, H, depth, used, lo, up):
        """Evaluate the nodes ``ids`` (``[k]``) from their histograms
        ``[k, F, B + 1, 2]`` (missing at bin ``B``) and cache their best
        splits; ``G``, ``H`` their sums. A node at ``max_depth`` (> 0)
        gets no candidate."""
        cfg = self.cfg
        with jax.named_scope("xgb.split_eval"):
            fm = self.node_masks(ids, depth, used)
            dec = eval_splits(
                hist, G, H, cfg.split, fm, self.B, mono=self.mono,
                node_lo=lo if cfg.has_monotone else None,
                node_up=up if cfg.has_monotone else None,
                cat_feats=self.cat_oh, cat_part=self.cat_part)
            gain = dec.loss
            if cfg.max_depth > 0:
                gain = jnp.where(depth >= cfg.max_depth, -jnp.inf, gain)
            q = q._replace(
                cand_gain=q.cand_gain.at[ids].set(gain),
                cand_dir=q.cand_dir.at[ids].set(dec.dir),
                cand_f=q.cand_f.at[ids].set(dec.f),
                cand_b=q.cand_b.at[ids].set(dec.b),
                cand_gl=q.cand_gl.at[ids].set(dec.GL),
                cand_hl=q.cand_hl.at[ids].set(dec.HL))
            if cfg.has_categorical:
                q = q._replace(cand_cat=q.cand_cat.at[ids].set(dec.cat_set))
        return q, dec

    def root(self, q: _Queue, hist0, G0, H0) -> _Queue:
        """The root's sums and its cached best split."""
        zero = jnp.zeros((1,), jnp.int32)
        q, dec = self.push(q, hist0, zero, G0[None], H0[None], zero,
                           q.used[:1], q.lo_b[:1], q.up_b[:1])
        return q._replace(node_g=q.node_g.at[0].set(G0),
                          node_h=q.node_h.at[0].set(H0),
                          node_w=q.node_w.at[0].set(dec.w_node[0]))

    def pop(self, q: _Queue, cut_values):
        """Split the open leaf with the largest candidate gain (the lower
        node id on a tie: ``argmax`` takes the first): write the split and
        its two children. Returns (queue, the split)."""
        cfg, p = self.cfg, self.cfg.split
        with jax.named_scope("xgb.split_eval"):
            node = jnp.argmax(q.cand_gain).astype(jnp.int32)
            l_id = q.n_alloc
            r_id = q.n_alloc + 1
            f, b = q.cand_f[node], q.cand_b[node]
            GL, HL = q.cand_gl[node], q.cand_hl[node]
            GR, HR = q.node_g[node] - GL, q.node_h[node] - HL
            if cfg.has_monotone:
                lo, up = q.lo_b[node], q.up_b[node]
                l_lo, l_up, r_lo, r_up, wl, wr = child_bounds_and_weights(
                    p, self.mono[f], GL, HL, GR, HR, lo, up)
                lo2, up2 = jnp.stack([l_lo, r_lo]), jnp.stack([l_up, r_up])
            else:
                wl, wr = calc_weight(GL, HL, p), calc_weight(GR, HR, p)
                lo2 = up2 = None
            if cfg.has_interaction:
                used = q.used[node] | jax.nn.one_hot(f, self.F, dtype=bool)
            else:
                used = q.used[0]
            d = q.depth[node] + 1
            kids = jnp.stack([l_id, r_id])
            q = q._replace(
                left=q.left.at[node].set(l_id),
                right=q.right.at[node].set(r_id),
                feature=q.feature.at[node].set(f),
                split_bin=q.split_bin.at[node].set(b),
                split_cond=q.split_cond.at[node].set(cut_values[f, b]),
                default_left=q.default_left.at[node].set(
                    q.cand_dir[node] == 1),
                loss_chg=q.loss_chg.at[node].set(q.cand_gain[node]),
                cand_gain=q.cand_gain.at[node].set(-jnp.inf),
                node_g=q.node_g.at[kids].set(jnp.stack([GL, GR])),
                node_h=q.node_h.at[kids].set(jnp.stack([HL, HR])),
                node_w=q.node_w.at[kids].set(jnp.stack([wl, wr])),
                depth=q.depth.at[kids].set(d),
                n_alloc=q.n_alloc + 2)
            if cfg.has_monotone:
                q = q._replace(lo_b=q.lo_b.at[kids].set(lo2),
                               up_b=q.up_b.at[kids].set(up2))
            if cfg.has_interaction:
                q = q._replace(used=q.used.at[kids].set(used))
            if cfg.has_categorical:
                q = q._replace(cat_set=q.cat_set.at[node].set(
                    q.cand_cat[node]))
        split = _Split(node=node, f=f, b=b,
                       default_left=q.cand_dir[node] == 1, l_id=l_id,
                       r_id=r_id, depth=d, lo=lo2, up=up2, used=used)
        return q, split

    def push_children(self, q: _Queue, s: _Split, hist2) -> _Queue:
        """Evaluate and cache the popped leaf's two children from their
        histograms ``[2, F, B + 1, 2]``."""
        kids = jnp.stack([s.l_id, s.r_id])
        used2 = jnp.broadcast_to(s.used, (2,) + s.used.shape)
        q, _ = self.push(q, hist2, kids, q.node_g[kids], q.node_h[kids],
                         jnp.broadcast_to(s.depth, (2,)), used2, s.lo, s.up)
        return q

    @staticmethod
    def more(q: _Queue, t, max_leaves: int):
        """The loop's condition: budget left and an open leaf worth
        splitting (``driver.h``: a candidate with gain over kRtEps)."""
        return (t < max_leaves - 1) & (jnp.max(q.cand_gain) > RT_EPS)

    @staticmethod
    def tree(q: _Queue, positions) -> AllocTree:
        return AllocTree(
            left=q.left, right=q.right, feature=q.feature,
            split_bin=q.split_bin, split_cond=q.split_cond,
            default_left=q.default_left, node_g=q.node_g, node_h=q.node_h,
            node_weight=q.node_w, loss_chg=q.loss_chg, n_nodes=q.n_alloc,
            positions=positions, cat_set=q.cat_set, depth=q.depth)


# ---------------------------------------------------------------------------
# the plain reference: every row carries its leaf, a segment_sum a step
# ---------------------------------------------------------------------------


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
    M = 2 * max_leaves - 1
    bins32 = bins.astype(jnp.int32)
    tr = _Tree(cfg, F, B, key, feature_weights)
    grad, hess = apply_row_sampling(cfg, tr.k_sub, grad, hess)
    gh = jnp.stack([grad, hess], axis=-1)

    pos = jnp.zeros((n,), jnp.int32)
    if cfg.axis_name is not None:
        # per-row positions are per-shard data: mark varying so the
        # expansion loop's carry types line up under check_vma
        pos = jax.lax.pcast(pos, (cfg.axis_name,), to="varying")
    with jax.named_scope("xgb.node_hist"):
        # all rows as "left"
        h0 = blocked_histogram(bins32, gh, jnp.zeros((n,), jnp.int32), 1, MB,
                               cfg.axis_name)
    q = tr.root(tr.empty_queue(M), h0, h0[0, 0, :, 0].sum(),
                h0[0, 0, :, 1].sum())

    def body(state):
        t, q, pos = state
        q, s = tr.pop(q, cut_values)
        with jax.named_scope("xgb.node_partition"):
            at = pos == s.node
            bv = jnp.take_along_axis(bins32, jnp.full((n, 1), s.f), axis=1)[:, 0]
            present = bv <= s.b
            if cfg.has_categorical:
                # the stored category set goes RIGHT (categorical.h Decision)
                in_set = q.cand_cat[s.node][jnp.minimum(bv, B - 1)]
                present = jnp.where(tr.cat_any[s.f], ~in_set, present)
            goleft = jnp.where(bv == B, s.default_left, present)
            pos = jnp.where(at, jnp.where(goleft, s.l_id, s.r_id), pos)
        with jax.named_scope("xgb.node_hist"):
            # both children in ONE masked pass over all n rows
            seg = jnp.where(pos == s.l_id, 0, jnp.where(pos == s.r_id, 1, -1))
            hist2 = blocked_histogram(bins32, gh, seg, 2, MB, cfg.axis_name)
        return t + 1, tr.push_children(q, s, hist2), pos

    _, q, pos = jax.lax.while_loop(
        lambda st: tr.more(st[1], st[0], max_leaves), body,
        (jnp.int32(0), q, pos))
    return tr.tree(q, pos)


# ---------------------------------------------------------------------------
# the chip path: rows grouped by leaf, the smaller child's rows alone
# ---------------------------------------------------------------------------


def _eval_form(hist, G, H, count):
    """``[F, 3, B]`` kernel histogram (g, h, rows; missing excluded) ->
    eval_splits's ``[F, B + 1, 2]``, the missing bin recovered as total -
    present, and exactly zero where a feature has no missing row."""
    hg, hh = hist[:, 0, :], hist[:, 1, :]
    none = count - jnp.sum(hist[:, 2, :], axis=-1) < 0.5
    g_miss = jnp.where(none, 0.0, G - jnp.sum(hg, axis=-1))
    h_miss = jnp.where(none, 0.0, H - jnp.sum(hh, axis=-1))
    return jnp.stack([jnp.concatenate([hg, g_miss[:, None]], axis=-1),
                      jnp.concatenate([hh, h_miss[:, None]], axis=-1)],
                     axis=-1)


@partial(jax.jit, static_argnames=("cfg", "max_leaves", "n"))
def grow_tree_lossguide_rows(
    binsT: jax.Array,  # [Fp, n_pad] int32 feature-major, missing == B
    packed: jax.Array,  # [ceil(F / 3), n] int32 (node_hist.pack_bins)
    grad: jax.Array,  # [n]
    hess: jax.Array,
    cut_values: jax.Array,  # [F, B]
    key: jax.Array,
    cfg: GrowParams,
    max_leaves: int,
    n: int,
    feature_weights: Optional[jax.Array] = None,
):
    """The chip path's tree (see the module's docstring): the same
    ``AllocTree`` the reference grows, and every node's row count ``[M]``
    int32 (``node_work`` makes the rows the partitions streamed and the node
    histograms built of it). ``binsT`` and ``packed`` come from
    ``chip_bins``, once a chunk."""
    from . import node_hist as nh

    Fp, n_pad = binsT.shape
    F, B = cut_values.shape
    M = 2 * max_leaves - 1
    tr = _Tree(cfg, F, B, key, feature_weights)
    grad, hess = apply_row_sampling(cfg, tr.k_sub, grad, hess)
    with jax.named_scope("xgb.node_hist"):
        gh = jnp.pad(jnp.stack([grad, hess, jnp.ones_like(grad)]),
                     ((0, 0), (0, n_pad - n)))
        h_root = nh._node_hist_pallas(binsT, gh, F=F, B=B)  # every row
        G0, H0 = jnp.sum(grad), jnp.sum(hess)
    q = tr.root(tr.empty_queue(M), _eval_form(h_root, G0, H0, n)[None], G0,
                H0)
    # every leaf's rows: arena[half, :, start : start + count]; the lanes
    # run a chunk past the rows, room for a histogram's last chunk
    ch = nh.chunk_rows(n_pad)
    with jax.named_scope("xgb.node_partition"):
        arena = nh.build_arena(packed, grad, hess, n_pad + ch)
    half = jnp.zeros((M,), jnp.int32)
    start = jnp.zeros((M,), jnp.int32)
    count = jnp.zeros((M,), jnp.int32).at[0].set(n)
    hists = jnp.zeros((M, F, 3, B), jnp.float32).at[0].set(h_root)

    def body(state):
        t, q, pos, arena, half, start, count, hists = state
        q, s = tr.pop(q, cut_values)
        s0, c, src = start[s.node], count[s.node], half[s.node]
        with jax.named_scope("xgb.node_partition"):
            # every row's leaf, in row order: one elementwise pass over the
            # split feature's row of the bins (no gather, no scatter; both
            # [1, n], so that the pass is one fusion)
            bv = jax.lax.dynamic_slice_in_dim(binsT, s.f, 1)[:, :n]
            goleft = jnp.where(bv >= B, s.default_left, bv <= s.b)
            pos = jnp.where(pos == s.node,
                            jnp.where(goleft, s.l_id, s.r_id), pos)
            arena, n_left = nh.partition_node(
                arena, src, s0, c, s.f, s.b, s.default_left, B=B)
        n_right = c - n_left
        kids = jnp.stack([s.l_id, s.r_id])
        half = half.at[kids].set(1 - src)
        start = start.at[kids].set(jnp.stack([s0, s0 + n_left]))
        count = count.at[kids].set(jnp.stack([n_left, n_right]))
        small_left = n_left <= n_right
        built = nh.node_hist(arena, 1 - src,
                             jnp.where(small_left, s0, s0 + n_left),
                             jnp.where(small_left, n_left, n_right),
                             F=F, B=B, Fp=Fp, ch=ch)
        with jax.named_scope("xgb.node_hist"):
            derived = hists[s.node] - built
            h_l = jnp.where(small_left, built, derived)
            h_r = jnp.where(small_left, derived, built)
            hists = hists.at[kids].set(jnp.stack([h_l, h_r]))
            hist2 = jnp.stack([
                _eval_form(h_l, q.node_g[s.l_id], q.node_h[s.l_id], n_left),
                _eval_form(h_r, q.node_g[s.r_id], q.node_h[s.r_id],
                           n_right)])
        q = tr.push_children(q, s, hist2)
        return t + 1, q, pos, arena, half, start, count, hists

    _, q, pos, _, _, _, count, _ = jax.lax.while_loop(
        lambda st: tr.more(st[1], st[0], max_leaves), body,
        (jnp.int32(0), q, jnp.zeros((1, n), jnp.int32), arena, half, start,
         count, hists))
    return tr.tree(q, pos[0]), count


def node_work(left, right, rows):
    """(rows the partitions streamed, rows the node histograms built) of
    allocation-ordered trees: ``left``, ``right`` and every node's row
    count ``rows`` (``grow_tree_lossguide_rows``'s), any leading shape. A
    split streams its node's rows and builds its smaller child's; the
    root's histogram over all n is in neither. Summed in int64 on the
    host."""
    left, right = np.asarray(left), np.asarray(right)
    rows = np.asarray(rows).astype(np.int64)
    split = left >= 0
    at = lambda ids: np.take_along_axis(  # noqa: E731
        rows, np.maximum(ids, 0), axis=-1)
    smaller = np.minimum(at(left), at(right))
    return int(rows[split].sum()), int(smaller[split].sum())


def chip_bins(bins, B: int):
    """A job's narrow bins ``[n, F]`` as the chip path reads them: the
    root's histogram's and the rows' leaves' feature-major int32 ``[Fp,
    n_pad]`` (the columns padded to whole sublanes and the rows to the node
    kernel's tile with the missing bin: ``hist_kernel._feature_major``'s
    form), and the arena's bin channels, three bins packed to a word
    (``node_hist.pack_bins``, ``[ceil(F / 3), n]``)."""
    from . import hist_kernel as hk
    from . import node_hist as nh

    n, F = bins.shape
    b32 = jnp.pad(bins.astype(jnp.int32),
                  ((0, nh.padded_rows(n) - n), (0, 0)), constant_values=B)
    binsT = hk._feature_major(b32, hk._SUBLANES, B)
    return binsT, nh.pack_bins(binsT, F)[:, :n]
