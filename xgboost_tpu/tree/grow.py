"""tpu_hist: fixed-shape level-wise tree growth as one XLA program.

Reference equivalents: ``grow_quantile_histmaker``
(``src/tree/updater_quantile_hist.cc``) and ``grow_gpu_hist``
(``src/tree/updater_gpu_hist.cu``) — histogram build
(``gpu_hist/histogram.cu:127``), split evaluation
(``gpu_hist/evaluate_splits.cu:211``), row partition
(``gpu_hist/row_partitioner.cu``), monotone/interaction constraints
(``src/tree/split_evaluator.h``, ``src/tree/constraints.cc``).

TPU-first redesign (SURVEY.md §7): instead of per-node ragged row sets and
per-level host readbacks (the reference's D2H candidate copies,
``updater_gpu_hist.cu:352``), the whole tree grows inside a single
``lax.fori_loop`` over depth with static shapes:

- nodes live in an implicit heap (children of ``i`` at ``2i+1``/``2i+2``);
- each row carries its current heap position; a level-d histogram is ONE
  ``segment_sum`` scatter-add over all rows into a padded
  ``[2^(max_depth-1), F, max_bin+1, 2]`` tensor (missing values land in the
  dedicated overflow bin — the ELLPACK null-symbol trick);
- split evaluation is a vmapped cumulative scan over bins with both
  missing-direction hypotheses evaluated in parallel (the reference's
  forward/backward enumeration, ``hist/evaluate_splits.h:61``);
- partition update is a pure gather/compare (no sorting, unlike
  ``row_partitioner.cuh``).

Because a row belongs to exactly one node per level, histogramming a whole
level costs one pass over the data regardless of node count — the dense
analog of the reference's "build smaller sibling + subtract" trick. TPU
scatter-adds are deterministic, so we get the reproducibility the reference
needs fixed-point atomics for (``gpu_hist/histogram.cu:81-120``) for free.

Monotone constraints follow the reference's bound-propagation design
(split_evaluator.h): every node carries a [lower, upper] weight interval;
candidate child weights are clamped into it, sign-violating candidates are
masked, and the winning split tightens the children's intervals around the
midpoint. Interaction constraints track the path's used-feature bitmask per
node and allow a feature iff it is on the path or in a constraint group
containing the whole path (constraints.cc:58-103 SplitImpl semantics).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..collective import psum as _coll_psum
from .param import RT_EPS, SplitParams, calc_gain, calc_gain_given_weight, calc_weight

__all__ = [
    "GrowParams", "HeapTree", "SplitDecision", "grow_tree", "prune_heap",
    "leaf_value_map", "eval_splits", "child_bounds_and_weights",
    "interaction_allowed", "seq_cumsum",
]

_INF = float(np.inf)


@dataclasses.dataclass(frozen=True)
class GrowParams:
    """Static hyper-parameters baked into the compiled tree builder."""

    # NOTE: eta deliberately lives OUTSIDE this struct (applied host-side in
    # RegTree.from_heap / leaf_value_map) so a LearningRateScheduler callback
    # can change it per-round without forcing an XLA recompile.
    max_depth: int = 6
    subsample: float = 1.0
    # "uniform" | "gradient_based" (MVS, gradient_based_sampler.cu)
    sampling_method: str = "uniform"
    colsample_bytree: float = 1.0
    colsample_bylevel: float = 1.0
    colsample_bynode: float = 1.0
    split: SplitParams = SplitParams()
    # per-feature -1/0/+1 monotone directions (empty = unconstrained)
    monotone: Tuple[int, ...] = ()
    # interaction groups as tuples of feature ids (empty = unconstrained)
    interaction: Tuple[Tuple[int, ...], ...] = ()
    # feature ids treated as categorical with ONE-HOT splits (one category
    # vs rest — reference's max_cat_to_onehot regime, evaluate_splits.h)
    categorical: Tuple[int, ...] = ()
    # feature ids treated as categorical with OPTIMAL-PARTITION splits:
    # categories sorted by gradient ratio, best prefix becomes the
    # right-going set (evaluate_splits.h:61-203 partition enum, the
    # LightGBM-style scan; optimal for convex losses)
    cat_partition: Tuple[int, ...] = ()
    # name of a mesh axis to psum histograms over (None = single device).
    # This is THE distributed hook: the reference's histogram AllReduce
    # (hist/histogram.h:201, updater_gpu_hist.cu:526) becomes one psum.
    axis_name: Optional[str] = None
    # native-boundary capability states snapshotted host-side when the
    # round's config is built (native/boundary.cap_snapshot). The grow
    # program resolves its tree_grow/level_hist routes at TRACE time, so
    # the states must be part of the STATIC jit key: a mid-train degrade
    # (or recovery) changes this tuple, the builder retraces, and the
    # in-trace resolves land on the re-routed impls.
    native_caps: Tuple[Tuple[str, int], ...] = ()

    @property
    def max_nodes(self) -> int:
        return (1 << (self.max_depth + 1)) - 1

    @property
    def level_width(self) -> int:
        return 1 << max(self.max_depth - 1, 0)

    @property
    def has_monotone(self) -> bool:
        return any(c != 0 for c in self.monotone)

    @property
    def has_interaction(self) -> bool:
        return len(self.interaction) > 0

    @property
    def has_categorical(self) -> bool:
        return len(self.categorical) > 0 or len(self.cat_partition) > 0

    @property
    def has_cat_partition(self) -> bool:
        return len(self.cat_partition) > 0

    def cat_mask_np(self, n_features: int) -> np.ndarray:
        """[F] bool: any-categorical (one-hot or partition)."""
        m = np.zeros(n_features, bool)
        for f in tuple(self.categorical) + tuple(self.cat_partition):
            if f < n_features:
                m[f] = True
        return m

    def cat_partition_mask_np(self, n_features: int) -> np.ndarray:
        m = np.zeros(n_features, bool)
        for f in self.cat_partition:
            if f < n_features:
                m[f] = True
        return m

    def cat_masks_jnp(self, n_features: int):
        """(any, one-hot, partition) [F] device masks for eval_splits —
        shared by both growers so the one-hot/partition rule can't diverge.
        one-hot and partition come back as None when their set is empty."""
        any_j = jnp.asarray(self.cat_mask_np(n_features))
        onehot_np = self.cat_mask_np(n_features) & ~self.cat_partition_mask_np(n_features)
        oh_j = jnp.asarray(onehot_np) if onehot_np.any() else None
        part_j = (
            jnp.asarray(self.cat_partition_mask_np(n_features))
            if self.has_cat_partition
            else None
        )
        return any_j, oh_j, part_j


class HeapTree(NamedTuple):
    """Heap-layout tree tensors produced on device."""

    is_split: jax.Array  # bool [max_nodes]
    feature: jax.Array  # int32 [max_nodes]
    split_bin: jax.Array  # int32 [max_nodes]
    split_cond: jax.Array  # f32 [max_nodes]
    default_left: jax.Array  # bool [max_nodes]
    node_g: jax.Array  # f32 [max_nodes] sum gradient
    node_h: jax.Array  # f32 [max_nodes] sum hessian
    node_weight: jax.Array  # f32 [max_nodes] pre-eta optimal weight
    loss_chg: jax.Array  # f32 [max_nodes]
    positions: jax.Array  # int32 [n_rows] final heap position of each row
    # [max_nodes, B] right-going category set per categorical split node
    # ([1, 1] placeholder when no categorical features)
    cat_set: jax.Array


def _sample_features_exact(
    key: jax.Array,
    n_features: int,
    frac: float,
    weights: Optional[jax.Array] = None,
) -> jax.Array:
    """Exact-k without-replacement feature subset (reference:
    ColumnSampler, src/common/random.h:120). With ``weights``
    (MetaInfo.feature_weights), sampling is probability-proportional via
    the Gumbel top-k trick."""
    k = max(1, int(round(frac * n_features)))
    if weights is not None:
        g = jax.random.gumbel(key, (n_features,))
        score = jnp.log(jnp.maximum(weights, 1e-30)) + g
        top = jnp.argsort(-score)[:k]
        return jnp.zeros((n_features,), bool).at[top].set(True)
    perm = jax.random.permutation(key, n_features)
    return jnp.zeros((n_features,), bool).at[perm[:k]].set(True)


def exact_k_subset(key: jax.Array, parent: jax.Array, k: int) -> jax.Array:
    """Exactly-k random subset NESTED inside ``parent`` (last axis = F),
    via Gumbel-top-k thresholding — the reference ColumnSampler's
    hierarchical exact-k semantics (``src/common/random.h:120``), replacing
    the Bernoulli approximation (review r2 weak #8: at small F a node
    could draw zero features)."""
    score = jnp.where(parent, jax.random.uniform(key, parent.shape), -jnp.inf)
    kth = jnp.sort(score, axis=-1)[..., -k]
    return score >= kth[..., None]


def mvs_sample(key, grad, hess, subsample: float, reg_lambda: float):
    """Minimal-Variance Sampling (reference:
    ``src/tree/gpu_hist/gradient_based_sampler.cu`` — the
    ``sampling_method="gradient_based"`` path). Rows are kept with
    probability ``p_i = min(1, u_i / tau)`` where ``u_i =
    sqrt(g_i^2 + lambda * h_i^2)`` and ``tau`` is chosen so the expected
    kept count is ``subsample * n``; kept rows' gradients are rescaled by
    ``1/p_i`` so histogram sums stay unbiased. Fixed-shape: tau comes from
    a sorted-suffix-sum search, not an iterative loop."""
    n = grad.shape[0]
    u = jnp.sqrt(grad * grad + reg_lambda * hess * hess)
    # target counts only live rows (u > 0): padded/inert rows carry zero
    # gradients and must not inflate the kept fraction
    target = subsample * (u > 0.0).sum()
    us = -jnp.sort(-u)  # descending
    # candidate k: rows [0, k) get p=1; tau_k = suffix_sum(k) / (target - k)
    suffix = jnp.cumsum(us[::-1])[::-1]  # suffix[k] = sum us[k:]
    k_idx = jnp.arange(n, dtype=jnp.float32)
    denom = jnp.maximum(target - k_idx, 1e-10)
    tau_k = suffix / denom
    # valid k: us[k] <= tau_k (the first k rows really do exceed tau)
    ok = (us <= tau_k) & (k_idx < target)
    first = jnp.argmax(ok)
    tau = jnp.where(jnp.any(ok), tau_k[first], us[0] + 1.0)
    p = jnp.clip(u / jnp.maximum(tau, 1e-30), 0.0, 1.0)
    keep = jax.random.uniform(key, (n,)) < p
    scale = jnp.where(keep, 1.0 / jnp.maximum(p, 1e-30), 0.0)
    return grad * scale, hess * scale


def apply_row_sampling(cfg, key, grad, hess):
    """Dispatch uniform vs gradient-based row subsampling (both zero the
    gradients of dropped rows — reference hist semantics: unsampled rows
    keep flowing through partitions but contribute no statistics)."""
    if cfg.subsample >= 1.0:
        return grad, hess
    if cfg.sampling_method == "gradient_based":
        return mvs_sample(key, grad, hess, cfg.subsample, cfg.split.reg_lambda)
    keep = jax.random.bernoulli(key, cfg.subsample, grad.shape)
    return jnp.where(keep, grad, 0.0), jnp.where(keep, hess, 0.0)


_HIST_BUDGET = 8_000_000  # (row, feature) workspace entries per block


def blocked_histogram(
    bins32: jax.Array,  # [n, F] int32 (missing == MB-1)
    gh: jax.Array,  # [n, 2]
    seg: jax.Array,  # [n] int32 target slot per row; -1 = skip
    K: int,  # number of slots
    MB: int,  # bins incl. missing
    axis_name=None,
) -> jax.Array:
    """[K, F, MB, 2] scatter-add histogram over all (row, feature) pairs —
    the analog of the reference's histogram kernels (CPU GHistBuilder
    hist_util.h:323, GPU gpu_hist/histogram.cu:127). Scanned over feature
    blocks so peak workspace is O(n * fb) instead of O(n * F) — the
    VMEM-tiling idea of the reference's shared-memory feature groups
    (gpu_hist/feature_groups.cu). Each block is one deterministic
    segment_sum; distributed shards psum the fixed-size result
    (histogram.h:201 / updater_gpu_hist.cu:526)."""
    n, F = bins32.shape
    fb = min(F, max(1, _HIST_BUDGET // max(n, 1)))
    nb = -(-F // fb)
    Fp = nb * fb
    if Fp != F:
        # pad with all-missing feature columns; their counts land in the
        # padded features' missing bins and are sliced away below
        pad = jnp.full((n, Fp - F), MB - 1, dtype=bins32.dtype)
        bins32 = jnp.concatenate([bins32, pad], axis=1)

    def block(i):  # -> [K, fb, MB, 2] histogram of features [i*fb, (i+1)*fb)
        blk = jax.lax.dynamic_slice_in_dim(bins32, i * fb, fb, axis=1)
        sid = (
            seg[:, None] * (fb * MB)
            + jnp.arange(fb, dtype=jnp.int32)[None, :] * MB
            + blk.astype(jnp.int32)
        )
        sid = jnp.where(seg[:, None] >= 0, sid, -1)
        ghb = jnp.broadcast_to(gh[:, None, :], (n, fb, 2)).reshape(-1, 2)
        h = jax.ops.segment_sum(ghb, sid.reshape(-1), num_segments=K * fb * MB)
        return h.reshape(K, fb, MB, 2)

    if nb == 1:
        hist = block(0)
    else:
        _, hs = jax.lax.scan(lambda c, i: (c, block(i)), None, jnp.arange(nb))
        hist = jnp.transpose(hs, (1, 0, 2, 3, 4)).reshape(K, Fp, MB, 2)[:, :F]
    # the hist/histogram.h:201 AllReduce, via the collective layer's
    # traced helper (identity when axis_name is None)
    hist = _coll_psum(hist, axis_name)
    return hist


def seq_cumsum(x: jax.Array) -> jax.Array:
    """Cumulative sum over the last axis with STRICT left-to-right f32
    association (((0+x0)+x1)+...). ``jnp.cumsum`` lowers to a
    reduce_window whose float association is backend-dependent; the
    native ``tree_grow`` kernel replicates split evaluation bit-for-bit,
    which requires an association a sequential C loop can reproduce."""
    xm = jnp.moveaxis(x, -1, 0)

    def step(c, v):
        c2 = c + v
        return c2, c2

    _, ys = jax.lax.scan(step, jnp.zeros(xm.shape[1:], x.dtype), xm)
    return jnp.moveaxis(ys, 0, -1)


class SplitDecision(NamedTuple):
    """Best split per node row (all [K])."""

    loss: jax.Array  # loss_chg of the winner (-inf if none valid)
    dir: jax.Array  # 1 = missing goes left
    f: jax.Array
    b: jax.Array
    GL: jax.Array  # left-child stats of the winner (missing included per dir)
    HL: jax.Array
    w_node: jax.Array  # (bound-clamped) node weight
    # [K, B] right-going category set of the winner (all-False for
    # numerical winners); only materialized when categorical features exist
    cat_set: Optional[jax.Array] = None


def eval_splits(
    hist: jax.Array,  # [K, F, MB, 2]
    Gtot: jax.Array,  # [K]
    Htot: jax.Array,
    p: SplitParams,
    node_fmask: jax.Array,  # [K, F] allowed features per node
    B: int,
    mono: Optional[jax.Array] = None,  # [F] -1/0/+1
    node_lo: Optional[jax.Array] = None,  # [K] weight bounds
    node_up: Optional[jax.Array] = None,
    cat_feats: Optional[jax.Array] = None,  # [F] bool: one-hot categorical
    cat_part: Optional[jax.Array] = None,  # [F] bool: partition categorical
) -> SplitDecision:
    """The ONE split evaluator (used by both depthwise and lossguide growers
    — the reference keeps a single HistEvaluator for the same reason,
    hist/evaluate_splits.h:26). Scans cumulative G/H over bins for both
    missing-direction hypotheses, applies min_child_weight / feature masks /
    monotone bound clamping, and argmaxes loss_chg per node.

    Categorical candidates (matching the reference's split enum,
    evaluate_splits.h:61-203; stored sets go RIGHT per categorical.h
    Decision): one-hot features score "category b right vs rest left";
    partition features sort categories by gradient ratio and score every
    prefix of the sorted order as the right-going set."""
    K, F = hist.shape[0], hist.shape[1]
    g_b, h_b = hist[:, :, :B, 0], hist[:, :, :B, 1]
    g_miss, h_miss = hist[:, :, B, 0], hist[:, :, B, 1]
    GL = seq_cumsum(g_b)
    HL = seq_cumsum(h_b)
    # dir 0: missing goes right (default_left=False); dir 1: missing left
    GLd = jnp.stack([GL, GL + g_miss[..., None]], axis=1)  # [K, 2, F, B]
    HLd = jnp.stack([HL, HL + h_miss[..., None]], axis=1)
    Gp, Hp = GL[..., -1:], HL[..., -1:]  # present-value totals
    # the categorical candidates under their own scope: a profile books
    # the sort and the prefix scan apart from the numerical evaluation
    if cat_feats is not None:
        with jax.named_scope("xgb.cat_split"):
            # one-hot: left = all-but-category-b (+ missing when
            # default-left)
            GLc = jnp.stack([Gp - g_b, Gp - g_b + g_miss[..., None]], axis=1)
            HLc = jnp.stack([Hp - h_b, Hp - h_b + h_miss[..., None]], axis=1)
            sel = cat_feats[None, None, :, None]
            GLd = jnp.where(sel, GLc, GLd)
            HLd = jnp.where(sel, HLc, HLd)
    inv_order = None
    if cat_part is not None:
        with jax.named_scope("xgb.cat_split"):
            # partition: sort categories by g/(h+lambda); candidate j =
            # first j+1 sorted categories form the RIGHT side
            present = (h_b > 0.0) | (g_b != 0.0)
            ratio = jnp.where(present, g_b / (h_b + p.reg_lambda), jnp.inf)
            order = jnp.argsort(ratio, axis=-1)  # [K, F, B]
            inv_order = jnp.argsort(order, axis=-1)  # rank of each bin
            g_s = jnp.take_along_axis(g_b, order, axis=-1)
            h_s = jnp.take_along_axis(h_b, order, axis=-1)
            GRs = jnp.cumsum(g_s, axis=-1)  # right side = sorted prefix
            HRs = jnp.cumsum(h_s, axis=-1)
            GLp = jnp.stack([Gp - GRs, Gp - GRs + g_miss[..., None]], axis=1)
            HLp = jnp.stack([Hp - HRs, Hp - HRs + h_miss[..., None]], axis=1)
            sel = cat_part[None, None, :, None]
            GLd = jnp.where(sel, GLp, GLd)
            HLd = jnp.where(sel, HLp, HLd)
    GRd = Gtot[:, None, None, None] - GLd
    HRd = Htot[:, None, None, None] - HLd

    if mono is not None:
        blo = node_lo[:, None, None, None]
        bup = node_up[:, None, None, None]
        wl = jnp.clip(calc_weight(GLd, HLd, p), blo, bup)
        wr = jnp.clip(calc_weight(GRd, HRd, p), blo, bup)
        gain = calc_gain_given_weight(GLd, HLd, wl, p) + calc_gain_given_weight(GRd, HRd, wr, p)
        w_node = jnp.clip(calc_weight(Gtot, Htot, p), node_lo, node_up)
        parent_gain = calc_gain_given_weight(Gtot, Htot, w_node, p)
        c = mono[None, None, :, None]
        mono_ok = ~(((c > 0) & (wl > wr)) | ((c < 0) & (wl < wr)))
    else:
        gain = calc_gain(GLd, HLd, p) + calc_gain(GRd, HRd, p)
        w_node = calc_weight(Gtot, Htot, p)
        parent_gain = calc_gain(Gtot, Htot, p)
    chg = gain - parent_gain[:, None, None, None]

    valid = (HLd >= p.min_child_weight) & (HRd >= p.min_child_weight)
    if mono is not None:
        valid = valid & mono_ok
    valid = valid & node_fmask[:, None, :, None]

    score = jnp.where(valid, chg, -jnp.inf)
    flat = score.reshape(K, -1)
    best_idx = jnp.argmax(flat, axis=-1)
    best_loss = jnp.take_along_axis(flat, best_idx[:, None], axis=1)[:, 0]
    FB = F * B
    pick = lambda a: jnp.take_along_axis(a.reshape(K, -1), best_idx[:, None], axis=1)[:, 0]
    best_f = ((best_idx % FB) // B).astype(jnp.int32)
    best_b = ((best_idx % FB) % B).astype(jnp.int32)

    cat_set = None
    if cat_feats is not None or cat_part is not None:
        iota_b = jnp.arange(B)
        cat_set = jnp.zeros((K, B), bool)
        if cat_feats is not None:  # one-hot winner: single-category set
            oh = iota_b[None, :] == best_b[:, None]
            cat_set = jnp.where(cat_feats[best_f][:, None], oh, cat_set)
        if cat_part is not None:  # partition winner: sorted prefix
            inv_f = jnp.take_along_axis(
                inv_order, best_f[:, None, None], axis=1
            )[:, 0, :]  # [K, B] rank of each bin under the winner feature
            pref = inv_f <= best_b[:, None]
            cat_set = jnp.where(cat_part[best_f][:, None], pref, cat_set)

    return SplitDecision(
        loss=best_loss,
        dir=(best_idx // FB).astype(jnp.int32),
        f=best_f,
        b=best_b,
        GL=pick(GLd),
        HL=pick(HLd),
        w_node=w_node,
        cat_set=cat_set,
    )


def child_bounds_and_weights(
    p: SplitParams,
    mono_f: jax.Array,  # [K] constraint sign of the winning feature
    GLb, HLb, GRb, HRb,
    node_lo, node_up,  # [K]
):
    """Monotone bound propagation for the two children (split_evaluator.h):
    tighten around the midpoint of the clamped child weights."""
    wl_b = jnp.clip(calc_weight(GLb, HLb, p), node_lo, node_up)
    wr_b = jnp.clip(calc_weight(GRb, HRb, p), node_lo, node_up)
    mid = 0.5 * (wl_b + wr_b)
    l_lo = jnp.where(mono_f < 0, jnp.maximum(node_lo, mid), node_lo)
    l_up = jnp.where(mono_f > 0, jnp.minimum(node_up, mid), node_up)
    r_lo = jnp.where(mono_f > 0, jnp.maximum(node_lo, mid), node_lo)
    r_up = jnp.where(mono_f < 0, jnp.minimum(node_up, mid), node_up)
    wl_c = jnp.clip(wl_b, l_lo, l_up)
    wr_c = jnp.clip(wr_b, r_lo, r_up)
    return l_lo, l_up, r_lo, r_up, wl_c, wr_c


def interaction_allowed(used: jax.Array, gmask: jax.Array) -> jax.Array:
    """[K, F] allowed mask from per-node used-feature bitmasks and [G, F]
    group masks (constraints.cc:58 SplitImpl semantics: allowed = path
    features ∪ groups containing the whole path; all features at the root)."""
    any_used = used.any(axis=1, keepdims=True)
    relevant = ~jnp.any(used[:, None, :] & ~gmask[None, :, :], axis=-1)  # [K, G]
    from_groups = jnp.any(relevant[:, :, None] & gmask[None, :, :], axis=1)
    return jnp.where(any_used, used | from_groups, jnp.ones_like(used))


def grow_tree(
    bins: jax.Array,  # [n, F] narrow int bin ids (missing == max_bin)
    grad: jax.Array,  # [n] f32
    hess: jax.Array,  # [n] f32
    cut_values: jax.Array,  # [F, max_bin] f32
    key: jax.Array,
    cfg: GrowParams,
    feature_weights: Optional[jax.Array] = None,  # [F] sampling weights
) -> HeapTree:
    """Host entry point: times the compiled dispatch as a ``grow_tree``
    span (hist build + split eval + partition for the whole tree). When
    invoked during program staging (inside ``shard_map``/``scan`` tracing,
    e.g. ``parallel.grow``) the span layer suppresses itself — telemetry
    stays host-side only."""
    from ..observability import trace

    with trace.span("grow_tree", depth=cfg.max_depth,
                    features=int(bins.shape[1])):
        return _grow_tree_impl(bins, grad, hess, cut_values, key, cfg,
                               feature_weights)


@partial(jax.jit, static_argnames=("cfg",))
def _grow_tree_impl(
    bins: jax.Array,
    grad: jax.Array,
    hess: jax.Array,
    cut_values: jax.Array,
    key: jax.Array,
    cfg: GrowParams,
    feature_weights: Optional[jax.Array] = None,
) -> HeapTree:
    n, F = bins.shape
    B = cut_values.shape[1]
    MB = B + 1  # +1 missing/overflow bin
    p = cfg.split
    max_depth = cfg.max_depth
    Nmax = cfg.level_width
    max_nodes = cfg.max_nodes
    bins32 = bins.astype(jnp.int32)

    k_sub, k_ctree, k_level = jax.random.split(key, 3)
    if cfg.axis_name is not None:
        # distributed: decorrelate ROW sampling across shards (each shard
        # holds different rows) while keeping FEATURE sampling identical on
        # every shard — the invariant the reference maintains by
        # broadcasting the column-sampler seed (src/common/random.h:146)
        k_sub = jax.random.fold_in(k_sub, jax.lax.axis_index(cfg.axis_name))

    # ---- row subsampling (uniform or MVS gradient-based) ----
    grad, hess = apply_row_sampling(cfg, k_sub, grad, hess)

    # ---- hierarchical column sampling ----
    if cfg.colsample_bytree < 1.0:
        tree_mask = _sample_features_exact(k_ctree, F, cfg.colsample_bytree, feature_weights)
    else:
        tree_mask = jnp.ones((F,), bool)

    # ---- constraint constants ----
    if cfg.has_monotone:
        mono = np.zeros(F, np.int32)
        mono[: len(cfg.monotone)] = cfg.monotone[:F]
        mono_j = jnp.asarray(mono)
    if cfg.has_interaction:
        gmask_np = np.zeros((len(cfg.interaction), F), bool)
        for gi, grp in enumerate(cfg.interaction):
            for f in grp:
                if f < F:
                    gmask_np[gi, f] = True
        gmask = jnp.asarray(gmask_np)  # [G, F]
    cat_j = None
    catp_j = None
    cat_any_j = None
    if cfg.has_categorical:
        cat_any_j, cat_j, catp_j = cfg.cat_masks_jnp(F)

    gh = jnp.stack([grad, hess], axis=-1)  # [n, 2]

    def body(d: jax.Array, state):
        (pos, is_split, feature, split_bin, split_cond, default_left,
         node_g, node_h, node_w, loss_chg, lo_b, up_b, used, cat_set_st) = state

        offset = (1 << d) - 1  # first heap id of this level
        width = 1 << d  # real nodes at this level (<= Nmax)
        local = pos - offset
        level_active = (local >= 0) & (local < width)

        # ---- histogram: scatter-add over all (row, feature) pairs, scanned
        # over feature blocks; under a mesh the fixed-size result is psum'd
        # (the one collective of the hot loop, cost independent of rows) ----
        seg = jnp.where(level_active, local, -1)
        hist = blocked_histogram(bins32, gh, seg, Nmax, MB, cfg.axis_name)

        # node totals: every row hits exactly one bin of feature 0
        Gtot = hist[:, 0, :, 0].sum(-1)  # [Nmax]
        Htot = hist[:, 0, :, 1].sum(-1)

        slots = offset + jnp.arange(Nmax)
        slot_real = jnp.arange(Nmax) < width
        widx = jnp.where(slot_real, slots, max_nodes)  # OOB -> dropped
        node_lo = lo_b[widx.clip(0, max_nodes - 1)]  # [Nmax] per-node bounds
        node_up = up_b[widx.clip(0, max_nodes - 1)]

        # ---- per-node feature masks: hierarchical EXACT-k column sampling
        # (random.h:120) + interaction constraints ----
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
                kn, jnp.broadcast_to(fmask[None, :], (Nmax, F)), k_nd
            )
        else:
            node_fmask = jnp.broadcast_to(fmask[None, :], (Nmax, F))
        if cfg.has_interaction:
            node_used = used[widx.clip(0, max_nodes - 1)]  # [Nmax, F]
            node_fmask = node_fmask & interaction_allowed(node_used, gmask)

        # ---- split evaluation (shared evaluator) ----
        dec = eval_splits(
            hist, Gtot, Htot, p, node_fmask, B,
            mono=mono_j if cfg.has_monotone else None,
            node_lo=node_lo if cfg.has_monotone else None,
            node_up=node_up if cfg.has_monotone else None,
            cat_feats=cat_j,
            cat_part=catp_j,
        )
        best_loss, best_dir, best_f, best_b = dec.loss, dec.dir, dec.f, dec.b
        w_node = dec.w_node

        can_split = (best_loss > RT_EPS) & (Htot > 0.0) & slot_real

        GLb, HLb = dec.GL, dec.HL
        GRb, HRb = Gtot - GLb, Htot - HLb

        cond = cut_values[best_f, best_b]  # [Nmax]

        # ---- write this level's nodes into the heap arrays ----
        is_split = is_split.at[widx].set(can_split, mode="drop")
        feature = feature.at[widx].set(best_f, mode="drop")
        split_bin = split_bin.at[widx].set(best_b, mode="drop")
        split_cond = split_cond.at[widx].set(cond, mode="drop")
        default_left = default_left.at[widx].set(best_dir == 1, mode="drop")
        node_g = node_g.at[widx].set(Gtot, mode="drop")
        node_h = node_h.at[widx].set(Htot, mode="drop")
        node_w = node_w.at[widx].set(w_node, mode="drop")
        loss_chg = loss_chg.at[widx].set(jnp.where(can_split, best_loss, 0.0), mode="drop")
        if cfg.has_categorical:
            cat_set_st = cat_set_st.at[widx].set(dec.cat_set, mode="drop")

        # children weights/bounds for the next level
        if cfg.has_monotone:
            l_lo, l_up, r_lo, r_up, wl_c, wr_c = child_bounds_and_weights(
                p, mono_j[best_f], GLb, HLb, GRb, HRb, node_lo, node_up
            )
        else:
            wl_c = calc_weight(GLb, HLb, p)
            wr_c = calc_weight(GRb, HRb, p)

        # pre-write children stats/weights — the only way depth-max leaves
        # (never histogrammed) get their values; inner nodes are refreshed
        # from their own histogram next iteration
        lidx = jnp.where(can_split, 2 * slots + 1, max_nodes)
        ridx = jnp.where(can_split, 2 * slots + 2, max_nodes)
        node_g = node_g.at[lidx].set(GLb, mode="drop").at[ridx].set(GRb, mode="drop")
        node_h = node_h.at[lidx].set(HLb, mode="drop").at[ridx].set(HRb, mode="drop")
        node_w = node_w.at[lidx].set(wl_c, mode="drop").at[ridx].set(wr_c, mode="drop")
        if cfg.has_monotone:
            lo_b = lo_b.at[lidx].set(l_lo, mode="drop").at[ridx].set(r_lo, mode="drop")
            up_b = up_b.at[lidx].set(l_up, mode="drop").at[ridx].set(r_up, mode="drop")
        if cfg.has_interaction:
            child_used = used[widx.clip(0, max_nodes - 1)] | jax.nn.one_hot(
                best_f, F, dtype=bool
            )
            used = used.at[lidx].set(child_used, mode="drop")
            used = used.at[ridx].set(child_used, mode="drop")

        # ---- partition: route rows of split nodes to their children ----
        goes = is_split[pos]
        f_of = feature[pos]
        b_of = split_bin[pos]
        dl_of = default_left[pos]
        bv = jnp.take_along_axis(bins32, f_of[:, None], axis=1)[:, 0]
        missing = bv == B
        present_goleft = bv <= b_of
        if cfg.has_categorical:
            # categorical (one-hot or partition): the stored set goes RIGHT
            in_set = cat_set_st[pos, jnp.minimum(bv, B - 1)]
            present_goleft = jnp.where(cat_any_j[f_of], ~in_set, present_goleft)
        goleft = jnp.where(missing, dl_of, present_goleft)
        pos = jnp.where(goes, jnp.where(goleft, 2 * pos + 1, 2 * pos + 2), pos)

        return (pos, is_split, feature, split_bin, split_cond, default_left,
                node_g, node_h, node_w, loss_chg, lo_b, up_b, used, cat_set_st)

    # constraint state tensors are 1-element dummies when unused, so the
    # compiled program carries no overhead for the common case
    n_b = max_nodes if cfg.has_monotone else 1
    n_u = max_nodes if cfg.has_interaction else 1
    n_cs, b_cs = (max_nodes, B) if cfg.has_categorical else (1, 1)
    pos0 = jnp.zeros((n,), jnp.int32)
    if cfg.axis_name is not None:
        # per-row positions are per-shard data: mark them varying up front
        # so the loop carry types match under shard_map's check_vma
        # (everything else in the carry stays provably replicated — the
        # histogram psum restores invariance each level)
        pos0 = jax.lax.pcast(pos0, (cfg.axis_name,), to="varying")
    init = (
        pos0,
        jnp.zeros((max_nodes,), bool),
        jnp.zeros((max_nodes,), jnp.int32),
        jnp.zeros((max_nodes,), jnp.int32),
        jnp.zeros((max_nodes,), jnp.float32),
        jnp.zeros((max_nodes,), bool),
        jnp.zeros((max_nodes,), jnp.float32),
        jnp.zeros((max_nodes,), jnp.float32),
        jnp.zeros((max_nodes,), jnp.float32),
        jnp.zeros((max_nodes,), jnp.float32),
        jnp.full((n_b,), -_INF),
        jnp.full((n_b,), _INF),
        jnp.zeros((n_u, F), bool),
        jnp.zeros((n_cs, b_cs), bool),
    )
    if max_depth == 0:
        state = init
        # single leaf: weight from global sums
        G, H = grad.sum(), hess.sum()
        G = _coll_psum(G, cfg.axis_name)
        H = _coll_psum(H, cfg.axis_name)
        state = (
            state[0], state[1], state[2], state[3], state[4], state[5],
            state[6].at[0].set(G), state[7].at[0].set(H),
            state[8].at[0].set(calc_weight(G, H, p)), state[9],
            state[10], state[11], state[12], state[13],
        )
    else:
        state = jax.lax.fori_loop(0, max_depth, body, init)

    (pos, is_split, feature, split_bin, split_cond, default_left,
     node_g, node_h, node_w, loss_chg, _, _, _, cat_set_st) = state
    return HeapTree(
        is_split=is_split, feature=feature, split_bin=split_bin,
        split_cond=split_cond, default_left=default_left,
        node_g=node_g, node_h=node_h, node_weight=node_w,
        loss_chg=loss_chg, positions=pos, cat_set=cat_set_st,
    )


def prune_heap(is_split: np.ndarray, loss_chg: np.ndarray, min_split_loss: float) -> np.ndarray:
    """Recursive bottom-up gamma pruning (reference: ``updater_prune.cc`` —
    chained after every grower; collapses split nodes whose children are
    leaves and whose loss_chg < gamma)."""
    out = is_split.copy()
    if min_split_loss <= 0.0:
        return out
    n = len(out)
    changed = True
    while changed:
        changed = False
        for i in range(n - 1, -1, -1):
            if not out[i]:
                continue
            l, r = 2 * i + 1, 2 * i + 2
            l_leaf = l >= n or not out[l]
            r_leaf = r >= n or not out[r]
            if l_leaf and r_leaf and loss_chg[i] < min_split_loss:
                out[i] = False
                changed = True
    return out


def leaf_value_map(
    pruned_is_split: np.ndarray, weight: np.ndarray, eta: float
) -> np.ndarray:
    """Map every heap node to the leaf value governing it in the (pruned)
    tree, so the prediction cache can be updated with one gather on the
    rows' final positions (reference: UpdatePredictionCache fast path,
    ``gbtree.cc:219`` / ``updater_quantile_hist.cc``)."""
    n = len(pruned_is_split)
    vals = np.full(n, np.nan, np.float32)
    if not pruned_is_split[0]:
        vals[:] = eta * weight[0]
        return vals
    for h in range(1, n):
        parent = (h - 1) // 2
        if not np.isnan(vals[parent]):
            vals[h] = vals[parent]  # below a leaf: inherit
        elif not pruned_is_split[h]:
            vals[h] = eta * weight[h]  # this node is a leaf
    return vals
