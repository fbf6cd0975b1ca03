"""AUC / AUC-PR (reference: ``src/metric/auc.{cc,cu,h}`` — binary ROC,
multiclass one-vs-rest, ranking group-mean; GPU via segmented scans).

TPU design: exact tie handling without ragged blocks — sort by score, build
tie-block segment ids from score boundaries, and compute
P(s_pos > s_neg) + 0.5 P(=) with weighted block sums via ``segment_sum``.
One fixed-shape program; deterministic.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..registry import METRICS
from .base import Metric, dist_reduce


def _dist_mean(local: float, local_w: float) -> float:
    """Weighted mean of per-process values (the reference's distributed
    AUC: each worker contributes (auc * w, w) to one Allreduce,
    auc.cc:293). NaN-weight-0 locals drop out; identity single-process."""
    if np.isnan(local):
        local, local_w = 0.0, 0.0
    s, w = dist_reduce(local * local_w, local_w)
    return s / w if w > 0 else float("nan")


# the scope goes UNDER the jit: one opened round a jitted call from outside
# does not enter its program, and a device profile would not name these ops
@jax.jit
@jax.named_scope("xgb.eval_metric")
def _binary_auc(score: jax.Array, label: jax.Array, weight: jax.Array) -> jax.Array:
    n = score.shape[0]
    order = jnp.argsort(score)
    s = score[order]
    y = label[order]
    w = weight[order]
    wp = w * y
    wn = w * (1.0 - y)
    # tie blocks
    newblk = jnp.concatenate([jnp.ones((1,), bool), s[1:] != s[:-1]])
    seg = jnp.cumsum(newblk) - 1  # [n] block id
    blk_wn = jax.ops.segment_sum(wn, seg, num_segments=n)  # padded with zeros
    cum_blk_wn = jnp.concatenate([jnp.zeros((1,)), jnp.cumsum(blk_wn)[:-1]])
    below = cum_blk_wn[seg]  # neg weight strictly below this block
    tied = blk_wn[seg]
    num = (wp * (below + 0.5 * tied)).sum()
    Wp, Wn = wp.sum(), wn.sum()
    return jnp.where((Wp > 0) & (Wn > 0), num / jnp.maximum(Wp * Wn, 1e-30), jnp.nan)


@partial(jax.jit, static_argnames=("n_groups",))
@jax.named_scope("xgb.eval_metric")
def _grouped_auc(score, label, weight, group_of, n_groups):
    """Per-group binary AUCs, averaged over groups that have both classes —
    segmented version of ``_binary_auc`` (one lexsort + segment_sums; the
    reference's GPU path, auc.cu, structures it the same way)."""
    n = score.shape[0]
    order = jnp.lexsort((score, group_of))
    g = group_of[order]
    s = score[order]
    y = label[order]
    w = weight[order]
    wp = w * y
    wn = w * (1.0 - y)
    newblk = jnp.concatenate(
        [jnp.ones((1,), bool), (s[1:] != s[:-1]) | (g[1:] != g[:-1])]
    )
    seg = jnp.cumsum(newblk) - 1
    blk_wn = jax.ops.segment_sum(wn, seg, num_segments=n)
    cum_blk = jnp.concatenate([jnp.zeros((1,)), jnp.cumsum(blk_wn)[:-1]])[seg]
    Wn_g = jax.ops.segment_sum(wn, g, num_segments=n_groups)
    grp_before = jnp.concatenate(
        [jnp.zeros((1,)), jnp.cumsum(Wn_g)[:-1]]
    )[g]
    below = cum_blk - grp_before  # negative weight strictly below, in-group
    tied = blk_wn[seg]
    num_g = jax.ops.segment_sum(wp * (below + 0.5 * tied), g,
                                num_segments=n_groups)
    Wp_g = jax.ops.segment_sum(wp, g, num_segments=n_groups)
    valid = (Wp_g > 0) & (Wn_g > 0)
    auc_g = num_g / jnp.maximum(Wp_g * Wn_g, 1e-30)
    cnt = valid.sum()
    # (sum over valid groups, valid count): the caller divides — and the
    # distributed reduction must weight by VALID groups, not all groups
    return jnp.where(valid, auc_g, 0.0).sum(), cnt


@METRICS.register("auc")
class AUC(Metric):
    name = "auc"
    maximize = True

    def evaluate(self, preds, label, weight=None, group_ptr=None, **kw):
        preds = jnp.asarray(preds)
        label_j = jnp.asarray(label, dtype=jnp.float32)
        n = label_j.shape[0]
        w = (
            jnp.asarray(weight, jnp.float32)
            if weight is not None and np.size(weight) == n
            else jnp.ones((n,), jnp.float32)
        )
        if preds.ndim == 2 and preds.shape[1] > 1:
            # multiclass: weighted one-vs-rest average (auc.cc:385)
            aucs = []
            for k in range(preds.shape[1]):
                aucs.append(float(_binary_auc(preds[:, k], (label_j == k).astype(jnp.float32), w)))
            return _dist_mean(float(np.mean(aucs)), float(w.sum()))
        if preds.ndim == 2:
            preds = preds[:, 0]
        if group_ptr is not None and len(group_ptr) > 2:
            # ranking: mean of per-group AUCs in ONE segmented program
            # (auc.cc:262-313 / auc.cu segmented scans) — no per-group
            # device calls
            sizes = np.diff(np.asarray(group_ptr)).astype(np.int64)
            group_of = np.repeat(np.arange(len(sizes), dtype=np.int32), sizes)
            auc_sum, cnt = _grouped_auc(
                preds, (label_j > 0).astype(jnp.float32), w,
                jnp.asarray(group_of), len(sizes))
            s, c = dist_reduce(float(auc_sum), float(cnt))
            return s / c if c > 0 else float("nan")
        return _dist_mean(float(_binary_auc(preds, label_j, w)),
                          float(w.sum()))


@METRICS.register("aucpr")
class AUCPR(Metric):
    name = "aucpr"
    maximize = True

    def evaluate(self, preds, label, weight=None, **kw):
        p = np.asarray(preds, dtype=np.float64).reshape(-1)
        y = np.asarray(label, dtype=np.float64)
        n = len(y)
        w = (
            np.asarray(weight, np.float64)
            if weight is not None and np.size(weight) == n
            else np.ones(n)
        )
        local = self._local_aucpr(p, y, w)
        # distributed: weighted mean of per-process local curves, invalid
        # shards contributing (0, 0) — the reference's pair allreduce
        # (auc.cc:115 Allreduce<Sum> over (auc * weight, weight))
        if local != local:
            s, c = dist_reduce(0.0, 0.0)
        else:
            s, c = dist_reduce(local * float(w.sum()), float(w.sum()))
        return s / c if c > 0 else float("nan")

    @staticmethod
    def _local_aucpr(p, y, w) -> float:
        order = np.argsort(-p, kind="stable")
        y, w, p = y[order], w[order], p[order]
        if len(y) == 0:
            return float("nan")
        tp = np.cumsum(w * y)
        fp = np.cumsum(w * (1 - y))
        total_pos = tp[-1]
        if total_pos <= 0:
            return float("nan")
        # evaluate only at tie-block ends
        ends = np.append(p[1:] != p[:-1], True)
        tp_e, fp_e = tp[ends], fp[ends]
        recall = tp_e / total_pos
        precision = tp_e / np.maximum(tp_e + fp_e, 1e-30)
        prev_r = np.concatenate([[0.0], recall[:-1]])
        return float(np.sum((recall - prev_r) * precision))
