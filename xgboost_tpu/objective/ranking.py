"""LambdaMART ranking objectives (reference: ``src/objective/rank_obj.cu`` —
``rank:pairwise``/``rank:ndcg``/``rank:map`` registered at :950-958).

TPU-first design, two regimes:

- small groups: pad each query group to ``max_group_size`` and compute ALL
  pairwise lambdas inside a masked [G, S, S] tensor — MXU-friendly,
  equivalent to the reference with ``num_pairsample -> inf``.
- large groups (MSLR-WEB30K-class, 1000+ docs/query): the cubic tensor is
  hundreds of GB, so pairs are SAMPLED the way the reference's
  ``rank_obj.cu:143-198`` segmented sampler does — every document draws
  ``lambdarank_num_pair_per_sample`` opponents uniformly from its group
  (mismatched labels kept), ranks come from one global sort instead of
  padding, and both pair ends receive their lambda. Peak memory is
  O(n * num_pair), independent of group size.

What depends on labels and groups only (group ids and bounds, gains, IDCG,
opponent counts, the discount table) is a ``RankLayout``, built once a
``DMatrix`` and kept on the device: a round's gradient is one device program
of the margin, the layout and a key (``_lambda_grad_sampled`` states the
sampler).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis.retrace import guard_jit
from ..observability import REGISTRY as _REGISTRY, trace as _trace
from ..registry import OBJECTIVES
from .base import ObjFunction, Task


def _pad_groups(group_ptr: np.ndarray) -> Tuple[np.ndarray, int]:
    sizes = np.diff(group_ptr)
    max_size = int(sizes.max(initial=1))
    return sizes, max_size


def _map_pair_delta(gather, hits, acc1, acc2, acc3, a, b, lab_a, lab_b,
                    total):
    """|delta AP| of swapping the docs at sorted positions ``a < b``
    (rank_obj.cu:436 GetLambdaMAP), shared by the padded and sampled paths;
    ``gather(arr, idx)`` resolves a (possibly local) position index into the
    caller's stats layout, returning 0 for idx == -1 (exclusive prefix)."""
    original = gather(acc1, b) - gather(acc1, a - 1)
    up = gather(acc3, b - 1) - gather(acc3, a) \
        + (gather(hits, a) + 1.0) / (a + 1.0)
    down = gather(acc2, b - 1) - gather(acc2, a) \
        + gather(hits, b) / (b + 1.0)
    changed = jnp.where(lab_a < lab_b, up, down)
    delta = jnp.abs(changed - original) / jnp.maximum(total, 1.0)
    return jnp.where((lab_a != lab_b) & (a != b) & (total > 0), delta, 0.0)


@partial(jax.jit, static_argnames=("n_groups", "max_size", "scheme"))
def _lambda_grad(
    margin: jax.Array,  # [n]
    label: jax.Array,  # [n]
    group_of: jax.Array,  # [n] int32
    rank_in_group: jax.Array,  # [n] int32
    n_groups: int,
    max_size: int,
    scheme: str,
) -> Tuple[jax.Array, jax.Array]:
    n = margin.shape[0]
    # scatter rows into padded [G, S] layout
    flat = group_of * max_size + rank_in_group
    S = n_groups * max_size
    pad_margin = jnp.zeros((S,), margin.dtype).at[flat].set(margin).reshape(n_groups, max_size)
    pad_label = jnp.zeros((S,), label.dtype).at[flat].set(label).reshape(n_groups, max_size)
    pad_valid = jnp.zeros((S,), bool).at[flat].set(True).reshape(n_groups, max_size)

    def per_group(m, y, v):
        # all-pairs lambdas within one (padded) group
        diff_label = y[:, None] - y[None, :]  # >0 where i should rank above j
        pair = (diff_label > 0) & v[:, None] & v[None, :]
        s_diff = m[:, None] - m[None, :]
        # RankNet lambda: sigmoid(-(si - sj)) for positive pairs
        rho = jax.nn.sigmoid(-s_diff)
        # the reference samples each doc's opponents uniformly among
        # DIFFERENT-label docs from both pair ends (rank_obj.cu:97-127,
        # scale 1/num_pairsample); its expectation gives every unordered
        # pair the weight 1/n_opp(i) + 1/n_opp(j) — the all-pairs path
        # applies that expectation exactly
        vf = v.astype(m.dtype)
        vcount = vf.sum()
        same_cnt = ((y[:, None] == y[None, :]) & v[:, None]
                    & v[None, :]).astype(m.dtype).sum(axis=1)
        opp = jnp.maximum(vcount - same_cnt, 1.0)
        end_w = jnp.where(v, 1.0 / opp, 0.0)
        samp_w = end_w[:, None] + end_w[None, :]  # [S, S]
        if scheme == "ndcg":
            # delta-NDCG weighting: |gain_i - gain_j| * |1/log2(ri+2) - 1/log2(rj+2)| / IDCG
            order = jnp.argsort(-jnp.where(v, m, -jnp.inf))
            ranks = jnp.zeros_like(order).at[order].set(jnp.arange(max_size))
            gains = (2.0 ** y - 1.0)
            discounts = 1.0 / jnp.log2(ranks.astype(m.dtype) + 2.0)
            ideal_order = jnp.sort(jnp.where(v, gains, 0.0))[::-1]
            idcg = (ideal_order / jnp.log2(jnp.arange(max_size, dtype=m.dtype) + 2.0)).sum()
            idcg = jnp.maximum(idcg, 1e-10)
            delta = (
                jnp.abs(gains[:, None] - gains[None, :])
                * jnp.abs(discounts[:, None] - discounts[None, :])
                / idcg
            )
            w_pair = jnp.where(pair, delta, 0.0)
        elif scheme == "map":
            # true MAP delta weights (rank_obj.cu:378 MAPLambdaWeightComputer):
            # prefix stats over the prediction-sorted list — ap_acc,
            # ap_acc_miss (a positive removed), ap_acc_add (a positive
            # inserted ahead), hit counts — then |delta AP| of swapping the
            # pair's sorted positions
            order = jnp.argsort(-jnp.where(v, m, -jnp.inf))
            ranks = jnp.zeros_like(order).at[order].set(jnp.arange(max_size))
            rel = ((y > 0) & v).astype(m.dtype)
            rel_sorted = jnp.zeros((max_size,), m.dtype).at[ranks].set(rel)
            hits = jnp.cumsum(rel_sorted)  # inclusive per position
            p1 = jnp.arange(max_size, dtype=m.dtype) + 1.0
            acc1 = jnp.cumsum(rel_sorted * hits / p1)
            acc2 = jnp.cumsum(rel_sorted * (hits - 1.0) / p1)
            acc3 = jnp.cumsum(rel_sorted * (hits + 1.0) / p1)
            total = hits[-1]

            def at(arr, idx):  # gather; idx == -1 -> 0 (exclusive prefix)
                return jnp.where(idx >= 0,
                                 arr[jnp.clip(idx, 0, max_size - 1)], 0.0)

            ri, rj = ranks[:, None], ranks[None, :]
            a, b = jnp.minimum(ri, rj), jnp.maximum(ri, rj)
            rel_i, rel_j = rel[:, None], rel[None, :]
            lab_a = jnp.where(ri <= rj, rel_i, rel_j)  # binary, earlier pos
            lab_b = jnp.where(ri <= rj, rel_j, rel_i)
            delta = _map_pair_delta(at, hits, acc1, acc2, acc3, a, b,
                                    lab_a, lab_b, total)
            w_pair = jnp.where(pair, delta, 0.0)
        else:  # pairwise: unit delta
            w_pair = jnp.where(pair, 1.0, 0.0)
        w_pair = w_pair * samp_w
        lam = rho * w_pair  # [S, S] contribution for (i above j)
        # reference hessian per pair end: 2 * w * p * (1 - p)
        # (rank_obj.cu:142 'gpair[...] += GradientPair(g*w, 2.0f*w*h)')
        hessian = 2.0 * rho * (1.0 - rho) * w_pair
        grad = -lam.sum(axis=1) + lam.sum(axis=0)  # winners pushed up, losers down
        hess = hessian.sum(axis=1) + hessian.sum(axis=0)
        return grad, jnp.maximum(hess, 1e-16)

    g_pad, h_pad = jax.vmap(per_group)(pad_margin, pad_label, pad_valid)
    grad = g_pad.reshape(-1)[flat]
    hess = h_pad.reshape(-1)[flat]
    return grad, hess


# all-pairs only while G * S^2 stays under this many elements; above it the
# sampled-pair path keeps memory O(n * num_pair) (rank_obj.cu:143-198)
_ALL_PAIRS_BUDGET = 1 << 25


class RankLayout(NamedTuple):
    """What a ranking gradient needs of a ``DMatrix`` besides the margin:
    everything that depends on labels and query groups only, as ``[n]``
    device arrays built once a matrix (``rank_layout``). Queries are
    contiguous row blocks, as ``group_ptr`` has them."""

    group_of: jax.Array  # int32: the row's query
    group_start: jax.Array  # int32: first row of the row's query
    group_size: jax.Array  # int32: documents in the row's query
    label: jax.Array  # f32
    gains: jax.Array  # f32: 2^label - 1
    idcg: jax.Array  # f32: the query's ideal DCG, floored at 1e-10
    end_w: jax.Array  # f32: 1 / max(different-label documents in the query, 1)
    discount: jax.Array  # f32 [max_size]: 1 / log2(rank + 2), from float64
    weight: Optional[jax.Array]  # f32 [n] factor (query or row weights)


class _LayoutEntry(NamedTuple):
    arrays: RankLayout
    n_groups: int
    max_size: int
    sources: tuple  # the host arrays it was built from (kept alive: identity)


def _build_layout(label, group_ptr, weight) -> _LayoutEntry:
    """One pass of numpy over the labels and groups, in float64 where a sum
    is taken, then one upload. The only O(n) host work and the only O(n)
    upload a ranking job makes: a round reads the layout on the device."""
    label_np = np.asarray(label, np.float32)
    n = len(label_np)
    gptr = (np.array([0, n], np.int64) if group_ptr is None
            else np.asarray(group_ptr, np.int64))
    with _trace.stage("rank_layout", rows=n, groups=len(gptr) - 1):
        sizes = np.diff(gptr)
        n_groups = len(sizes)
        max_size = int(sizes.max(initial=1))
        group_of = np.repeat(np.arange(n_groups, dtype=np.int32), sizes)
        start = gptr[:-1][group_of]
        gains = np.exp2(label_np.astype(np.float64)) - 1.0
        # IDCG: the query's gains in descending order against the discounts
        by_label = np.lexsort((-label_np, group_of))
        ideal = gains[by_label] / np.log2(np.arange(n) - start + 2.0)
        idcg = np.bincount(group_of, weights=ideal, minlength=n_groups)
        # documents of the row's own label in its query, from run lengths
        # of equal (query, label) in that order
        lab_sorted = label_np[by_label]
        new_run = np.ones(n, bool)
        new_run[1:] = ((group_of[1:] != group_of[:-1])
                       | (lab_sorted[1:] != lab_sorted[:-1]))
        run_id = np.cumsum(new_run) - 1
        same = np.empty(n, np.int64)
        same[by_label] = np.bincount(run_id)[run_id] if n else 0
        opp = np.maximum(sizes[group_of] - same, 1)
        w_row = None
        if weight is not None and len(weight) == n_groups:
            # per-query weights, normalized so their sum drops out
            # (reference ComputeWeightNormalizationFactor: ngroup / sum_w)
            w_np = np.asarray(weight, np.float64)
            norm = n_groups / max(float(w_np.sum()), 1e-30)
            w_row = jnp.asarray(np.repeat(w_np * norm, sizes)
                                .astype(np.float32))
        elif weight is not None and len(weight) == n:
            w_row = jnp.asarray(np.asarray(weight, np.float32))
        arrays = RankLayout(
            group_of=jnp.asarray(group_of),
            group_start=jnp.asarray(start.astype(np.int32)),
            group_size=jnp.asarray(sizes.astype(np.int32)[group_of]),
            label=jnp.asarray(label_np),
            gains=jnp.asarray(gains.astype(np.float32)),
            idcg=jnp.asarray(np.maximum(idcg, 1e-10)[group_of]
                             .astype(np.float32)),
            end_w=jnp.asarray((1.0 / opp).astype(np.float32)),
            discount=jnp.asarray(
                (1.0 / np.log2(np.arange(max(max_size, 1)) + 2.0))
                .astype(np.float32)),
            weight=w_row)
    _REGISTRY.counter(
        "rank_layout_builds_total",
        "Ranking layouts built (one a DMatrix; none inside a round)").inc()
    return _LayoutEntry(arrays, n_groups, max_size,
                        (label, group_ptr, weight))


def rank_layout(info) -> _LayoutEntry:
    """The layout of ``info`` (a ``MetaInfo``), built on first use and kept
    with it. It is rebuilt when ``label``, ``group_ptr`` or ``weight`` is
    *replaced* (the setters do that); writing into those arrays in place
    after the first round is not seen."""
    entry = getattr(info, "_rank_layout", None)
    sources = (info.label, info.group_ptr, info.weight)
    if entry is None or any(a is not b
                            for a, b in zip(entry.sources, sources)):
        entry = _build_layout(*sources)
        info._rank_layout = entry
    return entry


@guard_jit(name="rank_grad_sampled", static_argnames=("n_pair", "scheme"))
def _lambda_grad_sampled(
    margin: jax.Array,  # [n] or [n, 1]
    lay: RankLayout,
    key0: jax.Array,  # PRNGKey(seed)
    iteration: jax.Array,  # int32 scalar
    n_pair: int,
    scheme: str,
) -> Tuple[jax.Array, jax.Array]:
    """Sampled-pair LambdaMART as one device program of the margin, the
    layout and a key. The sampler (part of a configuration's semantics; the
    benchmark's reference implements it from this text):

    - ``u = jax.random.uniform(fold_in(key0, iteration), (n, P))``, float32,
      ``P = lambdarank_num_pair_per_sample``, ``key0 = PRNGKey(seed)``;
    - opponent ``p`` of document ``i`` is row ``group_start[i] +
      min(floor(u[i, p] * size), size - 1)`` of its own query, the product
      taken in float32; it may be ``i`` itself or a document of the same
      label: such a pair is kept and weighs zero;
    - a pair weighs ``size * (1/n_opp(i) + 1/n_opp(j)) / (2 P)`` (``n_opp``:
      documents of another label in the query, at least 1), times the
      scheme's delta: ``|gain_i - gain_j| * |1/log2(r_i + 2) - 1/log2(r_j +
      2)| / IDCG`` for ``ndcg``, with ``r`` the 0-based rank by descending
      margin in the query, ties in row order;
    - with ``rho = sigmoid(s_lo - s_hi)`` the higher label gets ``-w rho``,
      the lower ``+w rho``, both ``w * max(2 rho (1 - rho), 1e-16)`` of
      hessian, at both ends of the pair; a row's hessian is floored at
      ``1e-16``.

    The ranks come from one stable sort by (query, -margin): queries are
    contiguous, so sorted position ``p`` lies in the block of row ``p`` and
    its rank is ``p - group_start[p]``; a second sort, keyed by the
    permutation, carries the ranks back to row order (a scatter runs at a
    few GB/s on the TPU). Pair arrays are ``[P, n]``: the rows fill the
    lanes."""
    with jax.named_scope("xgb.gradient"):
        if margin.ndim == 2:
            margin = margin[:, 0]
        n = margin.shape[0]
        f32 = margin.dtype
        iota = jnp.arange(n, dtype=jnp.int32)
        with jax.named_scope("xgb.rank_sort"):
            _, _, order = jax.lax.sort((lay.group_of, -margin, iota),
                                       num_keys=2, is_stable=True)
            _, rank = jax.lax.sort((order, iota - lay.group_start),
                                   num_keys=1)
            # from the layout's table, not ``1 / log2`` on the device: the
            # delta below takes differences of discounts, and the TPU's
            # log2 is some 1e-6 off, 4e-5 of the largest |g| after them
            disc = lay.discount[rank]

        with jax.named_scope("xgb.rank_pairs"):
            key = jax.random.fold_in(key0, iteration)
            u = jax.random.uniform(key, (n, n_pair)).T  # [P, n]
            size = lay.group_size[None, :]
            size_f = size.astype(f32)
            j = lay.group_start[None, :] + jnp.minimum(
                (u * size_f).astype(jnp.int32), size - 1)  # [P, n] row ids
            m_i, g_i = margin[None, :], lay.gains[None, :]
            # one gather of a stacked [4, n] table, not four of [n]: on a
            # v5e at 2.27M rows the four take 176 ms in one program (17 ms
            # each alone) and the stacked one 13 ms
            m_j, d_j, g_j, e_j = jnp.stack(
                [margin, disc, lay.gains, lay.end_w])[:, j]  # [P, n] each
            valid = g_i != g_j  # 2^y - 1 is one-to-one in the label
            # E[update] equals the reference sampler's expectation
            # (1/n_opp(i) + 1/n_opp(j) a pair, rank_obj.cu:97-127): each
            # unordered pair is hit from both ends ~n_pair/size times here
            samp_w = size_f * (lay.end_w[None, :] + e_j) / (2.0 * n_pair)

            # orient each pair: hi = higher label
            i_is_hi = g_i > g_j
            s_hi = jnp.where(i_is_hi, m_i, m_j)
            s_lo = jnp.where(i_is_hi, m_j, m_i)
            rho = jax.nn.sigmoid(-(s_hi - s_lo))
            if scheme == "ndcg":
                delta = (jnp.abs(g_i - g_j)
                         * jnp.abs(disc[None, :] - d_j)
                         / lay.idcg[None, :])
                w_pair = jnp.where(valid, delta, 0.0)
            elif scheme == "map":
                w_pair = jnp.where(
                    valid, _map_delta_sampled(lay, order, rank, j), 0.0)
            else:
                w_pair = jnp.where(valid, 1.0, 0.0)
            w_pair = w_pair * samp_w
            lam = rho * w_pair  # pushes hi up, lo down
            # reference hessian per pair end: 2 * w * p * (1-p)
            # (rank_obj.cu:142)
            hes = jnp.maximum(2.0 * rho * (1.0 - rho), 1e-16) * w_pair

            sign_i = jnp.where(i_is_hi, -1.0, 1.0)  # hi gets -lambda
            grad = (sign_i * lam).sum(axis=0)
            hess = hes.sum(axis=0)
            # the opponent end of every pair gets the mirrored update
            grad = grad.at[j.reshape(-1)].add((-sign_i * lam).reshape(-1))
            hess = hess.at[j.reshape(-1)].add(hes.reshape(-1))
            hess = jnp.maximum(hess, 1e-16)
        if lay.weight is not None:
            grad, hess = grad * lay.weight, hess * lay.weight
        return grad, hess


def _map_delta_sampled(lay: RankLayout, order, rank, j):
    """|delta AP| of the sampled pairs ``(i, j[p, i])``: the MAPStats prefix
    scan (rank_obj.cu:474 GetMAPStats) segmented over the one global
    prediction sort. Queries are contiguous blocks in sorted layout, so a
    within-query inclusive cumsum is the cumsum minus its value just before
    the block's start."""
    n = order.shape[0]
    f32 = lay.label.dtype
    rel = (lay.label > 0).astype(f32)
    rel_sorted = rel[order]
    start = lay.group_start

    def segcum(x):
        cs = jnp.cumsum(x)
        base = jnp.where(start > 0, cs[jnp.maximum(start - 1, 0)], 0.0)
        return cs - base

    hits_s = segcum(rel_sorted)
    p_loc = (jnp.arange(n) - start).astype(f32) + 1.0
    acc1_s = segcum(rel_sorted * hits_s / p_loc)
    acc2_s = segcum(rel_sorted * (hits_s - 1.0) / p_loc)
    acc3_s = segcum(rel_sorted * (hits_s + 1.0) / p_loc)
    # a query's relevant documents: the hits at its last sorted position
    total = hits_s[start + lay.group_size - 1]

    r_i, r_j = rank[None, :], rank[j]
    a, b = jnp.minimum(r_i, r_j), jnp.maximum(r_i, r_j)

    def at(arr, local_idx):  # sorted-layout gather; local -1 -> 0
        gi = start[None, :] + jnp.clip(local_idx, 0, None)
        return jnp.where(local_idx >= 0, arr[jnp.clip(gi, 0, n - 1)], 0.0)

    rel_i, rel_j = rel[None, :], rel[j]
    lab_a = jnp.where(r_i <= r_j, rel_i, rel_j)
    lab_b = jnp.where(r_i <= r_j, rel_j, rel_i)
    return _map_pair_delta(at, hits_s, acc1_s, acc2_s, acc3_s, a, b,
                           lab_a, lab_b, total[None, :])


class _LambdaRankBase(ObjFunction):
    """LambdaMART over query groups. Small data (``n_groups * max_size^2``
    up to ``_ALL_PAIRS_BUDGET`` = 2^25 elements, e.g. 300 queries of 300
    documents) takes every pair of a query, exactly and without a random
    draw; anything larger (one query of 5,800 documents is enough) samples
    ``lambdarank_num_pair_per_sample`` opponents a document a round, as
    ``_lambda_grad_sampled`` specifies, from ``seed`` and the iteration."""

    task = Task.RANKING
    scheme = "pairwise"

    def gradient_of(self, margin, info, iteration: int = 0):
        return self._gradient(margin, rank_layout(info), iteration)

    def get_gradient(self, margin, label, weight, iteration=0, *, group_ptr=None, **kw):
        """The array form: builds the layout for this one call. A training
        job goes through ``gradient_of``, which keeps it with the matrix."""
        return self._gradient(margin, _build_layout(label, group_ptr, weight),
                              iteration)

    def _gradient(self, margin, entry: _LayoutEntry, iteration):
        if entry.n_groups * entry.max_size * entry.max_size \
                <= _ALL_PAIRS_BUDGET:
            # every pair, no draw: ``_lambda_grad`` as its own program
            lay = entry.arrays
            m = margin[:, 0] if margin.ndim == 2 else margin
            rank_in_group = (jnp.arange(m.shape[0], dtype=jnp.int32)
                             - lay.group_start)
            grad, hess = _lambda_grad(m, lay.label, lay.group_of,
                                      rank_in_group, entry.n_groups,
                                      entry.max_size, self.scheme)
            if lay.weight is not None:
                grad, hess = grad * lay.weight, hess * lay.weight
            return grad, hess
        n_pair = max(1, int(getattr(self.params,
                                    "lambdarank_num_pair_per_sample", 1)))
        seed = int(getattr(self.params, "seed", 0) or 0) & 0xFFFFFFFF
        if getattr(self, "_key0_seed", None) != seed:
            self._key0, self._key0_seed = jax.random.PRNGKey(seed), seed
        return _lambda_grad_sampled(margin, entry.arrays, self._key0,
                                    jax.device_put(np.int32(iteration)), n_pair,
                                    self.scheme)

    def default_metric(self):
        return "map" if self.scheme == "map" else ("ndcg" if self.scheme == "ndcg" else "map")


@OBJECTIVES.register("rank:pairwise")
class RankPairwise(_LambdaRankBase):
    scheme = "pairwise"


@OBJECTIVES.register("rank:ndcg")
class RankNDCG(_LambdaRankBase):
    scheme = "ndcg"


@OBJECTIVES.register("rank:map")
class RankMAP(_LambdaRankBase):
    scheme = "map"
