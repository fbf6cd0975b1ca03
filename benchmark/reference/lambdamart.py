"""A plain LambdaMART gradient and NDCG, the oracle of the ranking cells.

Written from the published description of the objective and from the
sampler's specification in the configuration's file, in numpy float64, and
sharing no code with ``xgboost_tpu/objective/ranking.py``.

The sampled-pair gradient of iteration ``t`` (``rank:ndcg`` and
``rank:pairwise``), for rows in contiguous query blocks:

1. ``u = jax.random.uniform(fold_in(PRNGKey(seed mod 2^32), t), (n, P))``,
   float32. The uniforms *are* the specification, so the reference draws
   them with the same documented call; nothing else here touches JAX.
2. Opponent ``p`` of document ``i`` is row ``start + min(floor(u[i, p] *
   size), size - 1)`` of its own query, the product in float32 (a float64
   product would pick another row about once in 10^7 draws). The opponent
   may be ``i`` itself or carry the same label: the pair weighs zero.
3. Ranks: 0-based position by descending margin inside the query, ties in
   row order (``np.lexsort``, which is stable).
4. A pair of different labels weighs ``size * (1/n_opp(i) + 1/n_opp(j)) /
   (2 P)``, ``n_opp`` the documents of another label in the query (at least
   1): the expectation of XGBoost's two-ended sampler (``rank_obj.cu``),
   under which the sum over a round estimates the all-pairs gradient. For
   ``rank:ndcg`` it is multiplied by ``|2^y_i - 2^y_j| * |1/log2(r_i + 2) -
   1/log2(r_j + 2)| / IDCG``, IDCG floored at 1e-10.
5. With ``hi`` the end of the higher label and ``rho = 1 / (1 + exp(s_hi -
   s_lo))``: ``g[hi] -= w rho``, ``g[lo] += w rho`` and both ends get ``w *
   max(2 rho (1 - rho), 1e-16)`` of hessian; a row's hessian is floored at
   1e-16.

Departures from upstream XGBoost, each deliberate and each the system's
too: upstream (v1.6) samples an opponent among the documents of *another*
label and scales by ``1 / num_pairsample``; here the opponent is uniform
over the query and the weight of item 4 restores the expectation.
``ndcg_at_k`` follows upstream: exponential gain, log2 discount, and a
query with no relevant document scores 1.
"""

import os

import numpy as np

from harness import HERE, load_module

grower = load_module(os.path.join(HERE, "reference", "grower.py"))

SCHEMES = {"rank:ndcg": "ndcg", "rank:pairwise": "pairwise"}


def group_ptr_of(qid: np.ndarray) -> np.ndarray:
    """[0, ..., n]: the row each query starts at (rows of a query are
    contiguous)."""
    change = np.flatnonzero(np.diff(qid)) + 1
    return np.concatenate([[0], change, [len(qid)]]).astype(np.int64)


def uniforms(seed: int, iteration: int, n: int, n_pair: int) -> np.ndarray:
    """The specification's draws, float32 [n, P]."""
    import jax

    key = jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0xFFFFFFFF),
                             int(iteration))
    return np.asarray(jax.random.uniform(key, (n, n_pair)), np.float32)


def _per_query(group_ptr: np.ndarray):
    sizes = np.diff(group_ptr)
    group_of = np.repeat(np.arange(len(sizes)), sizes)
    return sizes, group_of, group_ptr[:-1][group_of], sizes[group_of]


def ranks(margin: np.ndarray, group_ptr: np.ndarray) -> np.ndarray:
    """0-based rank of each row by descending margin inside its query."""
    _, group_of, start, _ = _per_query(group_ptr)
    order = np.lexsort((-np.asarray(margin, np.float64), group_of))
    rank = np.empty(len(order), np.int64)
    rank[order] = np.arange(len(order)) - start  # blocks stay in place
    return rank


def idcg(label: np.ndarray, group_ptr: np.ndarray) -> np.ndarray:
    """Ideal DCG of each query (over all its documents)."""
    sizes, group_of, start, _ = _per_query(group_ptr)
    best = np.lexsort((-label, group_of))
    terms = (2.0 ** label[best] - 1.0) / np.log2(
        np.arange(len(label)) - start + 2.0)
    return np.bincount(group_of, weights=terms, minlength=len(sizes))


def opponents_of_other_label(label: np.ndarray,
                             group_ptr: np.ndarray) -> np.ndarray:
    """n_opp: per row, the documents of its query with another label."""
    out = np.empty(len(label), np.float64)
    for lo, hi in zip(group_ptr[:-1], group_ptr[1:]):
        values, inverse, counts = np.unique(label[lo:hi], return_inverse=True,
                                            return_counts=True)
        out[lo:hi] = (hi - lo) - counts[inverse]
    return np.maximum(out, 1.0)


def gradient(objective: str, margin: np.ndarray, label: np.ndarray,
             group_ptr: np.ndarray, *, seed: int, iteration: int,
             n_pair: int = 1):
    """(g, h) float64 [n] of the sampled-pair objective."""
    scheme = SCHEMES[objective]
    s = np.asarray(margin, np.float64).reshape(-1)
    y = np.asarray(label, np.float64)
    n = len(s)
    _, group_of, start, size = _per_query(group_ptr)
    u = uniforms(seed, iteration, n, n_pair)
    local = np.floor(u * size.astype(np.float32)[:, None]).astype(np.int64)
    j = start[:, None] + np.minimum(local, size[:, None] - 1)  # [n, P]
    i = np.broadcast_to(np.arange(n)[:, None], j.shape)

    inv_opp = 1.0 / opponents_of_other_label(y, group_ptr)
    w = size[:, None] * (inv_opp[i] + inv_opp[j]) / (2.0 * n_pair)
    w = np.where(y[i] != y[j], w, 0.0)
    if scheme == "ndcg":
        gain = 2.0 ** y - 1.0
        disc = 1.0 / np.log2(ranks(s, group_ptr) + 2.0)
        ideal = np.maximum(idcg(y, group_ptr), 1e-10)[group_of]
        w = w * (np.abs(gain[i] - gain[j]) * np.abs(disc[i] - disc[j])
                 / ideal[:, None])
    i_is_hi = y[i] > y[j]
    hi = np.where(i_is_hi, i, j).reshape(-1)
    lo = np.where(i_is_hi, j, i).reshape(-1)
    w = w.reshape(-1)
    rho = 1.0 / (1.0 + np.exp(s[hi] - s[lo]))
    g = np.zeros(n)
    h = np.zeros(n)
    np.add.at(g, hi, -w * rho)
    np.add.at(g, lo, w * rho)
    hes = w * np.maximum(2.0 * rho * (1.0 - rho), 1e-16)
    np.add.at(h, hi, hes)
    np.add.at(h, lo, hes)
    return g, np.maximum(h, 1e-16)


def ndcg_at_k(score: np.ndarray, label: np.ndarray, group_ptr: np.ndarray,
              k: int = 10) -> float:
    """Mean NDCG@k over queries; ties in row order; a query without a
    relevant document scores 1 (upstream's convention)."""
    sizes, group_of, start, _ = _per_query(group_ptr)
    y = np.asarray(label, np.float64)
    n = len(y)
    pos = np.arange(n) - start

    def dcg(order):
        terms = (2.0 ** y[order] - 1.0) / np.log2(pos + 2.0)
        return np.bincount(group_of, weights=np.where(pos < k, terms, 0.0),
                           minlength=len(sizes))

    got = dcg(np.lexsort((-np.asarray(score, np.float64), group_of)))
    best = dcg(np.lexsort((-y, group_of)))
    return float(np.where(best > 0, got / np.maximum(best, 1e-300),
                          1.0).mean())


def replay_rounds(X, label, group_ptr, cuts, forest, *, objective, seed,
                  n_pair, eta, rounds, max_depth, lam=1.0,
                  min_child_weight=1.0, gamma=0.0):
    """``grower.replay_forest`` with this file's gradient: replay the first
    ``rounds`` trees of a saved forest from the base margin, the reference's
    gradient in, ``grower.replay_tree``'s report out, the same totals."""
    bins = grower.bin_rows(X, cuts)
    margin = np.full(len(X), forest.base_margin(), np.float64)
    total = {"nodes": 0, "same": 0, "tie": 0, "mismatch": [], "ungrown": [],
             "leaves_checked": 0, "leaf_err": 0.0, "leaf_tol_exceeded": [],
             "mcw_short": 0.0, "mcw_decided": 0}
    for t in range(rounds):
        g, h = gradient(objective, margin, label, group_ptr, seed=seed,
                        iteration=t, n_pair=n_pair)
        delta, rep = grower.replay_tree(
            bins, cuts, g, h, forest.trees[t], eta=eta, max_depth=max_depth,
            lam=lam, min_child_weight=min_child_weight, gamma=gamma)
        margin = margin + delta
        for key in ("nodes", "same", "tie", "leaves_checked", "mcw_decided"):
            total[key] += rep[key]
        for key in ("leaf_err", "mcw_short"):
            total[key] = max(total[key], rep[key])
        for key in ("mismatch", "ungrown", "leaf_tol_exceeded"):
            total[key] += [(t,) + m for m in rep[key]]
    return margin, total


def grow_tree(bins, rows, g, h, *, B, eta, max_depth, lam=1.0,
              min_child_weight=1.0, gamma=0.0):
    """One tree grown by the plain grower's own gains, following no tree of
    the system's: nested dicts, a leaf ``{"value": w}``, a split
    ``{"feature", "bin", "left", "right"}`` (``bin <= b`` goes left). At
    every node the best (feature, bin) by ``grower._split_gains`` among the
    splits whose children hold ``min_child_weight``; a node splits while the
    gain passes ``grower.RT_EPS``; then upstream's pruning
    (``updater_prune.cc``): a split under ``gamma`` whose children are both
    leaves becomes a leaf, bottom-up."""
    G, H = g[rows].sum(), h[rows].sum()
    leaf = {"value": -eta * G / (H + lam)}
    if max_depth == 0 or len(rows) == 0:
        return leaf
    gain, G, H, GL, HL = grower._split_gains(bins, rows, g, h, B, lam)
    gain[(HL < min_child_weight) | (H - HL < min_child_weight)] = -np.inf
    f, b = np.unravel_index(int(gain.argmax()), gain.shape)
    if not gain[f, b] > grower.RT_EPS:
        return leaf
    go_left = bins[rows, f] <= b
    kids = [grow_tree(bins, part, g, h, B=B, eta=eta, max_depth=max_depth - 1,
                      lam=lam, min_child_weight=min_child_weight, gamma=gamma)
            for part in (rows[go_left], rows[~go_left])]
    if gain[f, b] < gamma and all("value" in kid for kid in kids):
        return leaf
    return {"feature": int(f), "bin": int(b), "left": kids[0],
            "right": kids[1]}


def apply_tree(tree, bins, rows, out) -> None:
    """Add the tree's leaf values into ``out[rows]``."""
    if "value" in tree:
        out[rows] += tree["value"]
        return
    go_left = bins[rows, tree["feature"]] <= tree["bin"]
    apply_tree(tree["left"], bins, rows[go_left], out)
    apply_tree(tree["right"], bins, rows[~go_left], out)


def train_rounds(bins, label, group_ptr, *, objective, seed, n_pair, B, eta,
                 rounds, max_depth, lam=1.0, min_child_weight=1.0, gamma=0.0):
    """LambdaMART by the plain reference alone, from a zero margin (a
    constant moves neither the pairs' differences nor a ranking): this
    file's gradient in, ``grow_tree`` out. ``(trees, margin)``. What the
    holdout's band is confirmed with (PERF.md section 4)."""
    rows = np.arange(len(bins))
    margin = np.zeros(len(bins))
    trees = []
    for t in range(rounds):
        g, h = gradient(objective, margin, label, group_ptr, seed=seed,
                        iteration=t, n_pair=n_pair)
        trees.append(grow_tree(
            bins, rows, g, h, B=B, eta=eta, max_depth=max_depth, lam=lam,
            min_child_weight=min_child_weight, gamma=gamma))
        apply_tree(trees[-1], bins, rows, margin)
    return trees, margin
