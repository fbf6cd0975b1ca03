"""A plain numpy histogram grower, the oracle of the train cells.

Given the system's cut points and raw features it bins the rows itself
(``np.searchsorted``), and replays each tree the system grew: at every node
it builds the gradient histogram in float64, evaluates every (feature, bin)
split by the second-order gain XGBoost uses
(``G_L^2/(H_L+lambda) + G_R^2/(H_R+lambda) - G^2/(H+lambda)``, children
needing ``min_child_weight`` of hessian), and compares its best split with
the split the system chose at that node. Equal gains within ``gain_rtol``
are a tie (two candidates a rounding apart): the replay then follows the
system's split so that deeper nodes stay comparable, and the tie is counted.
Leaf values are ``-eta * G/(H+lambda)`` on the rows the replay routed there.

The replay also holds the system to the configured depth. Where the system
made a leaf above ``max_depth``, the reference builds that node's histogram
too, and a split it would have made there (children safely over
``min_child_weight``, gain over ``gamma`` by more than the system's rounding
can explain) is reported as *ungrown*: a forest cut short, or one of stumps,
does not pass. A split at or below ``max_depth`` is reported as well.

The tolerance on a leaf follows where the system gets its sums. It carries
each float32 gradient as two bf16 terms, so a histogram it accumulates is
off by at most ``U = 2^-15`` of the sum of |g| (or h) over the rows summed
(the class PR 21 measured on the chip). A node's G and H are read from its
*parent's* histogram, and a histogram is either accumulated directly or
taken as parent minus sibling, which inherits the parent's error. The replay
carries the worse of the two down the tree, so the bound holds whichever
child the system subtracts; plain bf16 accumulation (2^-8) would be two
orders outside it.

``min_child_weight`` is a threshold, and a sum a rounding away from it falls
on either side of it by the precision it was summed in: four rows of
``h = 0.25 - 4e-7`` (the second round of ``binary:logistic``) hold 0.9999986
in float64 and may read 1.0 from a float32 histogram taken as parent minus
sibling. The replay therefore holds the system's split to the threshold
within ``MCW_RTOL`` of it: a child of the system's split may fall short of
``min_child_weight`` by that share and no more (the largest shortfall seen
is reported as ``mcw_short``), and the system need not have found a split
of the reference's whose child clears the threshold by less than that.
PERF.md, section 6 (PR 30), has the two readings the limit sits between.

No missing values are handled: the benchmark's generators make none, and
the replay raises on a NaN rather than guess a default direction.
"""

import numpy as np


def bin_rows(X: np.ndarray, cuts: np.ndarray) -> np.ndarray:
    """Bin ids [n, F]: the count of cut points <= x, feature by feature."""
    if np.isnan(X).any():
        raise ValueError("the reference grower handles no missing values")
    return np.stack([np.searchsorted(cuts[f], X[:, f], side="right")
                     for f in range(X.shape[1])], axis=1)


def gradients(objective: str, margin: np.ndarray, y: np.ndarray,
              num_class: int = 1):
    """(g, h) [n, groups] float64 of the objectives the cells use."""
    m = np.asarray(margin, np.float64)
    if objective == "binary:logistic":
        p = 1.0 / (1.0 + np.exp(-m))
        return p - y[:, None], np.maximum(p * (1.0 - p), 1e-16)
    if objective in ("multi:softprob", "multi:softmax"):
        e = np.exp(m - m.max(axis=1, keepdims=True))
        p = e / e.sum(axis=1, keepdims=True)
        onehot = np.eye(num_class)[y.astype(np.int64)]
        return p - onehot, np.maximum(2.0 * p * (1.0 - p), 1e-16)
    raise ValueError(f"no reference gradient for objective {objective!r}")


U = 2.0 ** -15  # two bf16 terms carry about 16 significand bits an addend


RT_EPS = 1e-6  # XGBoost's kRtEps: a split needs more gain than this

# the share of ``min_child_weight`` by which a child of the system's split
# may fall short of it. Between its two readings (PERF.md section 6, PR 30):
# sound runs read 1.5e-6 at most (float32 sums a rounding under the
# threshold); a grower that drops the threshold, or sums in plain bfloat16,
# reads 0.2 or more (a whole row short)
MCW_RTOL = 1e-3


def _gain(G, H, lam):
    return G * G / (H + lam)


def _gain_err(G, H, eG, eH, lam):
    """How far ``_gain`` can move when G is off by eG and H by eH."""
    return 2.0 * np.abs(G) * eG / (H + lam) + G * G * eH / (H + lam) ** 2


def _split_gains(bins, rows, g, h, B, lam):
    """Gain of every (feature, bin) split of the node holding ``rows``,
    with the sums it came from: ``(gain, G, H, GL, HL)``, arrays [F, B]."""
    F = bins.shape[1]
    G, H = g[rows].sum(), h[rows].sum()
    hg = np.zeros((F, B + 1))
    hh = np.zeros((F, B + 1))
    for f in range(F):
        hg[f] = np.bincount(bins[rows, f], weights=g[rows],
                            minlength=B + 1)[:B + 1]
        hh[f] = np.bincount(bins[rows, f], weights=h[rows],
                            minlength=B + 1)[:B + 1]
    GL, HL = np.cumsum(hg, axis=1)[:, :B], np.cumsum(hh, axis=1)[:, :B]
    gain = _gain(GL, HL, lam) + _gain(G - GL, H - HL, lam) - _gain(G, H, lam)
    return gain, G, H, GL, HL


def replay_tree(bins, cuts, g, h, tree, *, eta, max_depth, lam=1.0,
                min_child_weight=1.0, gamma=0.0, gain_rtol=1e-3):
    """Replay one saved tree (arrays of reference/walk.py) on binned rows.

    Returns ``(leaf_delta [n], report)``: the margin update the reference
    gives every row, and counts of nodes that matched exactly, tied, or
    mismatched, the leaves above ``max_depth`` it would have split
    (``ungrown``), the largest leaf-value difference seen, the largest share
    of ``min_child_weight`` by which a child of a system split fell short of
    it (``mcw_short``) and the ties that only the slack on that threshold
    allows (``mcw_decided``)."""
    n, F = bins.shape
    B = cuts.shape[1]
    rep = {"nodes": 0, "same": 0, "tie": 0, "mismatch": [], "ungrown": [],
           "leaves_checked": 0, "leaf_err": 0.0, "leaf_tol_exceeded": [],
           "mcw_short": 0.0, "mcw_decided": 0}
    mcw_slack = MCW_RTOL * min_child_weight
    delta = np.zeros(n, np.float64)
    all_rows = np.arange(n)
    abs_g = np.abs(g)
    # (node, depth, rows, error bounds of the histogram its G and H were
    #  read from, error bounds of its own histogram)
    root_e = (U * abs_g.sum(), U * h.sum())
    stack = [(0, 0, all_rows, root_e, root_e)]
    while stack:
        node, depth, rows, (eG, eH), own_e = stack.pop()
        left = int(tree["left_children"][node])
        if left < 0:
            G, H = g[rows].sum(), h[rows].sum()
            want = -eta * G / (H + lam)
            got = float(tree["split_conditions"][node])
            tol = eta * (eG / (H + lam) + abs(G) * eH / (H + lam) ** 2) + 1e-6
            err = abs(want - got)
            rep["leaf_err"] = max(rep["leaf_err"], err)
            if err > tol:
                rep["leaf_tol_exceeded"].append((node, want, got, tol))
            delta[rows] = got
            if depth < max_depth and len(rows):
                # would the reference have split here? Only a split the
                # system cannot have lost to rounding counts: both children
                # over min_child_weight and the gain over gamma by more
                # than the error of the sums it is made of
                rep["leaves_checked"] += 1
                gain, G, H, GL, HL = _split_gains(bins, rows, g, h, B, lam)
                GR, HR = G - GL, H - HL
                wG, wH = max(eG, own_e[0]), max(eH, own_e[1])
                slack = (_gain_err(GL, HL, wG, wH, lam)
                         + _gain_err(GR, HR, wG, wH, lam)
                         + _gain_err(G, H, wG, wH, lam)
                         + 4e-6 * (_gain(GL, HL, lam) + _gain(GR, HR, lam)
                                   + _gain(G, H, lam)))
                sure = gain - slack
                sure[(HL < min_child_weight + wH)
                     | (HR < min_child_weight + wH)] = -np.inf
                if sure.max() > max(gamma, RT_EPS):
                    f_ref, b_ref = np.unravel_index(int(sure.argmax()),
                                                    sure.shape)
                    rep["ungrown"].append(
                        (node, f"leaf of {len(rows)} rows at depth {depth} "
                               f"< max_depth {max_depth}; reference splits "
                               f"f={f_ref} b={b_ref} gain "
                               f"{gain[f_ref, b_ref]:.6g} (rounding slack "
                               f"{slack[f_ref, b_ref]:.3g})"))
            continue
        rep["nodes"] += 1
        if depth >= max_depth:
            rep["mismatch"].append(
                (node, f"a split at depth {depth}, max_depth {max_depth}"))
        f_sys = int(tree["split_indices"][node])
        cond = np.float32(tree["split_conditions"][node])
        hit = np.flatnonzero(cuts[f_sys] == cond)
        if len(hit) == 0:
            rep["mismatch"].append((node, "threshold is not a cut point"))
            b_sys = int(np.searchsorted(cuts[f_sys], cond))
        else:
            b_sys = int(hit[0])
        gain, G, H, GL, HL = _split_gains(bins, rows, g, h, B, lam)
        light = np.minimum(HL, H - HL)  # the lighter child of every split
        strict = np.where(light < min_child_weight, -np.inf, gain)
        # what no rounding of the sums can take away from the system
        sure = np.where(light < min_child_weight + mcw_slack, -np.inf, gain)
        best, best_sure = float(strict.max()), float(sure.max())
        short = max(0.0, float(min_child_weight - light[f_sys, b_sys]))
        if min_child_weight > 0:
            rep["mcw_short"] = max(rep["mcw_short"], short / min_child_weight)
        g_sys = float(gain[f_sys, b_sys]) if short <= mcw_slack else -np.inf
        f_ref, b_ref = np.unravel_index(int(strict.argmax()), strict.shape)
        same_partition = (np.isfinite(best) and f_ref == f_sys
                          and np.array_equal(bins[rows, f_sys] <= b_sys,
                                             bins[rows, f_sys] <= b_ref))
        if same_partition:
            rep["same"] += 1
        elif np.isfinite(g_sys) and (not np.isfinite(best_sure) or best_sure
                                     - g_sys <= gain_rtol * abs(best_sure)):
            rep["tie"] += 1
            # a tie that only the slack on the threshold allows
            g_strict = float(strict[f_sys, b_sys])
            if not (np.isfinite(g_strict)
                    and best - g_strict <= gain_rtol * abs(best)):
                rep["mcw_decided"] += 1
        else:
            rep["mismatch"].append(
                (node, f"system split f={f_sys} b={b_sys} gain "
                       f"{gain[f_sys, b_sys]:.6g}, lighter child "
                       f"{short:.3g} under min_child_weight (slack "
                       f"{mcw_slack:.3g}); reference f={f_ref} b={b_ref} "
                       f"gain {best:.6g} (clear of the threshold: "
                       f"{best_sure:.6g})"))
        go_left = bins[rows, f_sys] <= b_sys
        rl, rr = rows[go_left], rows[~go_left]
        sl = (U * abs_g[rl].sum(), U * h[rl].sum())
        sr = (U * abs_g[rr].sum(), U * h[rr].sum())
        # a child's histogram: accumulated (its own sums) or parent - sibling
        el = tuple(max(a, o + b) for a, o, b in zip(sl, own_e, sr))
        er = tuple(max(a, o + b) for a, o, b in zip(sr, own_e, sl))
        stack.append((left, depth + 1, rl, own_e, el))
        stack.append((int(tree["right_children"][node]), depth + 1, rr,
                      own_e, er))
    return delta, rep


def replay_forest(X, y, cuts, forest, *, objective, eta, rounds, max_depth,
                  lam=1.0, min_child_weight=1.0, gamma=0.0):
    """Replay the first ``rounds`` rounds of a saved forest from base margin.

    Returns ``(margin [n, groups], report)``; the report sums the per-tree
    reports and lists every mismatch and every ungrown leaf."""
    bins = bin_rows(X, cuts)
    groups = forest.num_class
    margin = np.full((len(X), groups), forest.base_margin(), np.float64)
    total = {"nodes": 0, "same": 0, "tie": 0, "mismatch": [], "ungrown": [],
             "leaves_checked": 0, "leaf_err": 0.0, "leaf_tol_exceeded": [],
             "mcw_short": 0.0, "mcw_decided": 0}
    t = 0
    for _ in range(rounds):
        g, h = gradients(objective, margin, y, groups)
        new = margin.copy()
        for k in range(groups):
            tree = forest.trees[t]
            if int(forest.tree_group[t]) != k:
                raise ValueError(f"tree {t} is of group "
                                 f"{forest.tree_group[t]}, expected {k}")
            delta, rep = replay_tree(bins, cuts, g[:, k], h[:, k], tree,
                                     eta=eta, max_depth=max_depth, lam=lam,
                                     min_child_weight=min_child_weight,
                                     gamma=gamma)
            new[:, k] += delta
            for key in ("nodes", "same", "tie", "leaves_checked",
                        "mcw_decided"):
                total[key] += rep[key]
            for key in ("leaf_err", "mcw_short"):
                total[key] = max(total[key], rep[key])
            for key in ("mismatch", "ungrown", "leaf_tol_exceeded"):
                total[key] += [(t,) + m for m in rep[key]]
            t += 1
        margin = new
    return margin, total
