"""A plain numpy depth-wise grower with categorical splits, the oracle of
the categorical train cells, and a walk of the saved model's category sets.

XGBoost 1.6's categorical semantics (``src/tree/hist/evaluate_splits.h``,
``common/categorical.h``): a column of ``c`` categories takes one-vs-rest
candidates where ``c < max_cat_to_onehot`` (category ``b`` right, the rest
left) and partition candidates otherwise (the node's present categories
sorted by ``g / (h + lambda)``, ties by code, each prefix of the sorted
order right). Both directions of the missing values are tried, as for a
numerical column. A category goes right where it is in the node's stored
set. The numerical column takes exactly the rules of ``reference/grower.py``
(bins from ``np.searchsorted`` on the system's cuts, left where the bin is
at most the split's), which this module loads for its gradients, its
constants and its gain.

The replay follows ``grower.replay_tree``: at every node of a saved tree it
builds the gradient histogram in float64 over the rows the replay routed
there and compares its best candidate with the system's split. The same
partition of the node's rows is a match; a system split whose gain is the
reference's best within ``gain_rtol`` is a tie (float32 sums may order two
categories a rounding apart the other way); anything else is a mismatch,
and so is a categorical split on a column the configuration has as
numerical or the reverse, and a one-vs-rest column's split that sends more
than one category right. Leaves, ``min_child_weight``, depth and the
error bounds of the sums are ``grower.replay_tree``'s.

The walk (``CatForest``) reads the published JSON: ``split_type`` 1 marks a
categorical node, and ``categories_nodes`` / ``categories_segments`` /
``categories_sizes`` / ``categories`` give its set.
"""

import json
import os

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))


def _grower():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "cat_grower_numeric_rules", os.path.join(_HERE, "grower.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


grower = _grower()
U, RT_EPS, MCW_RTOL = grower.U, grower.RT_EPS, grower.MCW_RTOL
_gain, _gain_err = grower._gain, grower._gain_err


def bin_rows(X: np.ndarray, cuts: np.ndarray) -> np.ndarray:
    """Bin ids [n, F] (uint16): the count of cut points <= x, feature by
    feature; a missing value takes bin ``B``, the number of cuts. A
    categorical column's cuts are 1, 2, ..., so code ``c`` is bin ``c``."""
    B = cuts.shape[1]
    out = np.empty(X.shape, np.uint16)
    for f in range(X.shape[1]):
        x = X[:, f]
        out[:, f] = np.where(np.isnan(x), B,
                             np.searchsorted(cuts[f], x, side="right"))
    return out


def column_kinds(F: int, categorical, counts, max_cat_to_onehot: int):
    """[F] int: 0 numerical, 1 one-vs-rest, 2 partition."""
    kind = np.zeros(F, np.int64)
    for f, c in zip(categorical, counts):
        kind[f] = 1 if c < max_cat_to_onehot else 2
    return kind


class _Node:
    """The histogram of one node's rows and every candidate's sums."""

    def __init__(self, binsT, rows, g, h, B, kind, lam):
        F = binsT.shape[0]
        self.G, self.H = g[rows].sum(), h[rows].sum()
        hg = np.empty((F, B + 1))
        hh = np.empty((F, B + 1))
        gr, hr = g[rows], h[rows]
        for f in range(F):
            b = binsT[f, rows]
            hg[f] = np.bincount(b, weights=gr, minlength=B + 1)[:B + 1]
            hh[f] = np.bincount(b, weights=hr, minlength=B + 1)[:B + 1]
        g_b, h_b = hg[:, :B], hh[:, :B]
        gm, hm = hg[:, B:], hh[:, B:]  # [F, 1] the missing rows' sums
        Gp, Hp = g_b.sum(1, keepdims=True), h_b.sum(1, keepdims=True)
        # candidate b of a column: the sums that go LEFT with the missing
        # rows right (dir 0) or left (dir 1), and what goes right
        GL = np.cumsum(g_b, axis=1)
        HL = np.cumsum(h_b, axis=1)
        self.order = {}
        for f in np.flatnonzero(kind == 1):
            GL[f], HL[f] = Gp[f] - g_b[f], Hp[f] - h_b[f]
        for f in np.flatnonzero(kind == 2):
            present = (h_b[f] > 0) | (g_b[f] != 0)
            ratio = np.where(present, g_b[f] / (h_b[f] + lam), np.inf)
            order = np.argsort(ratio, kind="stable")
            self.order[f] = order
            GL[f] = Gp[f] - np.cumsum(g_b[f, order])
            HL[f] = Hp[f] - np.cumsum(h_b[f, order])
        self.GL = np.stack([GL, GL + gm])  # [2, F, B]
        self.HL = np.stack([HL, HL + hm])
        GR, HR = self.G - self.GL, self.H - self.HL
        self.gain = (_gain(self.GL, self.HL, lam) + _gain(GR, HR, lam)
                     - _gain(self.G, self.H, lam))
        self.B, self.kind = B, kind

    def right_set(self, f: int, b: int) -> np.ndarray:
        """Codes a categorical candidate ``(f, b)`` sends right."""
        if self.kind[f] == 1:
            return np.asarray([b])
        return np.sort(self.order[f][:b + 1])

    def goes_left(self, bins_f, d: int, f: int, b: int) -> np.ndarray:
        """Rows (their bins of column ``f``) that candidate ``(d, f, b)``
        sends left."""
        missing = bins_f == self.B
        if self.kind[f]:
            present_left = ~np.isin(bins_f, self.right_set(f, b))
        else:
            present_left = bins_f <= b
        return np.where(missing, d == 1, present_left)


def _err_of(gain_args, eG, eH, lam):
    GL, HL, G, H = gain_args
    GR, HR = G - GL, H - HL
    return (_gain_err(GL, HL, eG, eH, lam) + _gain_err(GR, HR, eG, eH, lam)
            + _gain_err(G, H, eG, eH, lam)
            + 4e-6 * (_gain(GL, HL, lam) + _gain(GR, HR, lam)
                      + _gain(G, H, lam)))


def _children_err(GL, HL, G, H, eG, eH, lam):
    """How far the children's terms of a candidate's gain can move when
    each sum is off by ``eG`` / ``eH`` (the parent's term is the same for
    every candidate of the node)."""
    return _gain_err(GL, HL, eG, eH, lam) + _gain_err(G - GL, H - HL, eG, eH,
                                                      lam)


def replay_tree(bins, binsT, g, h, tree, kind, *, eta, max_depth, lam=1.0,
                min_child_weight=1.0, gamma=0.0, gain_rtol=1e-3):
    """Replay one saved tree (``CatForest.trees`` entry) on binned rows.

    Returns ``(leaf_delta [n], report)`` with ``grower.replay_tree``'s
    counts, and ``cat_splits``: the system's categorical splits."""
    n = bins.shape[0]
    B = tree["_B"]
    rep = {"nodes": 0, "same": 0, "tie": 0, "mismatch": [], "ungrown": [],
           "leaves_checked": 0, "leaf_err": 0.0, "leaf_tol_exceeded": [],
           "mcw_short": 0.0, "mcw_decided": 0, "rounding_tie": 0,
           "cat_splits": 0}
    mcw_slack = MCW_RTOL * min_child_weight
    delta = np.zeros(n, np.float64)
    abs_g = np.abs(g)
    root_e = (U * abs_g.sum(), U * h.sum())
    stack = [(0, 0, np.arange(n), root_e, root_e)]
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
                rep["leaves_checked"] += 1
                nd = _Node(binsT, rows, g, h, B, kind, lam)
                wG, wH = max(eG, own_e[0]), max(eH, own_e[1])
                slack = _err_of((nd.GL, nd.HL, nd.G, nd.H), wG, wH, lam)
                sure = nd.gain - slack
                sure[(nd.HL < min_child_weight + wH)
                     | (nd.H - nd.HL < min_child_weight + wH)] = -np.inf
                if sure.max() > max(gamma, RT_EPS):
                    d, f, b = np.unravel_index(int(sure.argmax()), sure.shape)
                    rep["ungrown"].append(
                        (node, f"leaf of {len(rows)} rows at depth {depth} "
                               f"< max_depth {max_depth}; reference splits "
                               f"f={f} b={b} dir={d} gain "
                               f"{nd.gain[d, f, b]:.6g}"))
            continue
        rep["nodes"] += 1
        if depth >= max_depth:
            rep["mismatch"].append(
                (node, f"a split at depth {depth}, max_depth {max_depth}"))
        f_sys = int(tree["split_indices"][node])
        dl_sys = bool(tree["default_left"][node])
        bf = binsT[f_sys, rows]
        missing = bf == B
        is_cat = int(tree["split_type"][node]) == 1
        if is_cat:
            rep["cat_splits"] += 1
            cats = tree["_cats"].get(node, np.zeros(0, np.int64))
            present_left = ~np.isin(bf, cats)
            if kind[f_sys] == 0:
                rep["mismatch"].append(
                    (node, f"categorical split on numerical column {f_sys}"))
            elif kind[f_sys] == 1 and len(cats) != 1:
                rep["mismatch"].append(
                    (node, f"{len(cats)} categories right on one-vs-rest "
                           f"column {f_sys}"))
        else:
            if kind[f_sys]:
                rep["mismatch"].append(
                    (node, f"numerical split on categorical column {f_sys}"))
            cond = np.float32(tree["split_conditions"][node])
            hit = np.flatnonzero(tree["_cuts"][f_sys] == cond)
            if len(hit) == 0:
                rep["mismatch"].append((node, "threshold is not a cut point"))
                b_sys = int(np.searchsorted(tree["_cuts"][f_sys], cond))
            else:
                b_sys = int(hit[0])
            present_left = bf <= b_sys
        go_left = np.where(missing, dl_sys, present_left)
        nd = _Node(binsT, rows, g, h, B, kind, lam)
        GLs, HLs = g[rows][go_left].sum(), h[rows][go_left].sum()
        light_all = np.minimum(nd.HL, nd.H - nd.HL)
        strict = np.where(light_all < min_child_weight, -np.inf, nd.gain)
        sure = np.where(light_all < min_child_weight + mcw_slack, -np.inf,
                        nd.gain)
        best, best_sure = float(strict.max()), float(sure.max())
        light = min(HLs, nd.H - HLs)
        short = max(0.0, float(min_child_weight - light))
        if min_child_weight > 0:
            rep["mcw_short"] = max(rep["mcw_short"], short / min_child_weight)
        g_full = float(_gain(GLs, HLs, lam) + _gain(nd.G - GLs, nd.H - HLs,
                                                     lam)
                       - _gain(nd.G, nd.H, lam))
        g_sys = g_full if short <= mcw_slack else -np.inf
        d_ref, f_ref, b_ref = np.unravel_index(int(strict.argmax()),
                                               strict.shape)
        same_partition = np.isfinite(best) and np.array_equal(
            nd.goes_left(binsT[f_ref, rows], d_ref, f_ref, b_ref), go_left)
        # what the error of the node's sums can move the two gains by: the
        # system ranks its candidates on sums of that class
        wG, wH = max(eG, own_e[0]), max(eH, own_e[1])
        i_best = (d_ref, f_ref, b_ref)
        rounding = _children_err(GLs, HLs, nd.G, nd.H, wG, wH, lam) + (
            _children_err(nd.GL[i_best], nd.HL[i_best], nd.G, nd.H, wG, wH,
                          lam) if np.isfinite(best) else 0.0)
        if same_partition:
            rep["same"] += 1
        elif np.isfinite(g_sys) and (not np.isfinite(best_sure) or best_sure
                                     - g_sys <= gain_rtol * abs(best_sure)):
            rep["tie"] += 1
            # a tie that only the slack on the threshold allows
            if not (short == 0.0 and best - g_full <= gain_rtol * abs(best)):
                rep["mcw_decided"] += 1
        elif np.isfinite(g_sys) and best_sure - g_sys <= rounding:
            # further from the best than ``gain_rtol``, but no further
            # than the rounding of the sums can move two gains apart
            rep["rounding_tie"] += 1
        else:
            rep["mismatch"].append(
                (node, f"system split f={f_sys} "
                       f"{'set' if is_cat else 'bin'} gain {g_full:.6g}, "
                       f"lighter child {short:.3g} under min_child_weight, "
                       f"rounding {rounding:.3g}; "
                       f"reference f={f_ref} b={b_ref} dir={d_ref} gain "
                       f"{best:.6g}"))
        rl, rr = rows[go_left], rows[~go_left]
        sl = (U * abs_g[rl].sum(), U * h[rl].sum())
        sr = (U * abs_g[rr].sum(), U * h[rr].sum())
        el = tuple(max(a, o + b) for a, o, b in zip(sl, own_e, sr))
        er = tuple(max(a, o + b) for a, o, b in zip(sr, own_e, sl))
        stack.append((left, depth + 1, rl, own_e, el))
        stack.append((int(tree["right_children"][node]), depth + 1, rr,
                      own_e, er))
    return delta, rep


def replay_forest(X, y, cuts, forest, kind, *, objective, eta, rounds,
                  max_depth, lam=1.0, min_child_weight=1.0, gamma=0.0,
                  bins=None):
    """Replay the first ``rounds`` rounds of a saved forest from the base
    margin; ``kind`` from ``column_kinds``. Returns ``(margin [n, groups],
    report)``, the per-tree reports summed. ``bins``: ``bin_rows(X, cuts)``
    where the caller has it."""
    if bins is None:
        bins = bin_rows(X, cuts)
    binsT = np.ascontiguousarray(bins.T)
    groups = forest.num_class
    margin = np.full((len(X), groups), forest.base_margin(), np.float64)
    total = {"nodes": 0, "same": 0, "tie": 0, "mismatch": [], "ungrown": [],
             "leaves_checked": 0, "leaf_err": 0.0, "leaf_tol_exceeded": [],
             "mcw_short": 0.0, "mcw_decided": 0, "rounding_tie": 0,
             "cat_splits": 0}
    t = 0
    for _ in range(rounds):
        g, h = grower.gradients(objective, margin, y, groups)
        new = margin.copy()
        for k in range(groups):
            tree = dict(forest.trees[t], _cuts=cuts, _B=cuts.shape[1])
            delta, rep = replay_tree(bins, binsT, g[:, k], h[:, k], tree,
                                     kind, eta=eta, max_depth=max_depth,
                                     lam=lam,
                                     min_child_weight=min_child_weight,
                                     gamma=gamma)
            new[:, k] += delta
            for key in ("nodes", "same", "tie", "leaves_checked",
                        "mcw_decided", "rounding_tie", "cat_splits"):
                total[key] += rep[key]
            for key in ("leaf_err", "mcw_short"):
                total[key] = max(total[key], rep[key])
            for key in ("mismatch", "ungrown", "leaf_tol_exceeded"):
                total[key] += [(t,) + m for m in rep[key]]
            t += 1
        margin = new
    return margin, total


class CatForest:
    """Trees of one saved model with their category sets, as numpy arrays
    per tree (``split_type`` and ``_cats``: {node: the codes that go
    right})."""

    def __init__(self, doc: dict) -> None:
        learner = doc["learner"]
        model = learner["gradient_booster"]["model"]
        self.trees = []
        for t in model["trees"]:
            tree = {k: np.asarray(t[k], dt) for k, dt in (
                ("left_children", np.int64), ("right_children", np.int64),
                ("split_indices", np.int64), ("split_conditions", np.float32),
                ("default_left", np.bool_))}
            n = len(tree["left_children"])
            tree["split_type"] = np.asarray(t.get("split_type", [0] * n),
                                            np.int64)
            cats = np.asarray(t.get("categories", []), np.int64)
            tree["_cats"] = {
                int(node): np.sort(cats[s:s + z])
                for node, s, z in zip(t.get("categories_nodes", []),
                                      t.get("categories_segments", []),
                                      t.get("categories_sizes", []))}
            self.trees.append(tree)
        self.tree_group = np.asarray(model["tree_info"], np.int64)
        lmp = learner["learner_model_param"]
        self.num_class = max(int(lmp.get("num_class", "0")), 1)
        self.base_score = float(lmp["base_score"])
        self.objective = learner["objective"]["name"]

    @classmethod
    def from_bytes(cls, raw: bytes) -> "CatForest":
        return cls(json.loads(raw))

    def base_margin(self) -> float:
        if self.objective in ("binary:logistic", "reg:logistic"):
            p = min(max(self.base_score, 1e-7), 1 - 1e-7)
            return float(np.log(p / (1 - p)))
        return self.base_score

    def leaves(self, X: np.ndarray, tree: int) -> np.ndarray:
        """Leaf node id each row of ``X`` reaches: a numerical node sends
        ``x < split_condition`` left, a categorical one sends a code in its
        set right, a missing value goes by ``default_left``."""
        t = self.trees[tree]
        # in_set[node, code]; the last column holds every code no set has
        top = 1 + max((int(c.max()) for c in t["_cats"].values() if len(c)),
                      default=0)
        in_set = np.zeros((len(t["left_children"]), top + 1), bool)
        for nd, cats in t["_cats"].items():
            in_set[nd, cats] = True
        node = np.zeros(len(X), np.int64)
        rows = np.arange(len(X))
        while True:
            left = t["left_children"][node]
            active = left >= 0
            if not active.any():
                return node
            x = X[rows, t["split_indices"][node]]
            missing = np.isnan(x)
            code = np.where(missing | (x >= top) | (x < 0), top,
                            x).astype(np.int64)
            go_left = np.where(t["split_type"][node] == 1,
                               ~in_set[node, code],
                               x < t["split_conditions"][node])
            go_left = np.where(missing, t["default_left"][node], go_left)
            node = np.where(active, np.where(go_left, left,
                                             t["right_children"][node]),
                            node)

    def margin(self, X: np.ndarray, trees: int | None = None) -> np.ndarray:
        """float32-accumulated margin [n, num_class] over the first
        ``trees`` trees."""
        X = np.asarray(X, np.float32)
        out = np.full((len(X), self.num_class), self.base_margin(),
                      np.float32)
        n = len(self.trees) if trees is None else trees
        for i in range(n):
            leaf = self.leaves(X, i)
            out[:, self.tree_group[i]] += \
                self.trees[i]["split_conditions"][leaf]
        return out
