"""Device time of the level-histogram kernels per tree grown, mean chip:
``level_hist_roofline``'s denominator over the trees of the traced window,
so that a multiclass round (one tree a class) and a binary one are compared
tree for tree.

The trees are the program's own count: ``trees_grown_total`` (every path's
label) over ``rounds_total``, the trees it grew a round it boosted in this
process, times the traced rounds. Nothing from a program without the
counter, or where the two counters give no whole number of trees a round
(something other than boosting rounds grew trees)."""


def trees_per_round():
    """Trees a boosting round, from the program's two counters; nothing
    where either is missing or they do not divide."""
    from xgboost_tpu.observability import REGISTRY

    totals = []
    for name in ("trees_grown_total", "rounds_total"):
        fam = REGISTRY.get(name)
        if fam is None:
            return None
        totals.append(sum(int(child.value) for _, child in fam.series()))
    trees, rounds = totals
    if rounds <= 0 or trees <= 0 or trees % rounds:
        return None
    return trees // rounds


def read(summary, record, cell):
    rounds = record.get("traced_rounds")
    if not summary or not rounds or summary.get("level_hist_s", 0) <= 0:
        return None
    per_round = trees_per_round()
    if not per_round:
        return None
    return 1e3 * summary["level_hist_s"] / (rounds * per_round)
