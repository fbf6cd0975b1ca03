"""Traffic kind ``cat_train_window``: a job with categorical columns, boosted
for a fixed window.

What ``xgb.train`` does on the chip with ``enable_categorical`` (the
``DMatrix`` built with ``feature_types``) and no eval set:
``Booster.update_many`` in chunks, one device dispatch a chunk. The
generator says which columns are categorical (its ``feature_types``); the
configuration's ``max_cat_to_onehot`` says which take one-vs-rest splits and
which the sorted-prefix partition. Set-up makes the data from the seed and
the ``DMatrix`` (sketch, bins, the resident one-hot), boosts the job's first
two chunks (trace, compile or cache load), replays the first chunk's trees
over every training row against the numpy categorical grower
(``reference/cat_grower.py``) and, besides, three rounds of a sample. The
window boosts whole further chunks of the same ``Booster``; a traced run
traces one chunk.

**The guard.** Before a row is drawn the kind asks the program whether a
categorical matrix takes the scan chunk (``scan_rounds_supported`` of the
booster's tree model). A program without it would boost every round on the
per-round path, minutes where the cell expects seconds: the run fails at
once instead, with a line that says why. After the warm-up the program's
own ``scan_chunk_seconds`` must have counted every chunk.

``correct`` holds: both replays (every split the reference's best on the
same cuts given the float64 logistic gradient, or a tie within 1e-3 of the
gain, or, at no more than ``ROUNDING_TIE_SHARE`` of the nodes (one at
least), within what the rounding of the node's sums can move two gains
apart; no leaf above ``max_depth`` the reference would have split; a child
short of ``min_child_weight`` by no more than ``MCW_RTOL`` of it; leaf
values in the bf16 hi/lo class; margins to ``MARGIN_LIMIT``), the holdout
AUC of the first rounds inside the configuration's band by the numpy walk
of the category sets, the training loss falling, and categorical splits at
least ``CAT_SHARE_FLOOR`` of the timed chunk's splits by the program's own
counters (``cat_splits_total``, ``splits_total``): the cell measures
categorical splits, or it measures nothing it is for.

Mix parameter (``traffic/<mix>.json``): ``chunk``, the rounds a dispatch.
"""

from __future__ import annotations

import os
import time
import types

import numpy as np

from harness import HERE, BenchFailure, check_health, compile_count, \
    load_module

tw = load_module(os.path.join(HERE, "traffic", "train_window.py"))
cat_grower = load_module(os.path.join(HERE, "reference", "cat_grower.py"))
quality = load_module(os.path.join(HERE, "reference", "quality.py"))

WARMUP_CHUNKS = 2
TRACE_CHUNKS = 1
ORACLE_ROWS = 65_536
ORACLE_ROUNDS = 3
MARGIN_LIMIT = 1e-3
# the share of a replay's nodes (one node at least) whose split is further
# from the reference's best than 1e-3 of the gain but within what the
# rounding of the node's sums can move two gains apart
# (``cat_grower.replay_tree``). Between its two readings at full size on
# the chip: the program reads 1 of 1,575 nodes at most, plain bfloat16
# histograms 12 of 1,575 and 3 of 186 in the sample
ROUNDING_TIE_SHARE = 0.005
CAT_SHARE_FLOOR = 0.5
HOLDOUT_ROWS = 250_000


def _counter(name: str, **labels) -> float | None:
    """A counter of the program's registry summed over the series that
    carry ``labels``; nothing where the program has no such counter."""
    from xgboost_tpu.observability import REGISTRY

    fam = REGISTRY.get(name)
    if fam is None:
        return None
    return float(sum(getattr(c, "value", None) or getattr(c, "count", 0)
                     for lab, c in fam.series()
                     if all(lab.get(k) == v for k, v in labels.items())))


def split_counts() -> dict:
    """The program's split counters: every split, and the categorical
    ones by kind."""
    return {"splits": _counter("splits_total") or 0.0,
            "onehot": _counter("cat_splits_total", kind="onehot") or 0.0,
            "partition": _counter("cat_splits_total", kind="partition")
            or 0.0}


def require_categorical_scan(xgb, params: dict, feature_types) -> None:
    """Fail the run unless the program boosts a matrix with categorical
    columns through the scan chunk."""
    probe = xgb.DMatrix(np.zeros((8, len(feature_types)), np.float32),
                        label=np.zeros(8, np.float32),
                        feature_types=list(feature_types))
    bst = xgb.Booster(params, [probe])
    try:
        bst._configure()
        ok = bst._gbm.scan_rounds_supported(
            types.SimpleNamespace(categorical=(feature_types.index("c"),),
                                  is_paged=False), bst._obj, 1)
    except AttributeError as e:
        raise BenchFailure(f"cannot ask this program whether a categorical "
                           f"matrix takes the scan chunk: {e}") from None
    if not ok:
        raise BenchFailure(
            "this program boosts a matrix with categorical columns one "
            "round at a time, not through the scan chunk: a round of the "
            "cell would take its own dispatches for minutes")


def _kinds(ctx, binned) -> np.ndarray:
    return cat_grower.column_kinds(
        binned.n_features, binned.categorical, binned.cat_counts,
        int(ctx.config["params"].get("max_cat_to_onehot", 4)))


def replay(ctx, X, y, binned, bst, rounds: int, sys_margin, label: str,
           bins=None) -> dict:
    """The first ``rounds`` trees of ``bst``, grown on ``X``, replayed
    against the numpy categorical grower on the system's cuts;
    ``sys_margin`` is the system's margin after them."""
    params = tw._params(ctx.config, ctx.seed)
    forest = cat_grower.CatForest.from_bytes(bytes(bst.save_raw("json")))
    cuts = np.asarray(binned.cuts.values)
    ref_margin, rep = cat_grower.replay_forest(
        X, y, cuts, forest, _kinds(ctx, binned),
        objective=params["objective"], eta=float(params["eta"]),
        rounds=rounds, max_depth=int(params["max_depth"]),
        lam=float(params.get("lambda", 1.0)),
        min_child_weight=float(params.get("min_child_weight", 1.0)),
        gamma=float(params.get("gamma", 0.0)), bins=bins)
    margin_err = float(np.abs(
        sys_margin.reshape(ref_margin.shape) - ref_margin).max())
    out = {"rows": len(y), "rounds": rounds, "nodes": rep["nodes"],
           "cat_splits": rep["cat_splits"], "same": rep["same"],
           "ties": rep["tie"], "mismatches": len(rep["mismatch"]),
           "leaves_checked": rep["leaves_checked"],
           "ungrown": len(rep["ungrown"]), "leaf_err": float(rep["leaf_err"]),
           "leaf_tol_exceeded": len(rep["leaf_tol_exceeded"]),
           "margin_err": margin_err, "mcw_short": float(rep["mcw_short"]),
           "mcw_decided": rep["mcw_decided"],
           "rounding_ties": rep["rounding_tie"]}
    ctx.say(f"{label} (numpy categorical grower, same cuts): {out}")
    for m in (rep["mismatch"][:5] + rep["ungrown"][:5]
              + rep["leaf_tol_exceeded"][:5]):
        ctx.say(f"  {label} disagreement: {m}")
    out["ok"] = (not rep["mismatch"] and not rep["ungrown"]
                 and not rep["leaf_tol_exceeded"] and rep["nodes"] > 0
                 and rep["mcw_short"] <= cat_grower.MCW_RTOL
                 and rep["rounding_tie"] <= rounding_ties_allowed(rep["nodes"])
                 and margin_err <= MARGIN_LIMIT)
    return out


def rounding_ties_allowed(nodes: int) -> int:
    return max(1, int(ROUNDING_TIE_SHARE * nodes))


def check_sample(ctx, xgb, X, y, feature_types) -> dict:
    """Three rounds through the system's own path on a seeded sample of
    ``ORACLE_ROWS`` rows, replayed against the numpy categorical grower."""
    rng = np.random.default_rng(ctx.seed + 1)
    rows = np.sort(rng.choice(len(X), size=min(ORACLE_ROWS, len(X)),
                              replace=False))
    Xs, ys = np.ascontiguousarray(X[rows]), y[rows]
    params = tw._params(ctx.config, ctx.seed)
    d = xgb.DMatrix(Xs, label=ys, feature_types=feature_types)
    bst = xgb.Booster(params, [d])
    bst.update_many(d, 0, ORACLE_ROUNDS, chunk=ORACLE_ROUNDS)
    binned = d.get_binned(int(params["max_bin"]))
    bins = cat_grower.bin_rows(Xs, np.asarray(binned.cuts.values))
    if not np.array_equal(np.asarray(binned.bins)[:len(ys)], bins):
        raise BenchFailure("oracle: the system's bins differ from "
                           "np.searchsorted on its own cuts")
    return replay(ctx, Xs, ys, binned, bst, ORACLE_ROUNDS, tw._drain(bst, d),
                  "oracle", bins=bins)


def compared_of_replay(rep: dict, label: str) -> dict:
    """A replay's numbers, each beside its limit, for the result line."""
    return {
        f"{label}_mismatches": {"value": rep["mismatches"], "limit": 0},
        f"{label}_ungrown": {"value": rep["ungrown"], "limit": 0},
        f"{label}_leaf_tol_exceeded": {"value": rep["leaf_tol_exceeded"],
                                       "limit": 0},
        f"{label}_mcw_short": {"value": rep["mcw_short"],
                               "limit": cat_grower.MCW_RTOL},
        f"{label}_nodes": {"value": rep["nodes"], "limit_low": 1},
        f"{label}_rounding_ties": {
            "value": rep["rounding_ties"],
            "limit": rounding_ties_allowed(rep["nodes"])},
        f"{label}_margin_err": {"value": rep["margin_err"],
                                "limit": MARGIN_LIMIT}}


def holdout_auc(bst, Xh, yh, rounds: int) -> float:
    """Holdout AUC of the saved model cut to its first ``rounds`` rounds,
    by the numpy walk of its category sets."""
    forest = cat_grower.CatForest.from_bytes(bytes(bst.save_raw("json")))
    n = min(len(yh), HOLDOUT_ROWS)
    return quality.auc(forest.margin(Xh[:n], trees=rounds), yh[:n])


def run(ctx) -> dict:
    import jax

    import xgboost_tpu as xgb

    cfg, mix = ctx.config, ctx.mix
    params = tw._params(cfg, ctx.seed)
    feature_types = ctx.generator().feature_types()
    require_categorical_scan(xgb, params, feature_types)

    chunk = int(mix["chunk"])
    n_tr = int(cfg["data"]["rows_train"])
    t0 = time.perf_counter()
    X, y = ctx.make_data()
    Xtr, ytr = X[:n_tr], y[:n_tr]
    ctx.say(f"data {X.shape} from seed {ctx.seed}: "
            f"{time.perf_counter() - t0:.2f}s")

    record: dict = {"chunk": chunk, "rows_train": n_tr,
                    "cols": int(cfg["data"]["cols"]),
                    "categorical_cols": feature_types.count("c"),
                    "max_bin": int(params["max_bin"]),
                    "max_depth": int(params["max_depth"]),
                    "trees_per_round": 1}
    # the big matrix first, on an empty device: the hoist plan reads the
    # free HBM, and must read the same in every run
    t0 = time.perf_counter()
    dtrain = xgb.DMatrix(Xtr, label=ytr, feature_types=feature_types)
    binned = dtrain.get_binned(int(params["max_bin"]))
    jax.block_until_ready(binned.bins)
    record["dmatrix_build_s"] = time.perf_counter() - t0
    kinds = _kinds(ctx, binned)
    record["onehot_cols"] = int((kinds == 1).sum())
    record["partition_cols"] = int((kinds == 2).sum())
    ctx.say(f"DMatrix + sketch + bins: {record['dmatrix_build_s']:.2f}s; "
            f"{record['onehot_cols']} one-vs-rest and "
            f"{record['partition_cols']} partition columns")
    t0 = time.perf_counter()
    oh = jax.block_until_ready(binned.fused_onehot(int(params["max_depth"])))
    record["onehot_build_s"] = time.perf_counter() - t0
    if oh is not None:
        ctx.say(f"resident one-hot {tuple(oh.shape)} {oh.dtype}, "
                f"{int(oh.shape[1]) // record['max_bin']}/{record['cols']} "
                f"features hoisted: {record['onehot_build_s']:.2f}s")

    t0 = time.perf_counter()
    chunks0 = _counter("scan_chunk_seconds") or 0.0
    bst = xgb.Booster(params, [dtrain])
    first = chunk * WARMUP_CHUNKS
    counts0 = split_counts()
    bst.update_many(dtrain, 0, chunk, chunk=chunk)
    margin_first = tw._drain(bst, dtrain)
    counts1 = split_counts()
    loss_first = tw._train_loss(cfg, margin_first, ytr)
    for start in range(chunk, first, chunk):
        bst.update_many(dtrain, start, chunk, chunk=chunk)
        tw._drain(bst, dtrain)
    record["warmup_s"] = time.perf_counter() - t0
    scanned = (_counter("scan_chunk_seconds") or 0.0) - chunks0
    if scanned != WARMUP_CHUNKS:
        raise BenchFailure(f"{scanned:g} of the {WARMUP_CHUNKS} warm-up "
                           "chunks ran as scan programs")
    first_chunk = {k: counts1[k] - counts0[k] for k in counts0}
    cat_share = ((first_chunk["onehot"] + first_chunk["partition"])
                 / max(first_chunk["splits"], 1.0))
    record["first_chunk_splits"] = dict(first_chunk, cat_share=cat_share)
    ctx.say(f"warm-up ({first} rounds in chunks of {chunk}; trace, compile "
            f"or cache load): {record['warmup_s']:.2f}s  train loss after "
            f"the first chunk {loss_first:.5f}; the first chunk's splits "
            f"{record['first_chunk_splits']}")

    # the timed booster's first chunk, every training row, against the
    # reference; then the sample's three rounds
    t0 = time.perf_counter()
    full = replay(ctx, Xtr, ytr, binned, bst, chunk, margin_first,
                  "full replay")
    del margin_first
    record["full_replay"] = full
    record["full_replay_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    oracle = check_sample(ctx, xgb, Xtr, ytr, feature_types)
    record["oracle"] = oracle
    record["oracle_s"] = time.perf_counter() - t0
    check_health(ctx, "set-up")

    compiles0 = compile_count()
    counts0 = split_counts()
    trace_chunks = TRACE_CHUNKS if ctx.trace else 0
    if trace_chunks:
        ctx.start_trace()
    ctx.window_starts()

    done, t_last, chunk_s = 0, 0.0, []
    t_win = time.perf_counter()
    with ctx.span("bench.window"):
        while True:
            t_c = time.perf_counter()
            with ctx.span("bench.update_many"):
                bst.update_many(dtrain, first + done, chunk, chunk=chunk)
            with ctx.span("bench.drain"):
                margin = tw._drain(bst, dtrain)
            now = time.perf_counter()
            done += chunk
            t_last = now - t_win
            chunk_s.append(now - t_c)
            if trace_chunks:
                if done >= trace_chunks * chunk:
                    break
            elif t_last + chunk_s[-1] > ctx.seconds:
                break
    if trace_chunks:
        ctx.stop_trace()
    record["compiles_in_window"] = compile_count() - compiles0
    counts1 = split_counts()
    record["splits_total"] = counts1["splits"] - counts0["splits"]
    record["cat_splits_total"] = {k: counts1[k] - counts0[k]
                                  for k in ("onehot", "partition")}
    loss_last = tw._train_loss(cfg, margin, ytr)
    record.update(rounds=done, first_round=first, window_s=t_last,
                  chunk_s=chunk_s, traced_rounds=done if trace_chunks else 0,
                  loss_first=loss_first, loss_last=loss_last)

    q_rounds = int(cfg["quality"]["rounds"])
    q = holdout_auc(bst, X[n_tr:], y[n_tr:], q_rounds)
    lo, hi = cfg["quality"]["band"]
    record["quality"] = {"metric": "auc", "value": q, "rounds": q_rounds,
                         "band": [lo, hi]}
    ctx.say(f"holdout auc at {q_rounds} rounds: {q:.5f} (band {lo}..{hi});  "
            f"train loss {loss_first:.5f} -> {loss_last:.5f};  {done} rounds "
            f"in {t_last:.3f}s;  window splits {record['splits_total']:g}, "
            f"categorical {record['cat_splits_total']}")
    correct = (full["ok"] and oracle["ok"] and lo <= q <= hi
               and loss_last < loss_first and cat_share >= CAT_SHARE_FLOOR)
    compared = dict(compared_of_replay(full, "full"),
                    **compared_of_replay(oracle, "oracle"), **{
        "holdout_auc": {"value": q, "limit": [lo, hi]},
        "train_loss_last": {"value": loss_last, "limit": loss_first},
        "cat_split_share": {"value": cat_share,
                            "limit_low": CAT_SHARE_FLOOR}})
    return {"end_to_end": {"train_rounds_per_s": done / t_last},
            "attempted": done, "failed": 0, "correct": correct,
            "compared": compared, "record": record}
