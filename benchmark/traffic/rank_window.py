"""Traffic kind ``rank_window``: boost a ranking job's rounds for a window.

``train_window``'s protocol with query groups: set-up builds documents and
queries from the seed (``generate(...) -> (X, y, qid)``), the ``DMatrix``
with ``qid`` once, boosts the job's first two chunks and checks the program
against the plain references; the window then boosts whole further chunks of
the same ``Booster`` through ``Booster.update_many`` and stops when the next
chunk would pass ``--seconds``; every chunk is drained by
``predict(dtrain, output_margin=True)``. A traced run traces one chunk.

Three checks decide ``correct`` (``benchmark/reference/lambdamart.py`` is
the gradient's and the metric's reference, ``grower.py`` the trees'):

(a) three rounds on a sample of whole queries (65,536 rows or more, the
    largest query among them, so that the program samples pairs: all pairs
    are taken only up to 2^25 elements of ``queries x largest^2``) are
    replayed by the numpy grower on the system's cuts with the reference's
    gradient: every split the reference's best or a tie, no leaf it would
    have split, no child short of ``min_child_weight`` by more than
    ``grower.MCW_RTOL`` of it, leaf values in the bf16 hi/lo class, margins
    to 1e-3. A
    split's gain grows with the rows under it, so the sample's ``gamma`` is
    the configuration's times the sample's share of the rows: the pruning
    stays live and in proportion, and the replay must cover
    ``MIN_NODE_SHARE`` of the full trees' split nodes (under the
    configuration's own ``gamma`` a 65,536-row sample keeps a sixth);
(b) at the timed size, after warm-up, the system's gradient for the next
    iteration from the job's own margin against the reference's over every
    row: max |dg| and max |dh| at most ``GRAD_LIMIT`` of the largest |g|;
(c) holdout NDCG@10 of the first rounds in the configuration's band, the
    untrained model's below the band, and the train NDCG@10 after the last
    chunk above the one after the first.

Before the big matrix is built the sampler is tried on the sample: a
program whose pairs are not the configuration's (its file states the
sampler: ``seed`` enters the key) stops there, in seconds, with exit code 2.
The program's side of the comparison is ``Booster.gradient`` and its counter
``rank_layout_builds_total``: a program without them cannot run the kind.

Mix parameter (``traffic/<mix>.json``): ``chunk``, the rounds a dispatch.
"""

from __future__ import annotations

import os
import time

import numpy as np

from harness import (HERE, BenchFailure, check_health, compile_count,
                     load_module)

_REF = os.path.join(HERE, "reference")
grower = load_module(os.path.join(_REF, "grower.py"))
lambdamart = load_module(os.path.join(_REF, "lambdamart.py"))
walk = load_module(os.path.join(_REF, "walk.py"))
# the protocol is train_window's, to the letter: its constants and helpers
train_window = load_module(os.path.join(HERE, "traffic", "train_window.py"))

WARMUP_CHUNKS = train_window.WARMUP_CHUNKS
TRACE_CHUNKS = train_window.TRACE_CHUNKS
ORACLE_ROUNDS = train_window.ORACLE_ROUNDS
_params = train_window._params
_drain = train_window._drain
ALL_PAIRS_ELEMENTS = 1 << 25  # the program takes all pairs up to this
# check (a)'s sample: four times train_window's, so that depth-6 trees have
# rows to split on under a gamma in proportion (PERF.md section 4)
ORACLE_ROWS = 65_536
# between its two readings (PERF.md section 4): the replay covered 0.82-0.89
# of the full trees' split nodes over the builder's seeds; with the
# configuration's gamma unscaled the same sample keeps 0.17
MIN_NODE_SHARE = 0.55
# between its two readings (PERF.md section 4): float32 sigmoid and sums of
# a handful of terms read 1.6e-6 at most over the builder's chip runs; the
# same program with the discounts from the TPU's own float32 ``1 / log2``
# read 3.8e-5, and a margin kept in bfloat16 reads 0.29 (ranks change)
GRAD_LIMIT = 1e-5
NDCG_K = 10


def _gradient_gap(bst, d, y, gptr, params, iteration: int) -> dict:
    """The gradient the program would boost on in ``iteration``, from the
    job's own cached margin, against the reference's from the same margin."""
    margin = _drain(bst, d).reshape(-1)
    g, h = (np.asarray(a, np.float64) for a in bst.gradient(d, iteration))
    g_ref, h_ref = lambdamart.gradient(
        params["objective"], margin, y, gptr, seed=params["seed"],
        iteration=iteration,
        n_pair=int(params.get("lambdarank_num_pair_per_sample", 1)))
    scale = float(np.abs(g_ref).max())
    return {"rows": len(y), "iteration": iteration, "max_abs_g": scale,
            "dg": float(np.abs(g - g_ref).max()) / scale,
            "dh": float(np.abs(h - h_ref).max()) / scale,
            "limit": GRAD_LIMIT}


def _sample_of_queries(X, y, gptr, seed: int):
    """Whole queries, the largest first and seeded ones after it, until
    ``ORACLE_ROWS`` rows: (X, y, group_ptr) with the queries contiguous."""
    sizes = np.diff(gptr)
    order = np.random.default_rng(seed + 1).permutation(len(sizes))
    big = int(sizes.argmax())
    order = np.concatenate([[big], order[order != big]])
    take = order[:int(np.searchsorted(np.cumsum(sizes[order]),
                                      min(ORACLE_ROWS, sizes.sum()))) + 1]
    take.sort()
    rows = np.concatenate([np.arange(gptr[q], gptr[q + 1]) for q in take])
    sub = np.concatenate([[0], np.cumsum(sizes[take])]).astype(np.int64)
    return np.ascontiguousarray(X[rows]), y[rows], sub


def check_sampler(ctx, xgb, Xs, ys, sub) -> None:
    """The program's sampled pairs against the configuration's, on the
    sample, before anything large is built."""
    params = _params(ctx.config, ctx.seed)
    if (len(sub) - 1) * int(np.diff(sub).max()) ** 2 <= ALL_PAIRS_ELEMENTS:
        raise BenchFailure(
            f"the sample ({len(sub) - 1} queries, largest "
            f"{int(np.diff(sub).max())}) is under the all-pairs threshold: "
            "it would not take the sampled-pair branch")
    d = xgb.DMatrix(Xs, label=ys)
    d.set_group(np.diff(sub))
    bst = xgb.Booster(params, [d])
    gap = _gradient_gap(bst, d, ys, sub, params, iteration=1)
    ctx.say(f"sampler on the sample: {gap}")
    if not (gap["dg"] <= GRAD_LIMIT and gap["dh"] <= GRAD_LIMIT):
        raise BenchFailure(
            "the program's sampled-pair gradient is not the configuration's "
            f"(max |dg| {gap['dg']:.3g}, |dh| {gap['dh']:.3g} of the largest "
            f"|g| on {len(ys)} rows, limit {GRAD_LIMIT}): it cannot run "
            f"{ctx.config['name']}, whose file states the sampler")


def check_against_grower(ctx, xgb, Xs, ys, sub) -> dict:
    """Check (a): ``train_window``'s replay, with the reference's ranking
    gradient in place of ``grower.gradients`` and ``gamma`` in proportion
    to the sample's rows."""
    params = _params(ctx.config, ctx.seed)
    gamma = params["gamma"] = (float(params.get("gamma", 0.0)) * len(ys)
                               / int(ctx.config["data"]["rows_train"]))
    depth = int(params["max_depth"])
    d = xgb.DMatrix(Xs, label=ys)
    d.set_group(np.diff(sub))
    bst = xgb.Booster(params, [d])
    bst.update_many(d, 0, ORACLE_ROUNDS, chunk=ORACLE_ROUNDS)
    sys_margin = _drain(bst, d)
    forest = walk.Forest.from_bytes(bytes(bst.save_raw("json")))
    binned = d.get_binned(int(params["max_bin"]))
    cuts = np.asarray(binned.cuts.values)
    if not np.array_equal(np.asarray(binned.bins)[:len(ys)],
                          grower.bin_rows(Xs, cuts)):
        raise BenchFailure("oracle: the system's bins differ from "
                           "np.searchsorted on its own cuts")
    ref_margin, rep = lambdamart.replay_rounds(
        Xs, ys, sub, cuts, forest, objective=params["objective"],
        seed=params["seed"],
        n_pair=int(params.get("lambdarank_num_pair_per_sample", 1)),
        eta=float(params["eta"]), rounds=ORACLE_ROUNDS, max_depth=depth,
        lam=float(params.get("lambda", 1.0)),
        min_child_weight=float(params.get("min_child_weight", 1.0)),
        gamma=gamma)
    # the resident one-hot the fit streamed: where it is as wide as the
    # timed size's, the replayed levels ran the timed size's kernel and tiles
    oh = binned.fused_onehot(depth)
    full = ORACLE_ROUNDS * ((1 << depth) - 1)
    margin_err = float(np.abs(sys_margin.reshape(-1) - ref_margin).max())
    out = {"rows": len(ys), "queries": len(sub) - 1, "rounds": ORACLE_ROUNDS,
           "gamma": gamma, "hoisted_features":
               0 if oh is None else int(oh.shape[1]) // int(params["max_bin"]),
           "nodes": rep["nodes"], "nodes_of_full_trees": full,
           "same": rep["same"], "ties": rep["tie"],
           "mismatches": len(rep["mismatch"]),
           "leaves_checked": rep["leaves_checked"],
           "ungrown": len(rep["ungrown"]), "leaf_err": float(rep["leaf_err"]),
           "leaf_tol_exceeded": len(rep["leaf_tol_exceeded"]),
           "margin_err": margin_err, "mcw_short": float(rep["mcw_short"]),
           "mcw_decided": rep["mcw_decided"]}
    ctx.say("oracle (numpy grower, same cuts, reference gradient): "
            + str(out))
    for m in (rep["mismatch"][:5] + rep["ungrown"][:5]
              + rep["leaf_tol_exceeded"][:5]):
        ctx.say(f"  oracle disagreement: {m}")
    out["ok"] = (not rep["mismatch"] and not rep["ungrown"]
                 and not rep["leaf_tol_exceeded"]
                 and rep["nodes"] >= MIN_NODE_SHARE * full
                 and rep["tie"] <= train_window.TIE_SHARE_LIMIT * rep["nodes"]
                 and margin_err <= train_window.MARGIN_LIMIT)
    return out


def _layout_builds() -> int:
    """The program's counter ``rank_layout_builds_total``."""
    from xgboost_tpu.observability import REGISTRY

    return sum(int(c.value) for _, c
               in REGISTRY.get("rank_layout_builds_total").series())


def run(ctx) -> dict:
    import jax

    import xgboost_tpu as xgb

    cfg, mix = ctx.config, ctx.mix
    if ctx.chips != 1:
        raise BenchFailure("rank_window runs on one chip: the program has "
                           "no ranking path under a mesh")
    chunk = int(mix["chunk"])
    params = _params(cfg, ctx.seed)
    data = cfg["data"]
    n_tr, cols = int(data["rows_train"]), int(data["cols"])
    gen, law = ctx.generator(), data.get("generator_params", {})
    t0 = time.perf_counter()
    Xtr, ytr, qtr = gen.generate(rows=n_tr, cols=cols, seed=ctx.seed,
                                 queries=int(data["queries_train"]), **law)
    Xh, yh, qh = gen.generate(rows=int(data["rows_holdout"]), cols=cols,
                              seed=ctx.seed + 1,
                              queries=int(data["queries_holdout"]), **law)
    gptr, gptr_h = lambdamart.group_ptr_of(qtr), lambdamart.group_ptr_of(qh)
    ctx.say(f"data {Xtr.shape} in {len(gptr) - 1} queries of "
            f"{int(np.diff(gptr).min())}-{int(np.diff(gptr).max())} and "
            f"{Xh.shape} held out, from seed {ctx.seed}: "
            f"{time.perf_counter() - t0:.2f}s")

    sample = _sample_of_queries(Xtr, ytr, gptr, ctx.seed)
    check_sampler(ctx, xgb, *sample)

    record: dict = {"chunk": chunk, "rows_train": n_tr, "cols": cols,
                    "queries_train": len(gptr) - 1,
                    "max_bin": int(params["max_bin"]),
                    "max_depth": int(params["max_depth"]),
                    "trees_per_round": 1}
    # the big matrix first, on a device that holds nothing large yet: the
    # hoist plan reads the free HBM, and must read the same in every run
    t0 = time.perf_counter()
    dtrain = xgb.DMatrix(Xtr, label=ytr, qid=qtr)
    binned = dtrain.get_binned(int(params["max_bin"]))
    jax.block_until_ready(binned.bins)
    record["dmatrix_build_s"] = time.perf_counter() - t0
    ctx.say(f"DMatrix + sketch + bins: {record['dmatrix_build_s']:.2f}s")
    t0 = time.perf_counter()
    oh = jax.block_until_ready(binned.fused_onehot(int(params["max_depth"])))
    record["onehot_build_s"] = time.perf_counter() - t0
    record["hoisted_features"] = (
        0 if oh is None else int(oh.shape[1]) // record["max_bin"])
    if oh is not None:
        ctx.say(f"resident one-hot {tuple(oh.shape)} {oh.dtype}, "
                f"{record['hoisted_features']}/{cols} features hoisted: "
                f"{record['onehot_build_s']:.2f}s")

    def train_ndcg(margin) -> float:
        return lambdamart.ndcg_at_k(margin.reshape(-1), ytr, gptr, NDCG_K)

    t0 = time.perf_counter()
    bst = xgb.Booster(params, [dtrain])
    first = chunk * WARMUP_CHUNKS
    bst.update_many(dtrain, 0, chunk, chunk=chunk)
    ndcg_first = train_ndcg(_drain(bst, dtrain))
    for start in range(chunk, first, chunk):
        bst.update_many(dtrain, start, chunk, chunk=chunk)
        _drain(bst, dtrain)
    record["warmup_s"] = time.perf_counter() - t0
    ctx.say(f"warm-up ({first} rounds in chunks of {chunk}; trace, compile "
            f"or cache load): {record['warmup_s']:.2f}s  train ndcg@{NDCG_K} "
            f"after the first chunk {ndcg_first:.5f}")

    t0 = time.perf_counter()
    oracle = check_against_grower(ctx, xgb, *sample)
    gap = _gradient_gap(bst, dtrain, ytr, gptr, params, iteration=first)
    ctx.say(f"gradient at the timed size against the reference: {gap}")
    record["oracle"], record["gradient_check"] = oracle, gap
    record["oracle_s"] = time.perf_counter() - t0
    check_health(ctx, "set-up")

    compiles0, builds0 = compile_count(), _layout_builds()
    trace_chunks = TRACE_CHUNKS if ctx.trace else 0
    if trace_chunks:
        ctx.start_trace()
    ctx.window_starts()

    done, t_last, chunk_s = 0, 0.0, []  # the window starts at ``first``
    t_win = time.perf_counter()
    with ctx.span("bench.window"):
        while True:
            t_c = time.perf_counter()
            with ctx.span("bench.update_many"):
                bst.update_many(dtrain, first + done, chunk, chunk=chunk)
            with ctx.span("bench.drain"):
                margin = _drain(bst, dtrain)
            now = time.perf_counter()
            done += chunk
            t_last = now - t_win
            chunk_s.append(now - t_c)
            if trace_chunks:
                # nothing reads what a traced run boosts after this
                if done >= trace_chunks * chunk:
                    break
            elif t_last + chunk_s[-1] > ctx.seconds:
                break
    if trace_chunks:
        ctx.stop_trace()
    record["compiles_in_window"] = compile_count() - compiles0
    record["rank_layout_builds_in_window"] = _layout_builds() - builds0
    record["rank_layout_builds_total"] = _layout_builds()
    ndcg_last = train_ndcg(margin)

    record.update(rounds=done, first_round=first, window_s=t_last,
                  chunk_s=chunk_s, traced_rounds=done if trace_chunks else 0,
                  loss_first=-ndcg_first, loss_last=-ndcg_last)
    q_rounds = int(cfg["quality"]["rounds"])
    forest = walk.Forest.from_bytes(bytes(bst.save_raw("json")))
    q = lambdamart.ndcg_at_k(forest.margin(Xh, trees=q_rounds).reshape(-1),
                             yh, gptr_h, NDCG_K)
    q0 = lambdamart.ndcg_at_k(np.zeros(len(yh)), yh, gptr_h, NDCG_K)
    lo, hi = cfg["quality"]["band"]
    record["quality"] = {"metric": cfg["quality"]["metric"], "value": q,
                         "untrained": q0, "rounds": q_rounds,
                         "band": [lo, hi]}
    ctx.say(f"holdout ndcg@{NDCG_K} at {q_rounds} rounds: {q:.5f} (band "
            f"{lo}..{hi}; untrained {q0:.5f});  train ndcg@{NDCG_K} "
            f"{ndcg_first:.5f} -> {ndcg_last:.5f};  {done} rounds in "
            f"{t_last:.3f}s;  layouts built in the window: "
            f"{record['rank_layout_builds_in_window']}")
    correct = (oracle["ok"]
               and gap["dg"] <= GRAD_LIMIT and gap["dh"] <= GRAD_LIMIT
               and lo <= q <= hi and q0 < lo and ndcg_last > ndcg_first
               and record["rank_layout_builds_in_window"] == 0)
    compared = dict(
        train_window.compared_of_oracle(
            oracle, MIN_NODE_SHARE * oracle["nodes_of_full_trees"]),
        gradient_dg={"value": gap["dg"], "limit": GRAD_LIMIT},
        gradient_dh={"value": gap["dh"], "limit": GRAD_LIMIT},
        holdout_ndcg={"value": q, "limit": [lo, hi]},
        untrained_ndcg={"value": q0, "limit": lo},
        train_ndcg_first={"value": ndcg_first, "limit": ndcg_last},
        layouts_built_in_window={
            "value": record["rank_layout_builds_in_window"], "limit": 0})
    return {"end_to_end": {"train_rounds_per_s": done / t_last},
            "attempted": done, "failed": 0, "correct": correct,
            "compared": compared, "record": record}
