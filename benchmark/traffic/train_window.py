"""Traffic kind ``train_window``: boost a job's rounds for a fixed window.

What ``xgb.train`` does on the chip when nobody consumes a round's result
(no eval set, no callback): ``Booster.update_many`` in chunks, one device
dispatch a chunk. Set-up builds the data from the seed, the ``DMatrix``
(sketch, bins, one-hot) once, boosts the job's first chunks (trace, compile
or cache load) and checks three rounds on a small sample against the numpy
grower. The window then boosts whole further chunks of the same ``Booster``
and stops when the next chunk would pass ``--seconds``. A traced run stops
when the profiler does: nothing reads what it would boost after that.

The warm-up chunks are the measured ``Booster``'s own: the program keys its
scan program on the objective *instance*, so a throwaway ``Booster`` warms
only the disk cache and a fresh one traces again (PERF.md, PR 22). A job of
R rounds pays that once, and it is counted in ``setup_s``.

Set-up boosts two chunks: a job's second chunk is the first to start from a
chunk's own output and pays for that once (a second's compile on four chips
with a cold cache, PR 22). A traced run traces one chunk.

Mix parameter (``traffic/<mix>.json``): ``chunk``, the rounds a dispatch.
"""

from __future__ import annotations

import contextlib
import os
import time

import numpy as np

from harness import (HERE, BenchFailure, check_health, compile_count,
                     load_module)

_REF = os.path.join(HERE, "reference")
grower = load_module(os.path.join(_REF, "grower.py"))
quality = load_module(os.path.join(_REF, "quality.py"))
walk = load_module(os.path.join(_REF, "walk.py"))

WARMUP_CHUNKS = 2
TRACE_CHUNKS = 1
ORACLE_ROWS = 16_384
ORACLE_ROUNDS = 3
TIE_SHARE_LIMIT = 0.05
MARGIN_LIMIT = 1e-3
LOSS_ROWS = 65_536
HOLDOUT_ROWS = 250_000


def _params(cfg: dict, seed: int) -> dict:
    return dict(cfg["params"], seed=seed)


def _drain(bst, dtrain) -> np.ndarray:
    """Wait for every queued chunk by reading its result back through the
    public API: the training margin of ``dtrain``, which the ``Booster``
    keeps current, so no tree is walked."""
    return bst.predict(dtrain, output_margin=True)


def _train_loss(cfg: dict, margin: np.ndarray, y: np.ndarray) -> float:
    n = min(LOSS_ROWS, len(y))
    m = margin[:n]
    if int(cfg["params"].get("num_class", 0)) > 1:
        return quality.mlogloss_from_margin(m, y[:n])
    return quality.logloss_from_margin(m, y[:n])


def check_against_grower(ctx, xgb, X, y) -> dict:
    """Three rounds through the system's own path on a seeded sample, held
    to the numpy grower given the same cuts: bins equal to
    ``np.searchsorted``; every split the reference's best, or a tie within
    1e-3 of its gain; no leaf above ``max_depth`` that the reference would
    have split; a child of a split short of ``min_child_weight`` by no more
    than ``grower.MCW_RTOL`` of it; leaf values inside the bf16 hi/lo class
    (2^-15 of the sum of |g| they accumulate); margins to 1e-3."""
    cfg = ctx.config
    rng = np.random.default_rng(ctx.seed + 1)
    rows = np.sort(rng.choice(len(X), size=min(ORACLE_ROWS, len(X)),
                              replace=False))
    Xs, ys = np.ascontiguousarray(X[rows]), y[rows]
    params = _params(cfg, ctx.seed)
    d = xgb.DMatrix(Xs, label=ys)
    bst = xgb.Booster(params, [d])
    bst.update_many(d, 0, ORACLE_ROUNDS, chunk=ORACLE_ROUNDS)
    sys_margin = _drain(bst, d)
    forest = walk.Forest.from_bytes(bytes(bst.save_raw("json")))
    binned = d.get_binned(int(params["max_bin"]))
    cuts = np.asarray(binned.cuts.values)
    sys_bins = np.asarray(binned.bins)[:len(ys)]
    if not np.array_equal(sys_bins, grower.bin_rows(Xs, cuts)):
        raise BenchFailure("oracle: the system's bins differ from "
                           "np.searchsorted on its own cuts")
    ref_margin, rep = grower.replay_forest(
        Xs, ys, cuts, forest, objective=params["objective"],
        eta=float(params["eta"]), rounds=ORACLE_ROUNDS,
        max_depth=int(params["max_depth"]),
        lam=float(params.get("lambda", 1.0)),
        min_child_weight=float(params.get("min_child_weight", 1.0)),
        gamma=float(params.get("gamma", params.get("min_split_loss", 0.0))))
    margin_err = float(np.abs(
        sys_margin.reshape(ref_margin.shape) - ref_margin).max())
    out = {"rows": len(ys), "rounds": ORACLE_ROUNDS, "nodes": rep["nodes"],
           "same": rep["same"], "ties": rep["tie"],
           "mismatches": len(rep["mismatch"]),
           "leaves_checked": rep["leaves_checked"],
           "ungrown": len(rep["ungrown"]), "leaf_err": float(rep["leaf_err"]),
           "leaf_tol_exceeded": len(rep["leaf_tol_exceeded"]),
           "margin_err": margin_err, "mcw_short": float(rep["mcw_short"]),
           "mcw_decided": rep["mcw_decided"]}
    ctx.say("oracle (numpy grower, same cuts): " + str(out))
    for m in (rep["mismatch"][:5] + rep["ungrown"][:5]
              + rep["leaf_tol_exceeded"][:5]):
        ctx.say(f"  oracle disagreement: {m}")
    out["ok"] = (not rep["mismatch"] and not rep["ungrown"]
                 and not rep["leaf_tol_exceeded"]
                 and rep["nodes"] > 0
                 and rep["tie"] <= TIE_SHARE_LIMIT * rep["nodes"]
                 and margin_err <= MARGIN_LIMIT)
    return out


def compared_of_oracle(oracle: dict, min_nodes: float = 1) -> dict:
    """The replay's numbers, each beside its limit, for the result line."""
    return {
        "oracle_mismatches": {"value": oracle["mismatches"], "limit": 0},
        "oracle_ungrown": {"value": oracle["ungrown"], "limit": 0},
        "oracle_leaf_tol_exceeded": {"value": oracle["leaf_tol_exceeded"],
                                     "limit": 0},
        "oracle_mcw_short": {"value": oracle["mcw_short"],
                             "limit": grower.MCW_RTOL},
        "oracle_nodes": {"value": oracle["nodes"], "limit_low": min_nodes},
        "oracle_tie_share": {"value": oracle["ties"] / max(oracle["nodes"], 1),
                             "limit": TIE_SHARE_LIMIT},
        "oracle_margin_err": {"value": oracle["margin_err"],
                              "limit": MARGIN_LIMIT}}


def _holdout_quality(ctx, bst, Xh, yh, rounds: int) -> float:
    """The holdout metric of the saved model cut to its first ``rounds``
    rounds, by the numpy walk: the train cells check the trainer, and lean
    on no predictor of the package."""
    cfg = ctx.config
    forest = walk.Forest.from_bytes(bytes(bst.save_raw("json")))
    n = min(len(yh), HOLDOUT_ROWS)
    margin = forest.margin(Xh[:n], trees=rounds * forest.num_class)
    if cfg["quality"]["metric"] == "auc":
        return quality.auc(margin, yh[:n])
    if cfg["quality"]["metric"] == "mlogloss":
        return quality.mlogloss_from_margin(margin, yh[:n])
    raise BenchFailure(f"unknown quality metric {cfg['quality']['metric']!r}")


def run(ctx) -> dict:
    import jax

    import xgboost_tpu as xgb

    cfg, mix = ctx.config, ctx.mix
    chunk = int(mix["chunk"])
    params = _params(cfg, ctx.seed)
    n_tr = int(cfg["data"]["rows_train"])
    t0 = time.perf_counter()
    X, y = ctx.make_data()
    Xtr, ytr = X[:n_tr], y[:n_tr]
    ctx.say(f"data {X.shape} from seed {ctx.seed}: "
            f"{time.perf_counter() - t0:.2f}s")

    if ctx.chips > 1:
        from xgboost_tpu.parallel import make_mesh, mesh_context

        mesh_cm = mesh_context(make_mesh(ctx.chips))
    else:
        mesh_cm = contextlib.nullcontext()

    record: dict = {"chunk": chunk, "rows_train": n_tr,
                    "cols": int(cfg["data"]["cols"]),
                    "max_bin": int(params["max_bin"]),
                    "max_depth": int(params["max_depth"]),
                    "trees_per_round": max(int(params.get("num_class", 0)), 1)}
    with mesh_cm:
        # the big matrix first, on an empty device: the hoist plan reads the
        # free HBM, and must read the same in every run
        t0 = time.perf_counter()
        dtrain = xgb.DMatrix(Xtr, label=ytr)
        binned = dtrain.get_binned(int(params["max_bin"]))
        jax.block_until_ready(binned.bins)
        record["dmatrix_build_s"] = time.perf_counter() - t0
        ctx.say(f"DMatrix + sketch + bins: {record['dmatrix_build_s']:.2f}s")
        if ctx.chips == 1:
            # the resident one-hot, where one chip builds it outside the fit
            # (under a mesh the first chunk builds it: warmup_s has it)
            t0 = time.perf_counter()
            oh = jax.block_until_ready(
                binned.fused_onehot(int(params["max_depth"])))
            record["onehot_build_s"] = time.perf_counter() - t0
            if oh is not None:
                ctx.say(f"resident one-hot {tuple(oh.shape)} {oh.dtype}, "
                        f"{int(oh.shape[1]) // record['max_bin']}/"
                        f"{record['cols']} features hoisted: "
                        f"{record['onehot_build_s']:.2f}s")

        t0 = time.perf_counter()
        bst = xgb.Booster(params, [dtrain])
        first = chunk * WARMUP_CHUNKS
        bst.update_many(dtrain, 0, chunk, chunk=chunk)
        loss_first = _train_loss(cfg, _drain(bst, dtrain), ytr)
        for start in range(chunk, first, chunk):
            bst.update_many(dtrain, start, chunk, chunk=chunk)
            _drain(bst, dtrain)
        record["warmup_s"] = time.perf_counter() - t0
        ctx.say(f"warm-up ({first} rounds in chunks of {chunk}; trace, "
                f"compile or cache load): {record['warmup_s']:.2f}s  train "
                f"loss after the first chunk {loss_first:.5f}")

        t0 = time.perf_counter()
        oracle = check_against_grower(ctx, xgb, Xtr, ytr)
        record["oracle"] = oracle
        record["oracle_s"] = time.perf_counter() - t0
        check_health(ctx, "set-up")

        compiles0 = compile_count()
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
        loss_last = _train_loss(cfg, margin, ytr)

    record.update(rounds=done, first_round=first, window_s=t_last,
                  chunk_s=chunk_s, traced_rounds=done if trace_chunks else 0,
                  loss_first=loss_first, loss_last=loss_last)
    q_rounds = int(cfg["quality"]["rounds"])
    q = _holdout_quality(ctx, bst, X[n_tr:], y[n_tr:], q_rounds)
    lo, hi = cfg["quality"]["band"]
    record["quality"] = {"metric": cfg["quality"]["metric"], "value": q,
                         "rounds": q_rounds, "band": [lo, hi]}
    ctx.say(f"holdout {cfg['quality']['metric']} at {q_rounds} rounds: "
            f"{q:.5f} (band {lo}..{hi});  train loss {loss_first:.5f} -> "
            f"{loss_last:.5f};  {done} rounds in {t_last:.3f}s")
    correct = (oracle["ok"] and lo <= q <= hi
               and loss_last < loss_first)
    compared = dict(compared_of_oracle(oracle), **{
        "holdout_" + cfg["quality"]["metric"]: {"value": q,
                                                "limit": [lo, hi]},
        "train_loss_last": {"value": loss_last, "limit": loss_first}})
    return {"end_to_end": {"train_rounds_per_s": done / t_last},
            "attempted": done, "failed": 0, "correct": correct,
            "compared": compared, "record": record}
