"""Traffic kind ``train_eval_window``: one ``xgb.train`` job that watches a
validation metric, timed for a fixed window.

How most jobs are run: ``xgb.train(params, dtrain, evals=[(dholdout,
"holdout")], verbose_eval=False)`` through the package's public entry. An
eval set takes the job off the scan chunks: ``RoundPipeline``, one
``Booster.update`` a round, ``Booster.eval_set`` and a drain every round.
One job from round 0. Its first ``warmup_rounds`` rounds (trace, compile or
cache load, the eval ``DMatrix``'s first walk) are set-up; the window is the
whole rounds that fit ``--seconds`` after them, closed loop (a round starts
when the last one's metric is on the host), counted by a ``TrainingCallback``
that also ends the job. A traced run traces ``traced_rounds`` rounds and the
window ends with them; the job then runs on, untimed, to the configuration's
``quality.rounds`` where it has fewer, so that the band is read at its own
round count.

``correct`` is ``train_window``'s (its helpers are imported): the three-round
replay by the numpy grower on a 16,384-row sample, the holdout metric of the
first ``quality.rounds`` rounds in its band by the numpy walk, the training
loss falling; and the eval path is held to an answer: for ``CHECK_ROUNDS``
rounds drawn from the window, the ``holdout-<metric>`` the job reported is
within ``EVAL_LIMIT`` of the plain metric of the numpy walk's margin over the
same rows and the same trees.

Mix parameters (``traffic/<mix>.json``): ``eval_every`` (1: the package
evaluates every round; no other value is offered), ``eval_set`` (the name
the job reports under), ``eval_rows`` (the holdout's leading rows),
``eval_metric``, ``early_stopping_rounds`` (null), ``warmup_rounds``,
``traced_rounds``.
"""

from __future__ import annotations

import os
import time

import numpy as np
from xgboost_tpu.callback import TrainingCallback

from harness import (HERE, BenchFailure, check_health, compile_count,
                     load_module)

_REF = os.path.join(HERE, "reference")
quality = load_module(os.path.join(_REF, "quality.py"))
walk = load_module(os.path.join(_REF, "walk.py"))
# set-up, replay, band and loss are train_window's, to the letter
train_window = load_module(os.path.join(HERE, "traffic", "train_window.py"))

CHECK_ROUNDS = 3
# between its two readings (PERF.md section 4): the reported metric is
# printed to six decimals and read back, 3.4e-7 at most over the builder's
# chip runs; a margin one tree behind reads 3.8e-3 at round 14 and 3.2e-4
# a round at rounds 61-79 (the job's own successive values)
EVAL_LIMIT = 1e-4
ROUNDS_CAP = 1_000_000  # the callback ends the job, not the round count

_METRICS = {"auc": quality.auc}


class _Window(TrainingCallback):
    """The job's clock. ``after_iteration`` runs when a round's metric is
    on the host (the eval loop drains every round), so the time between two
    calls is one closed-loop round."""

    def __init__(self, ctx, dtrain, ytr, warmup: int, traced: int,
                 last: int) -> None:
        self.ctx, self.dtrain, self.ytr = ctx, dtrain, ytr
        self.warmup, self.traced = warmup, traced
        self.last = last  # the job runs on to this many rounds
        self.round_s: list = []
        self.window_s = 0.0
        self.t_win = self.t_prev = None
        self.compiles = None
        self.loss_first = None
        self._window = self._round = None
        self.open = False

    def before_iteration(self, model, epoch, evals_log) -> bool:
        if self.open:
            self._round = self.ctx.span("bench.round")
            self._round.__enter__()
        return False

    def after_iteration(self, model, epoch, evals_log) -> bool:
        now = time.perf_counter()
        ctx, done = self.ctx, epoch + 1
        if done < self.warmup:
            return False
        if done == self.warmup:
            # set-up ends here
            self.loss_first = train_window._train_loss(
                ctx.config, train_window._drain(model, self.dtrain), self.ytr)
            check_health(ctx, "set-up")
            self.compiles = compile_count()
            if ctx.trace:
                ctx.start_trace()
            ctx.window_starts()
            self._window = ctx.span("bench.window")
            self._window.__enter__()
            self.open = True
            self.t_win = self.t_prev = time.perf_counter()
            return False
        if self.open:
            self._round.__exit__(None, None, None)
            self.round_s.append(now - self.t_prev)
            self.t_prev = now
            self.window_s = now - self.t_win
            full = (len(self.round_s) >= self.traced if ctx.trace else
                    self.window_s + self.round_s[-1] > ctx.seconds)
            if full:
                self._window.__exit__(None, None, None)
                self.open = False
                if ctx.trace:
                    ctx.stop_trace()
                self.compiles = compile_count() - self.compiles
        return not self.open and done >= self.last


def _reported_against_walk(ctx, forest, Xh, yh, reported, rounds) -> dict:
    """The job's reported metric at ``rounds`` (0-based, ascending) against
    the plain metric of the numpy walk's margin over the same trees."""
    metric = _METRICS[ctx.mix["eval_metric"]]
    X = np.asarray(Xh, np.float32)
    margin = np.full(len(X), forest.base_margin(), np.float32)
    out, walked = [], 0
    for r in rounds:
        for t in range(walked, r + 1):  # one tree a round: a binary job
            margin += forest.trees[t]["split_conditions"][forest.leaves(X, t)]
        walked = r + 1
        want = metric(margin, yh)
        out.append({"round": int(r), "reported": reported[r], "walk": want,
                    "gap": abs(reported[r] - want)})
    return {"checked": out, "gap": max(c["gap"] for c in out),
            "limit": EVAL_LIMIT}


def run(ctx) -> dict:
    import jax

    import xgboost_tpu as xgb

    cfg, mix = ctx.config, ctx.mix
    if int(mix["eval_every"]) != 1 or mix["early_stopping_rounds"] is not None:
        raise BenchFailure("train_eval_window evaluates every round and "
                           "stops on its own clock")
    if mix["eval_metric"] not in _METRICS or int(
            cfg["params"].get("num_class", 0)) > 1:
        raise BenchFailure(f"no plain reference here for eval metric "
                           f"{mix['eval_metric']!r} of this objective")
    if ctx.chips != 1:
        raise BenchFailure("train_eval_window is a one-chip kind")
    warmup, traced = int(mix["warmup_rounds"]), int(mix["traced_rounds"])
    name = mix["eval_set"]
    params = dict(train_window._params(cfg, ctx.seed),
                  eval_metric=mix["eval_metric"])
    n_tr = int(cfg["data"]["rows_train"])
    t0 = time.perf_counter()
    X, y = ctx.make_data()
    Xtr, ytr = X[:n_tr], y[:n_tr]
    n_ev = min(int(mix["eval_rows"]), len(y) - n_tr)
    Xh, yh = X[n_tr:n_tr + n_ev], y[n_tr:n_tr + n_ev]
    ctx.say(f"data {X.shape} from seed {ctx.seed}: "
            f"{time.perf_counter() - t0:.2f}s")

    record: dict = {"rows_train": n_tr, "eval_rows": n_ev,
                    "cols": int(cfg["data"]["cols"]),
                    "max_bin": int(params["max_bin"]),
                    "max_depth": int(params["max_depth"]),
                    "trees_per_round": 1, "warmup_rounds": warmup}
    # the big matrix first, on an empty device, as train_window builds it
    t0 = time.perf_counter()
    dtrain = xgb.DMatrix(Xtr, label=ytr)
    binned = dtrain.get_binned(int(params["max_bin"]))
    jax.block_until_ready(binned.bins)
    record["dmatrix_build_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    oh = jax.block_until_ready(binned.fused_onehot(int(params["max_depth"])))
    record["onehot_build_s"] = time.perf_counter() - t0
    ctx.say(f"DMatrix + sketch + bins {record['dmatrix_build_s']:.2f}s; "
            f"resident one-hot "
            f"{None if oh is None else tuple(oh.shape)} "
            f"{record['onehot_build_s']:.2f}s")
    t0 = time.perf_counter()
    dholdout = xgb.DMatrix(Xh, label=yh)
    record["eval_dmatrix_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    oracle = train_window.check_against_grower(ctx, xgb, Xtr, ytr)
    record["oracle"] = oracle
    record["oracle_s"] = time.perf_counter() - t0

    q_rounds = int(cfg["quality"]["rounds"])
    clock = _Window(ctx, dtrain, ytr, warmup, traced,
                    last=max(q_rounds, warmup + 1))
    history: dict = {}
    t0 = time.perf_counter()
    bst = xgb.train(params, dtrain, ROUNDS_CAP, evals=[(dholdout, name)],
                    evals_result=history, verbose_eval=False,
                    callbacks=[clock])
    if clock.open or not clock.round_s:
        raise BenchFailure("the job ended before its window did")
    done, t_last = len(clock.round_s), clock.window_s
    reported = history[name][mix["eval_metric"]]
    margin = train_window._drain(bst, dtrain)
    loss_last = train_window._train_loss(cfg, margin, ytr)
    record.update(
        rounds=done, first_round=warmup, window_s=t_last,
        round_s=clock.round_s, traced_rounds=done if ctx.trace else 0,
        round_first_ms=1e3 * clock.round_s[0],
        round_last_ms=1e3 * clock.round_s[-1],
        rounds_boosted=bst.num_boosted_rounds(),
        compiles_in_window=clock.compiles,
        loss_first=clock.loss_first, loss_last=loss_last,
        job_s=time.perf_counter() - t0)

    q = train_window._holdout_quality(ctx, bst, Xh, yh, q_rounds)
    lo, hi = cfg["quality"]["band"]
    record["quality"] = {"metric": cfg["quality"]["metric"], "value": q,
                         "rounds": q_rounds, "band": [lo, hi]}
    forest = walk.Forest.from_bytes(bytes(bst.save_raw("json")))
    rng = np.random.default_rng(ctx.seed + 2)
    rounds = np.sort(rng.choice(np.arange(warmup, warmup + done),
                                size=min(CHECK_ROUNDS, done), replace=False))
    ev = _reported_against_walk(ctx, forest, Xh, yh, reported, rounds)
    record["eval_check"] = ev
    ctx.say(f"holdout {cfg['quality']['metric']} at {q_rounds} rounds: "
            f"{q:.5f} (band {lo}..{hi});  train loss "
            f"{clock.loss_first:.5f} -> {loss_last:.5f};  {done} rounds in "
            f"{t_last:.3f}s, the first {record['round_first_ms']:.2f} ms, "
            f"the last {record['round_last_ms']:.2f} ms;  reported "
            f"{name}-{mix['eval_metric']} against the numpy walk: "
            f"{ev['checked']}")
    correct = (oracle["ok"] and lo <= q <= hi
               and loss_last < clock.loss_first
               and ev["gap"] <= EVAL_LIMIT)
    compared = dict(train_window.compared_of_oracle(oracle), **{
        "holdout_" + cfg["quality"]["metric"]: {"value": q,
                                                "limit": [lo, hi]},
        "train_loss_last": {"value": loss_last, "limit": clock.loss_first},
        "eval_reported_gap": {"value": ev["gap"], "limit": EVAL_LIMIT}})
    return {"end_to_end": {"train_rounds_per_s": done / t_last},
            "attempted": done, "failed": 0, "correct": correct,
            "compared": compared, "record": record}
