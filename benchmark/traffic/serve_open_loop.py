"""Traffic kind ``serve_open_loop``: a model file behind ``ModelServer``.

Set-up trains the configuration's model from the seed on a sample, saves it
to its file and loads the file into an in-process ``ModelServer`` with
default batcher settings (a deployment serves a file), then warms every row
bucket the window can reach. The window offers requests on a schedule fixed
beforehand (open loop), through ``predict_async(..., deadline_ms=...)``.

The schedule is the cell's own, like a recorded trace that every run
replays: ``rate_rps x seconds`` requests at the order statistics of uniform
arrival times (a Poisson process given its count), with row counts at the
quantile midpoints of a clipped lognormal, shuffled, all drawn from the
mix's ``schedule_seed``. ``--seed`` draws the data, the model and the rows
each request carries. Arrival luck is the widest source of spread in an open
loop near its knee; a replayed schedule leaves the spread to the system.

Mix parameters: ``rate_rps``, ``deadline_ms``, ``schedule_seed``, ``rows_median``,
``rows_sigma``, ``rows_min``, ``rows_max``, ``predict_type`` (``value`` or
``margin``), ``model_rows``, ``model_trees``, ``check_share``,
``check_rows_over``, ``check_tol`` (on the answer as served).
"""

from __future__ import annotations

import os
import statistics
import time

import numpy as np

from harness import (HERE, BenchFailure, check_health, compile_count,
                     load_module)

_REF = os.path.join(HERE, "reference")
walk = load_module(os.path.join(_REF, "walk.py"))

BUCKETS = tuple(1 << k for k in range(4, 14))  # 16 ... 8192, the server's


def request_rows(n: int, mix: dict, rng) -> np.ndarray:
    """``n`` row counts: the (i + 0.5)/n quantiles of the lognormal, clipped,
    in an order drawn from ``rng``."""
    inv = statistics.NormalDist().inv_cdf
    z = np.array([inv((i + 0.5) / n) for i in range(n)])
    rows = np.exp(np.log(mix["rows_median"]) + mix["rows_sigma"] * z)
    rows = np.clip(np.rint(rows), mix["rows_min"], mix["rows_max"])
    return rng.permutation(rows.astype(np.int64))


def schedule(mix: dict, seconds: float, seed: int, pool_rows: int):
    """(due seconds [n], rows [n], offset into the row pool [n]): times and
    sizes from the mix's ``schedule_seed``, offsets from ``seed``."""
    rng = np.random.default_rng(int(mix["schedule_seed"]))
    n = max(int(round(mix["rate_rps"] * seconds)), 1)
    due = np.sort(rng.random(n)) * seconds
    rows = request_rows(n, mix, rng)
    offset = np.random.default_rng(seed + 2).integers(
        0, pool_rows - int(mix["rows_max"]), size=n)
    return due, rows, offset


def histogram_counts(name: str):
    """Bucket bounds and counts of the unlabelled child of a registry
    histogram (None if it does not exist yet)."""
    from xgboost_tpu.observability import REGISTRY

    fam = REGISTRY.get(name)
    if fam is None:
        return None
    for labels, child in fam.series():
        if not labels:
            return tuple(child.buckets), list(child.counts)
    return None


def build_server(ctx, xgb, X, y):
    """Train the model from the seed, save it, load the file into a server
    and warm the row buckets. Returns (server, model name, model path)."""
    from xgboost_tpu.serving.server import ModelServer

    cfg, mix = ctx.config, ctx.mix
    params = dict(cfg["params"], seed=ctx.seed)
    n = int(mix["model_rows"])
    trees = int(mix["model_trees"])
    t0 = time.perf_counter()
    d = xgb.DMatrix(X[:n], label=y[:n])
    bst = xgb.Booster(params, [d])
    bst.update_many(d, 0, trees)
    path = os.path.join(ctx.tmpdir, "model.json")
    bst.save_model(path)
    ctx.say(f"model: {trees} rounds on {n} rows, saved "
            f"({os.path.getsize(path) // 1024} KiB): "
            f"{time.perf_counter() - t0:.2f}s")
    del bst, d
    t0 = time.perf_counter()
    server = ModelServer({"model": path})
    # a dispatch carries at least one request and under twice rows_max
    warm = [b for b in BUCKETS
            if int(mix["rows_min"]) <= b <= 2 * int(mix["rows_max"])]
    for b in warm:
        server.predict("model", X[:b], predict_type=mix["predict_type"])
    ctx.say(f"server: file loaded, row buckets {warm[0]}..{warm[-1]} warm: "
            f"{time.perf_counter() - t0:.2f}s")
    return server, "model", path


def offer(ctx, server, name, pool, due, rows, offset):
    """Send the schedule open loop from this thread. Returns, per request,
    the seconds after the window's start at which it was sent and answered
    (NaN if never), the answers, and the errors by request index. ``done``
    is stamped by the future's callback, on the server's thread, when the
    answer is there."""
    mix = ctx.mix
    n = len(due)
    sent = np.zeros(n)
    done = np.full(n, np.nan)
    futures: list = [None] * n
    errors: dict = {}

    def stamp(i):
        def cb(_):
            done[i] = time.perf_counter()
        return cb

    t0 = time.perf_counter()
    with ctx.span("bench.submit"):
        for i in range(n):
            wait = t0 + due[i] - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            sent[i] = time.perf_counter()
            try:
                fut = server.predict_async(
                    name, pool[offset[i]:offset[i] + rows[i]],
                    deadline_ms=float(mix["deadline_ms"]),
                    predict_type=mix["predict_type"])
            except Exception as e:  # refused at the door: a failed request
                errors[i] = f"{type(e).__name__}: {e}"
                continue
            fut.add_done_callback(stamp(i))
            futures[i] = fut
    answers: list = [None] * n
    with ctx.span("bench.await"):
        give_up = time.perf_counter() + float(mix["deadline_ms"]) / 1e3 + 30.0
        for i, fut in enumerate(futures):
            if fut is None:
                continue
            try:
                answers[i] = fut.result(
                    timeout=max(give_up - time.perf_counter(), 0.1))
            except Exception as e:  # shed, timed out or failed
                errors[i] = f"{type(e).__name__}: {e}"
    return sent - t0, done - t0, answers, errors


def run(ctx) -> dict:
    import xgboost_tpu as xgb

    cfg, mix = ctx.config, ctx.mix
    n_tr = int(cfg["data"]["rows_train"])
    t0 = time.perf_counter()
    X, y = ctx.make_data()
    ctx.say(f"data {X.shape} from seed {ctx.seed}: "
            f"{time.perf_counter() - t0:.2f}s")
    server, name, path = build_server(ctx, xgb, X, y)
    pool = X[n_tr:]  # requests carry held-out rows
    due, rows, offset = schedule(mix, ctx.seconds, ctx.seed, len(pool))
    forest = walk.Forest.from_file(path)
    check_health(ctx, "set-up")
    try:
        server.obs.drain()
        compiles0 = compile_count()
        qw0 = histogram_counts("serving_queue_wait_seconds")
        seq0 = max((r["seq"] for r in server.obs.records()
                    if r.get("t") == "dispatch"), default=-1)
        if ctx.trace:
            ctx.start_trace()
        ctx.window_starts()
        with ctx.span("bench.window"):
            sent, done, answers, errors = offer(
                ctx, server, name, pool, due, rows, offset)
        if ctx.trace:
            ctx.stop_trace()
        server.obs.drain()
        compiles = compile_count() - compiles0
        qw1 = histogram_counts("serving_queue_wait_seconds")
        dispatches = [r for r in server.obs.records()
                      if r.get("t") == "dispatch" and r["seq"] > seq0]
    finally:
        server.close()

    n = len(due)
    latency_ms = (done - due) * 1e3
    answered = ~np.isnan(latency_ms)
    for i in errors:
        answered[i] = False
    in_time = answered & (latency_ms <= float(mix["deadline_ms"]))

    # correctness: a seeded tenth of the requests and every large one,
    # against the numpy walk of the saved file
    rng = np.random.default_rng(ctx.seed + 3)
    pick = (rng.random(n) < float(mix["check_share"])) \
        | (rows > int(mix["check_rows_over"]))
    wrong, checked, worst = 0, 0, 0.0
    t0 = time.perf_counter()
    for i in np.flatnonzero(pick & answered):
        want = forest.predict(pool[offset[i]:offset[i] + rows[i]],
                              mix["predict_type"])
        got = np.asarray(answers[i], np.float64).reshape(want.shape)
        err = float(np.abs(got - want).max())
        worst = max(worst, err)
        checked += 1
        wrong += err > float(mix["check_tol"])
    ctx.say(f"checked {checked} answers ({int(rows[pick & answered].sum())} "
            f"rows) against the numpy walk in {time.perf_counter() - t0:.1f}s:"
            f" {wrong} wrong, max |{mix['predict_type']} diff| {worst:.2e} "
            f"(tolerance {mix['check_tol']})")

    failed = int(n - in_time.sum())
    lat = latency_ms[answered]
    late = (sent - due) * 1e3
    record = {
        "requests": n, "rows_offered": int(rows.sum()),
        "rows_answered": int(rows[answered].sum()),
        "rows_in_time": int(rows[in_time].sum()),
        "errors": len(errors), "error_sample": list(errors.values())[:3],
        "late_answers": int((answered & ~in_time).sum()),
        "checked": checked, "wrong": int(wrong), "max_answer_err": worst,
        "compiles_in_window": compiles,
        "gen_late_p50_ms": float(np.percentile(late, 50)),
        "gen_late_p99_ms": float(np.percentile(late, 99)),
        "backlog_at_window_end": int(
            (answered & (done > ctx.seconds)).sum() + len(errors)),
        "drain_s": float(np.nanmax(done) - ctx.seconds) if answered.any()
        else None,
        "queue_wait_hist": None if qw0 is None or qw1 is None else {
            "buckets": qw1[0],
            "counts": [b - a for a, b in zip(qw0[1], qw1[1])]},
        "dispatches": len(dispatches),
        "dispatch_rows": int(sum(r["rows"] for r in dispatches)),
        "dispatch_reqs": int(sum(r["reqs"] for r in dispatches)),
        "model_trees": int(mix["model_trees"]) * max(forest.num_class, 1),
        "model_depth": int(cfg["params"]["max_depth"]),
        "cols": int(cfg["data"]["cols"]),
        "rate_rps": float(mix["rate_rps"]),
        "deadline_ms": float(mix["deadline_ms"]),
    }
    if len(lat) == 0:
        raise BenchFailure(f"no request was answered: {record}")
    for q in (50, 90, 95, 99):
        record[f"latency_p{q}_ms"] = float(np.percentile(lat, q))
    end_to_end = {
        "serve_p50_ms": record["latency_p50_ms"],
        "serve_p95_ms": record["latency_p95_ms"],
        "serve_rows_per_s": record["rows_in_time"] / ctx.seconds,
    }
    return {"end_to_end": end_to_end, "attempted": n, "failed": failed,
            "correct": wrong == 0 and checked > 0, "record": record}
