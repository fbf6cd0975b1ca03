"""Headline benchmark: synthetic 1M x 50 dense, binary:logistic, 500 rounds.

Mirrors the reference's published benchmark (doc/gpu/index.rst:206-223 and
tests/benchmark/benchmark_tree.py): gpu_hist 12.57s on GTX 1080 Ti,
hist 36.01s on 8-core Ryzen. vs_baseline is speedup over the CPU hist
number (36.01s), the same comparison the reference's table makes — and it
is reported as 0.0 whenever the measured workload is NOT the baseline's
1M x 50 (a row-halved run's ratio against a different workload is not a
speedup; review r5 weak #2).

Prints the training JSON line {"metric", "value", "unit", "vs_baseline"},
then (when the stage completes) ONE more line for the serving benchmark:
batched inplace-predict throughput in rows/s, with vs_baseline = the
inplace/DMatrix-path throughput ratio on the same batch (the serving
speedup this line exists to measure; docs/serving.md). A small-batch
latency sweep (1/16/256/4096 rows) and a concurrent-serving stage (K
client threads of ragged batches through the model server's micro-batcher
vs the same stream sequential: ``predict_served_rows_per_s`` with the
coalescing ratio) go to stderr + the partial sidecar.

Two configurations are measured:
- reference-default (max_bin=256): apples-to-apples with the reference's
  own defaults;
- tpu-tuned (max_bin=64): the TPU-first quantization choice. The level
  histogram's cost on TPU is linear in the bin count (the one-hot
  construction is the VPU floor — tree/hist_kernel.py), and 64 bins is the
  same quality/speed point LightGBM's GPU backend ships by default (63).

The tuned number is only reported as the primary metric when it passes an
AUC-parity gate against the reference-default run AT EQUAL ROUNDS on the
same held-out split (|dAUC| <= 0.002); otherwise the default-config number
is primary. Both timings and AUCs always go to stderr.

The platform is whatever JAX finds: nothing here sets ``JAX_PLATFORMS``,
probes the backend from a child process or re-executes onto another
backend. Every record printed carries ``"device": {platform, kind,
count}`` as JAX reports it, so a CPU run can never be read as a chip run.

Robustness:
- a GLOBAL WATCHDOG (daemon thread, armed first thing in main, deadline
  env-settable via XGBTPU_BENCH_DEADLINE, default 1500s) prints the
  best-completed JSON record and os._exit(0)s even while the main thread
  is wedged inside a device dispatch;
- a tiny smoke run compiles/executes the full pipeline first so backend
  problems surface in seconds;
- each workload is measured INCREMENTALLY in chunks of rounds under a
  wall-clock budget. If the budget runs out, the JSON line still prints,
  with the 500-round time extrapolated from the measured rounds/s and the
  metric name marked "_extrapolated";
- every completed chunk and config is appended to ``bench_partial.jsonl``
  as it happens, and the final JSON line is emitted from whatever was
  measured even when a later stage dies;
- row count halves on hard failure (OOM/backend error) until a measurement
  succeeds, reporting the achieved size in the metric name;
- if literally nothing could be measured, a schema-compatible JSON error
  line is printed and the exit code is still 0.
(Replacing all of this with a table of cells and one runner that fails
without a chip is ROADMAP Queue 1 item 1, a ``benchmark`` PR.)
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
import traceback

import numpy as np

BASELINE_HIST_SECONDS = 36.01  # reference doc/gpu/index.rst: 'hist' on Ryzen 7 2700
BASELINE_ROWS = 1_000_000  # the baseline number's workload shape
BASELINE_COLS = 50


def _vs_baseline(rows: int, cols: int, value: float) -> float:
    """Speedup over the reference hist baseline — defined ONLY on the
    baseline's own workload. A degraded run (rows halved) must report 0.0 rather than a cross-workload ratio that reads like a
    speedup (review r5 weak #2)."""
    if rows != BASELINE_ROWS or cols != BASELINE_COLS or value <= 0:
        return 0.0
    return round(BASELINE_HIST_SECONDS / value, 3)

PARTIAL_PATH = os.environ.get("XGBTPU_BENCH_PARTIAL",
                              "bench_partial.jsonl")

# The record the final JSON line is emitted from. Module-level so the
# watchdog thread can read whatever the measurement loop completed even
# while the main thread is stuck inside a wedged device dispatch.
_FINAL: dict = {}
# The serving (predict) benchmark's record — emitted as a SECOND JSON line
# when the stage completed; never emitted empty, so builds that die before
# the predict stage keep the original one-line contract.
_FINAL_PREDICT: dict = {}
_EMIT_LOCK = threading.Lock()
_EMITTED = False
# --bank rNN: after the contractual emit, write the canonical
# BENCH_rNN.json via the schema-validating ledger writer. Module-level
# (a one-element list, not a latch) so the watchdog's forced emit banks
# the best-completed record too.
_BANK_TAG: list = []


# The device as JAX reports it, noted once by _run_configs and stamped on
# every record printed. Module-level (never re-queried) so the watchdog's
# forced emit cannot block on a wedged backend; None = the run died
# before JAX named a device.
_DEVICE: dict = {}


def _note_device() -> dict:
    import jax

    dev = jax.devices()[0]
    _DEVICE.update(platform=dev.platform, kind=dev.device_kind,
                   count=len(jax.devices()))
    return dict(_DEVICE)


def _stamped(rec: dict) -> dict:
    return {**rec, "device": dict(_DEVICE) if _DEVICE else None}


def _emit_final_once() -> None:
    """Print the one contractual JSON line, exactly once, from whichever
    thread gets here first (main's finally or the watchdog)."""
    with _EMIT_LOCK:
        _emit_locked()


def _emit_locked() -> None:
    global _EMITTED
    if _EMITTED:
        return
    _EMITTED = True
    rec = _stamped(dict(_FINAL) if _FINAL else {
        "metric": "train_time_failed", "value": 0.0,
        "unit": "s", "vs_baseline": 0.0})
    sys.stdout.write(json.dumps(rec) + "\n")
    if _FINAL_PREDICT:
        sys.stdout.write(json.dumps(_stamped(dict(_FINAL_PREDICT))) + "\n")
    sys.stdout.flush()
    if _BANK_TAG:
        _write_bank_locked(_BANK_TAG[0], rec)


def _write_bank_locked(n: int, rec: dict) -> None:
    """Bank the emitted record(s) as BENCH_rNN.json (the protocol in
    docs/perf.md, 'Banking a round'). Validation failure refuses the
    write — a malformed bank would poison the perf ledger — but never
    breaks the bench's own exit."""
    try:
        from xgboost_tpu.observability import ledger

        records = [rec] + ([_stamped(dict(_FINAL_PREDICT))]
                           if _FINAL_PREDICT else [])
        env = os.environ.get("JAX_PLATFORMS")
        cmd = (f"JAX_PLATFORMS={env} " if env else "") \
            + "python bench.py " + " ".join(sys.argv[1:])
        path = ledger.write_bank(os.path.dirname(os.path.abspath(__file__)),
                                 n, cmd, 0 if _FINAL else 1, records)
        print(f"# banked {path}", file=sys.stderr, flush=True)
    except Exception as e:
        print(f"# bank refused: {type(e).__name__}: {e}", file=sys.stderr,
              flush=True)


_WATCHDOG_CANCEL: threading.Event | None = None


def _arm_watchdog() -> float:
    """Daemon thread that emits the best-completed record and hard-exits at
    an ABSOLUTE deadline, carried in the environment as an epoch
    timestamp (XGBTPU_BENCH_DEADLINE_AT). Cancelable:
    main()'s finally disarms, so an in-process caller (the tests) is never
    os._exit'd after main returns — only a genuinely wedged main thread is."""
    global _WATCHDOG_CANCEL
    _cancel_watchdog()
    cancel = _WATCHDOG_CANCEL = threading.Event()

    at = os.environ.get("XGBTPU_BENCH_DEADLINE_AT")
    if at is None:
        budget = float(os.environ.get("XGBTPU_BENCH_DEADLINE", "1500"))
        at = str(time.time() + budget)
        os.environ["XGBTPU_BENCH_DEADLINE_AT"] = at
    deadline_at = float(at)

    def _run():
        while True:
            left = deadline_at - time.time()
            if left <= 0:
                break
            if cancel.wait(min(left, 5.0)):
                return
        # the cancel check and the emit must be atomic with
        # _cancel_watchdog (which sets the event under the same lock):
        # otherwise a cancellation racing the deadline could os._exit an
        # in-process caller that believes main() returned cleanly
        with _EMIT_LOCK:
            if cancel.is_set():
                return
            print("# watchdog: deadline reached; emitting best-completed "
                  "record and exiting", file=sys.stderr, flush=True)
            _emit_locked()
        sys.stderr.flush()
        os._exit(0)

    threading.Thread(target=_run, name="bench-watchdog", daemon=True).start()
    return deadline_at


def _cancel_watchdog() -> None:
    with _EMIT_LOCK:
        if _WATCHDOG_CANCEL is not None:
            _WATCHDOG_CANCEL.set()


def _maybe_test_hang(point: str) -> None:
    """Fault injection for tests/test_bench.py: simulate the real failure
    mode (a dispatch that never returns) at a named point."""
    if os.environ.get("XGBTPU_BENCH_TEST_HANG") == point:
        print(f"# test hook: hanging forever at {point!r}",
              file=sys.stderr, flush=True)
        time.sleep(1e9)


def _log_partial(rec: dict) -> None:
    """Append a progress record to the sidecar file (best effort)."""
    try:
        with open(PARTIAL_PATH, "a") as f:
            f.write(json.dumps(_stamped(rec)) + "\n")
    except OSError:
        pass


def _release_device_memory() -> None:
    """After a hard failure (OOM, backend error), drop EVERY device buffer
    this process still references before retrying smaller: a failed
    attempt's arrays otherwise stay live through lingering caches and keep
    the allocator poisoned, turning one OOM into RESOURCE_EXHAUSTED at
    every subsequent size (observed round 5: the first 1M-row OOM made
    even 1953-row attempts fail). Everything the retry needs is rebuilt
    from host data, so deleting all live arrays and clearing jit caches is
    safe here (and ONLY here — mid-measurement state is still in use)."""
    try:
        import gc

        import jax

        gc.collect()
        arrs = jax.live_arrays()
        freed = 0
        for a in arrs:
            try:
                a.delete()
                freed += 1
            except Exception:
                pass
        jax.clear_caches()
        gc.collect()
        print(f"# released {freed}/{len(arrs)} live device arrays + jit "
              "caches after failure", file=sys.stderr, flush=True)
    except Exception as e:
        print(f"# device-memory release failed: {e}", file=sys.stderr,
              flush=True)


def _make_data(rows: int, cols: int, sparsity: float, seed: int = 42):
    rng = np.random.RandomState(seed)
    X = rng.randn(rows, cols).astype(np.float32)
    if sparsity > 0:
        X[rng.rand(rows, cols) < sparsity] = np.nan
    w = rng.randn(cols).astype(np.float32)
    logits = np.nan_to_num(X) @ w * 0.5
    y = (logits + rng.randn(rows).astype(np.float32) > 0).astype(np.float32)
    return X, y


def _drain(bst, dtrain):
    """Force ALL queued device work to finish (a value readback of the
    margin cache)."""
    entry = bst._caches.get(id(dtrain))
    if entry is not None and entry.margin is not None:
        float(np.asarray(entry.margin[:1, :1]).sum())


def _train_measured(xgb, X, y, params, rounds, budget_s, chunk=25,
                    test_size=0.25, eval_rows=25_000, on_chunk=None):
    """Train up to `rounds` in timed chunks under `budget_s` of wall clock.
    Returns (rounds_done, measured_seconds, auc). Compile time is excluded
    from measured_seconds via a warmup booster running the same chunk-sized
    update_many scan as the measured loop, matching how the reference's
    table times training only. If the scanned program fails anywhere
    (dispatch OR at the drain's value readback), the whole measurement
    restarts once from a fresh booster with per-round updates — the model
    state after a mid-chunk failure is not trustworthy, so no partial
    reuse."""
    n_train = int(len(X) * (1 - test_size))
    dtrain = xgb.DMatrix(X[:n_train], label=y[:n_train])

    def _run(use_scan):
        def _chunk(b, lo, k):
            if use_scan:
                b.update_many(dtrain, lo, k, chunk=k)
            else:
                for i in range(lo, lo + k):
                    b.update(dtrain, i)

        if use_scan:
            # compile-only probe (ISSUE 5 satellite): ONE per-round update
            # on a throwaway booster compiles the level kernels at the
            # real shapes, so a Mosaic rejection surfaces after seconds —
            # before the multi-minute chunk-scan warmup commits the window
            t0 = time.perf_counter()
            probe = xgb.Booster(params, [dtrain])
            probe.update(dtrain, 0)
            _drain(probe, dtrain)
            print(f"# compile probe (1 round incl. binning+compile): "
                  f"{time.perf_counter()-t0:.1f}s", file=sys.stderr,
                  flush=True)
            del probe

        t0 = time.perf_counter()
        warm = xgb.Booster(params, [dtrain])
        _chunk(warm, 0, min(chunk, rounds))
        _drain(warm, dtrain)
        print(f"# warmup (binning+compile+{min(chunk, rounds)} rounds): "
              f"{time.perf_counter()-t0:.1f}s", file=sys.stderr, flush=True)
        del warm

        bst = xgb.Booster(params, [dtrain])
        done = 0
        measured = 0.0
        while done < rounds:
            k = min(chunk, rounds - done)
            t0 = time.perf_counter()
            _chunk(bst, done, k)
            _drain(bst, dtrain)
            measured += time.perf_counter() - t0
            done += k
            print(f"# {done}/{rounds} rounds, {measured:.1f}s "
                  f"({done / measured:.1f} r/s)", file=sys.stderr, flush=True)
            if on_chunk is not None:
                on_chunk(done, measured)
            if measured > budget_s and done < rounds:
                print(f"# wall-clock budget {budget_s}s hit at {done} "
                      "rounds", file=sys.stderr, flush=True)
                break
        return bst, done, measured

    try:
        bst, done, measured = _run(use_scan=True)
    except Exception as e:
        print(f"# scanned training failed ({type(e).__name__}: {e}); "
              "restarting with per-round updates", file=sys.stderr,
              flush=True)
        bst, done, measured = _run(use_scan=False)

    # quality gate on a held-out subset (kept modest so a slow predictor
    # can't eat the budget). A predict failure must NEVER discard the
    # completed training measurement — fall back to smaller eval sizes.
    from xgboost_tpu.metric import create_metric

    auc = float("nan")
    ne = min(eval_rows, len(X) - n_train)
    while ne >= 200:
        try:
            dtest = xgb.DMatrix(X[n_train:n_train + ne])
            t0 = time.perf_counter()
            pred = bst.predict(dtest)
            auc = float(create_metric("auc").evaluate(
                pred, y[n_train:n_train + ne]))
            print(f"# predict+auc on {ne} rows: {time.perf_counter()-t0:.1f}s",
                  file=sys.stderr, flush=True)
            break
        except Exception as e:
            print(f"# predict at {ne} rows failed: {type(e).__name__}: {e}",
                  file=sys.stderr, flush=True)
            ne //= 4
    return done, measured, auc


def _predict_bench(xgb, X, y, args, final_predict: dict) -> None:
    """Serving benchmark stage: batched throughput of the DMatrix predict
    path (fresh DMatrix per request, the naive serving loop) vs zero-copy
    ``inplace_predict``, plus a small-batch latency sweep. Fills
    ``final_predict`` — the second JSONL metric line — whose
    ``vs_baseline`` is the inplace/DMatrix throughput ratio (>= 3x is the
    serving-path acceptance bar). Margin parity between the two paths is
    checked (|diff| < 1e-5) and a failure marks the metric instead of
    reporting a fast-but-wrong number."""
    rows = min(len(X), 100_000)
    Xs = np.ascontiguousarray(X[:rows])
    ys = y[:rows]
    params = {
        "objective": "binary:logistic", "tree_method": args.tree_method,
        "max_depth": args.max_depth, "max_bin": args.max_bin, "eta": 0.1,
        "verbosity": 0,
    }
    rounds = 10  # a serving-sized model: overheads must be visible
    t0 = time.perf_counter()
    d = xgb.DMatrix(Xs, label=ys)
    bst = xgb.train(params, d, rounds)
    print(f"# predict-bench model: {rounds}r on {rows}x{args.columns} "
          f"({time.perf_counter() - t0:.1f}s)", file=sys.stderr, flush=True)

    def dmatrix_once():
        return np.asarray(bst.predict(xgb.DMatrix(Xs)))

    def inplace_once():
        return np.asarray(bst.inplace_predict(Xs))

    # parity first (also warms both compiled paths)
    m_d = np.asarray(bst.predict(xgb.DMatrix(Xs), output_margin=True))
    m_i = np.asarray(bst.inplace_predict(Xs, predict_type="margin"))
    parity = float(np.max(np.abs(m_d.ravel() - m_i.ravel())))
    dmatrix_once()
    inplace_once()

    tp_budget = float(os.environ.get("XGBTPU_BENCH_PREDICT_BUDGET", "3.0"))

    def throughput(fn, min_reps=3):
        reps, t0 = 0, time.perf_counter()
        while True:
            fn()
            reps += 1
            el = time.perf_counter() - t0
            if reps >= min_reps and el > tp_budget:
                return rows * reps / el
    rps_d = throughput(dmatrix_once)
    rps_i = throughput(inplace_once)
    print(f"# predict throughput: dmatrix={rps_d:,.0f} rows/s "
          f"inplace={rps_i:,.0f} rows/s ({rps_i / max(rps_d, 1e-9):.2f}x) "
          f"margin parity {parity:.2e}", file=sys.stderr, flush=True)

    latency = {}
    for bs in (1, 16, 256, 4096):
        if bs > rows:
            continue
        xb = np.ascontiguousarray(Xs[:bs])
        bst.inplace_predict(xb)  # warm the bucket
        reps = 30 if bs <= 256 else 8
        t0 = time.perf_counter()
        for _ in range(reps):
            bst.inplace_predict(xb)
        latency[bs] = (time.perf_counter() - t0) / reps * 1e3
        print(f"# inplace latency {bs} rows: {latency[bs]:.2f} ms",
              file=sys.stderr, flush=True)

    served_info = None
    try:
        served_info = _served_bench(bst, Xs)
    except Exception as e:  # noqa: BLE001 — the server stage must never
        # cost the primary predict metric
        print(f"# served bench failed ({type(e).__name__}: {e}); skipping",
              file=sys.stderr, flush=True)

    if os.environ.get("XGBTPU_BENCH_ROUTED", "1") != "0":
        try:
            _routed_bench(bst, Xs)
        except Exception as e:  # noqa: BLE001 — informational stage
            print(f"# routed bench failed ({type(e).__name__}: {e}); "
                  "skipping", file=sys.stderr, flush=True)

    name = (f"predict_inplace_{rows // 1000}kx{args.columns}_"
            f"{bst.num_boosted_rounds()}r")
    ratio = round(rps_i / max(rps_d, 1e-9), 3)
    if parity > 1e-5:
        name += "_parity_failed"
        ratio = 0.0
        print(f"# predict parity FAILED: {parity:.2e}", file=sys.stderr,
              flush=True)
    final_predict.update({
        "metric": name,
        "value": round(rps_i, 1),
        "unit": "rows/s",
        "vs_baseline": ratio,
    })
    if served_info:
        # the concurrent-vs-sequential serving acceptance rides the
        # predict BENCH line (ISSUE 15 satellite)
        final_predict.update(served_info)
    _log_partial({"config": "predict", "rows": rows,
                  "dmatrix_rps": round(rps_d, 1),
                  "inplace_rps": round(rps_i, 1),
                  "parity": parity,
                  "latency_ms": {str(k): round(v, 3)
                                 for k, v in latency.items()}})


def _served_bench(bst, Xs: np.ndarray, n_threads: int = 8,
                  n_requests: int = 400) -> None:
    """Concurrent-serving stage (ISSUE 8 satellite): the same stream of
    ragged small batches served two ways — sequentially through
    ``inplace_predict`` (the naive loop) and concurrently through the
    model server's micro-batcher from ``n_threads`` client threads. Emits
    ``predict_served_rows_per_s`` to stderr + the partial sidecar with
    the coalescing ratio (requests per compiled-program dispatch)."""
    import threading

    from xgboost_tpu.observability import REGISTRY
    from xgboost_tpu.serving import ModelServer

    def counter(name):
        fam = REGISTRY.get(name)
        return 0.0 if fam is None else fam.labels().value

    rng = np.random.RandomState(11)
    reqs = [(int(lo), int(n)) for lo, n in zip(
        rng.randint(0, max(1, Xs.shape[0] - 64), n_requests),
        rng.randint(1, 65, n_requests))]
    total_rows = sum(n for _, n in reqs)

    # sequential baseline: one caller, one dispatch per request
    def run_sequential():
        t0 = time.perf_counter()
        for lo, n in reqs:
            bst.inplace_predict(Xs[lo:lo + n])
        return time.perf_counter() - t0

    srv = ModelServer(batch_wait_us=500)
    try:
        srv.load("bench", bst)
        srv.predict("bench", Xs[:16])
        shards = [reqs[k::n_threads] for k in range(n_threads)]
        errors = []

        def client(shard):
            try:
                for lo, n in shard:
                    srv.predict("bench", Xs[lo:lo + n], timeout=120)
            except Exception as e:  # noqa: BLE001 — surfaced below
                errors.append(repr(e))

        def run_stream():
            threads = [threading.Thread(target=client, args=(s,))
                       for s in shards]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            return time.perf_counter() - t0

        # untimed warm passes for BOTH paths first: the concurrent
        # clients produce COALESCED batch sizes (row buckets the
        # sequential loop never touches) whose first-touch compiles must
        # not read as serving slowness — same fairness rule as the
        # routed stage. Then the timed passes INTERLEAVE (seq, served,
        # ...) x5, MEAN each: single-core wall clock drifts in phases
        # (frequency/cache state — observed a 1.7x spread on the
        # identical sequential loop across whole-process runs), so
        # alternating exposes both paths to the same phases and the mean
        # compares them over the same wall-clock window.
        run_sequential()
        run_stream()
        if errors:
            raise RuntimeError(f"{len(errors)} warm requests failed: "
                               f"{errors[0]}")
        d0 = counter("serving_dispatches_total")
        b0 = counter("serving_requests_batched_total")
        seq_times, served_times = [], []
        for _ in range(5):
            seq_times.append(run_sequential())
            served_times.append(run_stream())
        seq_s = sum(seq_times) / len(seq_times)
        served_s = sum(served_times) / len(served_times)
        if errors:
            raise RuntimeError(f"{len(errors)} served requests failed: "
                               f"{errors[0]}")
        dispatches = counter("serving_dispatches_total") - d0
        batched = counter("serving_requests_batched_total") - b0
        coalesce = batched / max(dispatches, 1.0)
        # the SLO ledger's view of the same run (ISSUE 9): per-stage
        # p50/p99 says where a served request's time went — queue,
        # coalescing window, or the dispatch itself
        slo = srv.stats()["slo"]
    finally:
        srv.close()
    served_rps = total_rows / max(served_s, 1e-9)
    seq_rps = total_rows / max(seq_s, 1e-9)
    # acceptance (ISSUE 15 satellite): the concurrent micro-batched stream
    # must not fall below the same stream run sequentially — the batcher's
    # idle fast-path exists exactly for this number (a lone request no
    # longer pays the coalescing window)
    concurrent_ok = served_rps >= seq_rps
    print(f"# predict_served_rows_per_s={served_rps:,.0f} "
          f"(sequential {seq_rps:,.0f} rows/s, {n_threads} threads, "
          f"{n_requests} ragged reqs, coalescing {coalesce:.1f} req/dispatch"
          f" over {dispatches:.0f} dispatches)"
          + ("" if concurrent_ok else " CONCURRENT-BELOW-SEQUENTIAL FAILED"),
          file=sys.stderr, flush=True)
    stage_ms = {
        stage: {k: round(v * 1e3, 3) for k, v in qs.items()}
        for stage, qs in slo.get("stages", {}).items()}
    if stage_ms:
        print("# served stage latency (ms): " + "; ".join(
            f"{stage} p50={qs.get('p50', 0)} p99={qs.get('p99', 0)}"
            for stage, qs in stage_ms.items()),
            file=sys.stderr, flush=True)
    _log_partial({"config": "predict_served",
                  "metric": "predict_served_rows_per_s",
                  "value": round(served_rps, 1),
                  "sequential_rows_per_s": round(seq_rps, 1),
                  "concurrent_ge_sequential": concurrent_ok,
                  "threads": n_threads, "requests": n_requests,
                  "rows": total_rows,
                  "coalesce_ratio": round(coalesce, 2),
                  "dispatches": int(dispatches),
                  "stage_latency_ms": stage_ms})
    return {"served_rows_per_s": round(served_rps, 1),
            "served_sequential_rows_per_s": round(seq_rps, 1),
            "concurrent_ge_sequential": concurrent_ok}


def _routed_bench(bst, Xs: np.ndarray, n_threads: int = 4,
                  n_requests: int = 160) -> None:
    """Routed-fleet stage (ISSUE 11 satellite): the PR-7 concurrent
    ragged client stream through the consistent-hash router over TWO
    in-process replicas vs the same stream sent directly to one replica
    over the identical TCP JSONL protocol. Informational on this 1-core
    container (router + replicas + clients share the core, so routed
    throughput measures protocol overhead, not fleet scaling) —
    PARITY-gated, not speed-gated: every routed answer must match
    ``inplace_predict`` bit-for-float. Emits routed/direct rows/s and the
    re-route count to stderr + the partial sidecar. ``XGBTPU_BENCH_ROUTED=0``
    skips the stage (the tier-1 bench contract test does; the CI fleet
    lane covers this path end-to-end)."""
    import socket
    import tempfile
    import threading

    from xgboost_tpu.observability import REGISTRY
    from xgboost_tpu.serving.fleet import ReplicaEndpoint, Router
    from xgboost_tpu.serving.fleet.supervisor import free_port
    from xgboost_tpu.serving.server import serve_main

    def counter(name):
        fam = REGISTRY.get(name)
        return 0.0 if fam is None else fam.labels().value

    tmp = tempfile.mkdtemp(prefix="bench_fleet_")
    mpath = os.path.join(tmp, "model.json")
    bst.save_model(mpath)
    manifest = os.path.join(tmp, "manifest.json")

    ports = [free_port(), free_port()]
    for k, port in enumerate(ports):
        threading.Thread(target=serve_main, args=(
            ["--port", str(port), "--model", f"bench={mpath}",
             "--manifest", manifest, "--batch-wait-us", "500"],),
            kwargs={"stdout": open(os.devnull, "w")}, daemon=True).start()
    deadline = time.perf_counter() + 60
    for port in ports:  # READY = the replica accepts and answers a ping
        while True:
            try:
                with socket.create_connection(("127.0.0.1", port),
                                              timeout=1) as c:
                    c.sendall(b'{"op": "ping"}\n')
                    if c.recv(1 << 12):
                        break
            except OSError:
                if time.perf_counter() > deadline:
                    raise RuntimeError("fleet replicas never came up")
                time.sleep(0.1)

    rng = np.random.RandomState(13)
    reqs = [(int(lo), int(n)) for lo, n in zip(
        rng.randint(0, max(1, Xs.shape[0] - 64), n_requests),
        rng.randint(1, 65, n_requests))]
    total_rows = sum(n for _, n in reqs)
    ref = np.asarray(bst.inplace_predict(Xs), np.float64)

    def stream(send):
        """Drive the request stream from n_threads clients through
        ``send(msg) -> response``; returns (seconds, worst parity)."""
        errors, parity = [], [0.0]
        shards = [reqs[k::n_threads] for k in range(n_threads)]

        def client(shard):
            try:
                for lo, n in shard:
                    r = send({"op": "predict", "model": "bench",
                              "data": Xs[lo:lo + n].tolist(),
                              "timeout_s": 120.0})
                    if "result" not in r:
                        errors.append(r)
                        continue
                    d = float(np.max(np.abs(
                        np.asarray(r["result"], np.float64).ravel()
                        - ref[lo:lo + n].ravel())))
                    parity[0] = max(parity[0], d)
            except Exception as e:  # noqa: BLE001 — surfaced below
                errors.append(repr(e))

        threads = [threading.Thread(target=client, args=(s,))
                   for s in shards]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        el = time.perf_counter() - t0
        if errors:
            raise RuntimeError(f"{len(errors)} routed requests failed: "
                               f"{errors[0]}")
        return el, parity[0]

    router = Router(
        [ReplicaEndpoint(f"r{k}", "127.0.0.1", p)
         for k, p in enumerate(ports)], health_interval_s=0.25).start()
    direct = ReplicaEndpoint("direct", "127.0.0.1", ports[0])
    try:
        # warm both paths (first-touch compiles must not skew either)
        stream(lambda m: direct.rpc(m, 120.0))
        r0 = counter("fleet_reroutes_total")
        direct_s, parity_d = stream(lambda m: direct.rpc(m, 120.0))
        routed_s, parity_r = stream(lambda m: router.handle(m))
        reroutes = counter("fleet_reroutes_total") - r0
    finally:
        router.stop()
        for port in ports:
            try:
                with socket.create_connection(("127.0.0.1", port),
                                              timeout=5) as c:
                    c.sendall(b'{"op": "shutdown"}\n')
                    c.recv(1 << 12)
            except OSError:
                pass
        direct.reset()
    routed_rps = total_rows / max(routed_s, 1e-9)
    direct_rps = total_rows / max(direct_s, 1e-9)
    parity = max(parity_d, parity_r)
    parity_ok = parity < 1e-6
    print(f"# predict_routed_rows_per_s={routed_rps:,.0f} "
          f"(direct single-server {direct_rps:,.0f} rows/s, "
          f"{n_threads} threads, {n_requests} ragged reqs, 2 replicas, "
          f"{reroutes:.0f} re-routes, parity {parity:.2e}"
          + ("" if parity_ok else " PARITY FAILED") + ")",
          file=sys.stderr, flush=True)
    _log_partial({"config": "predict_routed",
                  "metric": "predict_routed_rows_per_s",
                  "value": round(routed_rps, 1) if parity_ok else 0.0,
                  "direct_rows_per_s": round(direct_rps, 1),
                  "threads": n_threads, "requests": n_requests,
                  "rows": total_rows, "replicas": 2,
                  "reroutes": int(reroutes),
                  "parity": parity, "parity_ok": parity_ok})


def _ingest_bench(X: np.ndarray, max_bin: int) -> float:
    """DMatrix-construction (sketch + bin) speedup of the dispatch-routed
    data plane vs the XLA route at the same shape (ISSUE 15 acceptance:
    >= 3x at 100k x 50 on CPU). Returns the measured speedup (0.0 when the
    routes resolve identically, e.g. the native toolchain is absent)."""
    from xgboost_tpu import dispatch
    from xgboost_tpu.data.quantile import BinnedMatrix

    rows = min(len(X), 100_000)
    Xs = np.ascontiguousarray(X[:rows])

    def build() -> float:
        t0 = time.perf_counter()
        bm = BinnedMatrix.from_dense(Xs, max_bin=max_bin)
        np.asarray(bm.bins)
        return time.perf_counter() - t0

    build()  # warm the active route's compile
    t_fast = min(build(), build())
    route = dispatch.last_decisions().get("sketch_cuts", "?")
    if route == "xla":
        print("# ingest bench: sketch_cuts already resolves to xla "
              "(native toolchain absent?); no speedup to report",
              file=sys.stderr, flush=True)
        return 0.0
    prev = os.environ.get("XGBTPU_DISPATCH")
    os.environ["XGBTPU_DISPATCH"] = (
        (prev + "," if prev else "") + "sketch_cuts=xla,bin_matrix=xla")
    try:
        build()  # warm the XLA route's compile
        t_xla = min(build(), build())  # best-of-2, same as the routed side
    finally:
        if prev is None:
            os.environ.pop("XGBTPU_DISPATCH", None)
        else:
            os.environ["XGBTPU_DISPATCH"] = prev
    speedup = t_xla / max(t_fast, 1e-9)
    print(f"# dmatrix ingest (sketch+bin) {rows // 1000}kx{Xs.shape[1]} "
          f"bin{max_bin}: {route}={t_fast:.3f}s xla={t_xla:.3f}s "
          f"-> {speedup:.2f}x", file=sys.stderr, flush=True)
    _log_partial({"config": "ingest", "rows": rows, "max_bin": max_bin,
                  "route": route,
                  "seconds_routed": round(t_fast, 3),
                  "seconds_xla": round(t_xla, 3),
                  "speedup": round(speedup, 2)})
    return round(speedup, 2)


def _paged_bench(xgb, X: np.ndarray, y: np.ndarray, args) -> dict:
    """Prefetch-overlapped external-memory stage (ISSUE 15): a few paged
    training rounds with the flight split showing the overlap — time
    blocked on an in-flight prefetch (``prefetch_wait``) vs synchronous
    page ingest (``ingest``). Returns the paged-stage flight deltas for
    the BENCH line. ``XGBTPU_BENCH_PAGED=0`` skips the stage."""
    from xgboost_tpu.data.external import ExternalMemoryQuantileDMatrix
    from xgboost_tpu.data.iterator import DataIter
    from xgboost_tpu.observability import flight

    rows = min(len(X), 100_000)
    Xs, ys = np.ascontiguousarray(X[:rows]), y[:rows]
    n_parts = 4
    step = -(-rows // n_parts)

    class _It(DataIter):
        def __init__(self):
            self.i = 0

        def reset(self):
            self.i = 0

        def next(self, input_data):
            if self.i >= n_parts:
                return 0
            lo = self.i * step
            input_data(data=Xs[lo:lo + step], label=ys[lo:lo + step])
            self.i += 1
            return 1

    bin_ = args.tuned_max_bin or args.max_bin
    params = {"objective": "binary:logistic", "tree_method": args.tree_method,
              "max_depth": args.max_depth, "max_bin": bin_, "verbosity": 0}
    stages0 = flight.stage_totals()
    t0 = time.perf_counter()
    d = ExternalMemoryQuantileDMatrix(_It(), max_bin=bin_, page_rows=step)
    rounds = 3
    xgb.train(params, d, rounds, verbose_eval=False)
    wall = time.perf_counter() - t0
    now = flight.stage_totals()
    delta = {k: round(now.get(k, 0.0) - stages0.get(k, 0.0), 3)
             for k in ("ingest", "prefetch_wait")}
    print(f"# paged train {rows // 1000}kx{Xs.shape[1]} bin{bin_} "
          f"{rounds}r ({n_parts} pages): {wall:.1f}s — "
          f"ingest={delta['ingest']:.3f}s "
          f"prefetch_wait={delta['prefetch_wait']:.3f}s "
          "(overlap = reads absorbed by the background decode)",
          file=sys.stderr, flush=True)
    _log_partial({"config": "paged", "rows": rows, "pages": n_parts,
                  "rounds": rounds, "seconds": round(wall, 3),
                  "ingest_s": delta["ingest"],
                  "prefetch_wait_s": delta["prefetch_wait"]})
    return {"prefetch_wait": delta["prefetch_wait"]}


def _report_arithmetic_intensity() -> None:
    """FLOPs / bytes-accessed of the guarded programs compiled so far
    (exported by the cost-analysis probe around the smoke run): the
    number that says whether a kernel is compute- or bandwidth-bound —
    the context every histogram-packing / fusion PR (ROADMAP 3) needs
    next to its timing delta."""
    try:
        from xgboost_tpu.observability import REGISTRY

        flops_fam = REGISTRY.get("xla_cost_flops")
        bytes_fam = REGISTRY.get("xla_cost_bytes_accessed")
        if flops_fam is None or bytes_fam is None:
            return
        by_fn = {}
        for labels, child in flops_fam.series():
            by_fn.setdefault(labels.get("fn", "?"), [0.0, 0.0])[0] = \
                child.value
        for labels, child in bytes_fam.series():
            by_fn.setdefault(labels.get("fn", "?"), [0.0, 0.0])[1] = \
                child.value
        rec = {"config": "cost_analysis"}
        for fn, (fl, by) in sorted(by_fn.items()):
            if fl <= 0 and by <= 0:
                continue
            ai = fl / by if by > 0 else 0.0
            print(f"# cost[{fn}]: {fl:.3e} flops, {by:.3e} bytes, "
                  f"arithmetic intensity {ai:.2f} flop/B",
                  file=sys.stderr, flush=True)
            rec[fn] = {"flops": fl, "bytes": by,
                       "intensity": round(ai, 3)}
        if len(rec) > 1:
            _log_partial(rec)
    except Exception as e:  # telemetry must never dent the bench
        print(f"# cost-analysis report skipped: {e}", file=sys.stderr,
              flush=True)


def _report_stage_breakdown(stages0: dict, label: str) -> dict:
    """Per-stage wall-clock deltas (sketch/grow/eval/checkpoint/sync) from
    the flight recorder since ``stages0`` — where the measured loop's time
    went, by phase (ISSUE 7 satellite). Returns the delta dict so the
    caller can fold it into the BENCH JSONL line itself (ISSUE 13
    satellite: the trajectory file records where each run spends a round,
    not just stderr)."""
    try:
        from xgboost_tpu.observability import flight

        now = flight.stage_totals()
        delta = {k: round(now.get(k, 0.0) - stages0.get(k, 0.0), 3)
                 for k in sorted(set(now) | set(stages0))}
        delta = {k: v for k, v in delta.items() if v > 0}
        if not delta:
            return {}
        print(f"# stage breakdown [{label}]: "
              + " ".join(f"{k}={v:.2f}s" for k, v in delta.items()),
              file=sys.stderr, flush=True)
        _log_partial({"config": f"stages_{label}", "stage_seconds": delta})
        return delta
    except Exception as e:
        print(f"# stage breakdown skipped: {e}", file=sys.stderr,
              flush=True)
        return {}


def _run_configs(args, final: dict) -> None:
    """The measurement body. Mutates ``final`` (the record the caller's
    ``finally`` prints) after every completed stage so a crash at ANY later
    point still reports the best completed measurement."""
    import xgboost_tpu as xgb
    from xgboost_tpu.config import enable_compile_cache

    # persistent compile cache (TPU backend only): at
    # JAX_COMPILATION_CACHE_DIR when set, else <checkout>/.jax_cache
    print(f"# device: {json.dumps(_note_device())}  compile cache: "
          f"{enable_compile_cache()}", file=sys.stderr, flush=True)

    def params_for(max_bin):
        return {
            "objective": "binary:logistic",
            "tree_method": args.tree_method,
            "max_depth": args.max_depth,
            "max_bin": max_bin,
            "eta": 0.1,
            # INFO level so the session log records which kernel path ran
            # (e.g. the hoisted one-hot activation line)
            "verbosity": 2,
        }

    def set_final(rows, done, measured, bin_suffix):
        """Fold a completed (possibly partial) measurement into the final
        record; extrapolate when fewer than the full rounds ran."""
        if done <= 0 or measured <= 0:
            return
        name = (f"train_time_{rows // 1000}kx{args.columns}_"
                f"{args.iterations}r_depth{args.max_depth}{bin_suffix}")
        if done == args.iterations:
            value = measured
        else:
            value = args.iterations * measured / done
            name += f"_extrapolated_from_{done}r"
        final.update({
            "metric": name,
            "value": round(value, 3),
            "unit": "s",
            "vs_baseline": _vs_baseline(rows, args.columns, value),
        })

    # ---- smoke: whole pipeline on a tiny shape; failures surface fast ----
    # The smoke run doubles as the XLA cost-analysis probe (ISSUE 7): with
    # XGBTPU_COST_ANALYSIS armed, every guarded program compiled here
    # exports its FLOPs/bytes so the arithmetic-intensity lines below come
    # for free; the flag is dropped afterwards so the measured loops never
    # pay the bookkeeping AOT compiles.
    t0 = time.perf_counter()
    cost_armed = os.environ.get("XGBTPU_COST_ANALYSIS") is None
    if cost_armed:
        os.environ["XGBTPU_COST_ANALYSIS"] = "1"
    smoke_rows = min(args.smoke_rows, args.rows)
    Xs, ys = _make_data(smoke_rows, args.columns, args.sparsity, seed=7)
    sd, ss, sauc = _train_measured(xgb, Xs, ys, params_for(args.max_bin),
                                   rounds=3, budget_s=1e9, chunk=3)
    if cost_armed:
        os.environ.pop("XGBTPU_COST_ANALYSIS", None)
    print(f"# smoke {smoke_rows}x{args.columns} 3r: {ss:.2f}s auc={sauc:.3f} "
          f"(total incl. compile {time.perf_counter() - t0:.1f}s)",
          file=sys.stderr, flush=True)
    _report_arithmetic_intensity()
    if sauc != sauc:
        raise SystemExit("smoke predict failed — predictor is broken")

    # ---- headline workload. The TUNED bin count (64) runs FIRST (ISSUE 5
    # satellite): a short run banks the primary metric before the
    # reference-default (256-bin) gate run, instead of spending the budget
    # on bin256 and dying before the number that matters. The AUC-parity
    # gate still runs — afterwards, demoting the tuned number if it fails.
    rows = args.rows
    tuned_first = bool(args.tuned_max_bin
                       and args.tuned_max_bin != args.max_bin)
    primary_bin = args.tuned_max_bin if tuned_first else args.max_bin
    primary_suffix = f"_bin{primary_bin}" if tuned_first else ""

    def on_chunk_primary(done, measured):
        _log_partial({"config": f"bin{primary_bin}", "rows": rows,
                      "rounds_done": done, "seconds": round(measured, 3)})
        set_final(rows, done, measured, primary_suffix)
        _maybe_test_hang("after_chunk")

    # On hard failure, FIRST step down the hoisted-one-hot HBM budget at
    # unchanged scale (a 1M-row number with a smaller / disabled hoist is
    # worth far more than a quarter-scale number at full hoist) — only
    # then halve rows. Budget 0
    # (construct in-kernel, the round-3 measured configuration) is known
    # to run the full 1M at both bin counts. An externally-set
    # XGBTPU_HOIST_BUDGET_MB disables the ladder. Failure KINDS route
    # through the resilience policy (ISSUE 5): transients retry the SAME
    # configuration (bounded by XGBTPU_RETRY, site "bench_train") before
    # any ladder step — a transient fault must not cost the hoist, let
    # alone half the rows.
    from xgboost_tpu.resilience import policy as res_policy

    hoist_ladder = [None, "2048", "0"]
    hoist_i = 0 if os.environ.get("XGBTPU_HOIST_BUDGET_MB") is None else \
        len(hoist_ladder)
    env_retries = res_policy.retry_budget("bench_train")
    transient_left = 1 if env_retries is None else max(0, env_retries)
    from xgboost_tpu.observability import flight as _flight

    stages0 = _flight.stage_totals()
    while True:
        try:
            X, y = _make_data(rows, args.columns, args.sparsity)
            done, measured, auc = _train_measured(
                xgb, X, y, params_for(primary_bin), args.iterations,
                args.budget, args.chunk, on_chunk=on_chunk_primary)
            break
        except Exception as e:  # OOM / backend error: classify, then act
            kind = res_policy.record_failure("bench_train", e)
            print(f"# {rows} rows failed ({kind}): "
                  f"{type(e).__name__}: {e}", file=sys.stderr, flush=True)
            # chunks completed before a HARD failure are not trustworthy
            # (unlike a clean budget stop): discard them from the record
            final.clear()
            _release_device_memory()
            if kind == res_policy.TRANSIENT and transient_left > 0:
                transient_left -= 1
                print(f"# transient: retrying the SAME configuration "
                      f"({transient_left} transient retries left)",
                      file=sys.stderr, flush=True)
                continue
            if hoist_i + 1 < len(hoist_ladder):
                hoist_i += 1
                os.environ["XGBTPU_HOIST_BUDGET_MB"] = hoist_ladder[hoist_i]
                print(f"# retrying {rows} rows with hoist budget "
                      f"{hoist_ladder[hoist_i]} MB", file=sys.stderr,
                      flush=True)
                continue
            rows //= 2
            if rows < 1000:
                raise SystemExit("benchmark failed at every size")

    rps = done / measured if measured > 0 else 0.0
    print(f"# [max_bin={primary_bin}] rounds/s: {rps:.2f}  test-auc: {auc:.4f}",
          file=sys.stderr, flush=True)
    stages_delta = _report_stage_breakdown(stages0, f"bin{primary_bin}")
    # the BENCH line itself carries the per-stage split + pipeline depth
    # (ISSUE 13 satellite): the trajectory file shows WHERE a round's time
    # went (grow dispatch vs pipeline sync vs sketch/eval), not just that
    # it moved
    if stages_delta:
        final["stages"] = stages_delta
    try:
        from xgboost_tpu.pipeline import pipeline_depth

        final["pipeline_depth"] = pipeline_depth()
    except Exception:
        pass
    try:
        # the routes the run actually took (op -> chosen impl): a perf
        # delta is only attributable when the trajectory file says which
        # kernel served each op (ISSUE 14 satellite)
        from xgboost_tpu import dispatch

        routed = dispatch.last_decisions()
        if routed:
            final["dispatch"] = routed
    except Exception:
        pass
    _log_partial({"config": f"bin{primary_bin}", "rows": rows,
                  "rounds_done": done, "seconds": round(measured, 3),
                  "auc": None if auc != auc else round(auc, 5),
                  "complete": True})
    if auc == auc and auc < 0.55:  # NaN (predict unavailable) skips the gate
        # report the timing but MARK it failed — a quality-failing model's
        # speed must never read as a normal success metric
        set_final(rows, done, measured, primary_suffix)
        final["metric"] += "_quality_failed"
        final["vs_baseline"] = 0.0
        print(f"# model quality check failed: test AUC {auc:.4f}",
              file=sys.stderr, flush=True)
        return
    set_final(rows, done, measured, primary_suffix)

    # ---- reference-default configuration at EQUAL rounds: the AUC-parity
    # gate for the already-banked tuned number. If the tuned run fails
    # parity (or the default is simply faster), the default becomes
    # primary — the same gate as before, decided in the other order.
    if tuned_first:
        try:
            def on_chunk_default(d_done, d_measured):
                _log_partial({"config": f"bin{args.max_bin}",
                              "rows": rows, "rounds_done": d_done,
                              "seconds": round(d_measured, 3)})

            d_done, d_measured, d_auc = _train_measured(
                xgb, X, y, params_for(args.max_bin), done,
                args.budget, args.chunk, on_chunk=on_chunk_default)
            d_rps = d_done / d_measured if d_measured > 0 else 0.0
            print(f"# [max_bin={args.max_bin}] rounds/s: {d_rps:.2f}  "
                  f"test-auc: {d_auc:.4f} (tuned gate: {auc:.4f} >= "
                  f"{d_auc:.4f} - 0.002)", file=sys.stderr, flush=True)
            _log_partial({"config": f"bin{args.max_bin}", "rows": rows,
                          "rounds_done": d_done,
                          "seconds": round(d_measured, 3),
                          "auc": None if d_auc != d_auc else round(d_auc, 5),
                          "complete": True})
            if d_done != done:
                # budget truncated the gate run: no equal-rounds
                # comparison exists — the banked tuned number stands
                print("# gate run truncated by budget; keeping the banked "
                      "tuned metric ungated", file=sys.stderr, flush=True)
            elif (d_auc == d_auc and auc == auc
                    and auc >= d_auc - 0.002 and measured < d_measured):
                print("# tuned config passes AUC parity -> stays primary",
                      file=sys.stderr, flush=True)
            else:
                set_final(rows, d_done, d_measured, "")
                print("# tuned config fails AUC parity (or is slower) -> "
                      "reference-default becomes primary", file=sys.stderr,
                      flush=True)
        except Exception as e:
            print(f"# reference-default gate run failed "
                  f"({type(e).__name__}: {e}); keeping the banked tuned "
                  "metric", file=sys.stderr, flush=True)

    # ---- data-plane stages (ISSUE 15): ingest speedup + paged overlap ----
    try:
        speedup = _ingest_bench(X, primary_bin)
        if speedup:
            final["ingest_speedup"] = speedup
    except Exception as e:  # informational: never dent the train metric
        print(f"# ingest bench failed ({type(e).__name__}: {e}); skipping",
              file=sys.stderr, flush=True)
    if os.environ.get("XGBTPU_BENCH_PAGED", "1") != "0":
        try:
            pg = _paged_bench(xgb, X, y, args)
            extra = {k: v for k, v in pg.items() if v > 0}
            if extra:
                final.setdefault("stages", {}).update(extra)
        except Exception as e:
            print(f"# paged bench failed ({type(e).__name__}: {e}); "
                  "skipping", file=sys.stderr, flush=True)

    # ---- serving benchmark: the second metric line. Never allowed to ----
    # ---- disturb the completed training measurement.                 ----
    try:
        _predict_bench(xgb, X, y, args, _FINAL_PREDICT)
    except Exception as e:
        print(f"# predict bench failed ({type(e).__name__}: {e}); "
              "train metric unaffected", file=sys.stderr, flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1_000_000)
    ap.add_argument("--columns", type=int, default=50)
    ap.add_argument("--iterations", type=int, default=500)
    ap.add_argument("--max_depth", type=int, default=6)
    ap.add_argument("--max_bin", type=int, default=256,
                    help="reference-default configuration")
    ap.add_argument("--tuned_max_bin", type=int, default=64,
                    help="tpu-tuned bin count (0 disables the tuned run)")
    ap.add_argument("--sparsity", type=float, default=0.0)
    ap.add_argument("--tree_method", type=str, default="tpu_hist")
    ap.add_argument("--smoke_rows", type=int, default=20_000)
    ap.add_argument("--budget", type=float, default=300.0,
                    help="wall-clock seconds per measured training loop")
    ap.add_argument("--chunk", type=int, default=25)
    ap.add_argument("--bank", type=str, default="",
                    help="bank the emitted record as BENCH_rNN.json "
                         "(pass rNN or NN; schema-validated — docs/perf.md)")
    args = ap.parse_args()

    global _EMITTED
    _EMITTED = False  # in-process test harnesses call main() repeatedly
    _FINAL.clear()
    _FINAL_PREDICT.clear()
    _BANK_TAG.clear()
    _DEVICE.clear()
    if args.bank:
        try:
            _BANK_TAG.append(int(args.bank.lstrip("rR")))
        except ValueError:
            ap.error(f"--bank {args.bank!r}: expected rNN or NN")

    try:
        try:
            deadline_at = _arm_watchdog()
            print(f"# watchdog armed: {deadline_at - time.time():.0f}s "
                  "until forced emit", file=sys.stderr, flush=True)
        except Exception as e:  # e.g. unparsable deadline env var
            print(f"# watchdog arm failed ({e}); running without it",
                  file=sys.stderr, flush=True)

        _run_configs(args, _FINAL)
    except BaseException as e:
        if isinstance(e, KeyboardInterrupt):
            print("# interrupted", file=sys.stderr, flush=True)
        else:
            traceback.print_exc(file=sys.stderr)
        print(f"# bench stage died: {type(e).__name__}: {e}; emitting best "
              "completed measurement", file=sys.stderr, flush=True)
    finally:
        _cancel_watchdog()
        _emit_final_once()


if __name__ == "__main__":
    main()
