"""The benchmark harness contract: bench.py prints its JSON record(s)
with the driver's schema, each naming the device JAX found (reference
harness analog: tests/benchmark/benchmark_tree.py)."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_watchdog_env():
    """In-process bench.main() calls write the absolute watchdog deadline
    into os.environ; scrub it so later tests/subprocesses don't inherit a
    stale deadline."""
    yield
    os.environ.pop("XGBTPU_BENCH_DEADLINE_AT", None)
    os.environ.pop("XGBTPU_HOIST_BUDGET_MB", None)


def test_bench_produces_json_lines():
    env = dict(os.environ)
    env.pop("XGBTPU_BENCH_DEADLINE_AT", None)  # in-process tests may set it
    env["JAX_PLATFORMS"] = "cpu"
    env["XGBTPU_BENCH_PREDICT_BUDGET"] = "1.0"  # contract, not measurement
    # contract test, not a measurement: skip the smoke run's AOT
    # cost-analysis compiles (tier-1 time budget; tests/test_flight.py
    # covers the export itself)
    env["XGBTPU_COST_ANALYSIS"] = "0"
    # and skip the routed-fleet stage (2 in-process replicas + router):
    # informational partial-only output, covered end-to-end by the CI
    # tier-1.8 fleet lane and tests/test_fleet.py
    env["XGBTPU_BENCH_ROUTED"] = "0"
    # and the paged external-memory stage (~15s of paged rounds):
    # partial-only output, covered by tests/test_data_plane.py and the
    # CI tier-1.5 paged chaos lane
    env["XGBTPU_BENCH_PAGED"] = "0"
    # contract-sized workload (was 20k x 8r: ~75s of 1-core tier-1
    # budget). 12k rows is the floor where the native walker's serving
    # bar still holds (measured 2.7-3.4x at 12k vs ~2x at 6k —
    # the DMatrix path's fixed per-request cost shrinks the ratio at
    # small batches); every other asserted behavior is size-independent.
    out = subprocess.run(
        [sys.executable, "bench.py", "--rows", "12000", "--iterations", "4",
         "--smoke_rows", "1500", "--budget", "120", "--chunk", "2",
         "--tuned_max_bin", "32"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [ln for ln in out.stdout.splitlines() if ln.strip()]
    # training metric first, serving (predict) metric second
    assert len(lines) == 2, out.stdout
    rec = json.loads(lines[0])
    # ISSUE 13 satellite: the BENCH line itself carries the per-stage
    # breakdown and the pipeline depth, so the trajectory file shows
    # where each run spends a round
    assert {"metric", "value", "unit", "vs_baseline"} <= set(rec)
    assert set(rec) <= {"metric", "value", "unit", "vs_baseline",
                        "stages", "pipeline_depth", "dispatch",
                        "ingest_speedup", "device"}
    # every record names the device JAX found (here: the CPU the test
    # forced), so a CPU run can never be read as a chip run
    assert rec["device"]["platform"] == "cpu", rec["device"]
    assert rec["device"]["kind"] and rec["device"]["count"] >= 1
    assert rec["pipeline_depth"] >= 0
    assert rec["stages"] and all(v > 0 for v in rec["stages"].values())
    assert "grow" in rec["stages"], rec["stages"]
    # ISSUE 15: DMatrix construction (sketch + bin) is a measured stage
    # on the BENCH line, and the routed-vs-XLA construction speedup rides
    # along when the native data plane resolved
    assert "ingest" in rec["stages"], rec["stages"]
    from xgboost_tpu.data.quantile import _ensure_sketch_ffi

    if _ensure_sketch_ffi():
        assert rec.get("ingest_speedup", 0) > 1.0, rec
    # ISSUE 14 satellite: the line also carries the routing map (op ->
    # chosen impl) so a perf delta is attributable to the kernel that
    # actually served it. ISSUE 17: when the whole-round tree_grow kernel
    # serves, the per-level ops (level_hist/depth_scan) never resolve and
    # the map instead names the fused route plus its sibling_sub mode.
    route = rec["dispatch"]
    if route.get("tree_grow") == "native":
        assert route.get("sibling_sub") in ("on", "off"), route
    else:
        assert route.get("level_hist") in ("native", "xla", "pallas"), route
        assert route.get("depth_scan") in ("scanned", "unrolled"), route
    assert all(isinstance(v, str) for v in rec["dispatch"].values())
    assert rec["unit"] == "s" and rec["value"] > 0
    assert rec["metric"].startswith("train_time_12kx50_4r_depth6")
    # off-baseline workload (12k != 1M rows): ratio must not pose as speedup
    assert rec["vs_baseline"] == 0.0
    pred = json.loads(lines[1])
    assert {"metric", "value", "unit", "vs_baseline"} <= set(pred)
    assert set(pred) <= {"metric", "value", "unit", "vs_baseline",
                         "served_rows_per_s",
                         "served_sequential_rows_per_s",
                         "concurrent_ge_sequential", "device"}
    assert pred["device"] == rec["device"]
    assert pred["unit"] == "rows/s" and pred["value"] > 0
    assert pred["metric"].startswith("predict_inplace_12kx50")
    assert "parity_failed" not in pred["metric"]
    assert pred["vs_baseline"] > 0
    # the acceptance bar (over the per-request DMatrix path) holds
    # when the native walker is available; without a toolchain the XLA
    # bucket path still runs, just without the order-of-magnitude walk win
    from xgboost_tpu.native import get_serving_lib

    if get_serving_lib() is not None:
        # the walk win is ~10x at serving scale; at this contract-sized
        # shape the measured ratio ranges 2.7-3.4x run-to-run (per-request
        # DMatrix fixed cost dominates and scheduler noise moves both
        # sides), so gate at 2.5x — losing the native walker drops the
        # ratio to ~1x, which this still catches
        assert pred["vs_baseline"] >= 2.5, pred
    # ISSUE 15 satellite: the concurrent micro-batched stream must not
    # fall below the same stream run sequentially. The bench records the
    # hard >= verdict (concurrent_ge_sequential) on the line; THIS gate
    # allows one-core scheduler noise (measured ±10% run-to-run on equal
    # code) while still catching the structural regressions it exists
    # for — the coalescing-window stall (0.65x before the idle
    # fast-path, whose latency contract test_data_plane pins exactly)
    # and cold-bucket compile skew (fixed by the warm passes).
    if "served_rows_per_s" in pred:
        assert pred["served_rows_per_s"] >= \
            0.75 * pred["served_sequential_rows_per_s"], pred


def test_vs_baseline_defined_only_on_baseline_workload():
    """review r5 weak #2: a row-halved run's time divided into the
    1M-row baseline is not a speedup — it must report 0.0."""
    sys.path.insert(0, REPO)
    try:
        import bench
    finally:
        sys.path.remove(REPO)
    assert bench._vs_baseline(100_000, 50, 79.0) == 0.0  # r05 bank's shape
    assert bench._vs_baseline(1_000_000, 40, 18.0) == 0.0  # wrong columns
    assert bench._vs_baseline(1_000_000, 50, 0.0) == 0.0
    assert bench._vs_baseline(1_000_000, 50, 18.005) == 2.0


def test_bench_emits_partial_on_midrun_crash(tmp_path, monkeypatch, capsys):
    """A stage dying AFTER a completed measurement must still emit that
    measurement as the final JSON line (round-3 regression: the tuned run
    crashed and took the completed 256-bin number with it)."""
    monkeypatch.chdir(tmp_path)
    sys.path.insert(0, REPO)
    try:
        import bench
    finally:
        sys.path.remove(REPO)

    def fake_run(args, final):
        final.update({"metric": "train_time_1000kx50_500r_depth6",
                      "value": 12.0, "unit": "s", "vs_baseline": 3.0})
        raise RuntimeError("device lost mid-tuned-run")

    monkeypatch.setattr(bench, "_run_configs", fake_run)
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    bench.main()
    out = capsys.readouterr().out
    lines = [ln for ln in out.splitlines() if ln.strip()]
    assert len(lines) == 1
    rec = json.loads(lines[0])
    assert rec["value"] == 12.0 and rec["vs_baseline"] == 3.0


def test_bench_emits_error_line_when_nothing_measured(tmp_path, monkeypatch,
                                                      capsys):
    monkeypatch.chdir(tmp_path)
    sys.path.insert(0, REPO)
    try:
        import bench
    finally:
        sys.path.remove(REPO)

    def fake_run(args, final):
        raise SystemExit("smoke predict failed")

    monkeypatch.setattr(bench, "_run_configs", fake_run)
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    bench.main()  # must NOT raise
    rec = json.loads(capsys.readouterr().out.strip())
    assert rec["metric"] == "train_time_failed"
    assert set(rec) == {"metric", "value", "unit", "vs_baseline", "device"}
    assert rec["device"] is None  # died before JAX named a device


@pytest.mark.slow  # real-time watchdog waits dominate (~150s wall)
def test_bench_watchdog_emits_on_midrun_hang():
    """The round-4 driver failure mode: the process wedges inside a device
    dispatch AFTER completing measurements, and nothing ever prints. The
    watchdog must emit the best-completed (extrapolated) record and exit 0
    while the main thread is still stuck."""
    env = dict(os.environ)
    env.pop("XGBTPU_BENCH_DEADLINE_AT", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["XGBTPU_BENCH_TEST_HANG"] = "after_chunk"
    env["XGBTPU_BENCH_DEADLINE"] = "150"
    env["XGBTPU_COST_ANALYSIS"] = "0"  # contract test: skip AOT cost pass
    out = subprocess.run(
        [sys.executable, "bench.py", "--rows", "4000", "--columns", "8",
         "--iterations", "6", "--smoke_rows", "2000", "--budget", "120",
         "--chunk", "2", "--tuned_max_bin", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=420,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [ln for ln in out.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, out.stdout
    rec = json.loads(lines[0])
    assert set(rec) == {"metric", "value", "unit", "vs_baseline", "device"}
    # one 2-round chunk of 6 completed before the hang -> extrapolated
    assert "_extrapolated_from_2r" in rec["metric"], rec
    assert rec["value"] > 0
    assert "watchdog: deadline reached" in out.stderr


def test_bench_hoist_ladder_before_row_halving(tmp_path, monkeypatch, capsys):
    """Hard failures first walk the hoist-budget ladder (library default ->
    2048 MB -> disabled) at UNCHANGED row count — a full-scale number with
    a smaller hoist beats a quarter-scale number — and only then halve
    rows."""
    import bench

    monkeypatch.chdir(tmp_path)
    monkeypatch.delenv("XGBTPU_HOIST_BUDGET_MB", raising=False)
    calls = []

    def fake_train(xgb, X, y, params, rounds, budget_s, chunk=25,
                   test_size=0.25, eval_rows=25_000, on_chunk=None):
        b = os.environ.get("XGBTPU_HOIST_BUDGET_MB")
        calls.append((len(X), b))
        if len(X) <= 4000:  # smoke workload: always succeeds
            return rounds, 0.5, 0.9
        if b != "0":  # synthetic chip too small for any resident hoist
            raise RuntimeError("RESOURCE_EXHAUSTED (synthetic)")
        return rounds, 10.0, 0.9

    monkeypatch.setattr(bench, "_train_measured", fake_train)
    monkeypatch.setattr(bench, "_release_device_memory", lambda: None)
    monkeypatch.setattr(bench, "_predict_bench",
                        lambda *a, **kw: None)  # ladder-only test
    monkeypatch.setattr(sys, "argv", [
        "bench.py", "--rows", "20000", "--iterations", "8",
        "--smoke_rows", "4000", "--tuned_max_bin", "0"])
    bench.main()
    out = [ln for ln in capsys.readouterr().out.splitlines() if ln.strip()]
    rec = json.loads(out[0])
    assert "20kx50" in rec["metric"], rec  # rows never halved
    assert rec["value"] == 10.0
    big = [b for (n, b) in calls if n == 20000]
    assert big == [None, "2048", "0"], calls
