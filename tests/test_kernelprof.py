"""Intra-round grow profiler (ISSUE 16): sampling grammar, sampled-round
bit-identity with the production fused driver, grow_detail record shape,
the ≤2% unprofiled-overhead pin, and the grow-report renderer."""

import json
import time

import numpy as np
import pytest

import xgboost_tpu as xgb
from conftest import require_native
from xgboost_tpu.observability import RECORDER, REGISTRY, flight, trace
from xgboost_tpu.observability import kernelprof


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    """No ambient profiling plan, fresh recorder ring per test — the
    profiler env is process-wide and the recorder is always on."""
    monkeypatch.delenv("XGBTPU_KERNEL_PROF", raising=False)
    for var in ("XGBTPU_TRACE", "XGBTPU_FLIGHT"):
        monkeypatch.delenv(var, raising=False)
    RECORDER.reset()
    trace.reset()
    yield
    kernelprof.disarm()  # a failing test must not leave a profile armed
    RECORDER.reset()
    trace.reset()


def _data(n=4000, F=12, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, F).astype(np.float32)
    y = ((X @ rng.randn(F)) > 0).astype(np.float32)
    return X, y


_PARAMS = {"objective": "binary:logistic", "max_depth": 4, "max_bin": 32,
           "verbosity": 0}


# ------------------------------------------------------ sampling grammar

def test_should_sample_every(monkeypatch):
    monkeypatch.setenv("XGBTPU_KERNEL_PROF", "every=2")
    assert [i for i in range(6) if kernelprof.should_sample(i)] == [0, 2, 4]


def test_should_sample_rounds(monkeypatch):
    monkeypatch.setenv("XGBTPU_KERNEL_PROF", "rounds=1,3")
    assert [i for i in range(6) if kernelprof.should_sample(i)] == [1, 3]


def test_unset_never_samples():
    assert not any(kernelprof.should_sample(i) for i in range(100))


@pytest.mark.parametrize("spec", ["", "every", "every=0", "every=x",
                                  "rounds=", "rounds=-1", "sometimes=3"])
def test_malformed_spec_means_off(monkeypatch, spec):
    """A malformed spec must not crash training — the profiler warns once
    and stays off (docs/observability.md grammar)."""
    monkeypatch.setenv("XGBTPU_KERNEL_PROF", spec)
    assert not any(kernelprof.should_sample(i) for i in range(8))


# ------------------------------------------- bit-identity + record shape

def test_sampled_rounds_bit_identical(monkeypatch):
    """THE acceptance pin: a run profiling EVERY round produces byte-for-
    byte the same model as an unprofiled run. The instrumented mirror
    reuses the production level machinery — only sync points differ."""
    X, y = _data()
    d = xgb.DMatrix(X, label=y)
    clean = xgb.train(_PARAMS, d, 5, verbose_eval=False)
    monkeypatch.setenv("XGBTPU_KERNEL_PROF", "every=1")
    profiled = xgb.train(_PARAMS, xgb.DMatrix(X, label=y), 5,
                         verbose_eval=False)
    assert profiled.save_raw() == clean.save_raw(), \
        "profiled rounds diverged from the production fused driver"


def test_grow_detail_record_on_sampled_rounds_only(monkeypatch):
    monkeypatch.setenv("XGBTPU_KERNEL_PROF", "rounds=1,3")
    X, y = _data()
    xgb.train(_PARAMS, xgb.DMatrix(X, label=y), 4, verbose_eval=False)
    rounds = {r["round"]: r for r in RECORDER.records()
              if r.get("t") == "round"}
    assert set(rounds) == {0, 1, 2, 3}
    assert not any("grow_detail" in rounds[i] for i in (0, 2)), \
        "unsampled rounds must not carry grow_detail"
    from xgboost_tpu import dispatch

    expect_route = ("tree_grow"
                    if dispatch.resolve("tree_grow").impl == "native"
                    else "level")
    for i in (1, 3):
        gd = rounds[i]["grow_detail"]
        assert gd["round"] == i and gd["driver"] == kernelprof.DRIVER
        assert gd["trees"] == 1
        # ISSUE 17: the record says which production route the mirror
        # replayed; one-dispatch rounds replay per-level with the
        # sibling-sub FFI entry (default sibling_sub=on)
        assert gd["route"] == expect_route
        assert gd["sibling_sub"] is (expect_route == "tree_grow")
        ops = gd["ops"]
        # depth-4 unrolled mirror: prep + 4x(hist+update) + partition +
        # finalize + leaf_delta = 12 brackets, one sync each
        assert len(ops) == 12 and gd["host_syncs"] == 12, ops
        by_op = {}
        for b in ops:
            by_op.setdefault(b["op"], []).append(b["depth"])
        assert sorted(by_op["level_hist"]) == [0, 1, 2, 3]
        assert sorted(by_op["level_update"]) == [0, 1, 2, 3]
        assert by_op["prep"] == [-1]
        assert by_op["level_partition"] == [4]
        assert by_op["finalize"] == [4] and by_op["leaf_delta"] == [4]
        for b in ops:
            assert b["count"] == 1 and b["impl"]
            assert b["wall_s"] >= 0 and b["host_s"] >= 0
            # fields are independently rounded to 6 decimals
            assert abs(b["wall_s"] - b["host_s"] - b["inflight_s"]) < 2e-6
        assert abs(gd["sum_s"] - sum(b["wall_s"] for b in ops)) < 1e-3


def test_grow_detail_quant_attribution(monkeypatch):
    """ISSUE 19: on the one-dispatch route the record attributes the
    resolved hist_acc impl and — on the quant route — carries the round's
    quantiser grid exponents, matching what _quant_scales computes from
    the round's gradients."""
    from xgboost_tpu import dispatch

    monkeypatch.setenv("XGBTPU_KERNEL_PROF", "rounds=1")
    X, y = _data()
    xgb.train(_PARAMS, xgb.DMatrix(X, label=y), 2, verbose_eval=False)
    rec = next(r for r in RECORDER.records()
               if r.get("t") == "round" and "grow_detail" in r)
    gd = rec["grow_detail"]
    # the CPU route is the whole-tree native kernel: anything else means
    # the native stack did not resolve
    require_native(gd["route"] == "tree_grow", "whole-tree native route")
    expect = dispatch.resolve("hist_acc").impl
    assert gd["hist_acc"] == expect
    if expect == "quant":
        qs = gd["quant_scales"]
        assert set(qs) == {"g_exp", "h_exp"}
        assert all(isinstance(v, int) for v in qs.values()), qs
    else:
        assert gd["quant_scales"] is None


def test_format_grow_detail_quant_route_note():
    """The quant replay advertises itself and its grid in the header."""
    rec = _fake_record()
    rec["hist_acc"] = "quant"
    rec["quant_scales"] = {"g_exp": 18, "h_exp": 19}
    txt = kernelprof.format_grow_detail(rec, grow_s=0.032)
    assert "route=tree_grow (quant replay, scales g=2^-18 h=2^-19)" \
        in txt, txt
    # a float-pinned run renders the sibling-sub note as before
    rec["hist_acc"] = "float"
    txt = kernelprof.format_grow_detail(rec, grow_s=0.032)
    assert "(sibling-sub replay)" in txt


def test_host_sync_counter_and_grow_spans(monkeypatch, tmp_path):
    """The seam's side channels: host_syncs_total{site=} in the metrics
    exposition, and one cat="grow" Chrome span per bracket nested under
    the round (consumed by trace-report's grow breakdown row)."""
    monkeypatch.setenv("XGBTPU_KERNEL_PROF", "rounds=2")
    out = tmp_path / "trace.json"
    monkeypatch.setenv("XGBTPU_TRACE", str(out))
    trace.reset()
    X, y = _data()
    xgb.train(_PARAMS, xgb.DMatrix(X, label=y), 3, verbose_eval=False)
    exp = REGISTRY.exposition()
    for site in ("prep", "level_hist", "level_update", "level_partition",
                 "finalize", "leaf_delta"):
        assert f'host_syncs_total{{site="{site}"}}' in exp, exp[-2000:]
    trace.flush()
    events = trace.load_trace(str(out))
    grow = [e for e in events
            if e.get("ph") == "X" and e.get("cat") == "grow"]
    assert {e["name"] for e in grow} == {
        "grow/prep", "grow/level_hist", "grow/level_update",
        "grow/level_partition", "grow/finalize", "grow/leaf_delta"}
    assert all("depth" in e["args"] and "impl" in e["args"] for e in grow)
    # nested: every grow span falls inside the sampled round's span
    rnd = next(e for e in events if e.get("ph") == "X"
               and e.get("name") == "round"
               and e.get("args", {}).get("iteration") == 2)
    for e in grow:
        assert rnd["ts"] <= e["ts"] and \
            e["ts"] + e["dur"] <= rnd["ts"] + rnd["dur"] + 1, (e, rnd)
    # trace-report renders the breakdown from the same spans
    from xgboost_tpu.observability.report import format_report, summarize
    txt = format_report(summarize(events))
    assert "grow breakdown (kernel-profiled substages):" in txt
    assert "grow/level_hist" in txt


def test_disarm_without_buckets_returns_none():
    kernelprof.arm(7)
    assert kernelprof.active()
    assert kernelprof.disarm() is None  # paged/mesh round: no brackets
    assert not kernelprof.active()


# ------------------------------------------------------------- perf pin

def test_unprofiled_overhead_at_most_2pct_of_round():
    """Acceptance: with XGBTPU_KERNEL_PROF unset the profiler costs one
    env probe per round. Methodology mirrors test_flight's recorder pin:
    per-cycle cost (best of 3 batches) vs the median measured round wall
    of the suite's standard small shape."""
    X, y = _data(n=600, F=6)
    d = xgb.DMatrix(X, label=y)
    xgb.train({"max_depth": 3, "max_bin": 16, "verbosity": 0}, d, 30,
              verbose_eval=False)
    walls = [r["wall_s"] for r in RECORDER.records()
             if r.get("t") == "round"][-30:]
    round_s = sorted(walls)[len(walls) // 2]
    per_cycle = float("inf")
    for _ in range(3):
        n = 1000
        t0 = time.perf_counter()
        for i in range(n):
            kernelprof.should_sample(i)
            kernelprof.active()
        per_cycle = min(per_cycle, (time.perf_counter() - t0) / n)
    assert per_cycle < 0.02 * round_s, (
        f"kernelprof per-round probe {per_cycle * 1e6:.1f}us exceeds 2% "
        f"of a {round_s * 1e3:.2f}ms round")


# ----------------------------------------------------------- grow-report

def _fake_record(round_idx=3, route="tree_grow", hist_wall=0.02):
    return {
        "round": round_idx, "driver": kernelprof.DRIVER, "trees": 1,
        "route": route, "sibling_sub": route == "tree_grow",
        "host_syncs": 3, "sum_s": 0.01 + hist_wall, "gap_s": 0.001,
        "ops": [
            {"op": "prep", "depth": -1, "impl": "xla", "count": 1,
             "wall_s": 0.01, "host_s": 0.009, "inflight_s": 0.001,
             "gap_s": 0.0},
            {"op": "level_hist", "depth": 0, "impl": "native", "count": 1,
             "wall_s": hist_wall, "host_s": hist_wall - 0.001,
             "inflight_s": 0.001, "gap_s": 0.001},
        ],
    }


def test_format_grow_detail_renders_table():
    txt = kernelprof.format_grow_detail(_fake_record(), grow_s=0.032)
    assert "round 3: grow detail" in txt
    assert "level_hist" in txt and "native" in txt
    assert "prep" in txt
    assert "substages = 93.8%" in txt, txt
    # ISSUE 17: one-dispatch rounds advertise the replayed route
    assert "route=tree_grow (sibling-sub replay)" in txt
    # pre-ISSUE-17 records (no route field) still render
    legacy = _fake_record()
    del legacy["route"], legacy["sibling_sub"]
    assert "route=" not in kernelprof.format_grow_detail(legacy)


@pytest.mark.parametrize("impl,note", [("pallas", True), ("native", False),
                                       ("xla", False)])
def test_format_grow_detail_says_the_pallas_mirror_builds_every_node(impl,
                                                                     note):
    """ISSUE 27: the program's Pallas level loop subtracts siblings, the
    mirror does not; its report says so on that route and no other."""
    rec = _fake_record(route="level")
    rec["ops"][1]["impl"] = impl
    txt = kernelprof.format_grow_detail(rec)
    assert ("route=level, sibling_sub=off (mirror)" in txt) is note
    assert "route=level" in txt


def test_grow_report_main_over_torn_sink(tmp_path, capsys):
    """grow-report over a hand-written run dir: sampled records render,
    a torn final line (SIGKILL mid-write) is tolerated, and a sink with
    no sampled rounds exits 1 with the arming hint."""
    d = tmp_path / "obs" / "rank0"
    d.mkdir(parents=True)
    rec = {"t": "round", "round": 3, "wall_s": 0.04,
           "stages": {"grow": 0.032}, "grow_detail": _fake_record()}
    with open(d / "flight.jsonl", "w") as f:
        f.write(json.dumps({"t": "meta", "rank": 0}) + "\n")
        f.write(json.dumps({"t": "round", "round": 2, "stages": {}}) + "\n")
        f.write(json.dumps(rec) + "\n")
        f.write('{"t": "round", "round": 4, "stag')  # torn mid-write
    assert kernelprof.main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "round 3: grow detail" in out and "level_hist" in out
    assert kernelprof.main([str(tmp_path), "--round", "9"]) == 1
    empty = tmp_path / "empty"
    (empty / "obs" / "rank0").mkdir(parents=True)
    (empty / "obs" / "rank0" / "flight.jsonl").write_text(
        json.dumps({"t": "meta"}) + "\n")
    assert kernelprof.main([str(empty)]) == 1
    err = capsys.readouterr().err
    assert "XGBTPU_KERNEL_PROF" in err


def test_grow_report_diff(tmp_path, capsys):
    """grow-report --diff A B: per-depth x per-op table across two run
    dirs with a delta column (ISSUE 17) — the before/after view for a
    kernel change, e.g. sibling-sub on vs off."""

    def _sink(name, route, hist_wall):
        d = tmp_path / name / "obs" / "rank0"
        d.mkdir(parents=True)
        rec = {"t": "round", "round": 3, "wall_s": 0.04,
               "stages": {"grow": 0.032},
               "grow_detail": _fake_record(route=route,
                                           hist_wall=hist_wall)}
        with open(d / "flight.jsonl", "w") as f:
            f.write(json.dumps({"t": "meta", "rank": 0}) + "\n")
            f.write(json.dumps(rec) + "\n")
        return str(tmp_path / name)

    a = _sink("a", "level", 0.02)
    b = _sink("b", "tree_grow", 0.005)
    assert kernelprof.main(["--diff", a, b]) == 0
    out = capsys.readouterr().out
    assert "grow detail diff:" in out
    assert "delta" in out and "level_hist" in out
    assert "-15.000ms" in out, out  # 5ms - 20ms on the hist bucket
    # --round filtering applies to both sides; a side with no sampled
    # records exits 1 with the arming hint
    assert kernelprof.main(["--diff", a, b, "--round", "9"]) == 1
    assert "XGBTPU_KERNEL_PROF" in capsys.readouterr().err
    assert kernelprof.main(["--diff", a]) == 1  # needs exactly two sides


def test_grow_report_diff_marks_impl_changes():
    """ISSUE 19: a row whose resolved impl flipped between the two runs
    (e.g. hist_acc float -> quant) carries a ``*`` marker and the table
    footnotes the count — a route flip must be visible without eyeballing
    the impl column."""
    rec_a, rec_b = _fake_record(), _fake_record()
    for op in rec_b["ops"]:
        if op["op"] == "level_hist":
            op["impl"] = "quant"

    def _diff(ra, rb):
        agg_a, rounds_a = kernelprof._aggregate_ops(
            [{"grow_detail": ra}])
        agg_b, rounds_b = kernelprof._aggregate_ops(
            [{"grow_detail": rb}])
        return kernelprof.format_grow_diff(
            agg_a, rounds_a, "A", agg_b, rounds_b, "B")

    txt = _diff(rec_a, rec_b)
    line = next(ln for ln in txt.splitlines() if "level_hist" in ln)
    assert "native->quant" in line and line.endswith(" *"), txt
    assert "* = resolved impl changed between runs (1 row(s))" in txt
    # identical impls: no marker, no footnote
    clean = _diff(rec_a, _fake_record())
    assert "*" not in clean, clean
