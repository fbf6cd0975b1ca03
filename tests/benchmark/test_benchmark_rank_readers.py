"""The five per-layer readers of the ranking cell (ISSUE 26): on the
recording (a program from before the scopes and spans: none raises, the
four that read a name of their own read nothing, and the remainder reads
what the older remainder reads) and on a hand-made table."""

import json
import os

import pytest

from bench_paths import DATA, REPO, load

phases = load("reduce/phases.py")
NAMES = ["rank_sort_ms_per_round", "rank_pairs_ms_per_round",
         "rank_unscoped_xla_ms_per_round", "round_gradient_host_ms",
         "round_boost_host_ms"]
readers = {name: load(f"layer_metrics/{name}.py") for name in NAMES}

SORT = "%sort.3 = (s32[8192]{0}, f32[8192]{0}, s32[8192]{0}) sort(%a, %b, %c)"
GATHER = "%gather.7 = f32[1,8192]{1,0} gather(f32[8192]{0} %m, s32[1,8192] %j)"
COPY = "%copy.2 = f32[8192]{0} copy(f32[8192]{0} %x)"
MUL = "%multiply.1 = f32[8192]{0} multiply(%g, %w)"
GRAD = "jit(_lambda_grad_sampled)/xgb.gradient/"


def _table(ops, host=()):
    return phases.reduce({
        "devices": {"/device:TPU:0": ops},
        "host_spans": [("bench.window", 0.0, 10_000.0)] + list(host)})


@pytest.fixture()
def recorded():
    with open(os.path.join(DATA, "v5e_small_scoped.phases.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", NAMES)
def test_a_program_from_before_pr_26(monkeypatch, recorded, name):
    assert "xgb.rank_sort" not in recorded["phases"]
    args = {"window_s": 1.0}, {"traced_rounds": 2}, {}
    if name == "rank_unscoped_xla_ms_per_round":
        # nothing to take off: the older reader's number (the recorded
        # table predates the chips' count that reader asks for)
        out = _table([(MUL, "jit(f)/xgb.gradient/mul:", 0.0, 60.0),
                      (COPY, "", 100.0, 100.0)])
        monkeypatch.setattr(phases, "table", lambda run_summary: out)
        assert readers[name].read(*args) == load(
            "layer_metrics/unscoped_xla_ms_per_round.py").read(*args) \
            == pytest.approx(50e-6)
    else:
        monkeypatch.setattr(phases, "table", lambda run_summary: recorded)
        assert readers[name].read(*args) is None


@pytest.mark.parametrize("name", NAMES)
def test_nothing_without_a_traced_run(name):
    assert readers[name].read(None, {"traced_rounds": 0}, {}) is None


def test_scopes_and_spans_are_read_per_round(monkeypatch):
    """Two rounds: sorts 400 ns and gathers 1,000 ns under their scopes, a
    multiply under ``xgb.gradient`` alone, a copy under none; a gradient
    span of 2 us and a boost span of 6 us a round."""
    out = _table(
        [(SORT, GRAD + "xgb.rank_sort/sort:", 0.0, 400.0),
         (GATHER, GRAD + "xgb.rank_pairs/gather:", 500.0, 1000.0),
         (MUL, GRAD + "mul:", 1600.0, 60.0),
         (COPY, "", 1700.0, 100.0)],
        host=[("xgb.round.gradient", 0.0, 2000.0),
              ("xgb.round.boost", 2000.0, 6000.0),
              ("xgb.round.gradient", 8000.0, 2000.0)])
    monkeypatch.setattr(phases, "table", lambda run_summary: out)
    args = {"window_s": 1e-5}, {"traced_rounds": 2}, {}
    assert readers["rank_sort_ms_per_round"].read(*args) == \
        pytest.approx(200e-6)
    assert readers["rank_pairs_ms_per_round"].read(*args) == \
        pytest.approx(500e-6)
    # what no phase metric claims: the copy, not the two ranking scopes
    assert readers["rank_unscoped_xla_ms_per_round"].read(*args) == \
        pytest.approx(50e-6)
    assert load("layer_metrics/unscoped_xla_ms_per_round.py").read(*args) \
        == pytest.approx(750e-6)
    assert load("layer_metrics/gradient_ms_per_round.py").read(*args) == \
        pytest.approx(30e-6)
    assert readers["round_gradient_host_ms"].read(*args) == \
        pytest.approx(2e-3)
    assert readers["round_boost_host_ms"].read(*args) == pytest.approx(6e-3)


def test_entries_list_the_ranking_cell():
    """Found by name: a later PR appends after them."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    for name in NAMES:
        m, = [e for e in manifest["per_layer"] if e["name"] == name]
        assert "mslr_rank_train" in m["workloads"]
        assert m["moves"] == "train_rounds_per_s" and m["better"] == "lower"
    cell, = [w for w in manifest["workloads"]
             if w["name"] == "mslr_rank_train"]
    assert cell["chips"] == 1 and cell["traffic"] == "rank_window_c2"
    # the scan path's chunk spans and the older unscoped reader stay off it
    for name in ("chunk_prepare_ms", "chunk_dispatch_ms", "chunk_commit_ms",
                 "unscoped_xla_ms_per_round"):
        entry, = [m for m in manifest["per_layer"] if m["name"] == name]
        assert "mslr_rank_train" not in entry["workloads"]
