"""The three per-layer readers of ISSUE 32 (``round_eval_host_ms``,
``eval_walk_ms_per_round``, ``level_hist_ms_per_tree``): on the recording (a
program from before ``xgb.eval_metric`` and ``trees_grown_total``: none
raises) and on hand-made tables; and what the manifest lists of them."""

import json
import os

import pytest

from bench_paths import DATA, REPO, load

phases = load("reduce/phases.py")
NAMES = ["round_eval_host_ms", "eval_walk_ms_per_round",
         "level_hist_ms_per_tree"]
readers = {name: load(f"layer_metrics/{name}.py") for name in NAMES}

WALK = "%fusion.9 = f32[250000]{0} fusion(f32[250000,50]{1,0} %x)"
SORT = "%sort.3 = (f32[250000]{0}, s32[250000]{0}) sort(%a, %b)"
MUL = "%multiply.1 = f32[8192]{0} multiply(%g, %w)"


def _table(ops, host=()):
    return phases.reduce({
        "devices": {"/device:TPU:0": ops},
        "host_spans": [("bench.window", 0.0, 10_000.0)] + list(host)})


@pytest.fixture()
def recorded():
    with open(os.path.join(DATA, "v5e_small_scoped.phases.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", NAMES)
def test_nothing_without_a_traced_run(name):
    assert readers[name].read(None, {"traced_rounds": 0}, {}) is None
    assert readers[name].read(None, {}, {}) is None


def test_on_the_recording_of_a_scan_program(monkeypatch, recorded):
    """No eval set: no ``xgb.eval`` span, and no op under
    ``xgb.eval_metric``; the recorded window holds one ``predict`` whose
    walk the second reader reads alone."""
    assert "xgb.eval" not in recorded["host"]
    assert "xgb.eval_metric" not in recorded["phases"]
    monkeypatch.setattr(phases, "table", lambda run_summary: recorded)
    args = {"window_s": 1.0}, {"traced_rounds": 2}, {}
    assert readers["round_eval_host_ms"].read(*args) is None
    walk = recorded["phases"]["xgb.predict_walk"]
    assert readers["eval_walk_ms_per_round"].read(*args) == pytest.approx(
        1e3 * (walk.get("xla", 0.0) + walk.get("mosaic", 0.0)) / 2)


def test_eval_span_and_scopes_are_read_per_round(monkeypatch):
    """Two rounds: a walk of 300 ns and a sort of 500 ns a round under
    their scopes, a multiply under ``xgb.gradient``; an eval span of 2 us
    and one of 4 us."""
    out = _table(
        [(WALK, "jit(_predict_margin_impl)/xgb.predict_walk/gather:", 0.0,
          300.0),
         (SORT, "jit(_binary_auc)/xgb.eval_metric/sort:", 400.0, 500.0),
         (MUL, "jit(f)/xgb.gradient/mul:", 1000.0, 60.0),
         (WALK, "jit(_predict_margin_impl)/xgb.predict_walk/gather:", 5000.0,
          300.0),
         (SORT, "jit(_binary_auc)/xgb.eval_metric/sort:", 5400.0, 500.0)],
        host=[("xgb.eval", 0.0, 2000.0), ("xgb.eval", 5000.0, 4000.0)])
    monkeypatch.setattr(phases, "table", lambda run_summary: out)
    args = {"window_s": 1e-5}, {"traced_rounds": 2}, {}
    assert readers["round_eval_host_ms"].read(*args) == pytest.approx(3e-3)
    assert readers["eval_walk_ms_per_round"].read(*args) == \
        pytest.approx(800e-6)
    # the walk is told from the metric: each scope's reader of its own
    assert phases.device_ms_per_round(*args[:2], "xgb.eval_metric") == \
        pytest.approx(500e-6)


def test_a_program_from_before_the_metrics_scope_reads_the_walk_alone(
        monkeypatch):
    out = _table([(WALK, "jit(f)/xgb.predict_walk/gather:", 0.0, 300.0),
                  (SORT, "jit(_binary_auc)/sort:", 400.0, 500.0)])
    monkeypatch.setattr(phases, "table", lambda run_summary: out)
    assert readers["eval_walk_ms_per_round"].read(
        {"window_s": 1e-5}, {"traced_rounds": 1}, {}) == pytest.approx(300e-6)


def _registry(monkeypatch, trees, rounds):
    import xgboost_tpu.observability as obs
    from xgboost_tpu.observability.metrics import MetricsRegistry

    reg = MetricsRegistry()
    for path, n in trees.items():
        reg.counter("trees_grown_total").labels(path=path).inc(n)
    if rounds:
        reg.counter("rounds_total").inc(rounds)
    monkeypatch.setattr(obs, "REGISTRY", reg)


@pytest.mark.parametrize("trees,rounds,per_round", [
    ({"scan": 8 * 18}, 18, 8),              # two warm-up chunks, 3, a chunk
    ({"scan": 28, "round": 10}, 38, 1),     # both paths, one tree a round
    ({"scan": 2 * 3 * 5}, 5, 6),            # 3 groups x 2 parallel trees
    ({}, 5, None),                          # a program without the counter
    ({"scan": 40}, 0, None),                # no rounds counted
    ({"scan": 41}, 5, None),                # not a whole number a round
])
def test_trees_a_round_from_the_programs_counters(monkeypatch, trees, rounds,
                                                  per_round):
    _registry(monkeypatch, trees, rounds)
    assert readers["level_hist_ms_per_tree"].trees_per_round() == per_round


def test_level_kernel_time_is_divided_by_the_trees_of_the_window(
        monkeypatch):
    """Five traced rounds of eight trees with 1.6 s of level kernels: 40 ms
    a tree, where ``pallas_ms_per_round`` less routing reads 320 a round."""
    _registry(monkeypatch, {"scan": 8 * 18}, 18)
    summary = {"level_hist_s": 1.6, "mosaic_s": 1.65}
    record = {"traced_rounds": 5}
    assert readers["level_hist_ms_per_tree"].read(summary, record, {}) == \
        pytest.approx(40.0)
    assert load("layer_metrics/pallas_ms_per_round.py").read(
        summary, record, {}) == pytest.approx(330.0)
    # nothing where no level kernel ran, or from a program without the
    # counter (the parent of ISSUE 32)
    assert readers["level_hist_ms_per_tree"].read(
        {"level_hist_s": 0.0}, record, {}) is None
    _registry(monkeypatch, {}, 18)
    assert readers["level_hist_ms_per_tree"].read(summary, record, {}) is None


def test_entries_list_their_cells():
    """Found by name: a later PR appends after them. The eval loop's cell
    was measured and left out (its six runs spread 1.76%, over half the
    bound; PERF.md section 7), so its two readers stay unlisted beside the
    kind and its rehearsal, as the serving kind's do."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    per_tree = by_name["level_hist_ms_per_tree"]
    assert set(per_tree["workloads"]) >= {"anchor_train", "covtype_train"}
    assert per_tree["moves"] == "train_rounds_per_s"
    assert per_tree["better"] == "lower"
    assert per_tree["source"] == "device_trace"
    for name in NAMES[:2]:
        assert name not in by_name
        assert os.path.isfile(os.path.join(
            REPO, "benchmark", "layer_metrics", name + ".py"))
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert "anchor_train_eval" not in cells
    assert cells["covtype_train"]["config"] == "covtype-581kx54-c8"
    assert cells["covtype_train"]["traffic"] == "train_window_c5"
    assert cells["covtype_train"]["chips"] == 1
    # the multiclass cell reports what the anchor's scan cell reports
    for m in manifest["per_layer"]:
        if "anchor_train" in m.get("workloads", ()):
            assert "covtype_train" in m["workloads"], m["name"]
    e2e, = [m for m in manifest["end_to_end"]
            if m["name"] == "train_rounds_per_s"]
    assert "covtype_train" in e2e["workloads"]
    config, = [c for c in manifest["configs"]
               if c["name"] == "covtype-581kx54-c8"]
    assert config["reduced"] == ["rounds"]
    # the kind is kept with a rehearsal cell and no mix of its own
    assert os.path.isfile(os.path.join(
        REPO, "benchmark", "traffic", "train_eval_window.py"))
    rehearsal = os.path.join(REPO, "benchmark", "rehearsal")
    with open(os.path.join(rehearsal, "workloads",
                           "tiny_train_eval.json")) as f:
        mix = json.load(f)["traffic"]
    with open(os.path.join(rehearsal, "traffic", mix + ".json")) as f:
        assert json.load(f)["kind"] == "train_eval_window"
