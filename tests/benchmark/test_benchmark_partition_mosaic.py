"""``partition_mosaic_ms_per_round`` (ISSUE 25): the Mosaic time under
``xgb.partition``, beside ``partition_ms_per_round``, which reads the XLA time
of the same scope. On the recording (a program that routed in XLA) it reads 0;
on a hand-made table with the routing kernel it reads that cell per round."""

import gzip
import json
import os

import pytest

from bench_paths import DATA, REPO, load

phases = load("reduce/phases.py")
summary = phases.summary
NAME = "partition_mosaic_ms_per_round"
reader = load(f"layer_metrics/{NAME}.py")
xla_reader = load("layer_metrics/partition_ms_per_round.py")

ROUTE = ('%_route_rows_pallas.3 = s32[8192,1]{1,0:T(8,128)} custom-call('
         's32[8192,50]{1,0:T(8,128)} %b, s32[8192,1]{1,0:T(8,128)} %p, '
         'f32[32,4]{1,0:T(8,128)} %t), custom_call_target="tpu_custom_call", '
         'operand_layout_constraints={}')
LEVEL = ROUTE.replace("_route_rows_pallas", "_hoisted_level_pallas")
REDUCE = "%reduce.94 = s32[8192]{0:T(1024)} reduce(s32[8192,1]{1,0} %r)"
BODY = "jit(_scan_rounds_impl)/while/body/"
ROUTE_PATH = BODY + "xgb.partition/jit(_route_rows_pallas)/pallas_call:"


def _table(ops_by_chip):
    return phases.reduce({
        "devices": {f"/device:TPU:{i}": ops
                    for i, ops in enumerate(ops_by_chip)},
        "host_spans": [("bench.window", 0.0, 1000.0)]})


@pytest.fixture()
def scoped_table():
    """The phase table of the recorded ``v5e_small_scoped`` trace."""
    with open(os.path.join(DATA, "v5e_small_scoped.phases.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("key", ["unit", "better", "source", "layer", "moves",
                                 "workloads"])
def test_entry_is_its_xla_twins_but_for_the_name(key):
    """Both entries found by name, wherever later PRs' appends leave them:
    the Mosaic reader is listed as its XLA twin is, cell for cell."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    entry, = [m for m in per_layer if m["name"] == NAME]
    twin, = [m for m in per_layer if m["name"] == "partition_ms_per_round"]
    assert set(entry) == set(twin) == {"name", "unit", "better", "source",
                                       "layer", "moves", "workloads"}
    assert entry[key] == twin[key]
    assert (entry["better"], entry["unit"]) == ("lower", "ms/round")
    assert entry["workloads"][:2] == ["anchor_train", "higgs_train_x4"]


def test_recording_that_routed_in_xla_reads_zero(monkeypatch, tmp_path):
    """The real file through the real reduction: ``xgb.partition`` is there
    (its XLA ops), and no Mosaic call sits under it."""
    path = tmp_path / "v5e_small_scoped.xplane.pb"
    with gzip.open(os.path.join(DATA, "v5e_small_scoped.xplane.pb.gz")) as f:
        path.write_bytes(f.read())
    out = phases.reduce(phases.load(str(path)))
    monkeypatch.setattr(phases, "table", lambda run_summary: out)
    args = {"window_s": out["window_s"]}, {"traced_rounds": 2}, {}
    assert reader.read(*args) == 0.0
    assert xla_reader.read(*args) > 0.0


def test_recorded_table_reads_zero(monkeypatch, scoped_table):
    monkeypatch.setattr(phases, "table", lambda run_summary: scoped_table)
    assert "mosaic" not in scoped_table["phases"]["xgb.partition"]
    assert reader.read({"window_s": 1.0}, {"traced_rounds": 2}, {}) == 0.0


@pytest.mark.parametrize("summary_,record", [
    (None, {}), ({}, {"traced_rounds": 2}),
    ({"window_s": 1e-6}, {"traced_rounds": 0})])
def test_nothing_without_a_traced_round(monkeypatch, summary_, record):
    if summary_:
        monkeypatch.setattr(phases, "table", lambda run_summary: _table(
            [[(ROUTE, ROUTE_PATH, 100.0, 300.0)]]))
    assert reader.read(summary_, record, {}) is None


def test_nothing_where_no_op_carries_the_scope(monkeypatch):
    out = _table([[(LEVEL, BODY + "xgb.level_hist/jit(_hoisted_level_pallas)"
                    "/pallas_call:", 100.0, 300.0)]])
    monkeypatch.setattr(phases, "table", lambda run_summary: out)
    assert reader.read({"window_s": 1e-6}, {"traced_rounds": 1}, {}) is None


def test_routing_kernel_is_read_per_round_beside_the_xla_ops(monkeypatch):
    """Two chips, two rounds: the routing kernel 300 and 200 ns, the relayout
    of its output (XLA, booked to the scope by the call's own path) 40 ns a
    chip, a level kernel under its own scope."""
    def chip(route_ns):
        return [(LEVEL, BODY + "xgb.level_hist/jit(_hoisted_level_pallas)/"
                 "pallas_call:", 0.0, 100.0),
                (ROUTE, ROUTE_PATH, 100.0, route_ns),
                (REDUCE, ROUTE_PATH, 500.0, 40.0)]
    out = _table([chip(300.0), chip(200.0)])
    assert out["phases"]["xgb.partition"] == {
        "mosaic": pytest.approx(250e-9), "xla": pytest.approx(40e-9)}
    monkeypatch.setattr(phases, "table", lambda run_summary: out)
    args = {"window_s": 1e-6}, {"traced_rounds": 2}, {}
    assert reader.read(*args) == pytest.approx(125e-6)
    assert xla_reader.read(*args) == pytest.approx(20e-6)
    # every Mosaic call is pallas_ms_per_round's; only the level kernel's
    # time is the roofline's denominator
    assert summary.kind_of(ROUTE) == "mosaic"
    assert not summary.is_level_kernel(ROUTE)
    assert summary.is_level_kernel(LEVEL)
