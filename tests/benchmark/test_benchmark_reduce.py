"""The trace reduction, on a hand-made event table and on a recording."""

import gzip
import json
import os

import pytest

from bench_paths import DATA, load

summary = load("reduce/summary.py")

MOSAIC = ('%_hoisted_level_pallas.7 = (s32[8,1]{1,0}, f32[4,128]{1,0}) '
          'custom-call(s32[8,2]{1,0} %x), '
          'custom_call_target="tpu_custom_call", frontend_attributes={}')
FUSION = "%fusion.3 = f32[8]{0} fusion(f32[8]{0} %y), kind=kLoop"
ALLRED = "%all-reduce.2 = f32[4,128]{1,0} all-reduce(f32[4,128]{1,0} %h)"
WHILE = "%while.1 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t)"


def _table():
    """Two chips, window 0..1000 ns (bench.window). Chip 0 runs one program
    100..700 (a while holding a Mosaic call 100..400, a fusion 400..500, an
    all-reduce 500..650, 50 ns of its own), chip 1 the same program
    100..600 with a shorter all-reduce."""
    def chip(ar_end, end):
        return {"modules": [["jit_step(1)", 100.0, end - 100.0]],
                "ops": [[WHILE, 100.0, end - 100.0],
                        [MOSAIC, 100.0, 300.0],
                        [FUSION, 400.0, 100.0],
                        [ALLRED, 500.0, ar_end - 500.0]]}
    return {"devices": {"/device:TPU:0": chip(650.0, 700.0),
                        "/device:TPU:1": chip(560.0, 600.0)},
            "host_spans": [["bench.window", 0.0, 1000.0, "main"],
                           ["bench.update_many", 0.0, 90.0, "main"],
                           ["bench.drain", 90.0, 910.0, "main"]]}


def test_union_and_clip():
    assert summary.union([(0, 2), (1, 3), (5, 6), (6, 7), (9, 9)]) == \
        [[0, 3], [5, 7]]
    assert summary.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]
    assert summary.total([(0, 3), (5, 7)]) == 5


def test_self_times_of_nested_ops():
    ops = [["outer", 0.0, 100.0], ["a", 10.0, 20.0], ["b", 40.0, 50.0],
           ["b.inner", 45.0, 10.0]]
    got = {n: s for n, _, _, s in summary.self_times(ops)}
    assert got == {"outer": 30.0, "a": 20.0, "b": 40.0, "b.inner": 10.0}


def test_kinds_and_labels():
    assert summary.kind_of(MOSAIC) == "mosaic"
    assert summary.kind_of(ALLRED) == "collective"
    assert summary.kind_of(
        "%all-reduce-done.1 = f32[2] all-reduce-done(f32[2] %s)") \
        == "collective"
    assert summary.kind_of(FUSION) == "xla"
    assert summary.is_level_kernel(MOSAIC)
    assert summary.is_level_kernel(MOSAIC.replace("_hoisted_", "_fused_"))
    assert not summary.is_level_kernel(
        MOSAIC.replace("_hoisted_level_pallas", "_build_onehot_pallas"))
    assert not summary.is_level_kernel(FUSION.replace("fusion", "level"))
    assert summary.label_of(MOSAIC) == \
        "_hoisted_level_pallas s32[8,1]+f32[4,128] (mosaic)"
    assert summary.label_of(FUSION) == "fusion"
    assert summary.label_of(WHILE) == "while"


def test_busy_idle_split_collective_and_gaps():
    out = summary.summarize(_table())
    assert out["chips"] == 2
    assert out["window_s"] == pytest.approx(1000e-9)
    assert out["busy_max_s"] == pytest.approx(600e-9)
    assert out["busy_min_s"] == pytest.approx(500e-9)
    assert out["busy_s"] == pytest.approx(550e-9)
    assert out["mosaic_s"] == pytest.approx(300e-9)
    assert out["level_hist_s"] == pytest.approx(300e-9)
    # exposed all-reduce: 150 ns on chip 0, 60 on chip 1
    assert out["collective_exposed_s"] == pytest.approx(105e-9)
    chip0 = out["per_chip"]["/device:TPU:0"]
    # the while's own 50 ns and the fusion's 100 are XLA leaf time
    assert chip0["xla_leaf_s"] == pytest.approx(150e-9)
    ops = dict(out["breakdown"]["device_ops"])
    assert ops["_hoisted_level_pallas s32[8,1]+f32[4,128] (mosaic)"] \
        == pytest.approx(300e-9)
    assert ops["all-reduce"] == pytest.approx(105e-9)
    # gaps of the idlest chip (chip 1): 0..100 and 600..1000; the first is
    # mostly under bench.update_many, the second under bench.drain
    gaps = dict(out["breakdown"]["idle_gaps"])
    assert gaps["bench.update_many"] == pytest.approx(100e-9)
    assert gaps["bench.drain"] == pytest.approx(400e-9)
    assert out["longest_gap_s"] == pytest.approx(400e-9)


def test_layer_metric_readers_on_the_table():
    out = summary.summarize(_table())
    record = {"traced_rounds": 2, "chips": 2}
    idle = load("layer_metrics/device_idle_pct.py").read(out, record, {})
    assert idle == pytest.approx(50.0)
    assert load("layer_metrics/pallas_ms_per_round.py").read(
        out, record, {}) == pytest.approx(150e-6)
    assert load("layer_metrics/xla_ms_per_round.py").read(
        out, record, {}) == pytest.approx(125e-6)
    assert load("layer_metrics/psum_exposed_ms_per_round.py").read(
        out, record, {}) == pytest.approx(52.5e-6)
    assert load("layer_metrics/chip_busy_skew_pct.py").read(
        out, record, {}) == pytest.approx(100.0 / 6)


def test_roofline_reads_the_level_kernels_alone():
    """A Mosaic call that builds no level histogram is Mosaic time, and not
    the level histogram's."""
    table = _table()
    other = MOSAIC.replace("_hoisted_level_pallas", "_build_onehot_pallas")
    for chip in table["devices"].values():
        chip["ops"][2] = [other, 400.0, 100.0]  # in place of the fusion
    out = summary.summarize(table)
    assert out["mosaic_s"] == pytest.approx(400e-9)
    assert out["level_hist_s"] == pytest.approx(300e-9)
    record = {"traced_rounds": 2, "chips": 2, "rows_train": 2000, "cols": 4,
              "max_bin": 16, "max_depth": 2, "device_kind": "TPU v5 lite"}
    got = load("layer_metrics/level_hist_roofline.py").read(out, record, {})
    shapes = load("shapes.py")
    peaks = shapes.load_peaks("TPU v5 lite")
    # depth 2: the root, and one child of its split (the level has two)
    least = sum(shapes.level_hist_min_seconds(1000, 4, 16, k, peaks)[0]
                for k in (1, 1))
    assert got == pytest.approx(100.0 * least / 150e-9)
    level0, level1 = record["level_hist_bound"].split("; ")
    assert level0.startswith("level 0:") and level0.endswith(", 1 built)")
    assert level1.startswith("level 1:") and level1.endswith(", 1 built)")
    assert "level_hist_over_floor" not in record
    # no level kernel in the trace (renamed, or another route): no reading
    for chip in table["devices"].values():
        chip["ops"][1] = [other, 100.0, 300.0]
    assert load("layer_metrics/level_hist_roofline.py").read(
        summary.summarize(table), record, {}) is None


def test_roofline_over_the_limit_is_returned_and_said(capsys):
    """A made-up summary whose level kernels take a tenth of the floor: the
    reading comes back as read, with a line on stderr and a mark in the
    record (the driver refuses it as ``impossible_gain``); at the floor's
    own time it reads 100 and says nothing."""
    reader = load("layer_metrics/level_hist_roofline.py")
    shapes = load("shapes.py")
    peaks = shapes.load_peaks("TPU v5 lite")
    least = sum(shapes.level_hist_min_seconds(
        1000, 4, 16, shapes.built_nodes(d), peaks)[0] for d in range(3))

    def record():
        return {"traced_rounds": 1, "chips": 1, "rows_train": 1000,
                "cols": 4, "max_bin": 16, "max_depth": 3,
                "device_kind": "TPU v5 lite"}

    at_floor = record()
    assert reader.read({"level_hist_s": least}, at_floor, {}) == \
        pytest.approx(100.0)
    assert "level_hist_over_floor" not in at_floor
    assert capsys.readouterr().err == ""
    over = record()
    assert reader.read({"level_hist_s": least / 10}, over, {}) == \
        pytest.approx(1000.0)
    assert over["level_hist_over_floor"] is True
    err = capsys.readouterr().err
    assert "level_hist_roofline 1000.000% > 105%" in err
    assert "benchmark/shapes.py" in err and "impossible_gain" in err
    assert over["level_hist_bound"].endswith(", 2 built)")


def test_window_defaults_to_the_extent_of_device_events():
    table = _table()
    table["host_spans"] = []
    out = summary.summarize(table)
    assert out["window_s"] == pytest.approx(600e-9)
    assert dict(out["breakdown"]["idle_gaps"]) == {
        "no benchmark span open": pytest.approx(100e-9)}


def test_queue_wait_quantile_from_bucket_deltas():
    read = load("layer_metrics/serve_queue_wait_p99_ms.py").read
    hist = {"buckets": (0.001, 0.01, 0.1), "counts": [90, 9, 1, 0]}
    # rank 99 of 100 ends the second bucket: its upper bound
    assert read(None, {"queue_wait_hist": hist}, {}) == pytest.approx(10.0)
    assert read(None, {"queue_wait_hist": {"buckets": (1,),
                                           "counts": [0, 0]}}, {}) is None


RECORDED = os.path.join(DATA, "v5e_small.xplane.pb.gz")


def test_recorded_v5e_trace(tmp_path):
    """Two rounds at 8,192 x 12 and three served requests recorded on one
    v5e chip (PR 22, python tracer off as the runner sets it): the loader
    finds the device plane, the Mosaic calls and the benchmark's spans, and
    the reduction's parts add up."""
    path = tmp_path / "v5e_small.xplane.pb"
    with gzip.open(RECORDED, "rb") as src:
        path.write_bytes(src.read())
    table = summary.xplane.load_table(str(path))
    assert list(table["devices"]) == ["/device:TPU:0"]
    dev = table["devices"]["/device:TPU:0"]
    assert dev["modules"] and dev["ops"]
    assert any(summary.MOSAIC in n for n, _, _ in dev["ops"])
    assert {n for n, *_ in table["host_spans"]} == {
        "bench.window", "bench.update_many", "bench.drain", "bench.submit",
        "bench.await"}
    out = summary.summarize(table)
    assert 0 < out["busy_s"] <= out["window_s"]
    assert 0 < out["mosaic_s"] < out["busy_s"]
    leaf = out["per_chip"]["/device:TPU:0"]
    # self times partition the op line: leaf time is the ops' extent, which
    # the programs' busy time covers
    assert leaf["mosaic_s"] + leaf["xla_leaf_s"] <= out["busy_s"] * 1.001
    assert out["breakdown"]["device_ops"][0][1] > 0
    with open(os.path.join(DATA, "v5e_small.expected.json")) as f:
        want = json.load(f)
    for key, value in want.items():
        assert out[key] == pytest.approx(value, rel=1e-9), key


X4 = os.path.join(DATA, "v5e_x4_events.json.gz")


@pytest.mark.skipif(not os.path.isfile(X4),
                    reason="no recorded four-chip event table")
def test_recorded_four_chip_events():
    """A trimmed event table of the four-chip cell (PR 22): four device
    planes, all-reduces on each, exposed collective time above zero."""
    with gzip.open(X4, "rt") as f:
        table = json.load(f)
    assert len(table["devices"]) == 4
    out = summary.summarize(table)
    assert out["chips"] == 4
    # two all-reduces a chip in these 60 ms (the root's and level 1's)
    for chip in table["devices"].values():
        assert sum(summary.kind_of(n) == "collective"
                   for n, _, _ in chip["ops"]) == 2
    assert 0 < out["collective_exposed_s"] < 1e-4
    assert 0.058 < out["busy_min_s"] <= out["busy_s"] <= out["busy_max_s"] \
        <= out["window_s"]
    assert out["mosaic_s"] > 0.8 * out["busy_s"]
    skew = load("layer_metrics/chip_busy_skew_pct.py").read(out, {}, {})
    assert 0 <= skew < 2
