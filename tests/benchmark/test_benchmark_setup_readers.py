"""The ten per-layer readers of ``setup_s`` (ISSUE 37): each reads the
program's own registry at the end of the run, nothing on an empty record or
from a program without the series, and what the manifest lists of them."""

import pytest

from bench_paths import harness, load

import xgboost_tpu.observability as obs
from xgboost_tpu.observability.metrics import MetricsRegistry

FIVE = ["anchor_train", "higgs_train_x4", "mslr_rank_train", "covtype_train",
        "epsilon_train"]
ONE_CHIP = ["anchor_train", "mslr_rank_train", "covtype_train",
            "epsilon_train"]
# name -> (unit, layer, cells, the value the filled registry below gives)
WANT = {
    "jit_trace_s": ("s", "entry", FIVE, 1.5 + 0.25),
    "jit_lower_s": ("s", "entry", FIVE, 0.5),
    "jit_compile_s": ("s", "entry", FIVE, 4.0 + 2.0),
    "jit_programs": ("count", "entry", FIVE, 3.0),
    "compile_cache_misses": ("count", "entry", FIVE, 2.0),
    "matrix_upload_s": ("s", "data plane and one-hot", FIVE, 0.75),
    "sketch_s": ("s", "data plane and one-hot", FIVE, 9.0),
    "bins_s": ("s", "data plane and one-hot", FIVE, 1.25),
    "onehot_s": ("s", "data plane and one-hot", ONE_CHIP, 1.125),
    "hbm_peak_data_plane_gb": ("GB", "device", FIVE, 8.0),
}
readers = {name: load(f"layer_metrics/{name}.py") for name in WANT}
RECORD = {"dmatrix_build_s": 11.0}  # any record of a run that got this far


@pytest.fixture()
def registry(monkeypatch):
    """A registry of the program's, empty; the readers look it up by the
    package's name at every read."""
    reg = MetricsRegistry()
    monkeypatch.setattr(obs, "REGISTRY", reg)
    return reg


def _fill(reg):
    sec = reg.counter("jit_seconds_total")
    sec.labels(stage="trace", fn="_scan_rounds_impl").inc(1.5)
    sec.labels(stage="trace", fn="multiply").inc(0.25)
    sec.labels(stage="lower", fn="_scan_rounds_impl").inc(0.5)
    sec.labels(stage="compile", fn="_scan_rounds_impl").inc(4.0)
    sec.labels(stage="compile", fn="run").inc(2.0)
    ev = reg.counter("jit_events_total")
    ev.labels(stage="trace", fn="_scan_rounds_impl").inc(3)
    ev.labels(stage="compile", fn="_scan_rounds_impl").inc(2)
    ev.labels(stage="compile", fn="run").inc(1)
    cache = reg.counter("compile_cache_events_total")
    cache.labels(result="hit").inc(1)
    cache.labels(result="miss").inc(2)
    stage = reg.counter("setup_stage_seconds_total")
    for name, s in (("upload", 0.75), ("sketch", 9.0), ("bins", 1.25),
                    ("onehot", 1.125), ("rank_layout", 3.0)):
        stage.labels(stage=name).inc(s)
    mark = reg.gauge("hbm_peak_bytes")
    mark.labels(stage="upload").set(3.2e9)
    mark.labels(stage="sketch").set(8.0e9)
    mark.labels(stage="bins").set(8.0e9)
    mark.labels(stage="rank_layout").set(9.9e9)  # not the data plane's


@pytest.mark.parametrize("name", list(WANT))
def test_a_reader_reads_the_registry_and_nothing_without_a_run(name,
                                                               registry):
    read = readers[name].read
    assert read(None, {}, {}) is None
    # a program from before the ledger: a registry without the series
    registry.counter("recompiles_total").labels(fn="x").inc()
    assert read(None, RECORD, {}) is None
    _fill(registry)
    assert read(None, {}, {}) is None  # still nothing on an empty record
    assert read(None, RECORD, {}) == pytest.approx(WANT[name][3])


def test_a_warm_run_reads_no_miss_and_a_run_without_a_cache_nothing(registry):
    read = readers["compile_cache_misses"].read
    registry.counter("jit_events_total").labels(
        stage="compile", fn="run").inc()
    assert read(None, RECORD, {}) is None  # no persistent cache: no event
    registry.counter("compile_cache_events_total").labels(
        result="hit").inc(40)
    assert read(None, RECORD, {}) == 0.0  # every program found: a number


def test_no_stage_no_metric(registry):
    """Under a mesh no stage sees the one-hot; off the chip no stage has a
    memory mark: the series has no such child, and the reader nothing."""
    registry.counter("setup_stage_seconds_total").labels(
        stage="sketch").inc(2.0)
    assert readers["onehot_s"].read(None, RECORD, {}) is None
    assert readers["sketch_s"].read(None, RECORD, {}) == 2.0
    assert readers["hbm_peak_data_plane_gb"].read(None, RECORD, {}) is None


@pytest.mark.parametrize("name", list(WANT))
def test_the_manifest_lists_it_as_issue_37_says(name):
    manifest = harness.load_manifest()
    entry = [m for m in manifest["per_layer"] if m["name"] == name]
    assert len(entry) == 1
    unit, layer, cells, _ = WANT[name]
    assert entry[0] == {"name": name, "unit": unit, "better": "lower",
                        "source": "program_counter", "layer": layer,
                        "moves": "setup_s", "workloads": cells}
    # a layer the manifest already had, letter for letter
    older = {m["layer"] for m in manifest["per_layer"]
             if m["name"] not in WANT}
    assert layer in older
