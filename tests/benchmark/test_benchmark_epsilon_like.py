"""The Epsilon generator and the manifest entries of ISSUE 35: the shapes
the source fixes, the laws the two seeds fix, the width guard, what the
accepted readers give at a wide round's shape."""

import json
import os
import tracemalloc

import numpy as np
import pytest

from bench_paths import BENCH, DATA, harness, load

gen = load("generators/epsilon_like.py")
roofline = load("layer_metrics/level_hist_roofline.py")
per_tree = load("layer_metrics/level_hist_ms_per_tree.py")
MANIFEST = harness.load_manifest()

ROWS, COLS = 12_000, 600
CONFIG, CELL = "epsilon-400kx2000-d6-b128", "epsilon_train"


@pytest.fixture(scope="module")
def drawn():
    return gen.generate(rows=ROWS, cols=COLS, seed=3500000017, law_seed=0)


def test_shape_dtype_and_unit_rows(drawn):
    X, y = drawn
    assert X.shape == (ROWS, COLS) and X.dtype == np.float32
    assert y.shape == (ROWS,) and y.dtype == np.float32
    assert np.isfinite(X).all()  # dense: no missing value
    norms = np.sqrt((X.astype(np.float64) ** 2).sum(axis=1))
    assert np.abs(norms - 1.0).max() < 1e-5


def test_labels_are_two_balanced_classes(drawn):
    _, y = drawn
    assert set(np.unique(y)) == {0.0, 1.0}
    assert abs(y.mean() - 0.5) < 0.02


def test_the_law_comes_from_law_seed_and_the_rows_from_seed(drawn):
    X, y = drawn
    X2, y2 = gen.generate(rows=ROWS, cols=COLS, seed=3500000017, law_seed=0)
    assert np.array_equal(X, X2) and np.array_equal(y, y2)
    # another --seed: other rows, the same task
    Xs, ys = gen.generate(rows=ROWS, cols=COLS, seed=7, law_seed=0)
    assert not np.array_equal(X, Xs)
    idx, w, pair_cols, pair_sign, median = gen.law(COLS, 0, 256, 8, 4)
    assert 0.0 < abs(median) < 0.2  # the pairs skew the score a little
    assert len(set(idx)) == 256 and pair_cols.shape == (4, 2)
    # eight equal strong columns carry most of the linear term
    assert np.ptp(np.abs(w[:8])) < 1e-7 and (w[:8] ** 2).sum() > 0.85
    assert abs(float((w.astype(np.float64) ** 2).sum()) - 1.0) < 1e-5
    for A, b in ((X, y), (Xs, ys)):
        score = A[:, idx] @ w  # the linear term alone, on the scaled rows
        assert np.corrcoef(score, b)[0, 1] > 0.3
    # another law_seed: the same rows, another task
    Xl, yl = gen.generate(rows=ROWS, cols=COLS, seed=3500000017, law_seed=1)
    assert np.array_equal(X, Xl)
    assert 0.3 < (y != yl).mean() < 0.7
    assert gen.law(COLS, 1, 256, 8, 4)[0].tolist() != idx.tolist()


def test_an_interaction_has_no_marginal_effect():
    """A pair's product moves the label; neither column of a pair that the
    linear term weighs little does alone: depth matters."""
    X, y = gen.generate(rows=40_000, cols=64, seed=11, law_seed=3,
                        informative=4, strong=4, pairs=2)
    _, _, pair_cols, pair_sign, _ = gen.law(64, 3, 4, 4, 2)
    z = X * np.sqrt(64.0)
    for (a, b), s in zip(pair_cols, pair_sign):
        prod = z[:, a] * z[:, b]
        assert s * np.corrcoef(prod, y)[0, 1] > 0.1


def test_generate_holds_under_twice_the_matrix(monkeypatch):
    """Made in row blocks, float32 throughout: the peak of ``generate`` is
    the matrix it returns and a block's scratch, far under twice the
    matrix (a float64 detour alone would be twice)."""
    monkeypatch.setattr(gen, "BLOCK_ROWS", 1024)
    rows, cols = 16_384, 512
    matrix = rows * cols * 4
    gen.law(cols, 0, 256, 8, 4)  # the law's own 268 MB of rows, made once
    tracemalloc.start()
    X, y = gen.generate(rows=rows, cols=cols, seed=5, law_seed=0)
    _, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert X.nbytes == matrix
    assert peak < 1.5 * matrix, (peak, matrix)


def test_the_guard_fails_a_tree_that_lost_the_width(monkeypatch):
    from xgboost_tpu.tree import hist_kernel

    seen = []

    def no_kernel(rows, F, K, B, onehot_width=0):
        seen.append((rows, F, K, B))
        return False

    monkeypatch.setattr(hist_kernel, "pallas_level_fits", no_kernel)
    with pytest.raises(harness.BenchFailure, match="no Mosaic level kernel"):
        gen.generate(rows=500_000, cols=2000, seed=1, law_seed=0, bins=128)
    assert seen == [(500_000, 2000, 1, 128)]  # asked before a row is drawn


def test_this_tree_takes_the_configurations_width():
    from xgboost_tpu.tree import hist_kernel

    doc = harness.load_json(os.path.join(BENCH, "configs", CONFIG + ".json"))
    d = doc["data"]
    assert hist_kernel.pallas_level_fits(
        d["rows_train"] + d["rows_holdout"], d["cols"], 1,
        d["generator_params"]["bins"])
    assert d["generator_params"]["bins"] == doc["params"]["max_bin"]


# ---------------------------------------------------------------------------
# the accepted readers at this shape
# ---------------------------------------------------------------------------

# a traced chunk of the cell as the kind records it (PERF.md section 5)
RECORD = {"traced_rounds": 2, "rows_train": 400_000, "chips": 1, "cols": 2000,
          "max_bin": 128, "max_depth": 6, "trees_per_round": 1,
          "device_kind": "TPU v5 lite"}


def test_roofline_floor_at_this_shape():
    """``benchmark/shapes.py`` reads columns, bins and depth from the record
    and needs no edit: 32 built nodes a tree, flops-bound at every level,
    133.07 ms a round; the level kernels' 1,113.07 ms read 11.955%."""
    record = dict(RECORD)
    pct = roofline.read({"level_hist_s": 2 * 1.11307}, record, {})
    assert pct == pytest.approx(11.955, abs=2e-3)
    bound = record["level_hist_bound"].split("; ")
    assert len(bound) == 6 and all("(flops, " in b for b in bound)
    assert [b.split(", ")[1] for b in bound] \
        == ["1 built)", "1 built)", "2 built)", "4 built)", "8 built)",
            "16 built)"]
    assert "level_hist_over_floor" not in record
    least = 1.11307 * pct / 100.0
    assert least == pytest.approx(0.13307, rel=1e-3)


@pytest.mark.parametrize("summary,record", [
    (None, RECORD), ({"level_hist_s": 0.0}, RECORD),
    ({"level_hist_s": 1.0}, dict(RECORD, traced_rounds=0))])
def test_roofline_reads_nothing_from_nothing(summary, record):
    assert roofline.read(summary, dict(record), {}) is None


def test_ms_per_tree_is_the_round_of_one_tree(monkeypatch):
    """One binary tree a round: the level kernels' time a tree is their time
    a round (the figure PERF.md divides by rows x columns x depth for the
    cost of a cell of the matrix: 0.2319 ns here, 0.249 at the anchor)."""
    from xgboost_tpu.observability import REGISTRY
    from xgboost_tpu.observability.metrics import MetricsRegistry

    fresh = MetricsRegistry()
    monkeypatch.setattr(REGISTRY, "get", fresh.get)
    fresh.counter("rounds_total", "rounds").inc(14)
    fresh.counter("trees_grown_total", "trees").inc(14)
    got = per_tree.read({"level_hist_s": 2 * 1.11307}, dict(RECORD), {})
    assert got == pytest.approx(1113.07, rel=1e-9)
    assert 1e6 * got / (400_000 * 2000 * 6) == pytest.approx(0.2319, abs=1e-4)


# ---------------------------------------------------------------------------
# the manifest: every new entry, found by name
# ---------------------------------------------------------------------------


def _by_name(section):
    return {e["name"]: e for e in MANIFEST[section]}


def test_manifest_has_the_configuration_and_the_cell():
    cfg = _by_name("configs")[CONFIG]
    assert cfg["file"] == f"benchmark/configs/{CONFIG}.json"
    assert cfg["reduced"] == ["rounds"]
    assert "Epsilon" in cfg["source"] and "catboost/benchmarks" in cfg["source"]
    cell = _by_name("workloads")[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, "train_window_c2", 1)
    loaded = harness.load_cell(BENCH, CELL)
    assert loaded["mix"] == {"kind": "train_window", "chunk": 2,
                             "note": loaded["mix"]["note"]}
    doc = loaded["config_doc"]
    assert doc["params"] == {"objective": "binary:logistic",
                             "tree_method": "tpu_hist", "max_depth": 6,
                             "eta": 0.1, "max_bin": 128}
    assert (doc["data"]["rows_train"], doc["data"]["rows_holdout"],
            doc["data"]["cols"]) == (400_000, 100_000, 2000)
    assert doc["data"]["generator"] == "epsilon_like"
    assert doc["reduced"] == ["rounds"] and doc["published_rounds"] == 400
    assert doc["quality"]["metric"] == "auc" and doc["quality"]["rounds"] == 4
    lo, hi = doc["quality"]["band"]
    assert hi - lo == pytest.approx(0.02)
    for key in ("guarantees", "assumed", "deployment"):
        assert doc[key]
    # every value quoted from memory is named as such
    for key in ("generator", "params_from_memory", "learning_rate", "defaults"):
        assert key in doc["assumed"], key


def test_the_cell_reports_what_the_scan_cells_on_one_chip_report():
    """Every per-layer metric listed for the other multi-chunk scan cell on
    one chip lists this one too, the end-to-end rate among them."""
    ours = {m["name"] for m in
            harness.cell_metrics(MANIFEST, CELL, "per_layer")}
    theirs = {m["name"] for m in
              harness.cell_metrics(MANIFEST, "covtype_train", "per_layer")}
    assert theirs == ours
    assert CELL in _by_name("end_to_end")["train_rounds_per_s"]["workloads"]
    assert "level_hist_roofline" in ours
