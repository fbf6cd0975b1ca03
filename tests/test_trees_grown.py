"""What a multiclass round says about itself (ISSUE 32): ``trees_grown_total``
by path, the ``groups`` and ``trees`` of a scan chunk's spans, the ``rows``
of ``xgb.eval``, and the ``xgb.eval_metric`` scope in the AUC's program."""

import numpy as np
import pytest

import jax
import xgboost_tpu as xgb
from xgboost_tpu.observability import REGISTRY, trace


def _data(classes, n=512, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, 6)).astype(np.float32)
    if classes <= 1:
        return X, (X[:, 0] + X[:, 1] > 0).astype(np.float32)
    return X, rng.integers(0, classes, n).astype(np.float32)


def _params(classes, npt=1):
    p = {"tree_method": "tpu_hist", "max_depth": 3, "max_bin": 16,
         "num_parallel_tree": npt, "seed": 3}
    if classes > 1:
        return dict(p, objective="multi:softmax", num_class=classes)
    return dict(p, objective="binary:logistic")


def _grown():
    fam = REGISTRY.get("trees_grown_total")
    out = {"scan": 0, "round": 0}
    if fam is not None:
        for labels, child in fam.series():
            out[labels["path"]] = int(child.value)
    return out


def _rounds():
    fam = REGISTRY.get("rounds_total")
    return int(fam.value) if fam is not None else 0


@pytest.mark.parametrize("classes,npt", [(1, 1), (3, 1), (8, 1), (3, 2)])
def test_counter_moves_by_the_rounds_trees_on_either_path(classes, npt):
    X, y = _data(classes)
    d = xgb.DMatrix(X, label=y)
    bst = xgb.Booster(_params(classes, npt), [d])
    per_round = max(classes, 1) * npt
    before, rounds0 = _grown(), _rounds()
    bst.update_many(d, 0, 4, chunk=2)  # two chunks of two rounds
    mid = _grown()
    assert mid["scan"] - before["scan"] == 4 * per_round
    assert mid["round"] == before["round"]
    bst.update(d, 4)
    after = _grown()
    assert after["round"] - mid["round"] == per_round
    assert after["scan"] == mid["scan"]
    assert _rounds() - rounds0 == 5
    assert bst.num_boosted_rounds() == 5


def test_a_round_that_refreshes_grows_no_tree():
    """``process_type=update`` re-stats the model's trees (and may drop
    one): the counter stays where it is."""
    X, y = _data(1)
    d = xgb.DMatrix(X, label=y)
    bst = xgb.train(_params(1), d, 3, verbose_eval=False)
    before = _grown()
    xgb.train(dict(_params(1), process_type="update", updater="refresh",
                   refresh_leaf=True), d, 3, xgb_model=bst,
              verbose_eval=False)
    assert _grown() == before


@pytest.fixture()
def chrome(tmp_path, monkeypatch):
    out = tmp_path / "chrome.json"
    monkeypatch.setenv("XGBTPU_TRACE", str(out))
    trace.reset()
    yield out
    monkeypatch.delenv("XGBTPU_TRACE")
    trace.reset()


@pytest.mark.parametrize("classes,npt", [(1, 1), (8, 1), (3, 2)])
def test_chunk_spans_carry_groups_and_trees(chrome, classes, npt):
    X, y = _data(classes)
    d = xgb.DMatrix(X, label=y)
    bst = xgb.Booster(_params(classes, npt), [d])
    bst.update_many(d, 0, 2, chunk=2)
    trace.flush()
    events = [e for e in trace.load_trace(str(chrome)) if e.get("ph") == "X"]
    for name in ("scan_chunk", "chunk.commit"):
        ev, = [e for e in events if e["name"] == name]
        assert ev["args"]["groups"] == max(classes, 1)
        assert ev["args"]["trees"] == 2 * max(classes, 1) * npt
    chunk, = [e for e in events if e["name"] == "scan_chunk"]
    assert chunk["args"]["rounds"] == 2 and chunk["args"]["start"] == 0


def test_eval_span_carries_the_rows_it_scores(chrome):
    X, y = _data(1)
    d, dv = xgb.DMatrix(X, label=y), xgb.DMatrix(X[:100], label=y[:100])
    bst = xgb.Booster(dict(_params(1), eval_metric="auc"), [d, dv])
    bst.update(d, 0)
    msg = bst.eval_set([(d, "train"), (dv, "holdout")], 0)
    assert "holdout-auc" in msg
    trace.flush()
    ev, = [e for e in trace.load_trace(str(chrome))
           if e.get("ph") == "X" and e["name"] == "eval"]
    assert ev["args"]["rows"] == 612 and ev["args"]["n_sets"] == 2


@pytest.mark.parametrize("fn,args", [
    ("_binary_auc", (np.zeros(64, np.float32),) * 3),
    ("_grouped_auc", (np.zeros(64, np.float32),) * 3
     + (np.zeros(64, np.int32), 4))])
def test_auc_programs_open_the_eval_metric_scope(fn, args):
    """The scope is inside the jitted metric: every op of its program
    carries ``xgb.eval_metric`` in its ``op_name``."""
    from xgboost_tpu.metric import auc

    program = getattr(auc, fn)
    static = {"n_groups": args[-1]} if fn == "_grouped_auc" else {}
    arrays = args[:4] if fn == "_grouped_auc" else args
    text = program.lower(*arrays, **static).as_text(debug_info=True)
    assert "xgb.eval_metric" in text
    named = [ln for ln in text.splitlines() if "sort" in ln and "loc(" in ln]
    assert named
    # and the values are what they were: a constant score is a coin
    if fn == "_binary_auc":
        label = np.r_[np.ones(32), np.zeros(32)].astype(np.float32)
        got = float(program(np.zeros(64, np.float32), label,
                            np.ones(64, np.float32)))
        assert got == pytest.approx(0.5)
