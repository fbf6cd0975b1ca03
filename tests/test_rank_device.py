"""The ranking objective as a device program (ISSUE 26): the sampled-pair
gradient against the benchmark's plain reference, the sampler's key, the
per-matrix layout (built once, nothing O(n) uploaded in a round), the row
padding, and the names a profile reads."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import xgboost_tpu as xgb
from xgboost_tpu.objective import create_objective
from xgboost_tpu.objective import ranking as R
from xgboost_tpu.observability import REGISTRY

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "benchmark"))
try:
    from bench_paths import load
finally:
    sys.path.pop(0)

lambdamart = load("reference/lambdamart.py")

SEED = 2500000037  # over 2^31, as the driver's seeds are


class _Params:
    def __init__(self, n_pair=1, seed=SEED):
        self.lambdarank_num_pair_per_sample = n_pair
        self.seed = seed


@pytest.fixture(scope="module")
def queries():
    """Seeded queries with the shapes that matter: one document, one label
    only, 1,251 documents (enough for the sampled-pair branch)."""
    rng = np.random.default_rng(0)
    sizes = np.concatenate([[1, 1251, 7], rng.integers(2, 300, 60)])
    gptr = np.concatenate([[0], np.cumsum(sizes)]).astype(np.int64)
    n = int(gptr[-1])
    y = rng.choice(5, n, p=[.515, .323, .134, .019, .009]).astype(np.float32)
    y[gptr[2]:gptr[3]] = 2.0  # a query of one label
    assert len(sizes) * 1251 ** 2 > R._ALL_PAIRS_BUDGET
    return sizes, gptr, y


def _margins(kind, n):
    if kind == "equal":
        return np.full(n, 0.5, np.float32)
    return np.random.default_rng(1).standard_normal(n).astype(np.float32)


@pytest.mark.parametrize("n_pair", [1, 2])
@pytest.mark.parametrize("margins", ["equal", "random"])
@pytest.mark.parametrize("objective", ["rank:ndcg", "rank:pairwise"])
def test_sampled_gradient_is_the_references(queries, objective, margins,
                                            n_pair):
    sizes, gptr, y = queries
    m = _margins(margins, len(y))
    obj = create_objective(objective, _Params(n_pair))
    g, h = obj.get_gradient(jnp.asarray(m), jnp.asarray(y), None, 3,
                            group_ptr=gptr)
    g_ref, h_ref = lambdamart.gradient(objective, m, y, gptr, seed=SEED,
                                       iteration=3, n_pair=n_pair)
    scale = np.abs(g_ref).max()
    assert scale > 0
    assert np.abs(np.asarray(g) - g_ref).max() <= 1e-5 * scale
    assert np.abs(np.asarray(h) - h_ref).max() <= 1e-5 * scale
    # the query of one document and the query of one label weigh nothing
    assert np.all(np.asarray(g)[gptr[0]:gptr[1]] == 0)
    assert np.all(np.asarray(g)[gptr[2]:gptr[3]] == 0)


def test_reference_in_bfloat16_misses_the_limit(queries):
    """The benchmark's limit (1e-5 of the largest |g|) from its other side:
    the same gradient from a margin kept in bfloat16 is two orders out."""
    sizes, gptr, y = queries
    m = _margins("random", len(y))
    m16 = np.asarray(jnp.asarray(m).astype(jnp.bfloat16).astype(jnp.float32))
    g, _ = lambdamart.gradient("rank:ndcg", m, y, gptr, seed=SEED,
                               iteration=3)
    g16, _ = lambdamart.gradient("rank:ndcg", m16, y, gptr, seed=SEED,
                                 iteration=3)
    assert np.abs(g16 - g).max() > 1e-3 * np.abs(g).max()


def test_seed_enters_the_key_and_repeats(queries):
    sizes, gptr, y = queries
    m, yj = jnp.asarray(_margins("random", len(y))), jnp.asarray(y)

    def grad(seed, iteration):
        obj = create_objective("rank:ndcg", _Params(seed=seed))
        return np.asarray(obj.get_gradient(m, yj, None, iteration,
                                           group_ptr=gptr)[0])

    a = grad(7, 2)
    assert np.array_equal(a, grad(7, 2))
    assert not np.array_equal(a, grad(8, 2))
    assert not np.array_equal(a, grad(7, 3))


def _ranking_job(seed=7):
    rng = np.random.default_rng(3)
    sizes = np.concatenate([[1251], rng.integers(2, 120, 40)])
    n = int(sizes.sum())
    X = rng.standard_normal((n, 6)).astype(np.float32)
    y = np.clip(np.rint(X[:, 0] + 0.5 * rng.standard_normal(n) + 1), 0,
                4).astype(np.float32)
    d = xgb.DMatrix(X, label=y)
    d.set_group(sizes)
    bst = xgb.Booster({"objective": "rank:ndcg", "max_depth": 3,
                       "eta": 0.3, "min_child_weight": 0.1, "seed": seed},
                      [d])
    return d, bst, y, sizes


def _layout_builds():
    fam = REGISTRY.get("rank_layout_builds_total")
    return 0 if fam is None else sum(int(c.value) for _, c in fam.series())


def test_layout_is_built_once_a_dmatrix_and_rounds_upload_nothing(
        monkeypatch):
    d, bst, y, sizes = _ranking_job()
    before = _layout_builds()
    bst.update(d, 0)
    assert _layout_builds() == before + 1
    layout = d.info._rank_layout

    n = len(y)
    sent = []
    asarray = jnp.asarray
    monkeypatch.setattr(jnp, "asarray", lambda a, *k, **kw: (
        sent.append(np.size(a)) if not isinstance(a, jax.Array) else None,
        asarray(a, *k, **kw))[1])
    # no implicit transfer at all in the objective (the cached margin's
    # read fills the base margin from a scalar first), and no explicit one
    # of O(n): the label, the groups and what hangs on them are on the device
    margin = bst._cached_margin(d)
    with jax.transfer_guard_host_to_device("disallow"):
        bst._gradient(d, margin, 1)
    bst.update_many(d, 1, 2, chunk=2)
    # (the cut points, [F, max_bin], go up with every tree)
    assert max(sent, default=0) < n, sent
    assert _layout_builds() == before + 1
    assert d.info._rank_layout is layout
    # a replaced label is a new layout
    d.set_label(y[::-1].copy())
    bst.update(d, 3)
    assert _layout_builds() == before + 2


def test_gradient_names_the_round_would_boost_on():
    d, bst, y, sizes = _ranking_job()
    bst.update(d, 0)
    margin = bst.predict(d, output_margin=True)
    g, h = bst.gradient(d, 1)
    g_ref, h_ref = lambdamart.gradient(
        "rank:ndcg", margin, y, np.concatenate([[0], np.cumsum(sizes)]),
        seed=7, iteration=1)
    assert np.abs(np.asarray(g) - g_ref).max() <= 1e-5 * np.abs(g_ref).max()
    assert np.array_equal(margin, bst.predict(d, output_margin=True))


def test_padded_rows_weigh_nothing():
    """1,251 + ... rows are no whole tile: the grower pads them, and the
    root's hessian is the real rows' alone."""
    from xgboost_tpu.gbm.gbtree import _pad_gh

    d, bst, y, sizes = _ranking_job()
    g, h = bst.gradient(d, 0)
    n = len(y)
    n_pad = -(-n // 1024) * 1024
    assert n_pad != n
    gp, hp = _pad_gh(g, h, n_pad=n_pad)
    assert gp.shape == hp.shape == (n_pad,)
    assert not np.asarray(gp[n:]).any() and not np.asarray(hp[n:]).any()
    assert np.array_equal(np.asarray(gp[:n]), np.asarray(g))
    bst.update(d, 0)
    tree = bst.get_dump(dump_format="json", with_stats=True)[0]
    import json

    cover = json.loads(tree)["cover"]
    assert cover == pytest.approx(float(np.asarray(h, np.float64).sum()),
                                  rel=1e-4)


def test_device_program_carries_the_scopes(queries):
    """``xgb.gradient`` with ``xgb.rank_sort`` and ``xgb.rank_pairs`` inside
    it, in the objective's own program (a scope round a jitted call from
    outside does not enter it): sorts under the first, gathers and
    scatter-adds under the second."""
    sizes, gptr, y = queries
    entry = R._build_layout(y, gptr, None)
    text = R._lambda_grad_sampled._guarded_jit.lower(
        jnp.zeros(len(y)), entry.arrays, jax.random.PRNGKey(1),
        jnp.int32(0), n_pair=1, scheme="ndcg").as_text(debug_info=True)
    locs = [ln for ln in text.splitlines() if ln.startswith("#loc")]
    assert any("xgb.gradient/xgb.rank_sort/sort" in ln for ln in locs)
    assert any("xgb.gradient/xgb.rank_pairs/" in ln and "gather" in ln
               for ln in locs)
    assert any("xgb.gradient/xgb.rank_pairs/" in ln and "scatter" in ln
               for ln in locs)
    assert not any("scatter" in ln and "xgb.rank_sort" in ln for ln in locs)


def test_round_spans_and_layout_span_are_on_the_profilers_clock(tmp_path):
    """``xgb.rank_layout`` once, ``xgb.round.gradient`` and
    ``xgb.round.boost`` once a round, read from the profiler's own file as
    the benchmark reads them."""
    phases = load("reduce/phases.py")
    d, bst, y, sizes = _ranking_job()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        bst.update_many(d, 0, 2, chunk=2)
        jax.block_until_ready(bst.gradient(d, 2))
    finally:
        jax.profiler.stop_trace()
    import glob

    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    names = [n for n, _, _ in phases.load(path)["host_spans"]]
    assert names.count("xgb.rank_layout") == 1
    assert names.count("xgb.round.gradient") == 2
    assert names.count("xgb.round.boost") == 2
