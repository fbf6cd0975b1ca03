"""Ranking (LambdaMART) and survival (AFT/Cox) end-to-end tests
(reference analogs: tests/python/test_ranking.py, test_survival.py)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import xgboost_tpu as xgb


def _ranking_data(n_groups=30, group_size=20, f=5, seed=0):
    rng = np.random.RandomState(seed)
    n = n_groups * group_size
    X = rng.randn(n, f).astype(np.float32)
    # relevance driven by f0 with noise, 3 levels
    score = X[:, 0] + 0.3 * rng.randn(n)
    y = np.zeros(n, np.float32)
    for g in range(n_groups):
        sl = slice(g * group_size, (g + 1) * group_size)
        r = np.argsort(np.argsort(-score[sl]))
        y[sl] = np.where(r < 3, 2.0, np.where(r < 8, 1.0, 0.0))
    qid = np.repeat(np.arange(n_groups), group_size)
    return X, y, qid


@pytest.mark.parametrize("objective", ["rank:pairwise", "rank:ndcg"])
def test_ranking_improves_ndcg(objective):
    X, y, qid = _ranking_data()
    d = xgb.DMatrix(X, label=y, qid=qid)
    res = {}
    bst = xgb.train(
        {"objective": objective, "max_depth": 3, "eta": 0.3,
         "eval_metric": ["ndcg@5", "map"]},
        d, num_boost_round=15, evals=[(d, "train")], evals_result=res,
        verbose_eval=False,
    )
    ndcg = res["train"]["ndcg@5"]
    assert ndcg[-1] > 0.8
    assert ndcg[-1] > ndcg[0]


def test_ranking_group_param():
    X, y, qid = _ranking_data(10, 15)
    d = xgb.DMatrix(X, label=y, group=[15] * 10)
    bst = xgb.train({"objective": "rank:pairwise", "max_depth": 2},
                    d, num_boost_round=3, verbose_eval=False)
    assert bst.num_boosted_rounds() == 3


def test_xgbranker_sklearn():
    from xgboost_tpu.sklearn import XGBRanker

    X, y, qid = _ranking_data(20, 10)
    r = XGBRanker(n_estimators=5, max_depth=2)
    r.fit(X, y, qid=qid)
    s = r.predict(X)
    assert s.shape == (200,)
    with pytest.raises(ValueError):
        XGBRanker(n_estimators=1).fit(X, y)  # no group/qid


# ----------------------------------------------------------------- survival
def test_aft_uncensored_recovers_log_time():
    rng = np.random.RandomState(0)
    X = rng.randn(2000, 3).astype(np.float32)
    t = np.exp(1.0 + 0.8 * X[:, 0] + 0.1 * rng.randn(2000)).astype(np.float32)
    d = xgb.DMatrix(X, label_lower_bound=t, label_upper_bound=t)
    res = {}
    bst = xgb.train(
        {"objective": "survival:aft", "max_depth": 3, "eta": 0.3,
         "aft_loss_distribution": "normal", "aft_loss_distribution_scale": 1.0,
         "eval_metric": "aft-nloglik"},
        d, num_boost_round=20, evals=[(d, "train")], evals_result=res,
        verbose_eval=False,
    )
    nll = res["train"]["aft-nloglik"]
    assert nll[-1] < nll[0]
    pred = bst.predict(d)  # exp(margin) = predicted time
    corr = np.corrcoef(np.log(pred), np.log(t))[0, 1]
    assert corr > 0.8


def test_aft_right_censored_pushes_up():
    rng = np.random.RandomState(1)
    X = rng.randn(1000, 2).astype(np.float32)
    lower = np.full(1000, 10.0, np.float32)
    upper = np.full(1000, np.inf, np.float32)  # all right-censored at 10
    d = xgb.DMatrix(X, label_lower_bound=lower, label_upper_bound=upper)
    bst = xgb.train({"objective": "survival:aft", "max_depth": 2, "eta": 0.5},
                    d, num_boost_round=20, verbose_eval=False)
    pred = bst.predict(d)
    assert np.median(pred) > 8.0  # predictions pushed above/near the bound


def test_interval_regression_accuracy_metric():
    rng = np.random.RandomState(2)
    X = rng.randn(500, 2).astype(np.float32)
    lower = np.exp(rng.randn(500)).astype(np.float32)
    upper = lower * 2.0
    d = xgb.DMatrix(X, label_lower_bound=lower, label_upper_bound=upper)
    res = {}
    xgb.train(
        {"objective": "survival:aft", "max_depth": 2,
         "eval_metric": "interval-regression-accuracy"},
        d, num_boost_round=10, evals=[(d, "train")], evals_result=res,
        verbose_eval=False,
    )
    acc = res["train"]["interval-regression-accuracy"]
    assert acc[-1] >= acc[0]


def test_cox_orders_risk():
    rng = np.random.RandomState(3)
    n = 1000
    X = rng.randn(n, 3).astype(np.float32)
    risk = X[:, 0]  # higher risk -> earlier event
    t = np.exp(-risk + 0.5 * rng.randn(n))
    order = np.argsort(t)  # cox requires time-ascending sort
    X, t, risk = X[order], t[order], risk[order]
    y = t.astype(np.float32)  # all events (no censoring): positive labels
    d = xgb.DMatrix(X, label=y)
    bst = xgb.train({"objective": "survival:cox", "max_depth": 2, "eta": 0.3,
                     "eval_metric": "cox-nloglik"},
                    d, num_boost_round=15, verbose_eval=False)
    margin = bst.predict(d, output_margin=True)
    corr = np.corrcoef(margin, risk)[0, 1]
    assert corr > 0.6, corr


@pytest.mark.slow  # ~15s of tier-1 budget (1-core box); run with -m slow
def test_ranking_large_groups_sampled_path():
    """MSLR-WEB30K-shaped: groups of 1000+ docs at ~100k rows must train
    without materializing the [G, S, S] all-pairs tensor (review r2 weak
    item 4; reference pair sampling rank_obj.cu:143-198) and NDCG must
    improve over the untrained model."""
    rng = np.random.RandomState(3)
    G, S = 80, 1300  # max group size comparable to MSLR's worst case
    sizes = rng.randint(900, S + 1, G)
    n = int(sizes.sum())
    F = 12
    X = rng.randn(n, F).astype(np.float32)
    w = rng.randn(F)
    rel = X @ w + 0.8 * rng.randn(n)
    label = np.clip(np.digitize(rel, np.quantile(rel, [0.5, 0.75, 0.9, 0.97])),
                    0, 4).astype(np.float32)
    d = xgb.DMatrix(X, label=label)
    d.set_group(sizes)
    from xgboost_tpu.metric import create_metric

    ndcg = create_metric("ndcg@10")
    gptr = np.concatenate([[0], np.cumsum(sizes)])
    before = float(ndcg.evaluate(jnp.zeros(n), jnp.asarray(label),
                                 group_ptr=gptr))
    bst = xgb.train({"objective": "rank:ndcg", "max_depth": 5, "eta": 0.3,
                     "lambdarank_num_pair_per_sample": 2},
                    d, 15, verbose_eval=False)
    after = float(ndcg.evaluate(jnp.asarray(bst.predict(d)),
                                jnp.asarray(label), group_ptr=gptr))
    assert after > before + 0.05, (before, after)


def test_ranking_sampled_matches_allpairs_direction():
    """On small groups both paths must produce correlated gradients (the
    sampled estimator is unbiased up to pair-count scaling)."""
    from xgboost_tpu.objective import create_objective
    from xgboost_tpu.objective import ranking as R

    rng = np.random.RandomState(0)
    G, S = 30, 20
    sizes = np.full(G, S)
    n = G * S
    margin = jnp.asarray(rng.randn(n).astype(np.float32))
    label = jnp.asarray(rng.randint(0, 3, n).astype(np.float32))
    gptr = np.concatenate([[0], np.cumsum(sizes)])
    obj = create_objective("rank:pairwise", None)
    g_all, _ = obj.get_gradient(margin, label, None, group_ptr=gptr)
    old_budget = R._ALL_PAIRS_BUDGET
    try:
        R._ALL_PAIRS_BUDGET = 1  # force the sampled path
        class P: lambdarank_num_pair_per_sample = 8
        obj2 = create_objective("rank:pairwise", P())
        g_s, _ = obj2.get_gradient(margin, label, None, group_ptr=gptr)
    finally:
        R._ALL_PAIRS_BUDGET = old_budget
    corr = np.corrcoef(np.asarray(g_all), np.asarray(g_s))[0, 1]
    assert corr > 0.7, corr


def _map_delta_oracle(preds, labels):
    """Direct numpy transcription of the reference's MAP delta math
    (rank_obj.cu:474 GetMAPStats + :436 GetLambdaMAP) for ONE group.
    Returns delta[i, j] for every ordered doc pair (by original index)."""
    n = len(preds)
    order = np.argsort(-np.asarray(preds), kind="stable")
    pos_of = np.empty(n, np.int64)
    pos_of[order] = np.arange(n)
    sorted_labels = np.asarray(labels)[order]
    hit, a1, a2, a3 = 0.0, 0.0, 0.0, 0.0
    acc1, acc2, acc3, hits = [], [], [], []
    for i in range(1, n + 1):
        if sorted_labels[i - 1] > 0:
            hit += 1
            a1 += hit / i
            a2 += (hit - 1) / i
            a3 += (hit + 1) / i
        acc1.append(a1); acc2.append(a2); acc3.append(a3); hits.append(hit)

    def lam(pi, ni, pl, nl):
        if pi == ni or hits[-1] == 0:
            return 0.0
        if pi > ni:
            pi, ni, pl, nl = ni, pi, nl, pl
        original = acc1[ni] - (acc1[pi - 1] if pi else 0.0)
        l1, l2 = float(pl > 0), float(nl > 0)
        if l1 == l2:
            return 0.0
        if l1 < l2:
            changed = acc3[ni - 1] - acc3[pi] + (hits[pi] + 1.0) / (pi + 1)
        else:
            changed = acc2[ni - 1] - acc2[pi] + hits[ni] / (ni + 1)
        return abs(changed - original) / hits[-1]

    out = np.zeros((n, n))
    for i in range(n):
        for j in range(n):
            out[i, j] = lam(pos_of[i], pos_of[j], labels[i], labels[j])
    return out


def test_rank_map_deltas_match_reference_oracle():
    """Both the padded all-pairs path and the sampled path must weight
    pairs with the reference's exact MAP deltas."""
    from xgboost_tpu.objective.ranking import (
        _lambda_grad,
        _lambda_grad_sampled,
    )

    rng = np.random.RandomState(11)
    sizes = [7, 12, 5]
    gptr = np.concatenate([[0], np.cumsum(sizes)])
    n = int(gptr[-1])
    p = rng.randn(n).astype(np.float32)
    y = rng.randint(0, 2, n).astype(np.float32)

    # oracle gradient: all-pairs RankNet lambdas weighted by MAP deltas
    # times the reference sampler's expectation weight
    # 1/n_opp(i) + 1/n_opp(j) (rank_obj.cu:97-127 two-ended uniform draws)
    g_oracle = np.zeros(n)
    for g in range(len(sizes)):
        lo, hi = gptr[g], gptr[g + 1]
        deltas = _map_delta_oracle(p[lo:hi], y[lo:hi])
        yg = y[lo:hi]
        opp = np.array([(yg != yg[i]).sum() for i in range(sizes[g])],
                       float)
        opp = np.maximum(opp, 1.0)
        for i in range(sizes[g]):
            for j in range(sizes[g]):
                if y[lo + i] > y[lo + j]:
                    rho = 1.0 / (1.0 + np.exp(p[lo + i] - p[lo + j]))
                    lamv = rho * deltas[i, j] * (1.0 / opp[i] + 1.0 / opp[j])
                    g_oracle[lo + i] -= lamv
                    g_oracle[lo + j] += lamv

    group_of = np.repeat(np.arange(3, dtype=np.int32), sizes)
    rig = np.concatenate([np.arange(s, dtype=np.int32) for s in sizes])
    g_pad, _ = _lambda_grad(jnp.asarray(p), jnp.asarray(y),
                            jnp.asarray(group_of), jnp.asarray(rig),
                            3, max(sizes), "map")
    np.testing.assert_allclose(np.asarray(g_pad), g_oracle, atol=1e-5)

    # sampled path: the estimator now carries the reference-expectation
    # weights internally, so many draws must recover the oracle DIRECTLY
    # (no rescaling)
    from xgboost_tpu.objective.ranking import _build_layout

    n_pair = 256
    g_s, _ = _lambda_grad_sampled(
        jnp.asarray(p), _build_layout(y, gptr, None).arrays,
        jax.random.PRNGKey(0), jnp.int32(0), n_pair=n_pair, scheme="map")
    gs = np.asarray(g_s)
    corr = np.corrcoef(gs, g_oracle)[0, 1]
    assert corr > 0.98, corr
    rel_err = np.linalg.norm(gs - g_oracle) / np.linalg.norm(g_oracle)
    assert rel_err < 0.2, rel_err


def test_rank_map_differs_from_pairwise_and_improves_map():
    rng = np.random.RandomState(4)
    G, S = 40, 12
    n = G * S
    X = rng.randn(n, 6).astype(np.float32)
    w = rng.randn(6)
    rel = (X @ w + 0.7 * rng.randn(n) > 0.6).astype(np.float32)
    qid = np.repeat(np.arange(G), S)
    d = xgb.DMatrix(X, label=rel, qid=qid)
    res_m, res_p = {}, {}
    bm = xgb.train({"objective": "rank:map", "max_depth": 3,
                    "eval_metric": "map@5", "seed": 7},
                   d, 15, evals=[(d, "t")], evals_result=res_m,
                   verbose_eval=False)
    bp = xgb.train({"objective": "rank:pairwise", "max_depth": 3,
                    "eval_metric": "map@5", "seed": 7},
                   d, 15, evals=[(d, "t")], evals_result=res_p,
                   verbose_eval=False)
    m_hist = res_m["t"]["map@5"]
    assert m_hist[-1] > m_hist[0]  # map@n improves during training
    # the two objectives genuinely differ now
    assert not np.allclose(bm.predict(d), bp.predict(d))


def test_aft_nloglik_metric_uses_configured_distribution():
    """aft-nloglik must evaluate with the objective's configured
    distribution/scale (reference survival_metric.cu shares AFTParam), not
    a fresh default."""
    rng = np.random.RandomState(1)
    X = rng.randn(300, 3).astype(np.float32)
    t = np.exp(X[:, 0] + 0.1 * rng.randn(300)).astype(np.float32)
    d = xgb.DMatrix(X, label_lower_bound=t, label_upper_bound=t * 1.5)
    out = {}
    xgb.train({"objective": "survival:aft",
               "aft_loss_distribution": "logistic",
               "aft_loss_distribution_scale": 2.0,
               "eval_metric": "aft-nloglik", "max_depth": 2},
              d, 3, evals=[(d, "t")], evals_result=out, verbose_eval=False)
    v_logistic = out["t"]["aft-nloglik"][-1]
    out2 = {}
    xgb.train({"objective": "survival:aft",
               "aft_loss_distribution": "normal",
               "aft_loss_distribution_scale": 1.0,
               "eval_metric": "aft-nloglik", "max_depth": 2},
              d, 3, evals=[(d, "t")], evals_result=out2, verbose_eval=False)
    # different configured distributions must yield different metric values
    assert abs(v_logistic - out2["t"]["aft-nloglik"][-1]) > 1e-4
