"""Quality metrics in plain numpy (float64), independent of the package."""

import numpy as np


def auc(score: np.ndarray, label: np.ndarray) -> float:
    """Area under the ROC curve by the rank statistic, ties averaged."""
    score = np.asarray(score, np.float64).ravel()
    label = np.asarray(label).ravel() > 0.5
    order = np.argsort(score, kind="mergesort")
    s = score[order]
    # the average rank of each run of equal scores
    edges = np.flatnonzero(np.r_[True, s[1:] != s[:-1], True])
    run = np.repeat(np.arange(len(edges) - 1), np.diff(edges))
    ranks = (0.5 * (edges[:-1] + edges[1:] - 1) + 1)[run]
    r = np.empty_like(ranks)
    r[order] = ranks
    n_pos = int(label.sum())
    n_neg = len(label) - n_pos
    if n_pos == 0 or n_neg == 0:
        raise ValueError("AUC needs both classes")
    return float((r[label].sum() - n_pos * (n_pos + 1) / 2) / (n_pos * n_neg))


def logloss_from_margin(margin: np.ndarray, label: np.ndarray) -> float:
    """Mean binary cross-entropy of ``sigmoid(margin)``."""
    m = np.asarray(margin, np.float64).ravel()
    y = np.asarray(label, np.float64).ravel()
    # log(1 + exp(-m)) for y = 1, log(1 + exp(m)) for y = 0, stably
    return float(np.mean(np.logaddexp(0.0, np.where(y > 0.5, -m, m))))


def mlogloss_from_margin(margin: np.ndarray, label: np.ndarray) -> float:
    """Mean multiclass cross-entropy of ``softmax(margin)``, margin [n, C]."""
    m = np.asarray(margin, np.float64)
    m = m - m.max(axis=1, keepdims=True)
    logp = m - np.log(np.exp(m).sum(axis=1, keepdims=True))
    idx = np.asarray(label).astype(np.int64).ravel()
    return float(-np.mean(logp[np.arange(len(idx)), idx]))

