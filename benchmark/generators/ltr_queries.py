"""Query-grouped documents with graded relevance, shaped as MSLR-WEB30K.

From the source (Qin & Liu 2013, Fold1's training set), exactly: the column
count, the row and query counts the configuration gives, query sizes in
[1, 1251] (one query of each extreme, where the counts allow) with the mean the
counts imply (120), labels 0-4 with MSLR's marginals (51.5 / 32.3 / 13.4 /
1.9 / 0.8 %, rounded from the published counts; the tenth of a percent the
rounding drops falls to label 4, which draws 0.9 %), and the rows of a
query contiguous, queries in ascending ``qid``.

Assumed, since the data set itself cannot be shipped or fetched here:

- sizes: a log-normal law (sigma 0.55) scaled to the mean, rounded, clipped
  to [1, 1251], then moved by single documents at random queries until they
  sum to ``rows``;
- features: a latent standard normal ``z`` a cell; ``COUNT_SHARE`` of the
  columns (which ones: ``law_seed``) are shown as low-cardinality counts
  ``min(floor(exp(0.7 z + 0.5)), 50)``, as MSLR's term-frequency columns
  are, the rest as ``z`` itself, float32;
- relevance: ``score = z . w + 0.5 * (query's shift) + 0.8 * noise`` with
  unit-norm ``w`` on 30% of the columns, magnitudes falling as 1, 1/2,
  1/3, ... (a few strong signals and a long tail, as a ranker's features
  are; with equal weights on forty columns a six-round model's NDCG@10
  moved by 0.006 from seed to seed), cut at the fixed quantiles of its law
  (``N(0, 1.89)``) that give the marginals: documents compete inside a
  query, and a query as a whole can be easy or hard.

``w`` and the choice of count columns are the task and come from
``law_seed``, fixed in the configuration's file; ``--seed`` draws the sizes,
the rows and the noise, so every run learns the same function.
"""

from statistics import NormalDist

import numpy as np

MAX_QUERY = 1251
# labels 0..4; the last is what the others leave (the source's reads 0.8 %)
LABEL_SHARE = (0.515, 0.323, 0.134, 0.019, 0.009)
W_SHARE = 0.3
COUNT_SHARE = 0.3
SHIFT_SD, NOISE_SD = 0.5, 0.8
_CHUNK = 1 << 18  # rows drawn at a time: the noise and z stay small


def query_sizes(rng, rows: int, queries: int, sigma: float = 0.55):
    """[queries] int64 in [1, MAX_QUERY], summing to ``rows``."""
    if not queries <= rows <= queries * MAX_QUERY:
        raise ValueError(f"{rows} rows do not fit {queries} queries of 1 to "
                         f"{MAX_QUERY} documents")
    mean = rows / queries
    raw = rng.lognormal(np.log(mean) - sigma * sigma / 2.0, sigma, queries)
    sizes = np.clip(np.rint(raw), 1, MAX_QUERY).astype(np.int64)
    free = np.ones(queries, bool)
    rest = rows - 1 - MAX_QUERY
    if queries >= 3 and queries - 2 <= rest <= (queries - 2) * MAX_QUERY:
        # the source's extremes, and they stay
        lo, hi = rng.choice(queries, 2, replace=False)
        sizes[lo], sizes[hi] = 1, MAX_QUERY
        free[[lo, hi]] = False
    while (diff := rows - int(sizes.sum())) != 0:
        step = 1 if diff > 0 else -1
        room = np.flatnonzero(free & ((sizes < MAX_QUERY) if step > 0
                                      else (sizes > 1)))
        pick = rng.choice(room, min(abs(diff), len(room)), replace=False)
        sizes[pick] += step
    return sizes


def generate(rows: int, cols: int, seed: int, law_seed: int = 0,
             queries: int = 1, **_):
    """(X float32 [rows, cols], y float32 [rows] in 0..4, qid int32 [rows])
    from ``seed``; the task from ``law_seed``."""
    law = np.random.default_rng(law_seed)
    informative = np.flatnonzero(law.random(cols) < W_SHARE)
    if not len(informative):
        informative = np.array([0])
    # a few strong signals and a long tail, as a ranker's features are
    # (BM25 beside hundreds of weak ones): magnitudes 1, 1/2, 1/3, ...
    w = np.zeros(cols)
    w[law.permutation(informative)] = (
        law.choice([-1.0, 1.0], len(informative))
        / np.arange(1, len(informative) + 1))
    w = (w / np.linalg.norm(w)).astype(np.float32)
    is_count = law.random(cols) < COUNT_SHARE
    sd = float(np.sqrt(1.0 + SHIFT_SD ** 2 + NOISE_SD ** 2))
    cuts = np.array([sd * NormalDist().inv_cdf(c)
                     for c in np.cumsum(LABEL_SHARE)[:-1]], np.float32)

    rng = np.random.default_rng(seed)
    sizes = query_sizes(rng, rows, queries)
    qid = np.repeat(np.arange(queries, dtype=np.int32), sizes)
    shift = rng.standard_normal(queries, dtype=np.float32)
    X = np.empty((rows, cols), np.float32)
    y = np.empty(rows, np.float32)
    for lo in range(0, rows, _CHUNK):
        hi = min(lo + _CHUNK, rows)
        z = rng.standard_normal((hi - lo, cols), dtype=np.float32)
        score = (z @ w + SHIFT_SD * shift[qid[lo:hi]] + NOISE_SD
                 * rng.standard_normal(hi - lo, dtype=np.float32))
        y[lo:hi] = np.searchsorted(cuts, score)
        zc = z[:, is_count]
        z[:, is_count] = np.minimum(np.floor(np.exp(0.7 * zc + 0.5)), 50.0)
        X[lo:hi] = z
    return X, y, qid
