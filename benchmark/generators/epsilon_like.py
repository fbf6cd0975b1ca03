"""Rows shaped as Epsilon (PASCAL Large Scale Learning Challenge 2008, the
``epsilon_normalized`` files of the LIBSVM data page): the field's standing
wide and dense table.

From the source, exactly: every value present (no missing value), dense
float32, **every row scaled to unit length**, two balanced classes. The
source's labels are +1 / -1; a ``binary:logistic`` job takes them as 1 / 0,
and that is what comes back here.

Assumed, since the file itself (12 GB of text) cannot be shipped or fetched
here: the challenge never published how Epsilon was made, so the columns are
independent standard normals before the row is scaled, and the label is the
sign of a noisy score: a linear term over ``informative`` of the columns
(``strong`` of them with equal weight, which carry most of it and which every
tree splits on, and a tail whose weights fall as 1 / sqrt(rank)), plus
``pairs`` products of two of the strong columns each (an interaction has no
marginal effect of its own: only a tree deep enough to split on one column
and then on the other sees it, so depth matters), plus normal noise. A
product of two columns of the linear term skews the score (52.6% of the rows
lie above zero), so the label is the score against its median, which is part
of the law: estimated once, on rows drawn from ``law_seed`` alone. (A first
law, with every weight falling as 1 / sqrt(rank), was dropped before any
chip reading: which of its many near-equal columns a
four-round forest split on moved with the sample, and the holdout AUC with
it, by 0.0027 a seed at full size, too much for a band of 0.01. A second,
whose pairs took their second column from outside the linear term, read
0.8460-0.8546 over three seeds: whether a tree found such a partner among
2,000 columns moved with the sample too.)

``law_seed`` is the task (which columns, their weights, the pairs) and is
fixed in the configuration's file; ``--seed`` draws the rows and their noise
only, so every run learns the same function.

The matrix is made in blocks of rows, float32 throughout: the float64
``[500000, 2000]`` a single vectorised call would pass through is 8 GB, and
the result is 4 GB already.

**The width guard.** The cells have no path off the Mosaic kernels, as they
have none off the chip: a program whose level kernels do not take this width
would send every level to a ``segment_sum`` scatter at a few GB/s and sit in
the first chunk for many minutes, or be killed for memory inside the sketch.
So, before a row is drawn, ``generate`` asks the package's own shape-only
predicate whether a Mosaic level kernel takes the matrix
(``hist_kernel.pallas_level_fits(rows, cols, 1, bins)``) and fails the run if
not: a tree that loses the width fails fast, with a line that says why.
"""

import functools

import numpy as np

from harness import BenchFailure

BLOCK_ROWS = 32_768
LINEAR_SD, PAIR_WEIGHT, NOISE_SD = 1.0, 0.5, 0.3
TAIL_WEIGHT = 0.15  # of a strong column's, at the tail's first rank
_MEDIAN_ROWS = 1 << 18


@functools.lru_cache(maxsize=8)
def law(cols: int, law_seed: int, informative: int, strong: int, pairs: int):
    """(columns of the linear term, their weights, the pairs' columns
    ``[pairs, 2]``, the pairs' signs, the score's median): the task, from
    ``law_seed`` alone. The linear term has standard deviation
    ``LINEAR_SD`` over unit-variance columns: the first ``strong`` columns
    weigh the same, the others ``TAIL_WEIGHT / sqrt(rank)`` of that; the
    pairs are made of the strong columns, which a tree splits on anyway.
    The median is read off ``_MEDIAN_ROWS`` scores drawn from the same
    generator, after the law."""
    rng = np.random.default_rng(law_seed)
    informative = min(int(informative), cols)
    strong = min(int(strong), informative)
    idx = rng.choice(cols, size=informative, replace=False)
    rank = np.arange(1, informative + 1)
    w = rng.choice([-1.0, 1.0], size=informative) * np.where(
        rank <= strong, 1.0, TAIL_WEIGHT / np.sqrt(rank))
    w = (w * LINEAR_SD / np.sqrt((w ** 2).sum())).astype(np.float32)
    pairs = min(int(pairs), strong // 2)
    pair_cols = idx[:2 * pairs].reshape(pairs, 2)
    pair_sign = rng.choice([-1.0, 1.0], size=pairs).astype(np.float32)
    # only the law's own columns move the score: draw those alone
    z = rng.standard_normal((_MEDIAN_ROWS, informative), dtype=np.float32)
    local = np.arange(2 * pairs).reshape(pairs, 2)
    median = float(np.median(
        _score(rng, z, np.arange(informative), w, local, pair_sign)))
    return idx, w, pair_cols, pair_sign, median


def _score(rng, z, idx, w, pair_cols, pair_sign):
    """A block's noisy scores from its unit-variance columns ``z``."""
    score = z[:, idx] @ w
    score += PAIR_WEIGHT * (
        (z[:, pair_cols[:, 0]] * z[:, pair_cols[:, 1]]) @ pair_sign)
    score += NOISE_SD * rng.standard_normal(len(z), dtype=np.float32)
    return score


def require_mosaic_level_kernel(rows: int, cols: int, bins: int) -> None:
    """Fail the run unless the package's level kernels take this width."""
    from xgboost_tpu.tree import hist_kernel

    if not hist_kernel.pallas_level_fits(int(rows), int(cols), 1, int(bins)):
        raise BenchFailure(
            f"no Mosaic level kernel of this tree takes a {rows} x {cols} "
            f"matrix at {bins} bins (hist_kernel.pallas_level_fits is "
            "false): every level would fall to the XLA scatter, and the "
            "cells have no path off the Mosaic kernels")


def generate(rows: int, cols: int, seed: int, law_seed: int = 0,
             bins: int = 128, informative: int = 256, strong: int = 8,
             pairs: int = 4, **_):
    """(X float32 [rows, cols] with unit rows, y float32 [rows] in {0, 1})
    from ``seed``; the label's law from ``law_seed``."""
    require_mosaic_level_kernel(rows, cols, bins)
    idx, w, pair_cols, pair_sign, median = law(
        int(cols), int(law_seed), int(informative), int(strong), int(pairs))
    rng = np.random.default_rng(seed)
    X = np.empty((rows, cols), np.float32)
    y = np.empty((rows,), np.float32)
    for r0 in range(0, rows, BLOCK_ROWS):
        r1 = min(r0 + BLOCK_ROWS, rows)
        z = rng.standard_normal((r1 - r0, cols), dtype=np.float32)
        y[r0:r1] = _score(rng, z, idx, w, pair_cols, pair_sign) > median
        norm = np.sqrt(np.einsum("ij,ij->i", z, z, dtype=np.float32))
        np.divide(z, norm[:, None], out=X[r0:r1])
    return X, y
