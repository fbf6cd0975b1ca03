"""Rows shaped as folktables' ACSEmployment task (Ding, Hardt, Miller and
Schmidt, "Retiring Adult", NeurIPS 2021): the 2018 ACS 1-Year person survey
of every state, ages 16-90, sixteen features, label ESR == 1 (employed).

From the source: the sixteen columns in folktables' order (``COLUMNS``),
AGEP a whole number of years from 16 to 90 and the other fifteen coded
categories, each at the count of codes ``CARDS`` gives (SCHL 24, RELP 18,
ESP 9, RAC1P 9, MAR 5, CIT 5, MIL 5, ANC 5, DREM 3, MIG 3; DIS, NATIVITY,
DEAR, DEYE, SEX 2 each), as codes ``0 .. card - 1`` in float32. No value is
missing: folktables fills the survey's blanks with a code of their own.

Assumed, since the survey's files cannot be shipped or fetched here:

- a column's codes are drawn independently of the other columns', with
  shares falling as ``1 / rank ** 1.2`` (a few dominant levels and a long
  tail, as SCHL's and RELP's are), the ranks dealt to the codes by
  ``law_seed``; every code keeps a share of at least 0.2%, so that 2.59M
  rows hold each category many times over;
- AGEP is flat from 16 to 64 and falls linearly to a fifth of that at 90;
- the label is a Bernoulli draw of the logistic of a score: one effect per
  category of every column (normal, sd ``EFFECT_SD`` of the column, drawn
  by ``law_seed`` and so not monotone in the code: one-vs-rest and a
  threshold on the code both lose to a partition of the categories), an
  age curve that peaks at 40, effects of three pairs of columns (SCHL x
  SEX, MAR x RELP, DIS x ESP), and an intercept fitted on rows drawn from
  ``law_seed`` alone until the positive share is ``POSITIVE_SHARE`` (0.46,
  about the task's, quoted from memory).

``law_seed`` is the task and is fixed in the configuration's file; ``--seed``
draws the rows and their labels only, so every run learns the same function.
"""

import functools

import numpy as np

COLUMNS = ("AGEP", "SCHL", "MAR", "RELP", "DIS", "ESP", "CIT", "MIG", "MIL",
           "ANC", "NATIVITY", "DEAR", "DEYE", "DREM", "SEX", "RAC1P")
# codes of each column; 0 for the one numerical column, AGEP
CARDS = (0, 24, 5, 18, 2, 9, 5, 3, 5, 5, 2, 2, 2, 3, 2, 9)
AGE_LO, AGE_HI = 16, 90
ZIPF, MIN_SHARE = 1.2, 0.002
# the score's parts: per-column effect sd, the pairs of columns and their sd
EFFECT_SD = {"SCHL": 0.7, "RELP": 0.6, "ESP": 0.5, "RAC1P": 0.3, "MAR": 0.4,
             "CIT": 0.2, "MIL": 0.3, "ANC": 0.2, "DREM": 0.5, "MIG": 0.2,
             "DIS": 0.8, "NATIVITY": 0.1, "DEAR": 0.3, "DEYE": 0.3,
             "SEX": 0.3}
PAIRS = (("SCHL", "SEX", 0.3), ("MAR", "RELP", 0.3), ("DIS", "ESP", 0.4))
AGE_PEAK, AGE_WIDTH, AGE_GAIN = 40.0, 16.0, 1.6
POSITIVE_SHARE = 0.46
_FIT_ROWS, _FIT_STEPS = 1 << 18, 50
_CHUNK = 1 << 19


@functools.lru_cache(maxsize=4)
def _law(law_seed: int):
    """(shares, effects, pair effects, intercept) of the task ``law_seed``."""
    rng = np.random.default_rng([law_seed, 0xAC5])
    shares, effects = [], []
    for name, card in zip(COLUMNS, CARDS):
        if not card:
            shares.append(None)
            effects.append(None)
            continue
        p = 1.0 / np.arange(1, card + 1) ** ZIPF
        p = np.maximum(p / p.sum(), MIN_SHARE)
        shares.append(rng.permutation(p / p.sum()))
        effects.append(rng.normal(0.0, EFFECT_SD[name], card))
    pairs = [(COLUMNS.index(a), COLUMNS.index(b),
              rng.normal(0.0, sd, (CARDS[COLUMNS.index(a)],
                                   CARDS[COLUMNS.index(b)])))
             for a, b, sd in PAIRS]
    law = (tuple(shares), tuple(effects), tuple(pairs))
    X = _features(np.random.default_rng([law_seed, 0xF17]), _FIT_ROWS,
                  law[0])
    s = _score(X, law)
    lo, hi = -10.0, 10.0
    for _ in range(_FIT_STEPS):  # the intercept of the positive share
        mid = 0.5 * (lo + hi)
        if np.mean(1.0 / (1.0 + np.exp(-(s + mid)))) < POSITIVE_SHARE:
            lo = mid
        else:
            hi = mid
    return law + (0.5 * (lo + hi),)


def _ages(rng, rows: int) -> np.ndarray:
    ages = np.arange(AGE_LO, AGE_HI + 1)
    w = np.where(ages < 65, 1.0, 1.0 - 0.8 * (ages - 64) / (AGE_HI - 64))
    return rng.choice(ages, size=rows, p=w / w.sum())


def _features(rng, rows: int, shares) -> np.ndarray:
    X = np.empty((rows, len(COLUMNS)), np.float32)
    for f, p in enumerate(shares):
        if p is None:
            X[:, f] = _ages(rng, rows)
        else:
            X[:, f] = np.searchsorted(np.cumsum(p)[:-1], rng.random(rows),
                                      side="right")
    return X


def _score(X: np.ndarray, law) -> np.ndarray:
    _, effects, pairs = law[:3]
    codes = X.astype(np.int64)
    s = np.zeros(len(X))
    for f, e in enumerate(effects):
        if e is not None:
            s += e[codes[:, f]]
    s += AGE_GAIN * (1.0 - ((X[:, 0] - AGE_PEAK) / AGE_WIDTH) ** 2).clip(-2)
    for a, b, e in pairs:
        s += e[codes[:, a], codes[:, b]]
    return s


def generate(rows: int, cols: int, seed: int, law_seed: int = 0):
    """(X [rows, 16] float32, y [rows] float32 in {0, 1}); column 0 is AGEP,
    the others category codes."""
    if cols != len(COLUMNS):
        raise ValueError(f"ACSEmployment has {len(COLUMNS)} columns, "
                         f"not {cols}")
    law = _law(law_seed)
    rng = np.random.default_rng([seed, law_seed])
    X = _features(rng, rows, law[0])
    y = np.empty(rows, np.float32)
    for lo in range(0, rows, _CHUNK):
        s = _score(X[lo:lo + _CHUNK], law) + law[3]
        p = 1.0 / (1.0 + np.exp(-s))
        y[lo:lo + _CHUNK] = rng.random(len(p)) < p
    return X, y


def feature_types() -> list:
    """``DMatrix(feature_types=...)``: ``q`` for AGEP, ``c`` for the rest."""
    return ["c" if card else "q" for card in CARDS]
