"""Rows shaped as UCI Covertype (Blackard & Dean 1999), the table of XGBoost's
``demo/gpu_acceleration/cover_type.py``.

From the source, exactly: 54 columns of three kinds, every value a whole
number in float32: columns 0-9 the ten quantitative measurements in their
published ranges (``QUANT``: elevation 1859-3858 m, aspect 0-360, slope 0-66,
the distances to hydrology, roadways and fire points, three hillshade
indices 0-254); columns 10-13 one one-hot group, the wilderness area, with
the published counts (``WILDERNESS``: 260,796 / 29,884 / 253,364 / 36,968 of
581,012); columns 14-53 a second one-hot group, the forty soil types; labels
1-7 with the published class counts (``CLASS_COUNT``), label 0 never drawn,
which is why the demo trains with ``num_class`` 8.

Assumed, since the file itself cannot be shipped or fetched here:

- the quantitative columns are independent: aspect uniform, the others a
  normal law with the published mean and standard deviation (quoted from
  memory of ``covtype.info``), rounded and clipped to the range;
- the soil types' shares fall as 1 / rank (the largest 23%, the source's
  19.8%; the smallest 0.58%, where the source has types of three rows: a
  type absent from a 16,384-row sample would make two all-zero columns
  there, and equal columns tie in the replay), the ranks dealt to the
  columns by ``law_seed``; soil and wilderness are drawn independently;
- the label is the largest of seven noisy scores: ``z . W`` over the
  standardised quantitative columns, plus an effect of the row's wilderness
  area and one of its soil type, plus the class's intercept, plus standard
  normal noise. ``W`` and the effects come from ``law_seed``; the intercepts
  are fitted, on rows drawn from ``law_seed`` alone, until the classes'
  shares are the published ones.

``law_seed`` is the task and is fixed in the configuration's file; ``--seed``
draws the rows and their noise only, so every run learns the same function.
"""

import functools

import numpy as np

COLS = 54
# (low, high, mean, sd) of the ten quantitative columns; sd 0: uniform
QUANT = ((1859, 3858, 2959.0, 280.0),   # elevation
         (0, 360, 0.0, 0.0),            # aspect
         (0, 66, 14.1, 7.5),            # slope
         (0, 1397, 269.0, 212.0),       # horizontal distance to hydrology
         (-173, 601, 46.0, 58.0),       # vertical distance to hydrology
         (0, 7117, 2350.0, 1559.0),     # horizontal distance to roadways
         (0, 254, 212.0, 27.0),         # hillshade 9 am
         (0, 254, 223.0, 20.0),         # hillshade noon
         (0, 254, 142.0, 38.0),         # hillshade 3 pm
         (0, 7173, 1980.0, 1324.0))     # horizontal distance to fire points
WILDERNESS = (260_796, 29_884, 253_364, 36_968)
SOILS = 40
CLASS_COUNT = (211_840, 283_301, 35_754, 2_747, 9_493, 17_367, 20_510)
W_SD, WILD_SD, SOIL_SD = 0.6, 1.0, 1.0
_FIT_ROWS, _FIT_STEPS = 1 << 18, 60
_CHUNK = 1 << 18


def _features(rng, rows: int, soil_share) -> np.ndarray:
    X = np.zeros((rows, COLS), np.float32)
    for f, (lo, hi, mean, sd) in enumerate(QUANT):
        if sd:
            x = mean + sd * rng.standard_normal(rows, dtype=np.float32)
        else:
            x = rng.uniform(lo, hi, rows)
        X[:, f] = np.clip(np.rint(x), lo, hi)
    area = rng.choice(len(WILDERNESS), rows,
                      p=np.array(WILDERNESS) / sum(WILDERNESS))
    X[np.arange(rows), len(QUANT) + area] = 1.0
    soil = rng.choice(SOILS, rows, p=soil_share)
    X[np.arange(rows), len(QUANT) + len(WILDERNESS) + soil] = 1.0
    return X


def _scores(rng, X, law) -> np.ndarray:
    """[rows, 7] float32: the classes' scores before their intercepts."""
    q = len(QUANT)
    mean = np.array([m if sd else (lo + hi) / 2 for lo, hi, m, sd in QUANT],
                    np.float32)
    sd = np.array([sd if sd else (hi - lo) / 12 ** 0.5
                   for lo, hi, _, sd in QUANT], np.float32)
    z = (X[:, :q] - mean) / sd
    return (z @ law["w"] + X[:, q:] @ law["effect"]
            + rng.standard_normal((len(X), len(CLASS_COUNT)),
                                  dtype=np.float32))


@functools.lru_cache(maxsize=4)
def law_of(law_seed: int) -> dict:
    """The task: the soil types' shares, the scores' weights, and the
    intercepts that give the published class shares. Nothing of ``--seed``."""
    rng = np.random.default_rng(law_seed)
    share = 1.0 / np.arange(1, SOILS + 1)
    law = {"soil_share": rng.permutation(share / share.sum()),
           "w": (W_SD * rng.standard_normal(
               (len(QUANT), len(CLASS_COUNT)))).astype(np.float32),
           "effect": np.concatenate([
               WILD_SD * rng.standard_normal((len(WILDERNESS),
                                              len(CLASS_COUNT))),
               SOIL_SD * rng.standard_normal((SOILS, len(CLASS_COUNT)))]
           ).astype(np.float32)}
    s = _scores(rng, _features(rng, _FIT_ROWS, law["soil_share"]), law)
    want = np.array(CLASS_COUNT) / sum(CLASS_COUNT)
    b = np.zeros(len(CLASS_COUNT), np.float32)
    for _ in range(_FIT_STEPS):
        got = np.bincount((s + b).argmax(axis=1), minlength=len(want))
        b += np.log(want / np.maximum(got / len(s), 1e-6)).astype(np.float32)
    law["intercept"] = b
    return law


def generate(rows: int, cols: int, seed: int, law_seed: int = 0, **_):
    """(X float32 [rows, 54] of whole numbers, y float32 [rows] in 1..7)
    from ``seed``; the task from ``law_seed``."""
    if cols != COLS:
        raise ValueError(f"Covertype has {COLS} columns, not {cols}")
    law = law_of(int(law_seed))
    rng = np.random.default_rng(seed)
    X = np.empty((rows, COLS), np.float32)
    y = np.empty(rows, np.float32)
    for lo in range(0, rows, _CHUNK):
        hi = min(lo + _CHUNK, rows)
        X[lo:hi] = _features(rng, hi - lo, law["soil_share"])
        y[lo:hi] = 1 + (_scores(rng, X[lo:hi], law)
                        + law["intercept"]).argmax(axis=1)
    return X, y
