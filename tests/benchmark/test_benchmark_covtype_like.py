"""The Cover Type generator (ISSUE 32): the shapes the source fixes, the
laws the two seeds fix."""

import numpy as np
import pytest

from bench_paths import load

gen = load("generators/covtype_like.py")

ROWS = 60_000
Q, W, S = len(gen.QUANT), len(gen.WILDERNESS), gen.SOILS


@pytest.fixture(scope="module")
def drawn():
    return gen.generate(rows=ROWS, cols=54, seed=3200000037, law_seed=0)


def test_shapes_dtype_and_whole_numbers(drawn):
    X, y = drawn
    assert X.shape == (ROWS, 54) and X.dtype == np.float32
    assert y.shape == (ROWS,) and y.dtype == np.float32
    assert np.array_equal(X, np.rint(X))
    assert (Q, W, S) == (10, 4, 40) and Q + W + S == gen.COLS == 54


@pytest.mark.parametrize("f", range(10))
def test_quantitative_column_keeps_the_published_range(drawn, f):
    X, _ = drawn
    lo, hi, _, _ = gen.QUANT[f]
    assert lo <= X[:, f].min() and X[:, f].max() <= hi
    # many values, not two: these columns fill their bins
    assert len(np.unique(X[:, f])) > 40


def test_published_ranges_are_the_sources():
    assert [q[:2] for q in gen.QUANT] == [
        (1859, 3858), (0, 360), (0, 66), (0, 1397), (-173, 601), (0, 7117),
        (0, 254), (0, 254), (0, 254), (0, 7173)]
    assert gen.WILDERNESS == (260_796, 29_884, 253_364, 36_968)
    assert sum(gen.WILDERNESS) == sum(gen.CLASS_COUNT) == 581_012


@pytest.mark.parametrize("lo,hi,share", [
    (10, 14, np.array(gen.WILDERNESS) / 581_012), (14, 54, None)])
def test_one_hot_groups(drawn, lo, hi, share):
    X, _ = drawn
    block = X[:, lo:hi]
    assert set(np.unique(block)) == {0.0, 1.0}
    assert np.all(block.sum(axis=1) == 1.0)
    if share is not None:
        assert np.abs(block.mean(axis=0) - share).max() < 0.01
    else:
        # skewed, every soil type present, fixed by the law
        got = block.mean(axis=0)
        assert got.min() > 0.003 and got.max() > 0.15
        assert np.abs(got - gen.law_of(0)["soil_share"]).max() < 0.01


def test_no_two_columns_are_equal_on_a_replay_sample(drawn):
    X, _ = drawn
    rows = np.sort(np.random.default_rng(5).choice(ROWS, 16_384,
                                                   replace=False))
    cols = {X[rows, f].tobytes() for f in range(54)}
    assert len(cols) == 54


def test_labels_one_to_seven_with_the_published_priors():
    _, y = gen.generate(rows=200_000, cols=54, seed=11, law_seed=0)
    assert set(np.unique(y)) == {1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 7.0}
    share = np.bincount(y.astype(int), minlength=8) / len(y)
    assert share[0] == 0.0  # label 0 is never drawn: num_class is 8
    want = np.array(gen.CLASS_COUNT) / sum(gen.CLASS_COUNT)
    assert np.abs(share[1:] - want).max() < 0.01


def test_seed_draws_the_rows_and_repeats(drawn):
    X, y = drawn
    X2, y2 = gen.generate(rows=ROWS, cols=54, seed=3200000037, law_seed=0)
    assert np.array_equal(X, X2) and np.array_equal(y, y2)
    X3, y3 = gen.generate(rows=ROWS, cols=54, seed=3200000038, law_seed=0)
    assert not np.array_equal(X, X3) and not np.array_equal(y, y3)


def test_the_law_is_law_seeds_and_not_seeds(drawn):
    """Another ``--seed`` keeps the task (weights, effects, soil shares,
    intercepts); another ``law_seed`` is another task on the same rows'
    quantitative draws."""
    law = gen.law_of(0)
    gen.generate(rows=1000, cols=54, seed=99, law_seed=0)
    assert gen.law_of(0) is law
    other = gen.law_of(1)
    assert not np.allclose(other["w"], law["w"])
    assert not np.allclose(other["soil_share"], law["soil_share"])
    X, y = drawn
    X2, y2 = gen.generate(rows=ROWS, cols=54, seed=3200000037, law_seed=1)
    assert np.array_equal(X[:, :Q], X2[:, :Q])
    assert (y != y2).mean() > 0.2


def test_the_label_can_be_learnt_from_the_columns(drawn):
    """The scores are a function of the columns: the two large classes
    differ in the mean of the quantitative column that tells them apart
    most, in the direction of its weights."""
    X, y = drawn
    w = gen.law_of(0)["w"]
    f = int(np.abs(w[:, 0] - w[:, 1]).argmax())
    gap = (X[y == 1.0, f].mean() - X[y == 2.0, f].mean()) / X[:, f].std()
    assert gap * np.sign(w[f, 0] - w[f, 1]) > 0.1


def test_another_column_count_is_refused():
    with pytest.raises(ValueError):
        gen.generate(rows=10, cols=50, seed=1)
