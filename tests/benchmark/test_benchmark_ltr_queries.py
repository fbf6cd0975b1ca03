"""The ranking cell's generator and plain reference (ISSUE 26): the counts the
source fixes, the laws the seeds fix, and NDCG by hand."""

import numpy as np
import pytest

from bench_paths import load

gen = load("generators/ltr_queries.py")
lambdamart = load("reference/lambdamart.py")

ROWS, QUERIES, COLS = 24_000, 200, 9


@pytest.fixture(scope="module")
def drawn():
    return gen.generate(rows=ROWS, cols=COLS, seed=2500000037, law_seed=0,
                        queries=QUERIES)


def test_counts_are_exact_and_sizes_span_the_sources_range(drawn):
    X, y, qid = drawn
    assert X.shape == (ROWS, COLS) and X.dtype == np.float32
    assert y.shape == qid.shape == (ROWS,) and y.dtype == np.float32
    sizes = np.bincount(qid)
    assert len(sizes) == QUERIES and sizes.sum() == ROWS
    assert sizes.min() == 1 and sizes.max() == gen.MAX_QUERY == 1251
    assert set(np.unique(y)) <= {0.0, 1.0, 2.0, 3.0, 4.0}


def test_queries_are_contiguous_and_ascending(drawn):
    _, _, qid = drawn
    assert np.all(np.diff(qid) >= 0)
    gptr = lambdamart.group_ptr_of(qid)
    assert len(gptr) == QUERIES + 1 and gptr[0] == 0 and gptr[-1] == ROWS


def test_label_marginals_are_the_sources():
    _, y, _ = gen.generate(rows=200_000, cols=COLS, seed=3, queries=1700)
    share = np.bincount(y.astype(int), minlength=5) / len(y)
    assert np.abs(share - np.array(gen.LABEL_SHARE)).max() < 0.01


def test_seed_draws_the_rows_and_repeats(drawn):
    X, y, qid = drawn
    X2, y2, q2 = gen.generate(rows=ROWS, cols=COLS, seed=2500000037,
                              law_seed=0, queries=QUERIES)
    assert np.array_equal(X, X2) and np.array_equal(y, y2)
    assert np.array_equal(qid, q2)
    X3, y3, q3 = gen.generate(rows=ROWS, cols=COLS, seed=2500000038,
                              law_seed=0, queries=QUERIES)
    assert not np.array_equal(X, X3) and not np.array_equal(qid, q3)


def test_law_seed_fixes_the_task_and_the_count_columns(drawn):
    """Same ``--seed``, another ``law_seed``: another choice of count
    columns and another label for the same latent rows."""
    X, y, _ = drawn
    X2, y2, _ = gen.generate(rows=ROWS, cols=COLS, seed=2500000037,
                             law_seed=1, queries=QUERIES)

    def counts(M):
        return [f for f in range(COLS)
                if np.array_equal(M[:, f], np.floor(M[:, f]))]

    assert counts(X) and counts(X) != counts(X2)
    assert (y != y2).mean() > 0.2
    # a count column is low-cardinality, the others continuous
    f = counts(X)[0]
    assert len(np.unique(X[:, f])) <= 51 and X[:, f].min() >= 0


@pytest.mark.parametrize("rows,queries", [(5, 5), (1251 * 3, 3), (40, 7)])
def test_sizes_at_the_edges(rows, queries):
    sizes = gen.query_sizes(np.random.default_rng(0), rows, queries)
    assert sizes.sum() == rows and sizes.min() >= 1
    assert sizes.max() <= gen.MAX_QUERY


def test_rows_that_fit_no_such_queries_are_refused():
    with pytest.raises(ValueError):
        gen.query_sizes(np.random.default_rng(0), 1252 * 2, 2)


def test_ndcg_by_hand_and_the_query_without_a_relevant_document():
    # query 1: labels 2,0,1 scored in the order 0,1,2 -> DCG 3 + 0 + 1/2
    # against the ideal 3 + 1/log2(3); query 2 has no relevant document
    label = np.array([2.0, 0.0, 1.0, 0.0, 0.0])
    score = np.array([3.0, 2.0, 1.0, 0.5, 0.1])
    gptr = np.array([0, 3, 5])
    want = (3.0 + 0.5) / (3.0 + 1.0 / np.log2(3.0))
    assert lambdamart.ndcg_at_k(score, label, gptr, 10) == pytest.approx(
        (want + 1.0) / 2)
    # k cuts the list; ties rank in row order
    assert lambdamart.ndcg_at_k(np.zeros(5), label, gptr, 1) == pytest.approx(
        (1.0 + 1.0) / 2)
    # reversed: [0, 0] has no relevant document; [1, 0, 2] unscored shows
    # the label 1 first, 1 of an ideal 3
    assert lambdamart.ndcg_at_k(np.zeros(5), label[::-1].copy(),
                                np.array([0, 2, 5]), 1) == pytest.approx(
        (1.0 + 1.0 / 3.0) / 2)
