"""Native C++ parser tests (parity vs the pure-Python parser on the
reference's own demo data)."""

import os

import numpy as np
import pytest

from conftest import require_native
from xgboost_tpu.native import get_lib, load_csv_native, load_svmlight_native

AGARICUS = "/root/reference/demo/data/agaricus.txt.train"



@pytest.fixture(autouse=True, scope="module")
def _native_or_fail():
    require_native(get_lib() is not None, "native parser library")


# the reference checkout (and its demo data) is not part of this
# container image: parity-vs-demo-data tests skip rather than fail
needs_reference_data = pytest.mark.skipif(
    not os.path.exists(AGARICUS),
    reason=f"reference demo data absent ({AGARICUS})")


@needs_reference_data
def test_native_libsvm_matches_python():
    from xgboost_tpu.data.adapters import _load_svmlight_py

    Xn, yn, qn = load_svmlight_native(AGARICUS)
    Xp, yp, qp = _load_svmlight_py(AGARICUS)
    assert Xn.shape == Xp.shape
    np.testing.assert_array_equal(yn, yp)
    np.testing.assert_array_equal(np.isnan(Xn), np.isnan(Xp))
    np.testing.assert_allclose(np.nan_to_num(Xn), np.nan_to_num(Xp))
    assert qn is None and qp is None


def test_native_libsvm_qid(tmp_path):
    p = tmp_path / "rank.txt"
    p.write_text("1 qid:1 0:1.5 2:2.5\n0 qid:1 1:0.5\n2 qid:2 0:-1e-2\n")
    X, y, qid = load_svmlight_native(str(p))
    np.testing.assert_array_equal(y, [1, 0, 2])
    np.testing.assert_array_equal(qid, [1, 1, 2])
    assert X.shape == (3, 3)
    assert X[0, 0] == pytest.approx(1.5)
    assert X[2, 0] == pytest.approx(-0.01)
    assert np.isnan(X[1, 0])


def test_native_csv(tmp_path):
    p = tmp_path / "d.csv"
    p.write_text("1,0.5,-2.25\n0,3e2,4\n1,-0.125,0.0\n")
    X, y = load_csv_native(str(p))
    np.testing.assert_array_equal(y, [1, 0, 1])
    np.testing.assert_allclose(X, [[0.5, -2.25], [300.0, 4.0], [-0.125, 0.0]])


def test_native_csv_empty_field_is_nan(tmp_path):
    p = tmp_path / "d.csv"
    p.write_text("1,,2\n0,3,\n")
    X, y = load_csv_native(str(p))
    assert np.isnan(X[0, 0]) and X[0, 1] == 2
    assert X[1, 0] == 3 and np.isnan(X[1, 1])


def test_native_libsvm_malformed_tokens_no_hang(tmp_path):
    # non-numeric junk must not hang the parser (progress guarantee)
    p = tmp_path / "bad.txt"
    p.write_text("abc 1:2\n1 0:junk 1:3.5\nNA 0:1\n0 garbage 1:2\n")
    X, y, _ = load_svmlight_native(str(p))
    # only the two numeric-label lines survive; malformed values dropped
    np.testing.assert_array_equal(y, [1, 0])
    assert X[0, 1] == pytest.approx(3.5)
    assert X[1, 1] == pytest.approx(2.0)


def test_native_csv_skips_header_and_comments(tmp_path):
    p = tmp_path / "h.csv"
    p.write_text("id,value,other\n# a comment\n1,0.5,2\n0,1.5,3\n")
    X, y = load_csv_native(str(p))
    np.testing.assert_array_equal(y, [1, 0])
    np.testing.assert_allclose(X, [[0.5, 2.0], [1.5, 3.0]])


def test_native_no_trailing_newline(tmp_path):
    p = tmp_path / "t.txt"
    with open(p, "w") as f:
        f.write("1 0:2.5")  # no trailing newline
    X, y, _ = load_svmlight_native(str(p))
    np.testing.assert_array_equal(y, [1])
    assert X[0, 0] == pytest.approx(2.5)


@needs_reference_data
def test_dmatrix_uses_native_path():
    import xgboost_tpu as xgb

    d = xgb.DMatrix(AGARICUS)
    assert d.num_row() == 6513 and d.num_col() == 127
