"""The tree's last routing step as a Mosaic kernel (ISSUE 25).

``_route_rows_pallas`` runs ``_partition_tile``, the body both level kernels
share, over row tiles and nothing else; ``partition_apply`` reaches it through
the ``level_partition`` row of the dispatch table where the call site's
``pallas`` flag is set. Here the real kernel body runs in interpret mode on the
CPU against ``partition_apply_xla``: the decisions are integers, so equality is
exact. Positions are ``[1, n]`` (rows on the lanes, ISSUE 31), in and out. The
chip's compiler is held to it in ``tests/test_device_phases.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import xgboost_tpu as xgb
from xgboost_tpu import dispatch
from xgboost_tpu.parallel import make_mesh
from xgboost_tpu.parallel.mesh import ROW_AXIS, shard_rows
from xgboost_tpu.tree import hist_kernel as hk

N, F, B = 2 * hk.TR, 7, 16


@pytest.fixture()
def interpret(monkeypatch):
    monkeypatch.setattr(hk, "_INTERPRET", True)


def _case(Kp, *, default_left=None, cats=False, seed=0, n=N):
    """Rows at level ``d - 1`` of a heap and its decision table: a tenth of
    the bins missing, rows three nodes either side of the level, about a
    third of the nodes unsplit."""
    rng = np.random.RandomState(seed + Kp)
    d = Kp.bit_length()
    prev_offset = (1 << (d - 1)) - 1
    bins = rng.randint(0, B, size=(n, F)).astype(np.int32)
    bins[rng.rand(n, F) < 0.1] = B
    pos = rng.randint(max(prev_offset - 3, 0), prev_offset + Kp + 3,
                      size=(1, n)).astype(np.int32)
    ptab = np.zeros((Kp, 5 + B if cats else 4), np.float32)
    ptab[:, 0] = rng.rand(Kp) < 0.7 if Kp > 1 else 1.0
    ptab[:, 1] = rng.randint(0, F, Kp)
    ptab[:, 2] = rng.randint(0, B, Kp)
    ptab[:, 3] = (rng.randint(0, 2, Kp) if default_left is None
                  else default_left)
    if cats:
        ptab[:, 4] = rng.rand(Kp) < 0.5
        ptab[:, 5:] = rng.rand(Kp, B) < 0.4
    return jnp.asarray(bins), jnp.asarray(pos), jnp.asarray(ptab), d


def _both(bins, pos, ptab, Kp, d, **kw):
    want = hk.partition_apply_xla(bins, pos, ptab, Kp=Kp, B=B, d=d)
    got = hk.partition_apply(bins, pos, ptab, Kp=Kp, B=B, d=d, pallas=True,
                             **kw)
    assert dispatch.last_decisions()["level_partition"] == "pallas"
    return np.asarray(want), np.asarray(got)


@pytest.mark.parametrize("default_left", [0, 1])
@pytest.mark.parametrize("Kp", [1, 8, 32, 128])
def test_route_rows_equals_the_xla_partition(interpret, Kp, default_left):
    bins, pos, ptab, d = _case(Kp, default_left=default_left)
    want, got = _both(bins, pos, ptab, Kp, d)
    np.testing.assert_array_equal(got, want)
    assert got.dtype == np.int32 and got.shape == (1, N)
    # the case holds what it says it holds
    before = np.asarray(pos)
    lp = before[0] - ((1 << (d - 1)) - 1)
    outside = (lp < 0) | (lp >= Kp)
    unsplit = ~outside & (np.asarray(ptab)[np.clip(lp, 0, Kp - 1), 0] == 0)
    assert outside.any() and (Kp == 1 or unsplit.any())
    assert (np.asarray(bins) == B).any()
    np.testing.assert_array_equal(got[0, outside | unsplit],
                                  before[0, outside | unsplit])
    moved = got[0, ~(outside | unsplit)]
    assert set(np.unique(moved - 2 * before[0, ~(outside | unsplit)])) == {1, 2}


@pytest.mark.parametrize("Kp", [2, 32])
def test_route_rows_categorical_table(interpret, Kp):
    bins, pos, ptab, d = _case(Kp, cats=True)
    assert ptab.shape == (Kp, 5 + B)
    want, got = _both(bins, pos, ptab, Kp, d)
    np.testing.assert_array_equal(got, want)


def test_route_rows_under_a_two_device_shard_map(monkeypatch):
    """As ``parallel/grow.py`` runs it: rows sharded, the table replicated.
    With ``check_vma`` on the program must type (the output varies over the
    row axis, the table is cast to varying); the interpreter cannot run a
    kernel body under that check, so the values come from a second pass
    with the check off, as ``tests/test_distributed.py`` does."""
    Kp = 8
    bins, pos, ptab, d = _case(Kp, n=4 * hk.TR)
    mesh = make_mesh(2)

    def route(bins_s, pos_s, ptab_s):
        return hk.partition_apply(bins_s, pos_s, ptab_s, Kp=Kp, B=B, d=d,
                                  pallas=True, axis_name=ROW_AXIS)

    def sharded(check_vma):
        return jax.shard_map(
            route, mesh=mesh,
            in_specs=(P(ROW_AXIS, None), P(None, ROW_AXIS), P(None, None)),
            out_specs=P(None, ROW_AXIS), check_vma=check_vma)

    args = (shard_rows(bins, mesh),
            jax.device_put(pos, NamedSharding(mesh, P(None, ROW_AXIS))), ptab)
    typed = jax.make_jaxpr(sharded(True))(*args)
    assert "pallas_call" in str(typed) and "{V:%s}" % ROW_AXIS in str(typed)
    assert dispatch.last_decisions()["level_partition"] == "pallas"

    monkeypatch.setattr(hk, "_INTERPRET", True)
    got = sharded(False)(*args)
    want = hk.partition_apply_xla(bins, pos, ptab, Kp=Kp, B=B, d=d)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("rows,features,nodes,width,fits", [
    (733 * hk.TR, 50, 32, 4, True),  # the anchor
    (2564 * hk.TR, 28, 128, 4, True),  # HIGGS depth 8, a chip
    (hk.TR, 50, 128, 5 + 256, True),  # categorical, bin256
    (hk.TR + 8, 50, 32, 4, False),  # ragged rows
    (0, 50, 32, 4, False),
    # the row tile comes from the width (ISSUE 35): no cap at the untiled
    # level kernels' 512 columns; the whole bins row has to fit a 128-row
    # tile, which it does to about 5,000 columns
    (hk.TR, hk._MAX_KERNEL_FEATURES + 1, 32, 4, True),
    (hk.TR, hk._MAX_KERNEL_FEATURES, 32, 4, True),
    (hk.TR, hk._MAX_KERNEL_FEATURES, 128, 5 + 256, True),  # a smaller tile
    (391 * hk.TR, 2000, 32, 4, True),  # Epsilon
    (hk.TR, 6000, 32, 4, False),
    (hk.TR, 384, 32, 4, True),
    (hk.TR, 300, 128, 4, True),
])
def test_pallas_route_fits(rows, features, nodes, width, fits):
    assert hk.pallas_route_fits(rows, features, nodes, width) is fits


def test_fit_is_byte_equal_with_the_partition_at_pallas_and_at_xla(
        monkeypatch, interpret):
    """A whole fit through the interpreted kernels, the last routing once
    through the Mosaic kernel and once pinned to the XLA form: the same
    forest to the byte."""
    monkeypatch.setattr(hk, "use_pallas", lambda: True)
    rng = np.random.RandomState(3)
    X = rng.randn(900, 6).astype(np.float32)
    X[rng.rand(*X.shape) < 0.05] = np.nan
    y = (np.nan_to_num(X) @ rng.randn(6) > 0).astype(np.float32)
    params = {"objective": "binary:logistic", "max_depth": 3, "max_bin": 16,
              "eta": 0.3, "seed": 0}

    def fit(pin):
        jax.clear_caches()  # routes are resolved when a program is traced
        if pin:
            monkeypatch.setenv("XGBTPU_DISPATCH", pin)
        bst = xgb.train(params, xgb.DMatrix(X, label=y), num_boost_round=3)
        monkeypatch.delenv("XGBTPU_DISPATCH", raising=False)
        return bst.save_raw(), dispatch.last_decisions()["level_partition"]

    raw_pallas, route_pallas = fit("")
    raw_xla, route_xla = fit("level_partition=xla")
    assert (route_pallas, route_xla) == ("pallas", "xla")
    assert bytes(raw_pallas) == bytes(raw_xla)
