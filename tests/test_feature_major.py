"""The untiled level kernels on the feature-major bins (ISSUE 38).

Every Mosaic call of a tree reads the widened bins ``[Fp, n]`` (rows on the
lanes, padded with the missing bin to whole sublanes) and builds a column's
one-hot ``[B, tr]`` by a sublane broadcast, as the tiled kernel has since
ISSUE 36. Here, on the CPU with the kernel bodies interpreted, the streaming
kernel (a resident prefix of none, some or all columns), the construct-only
kernel and the routing kernel are held to the parent's ROW-MAJOR layout bit
for bit: ``_rows_major_level`` is the parent's untiled level, its ``(tr, F)``
bins block, a column's ``[tr, B]`` one-hot by a lane broadcast and the
``[2M, tr] @ [tr, B]`` product, at the same row tile, with the rows routed
by ``partition_apply_xla`` (integer decisions: the parent's kernel routed to
the same positions). A small job's forest is the parent layout's byte for
byte, and Epsilon's tree program, on the tiled kernel throughout, is the
parent's to the byte.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import xgboost_tpu as xgb
from xgboost_tpu.tree import hist_kernel as hk

N, TR, F, B = 1024, 256, 13, 16  # F pads to 16: a partial sublane group


@pytest.fixture
def mosaic_route(monkeypatch):
    """The route the chip takes: the Pallas kernels, their bodies
    interpreted."""
    monkeypatch.setattr(hk, "use_pallas", lambda: True)
    monkeypatch.setattr(hk, "_INTERPRET", True)
    monkeypatch.setenv("XGBTPU_HOIST_BUDGET_MB", "0")


def _level_inputs(d, T=None, cats=False, seed=0, B=B):
    """Bins with missing values, positions at level ``d - 1`` (some rows
    stayed above it), gradients, and decision tables whose first column
    marks no split, the left or the right child; ``T`` trees' where given;
    a categorical table (``[Kp, 5 + B]``) where ``cats``."""
    rng = np.random.RandomState(seed + 7 * d + 31 * (T or 0))
    R = T or 1
    bins = rng.randint(0, B + 1, (N, F)).astype(np.int32)
    Kp = (1 << d) >> 1
    W = 5 + B if cats else 4
    if d == 0:
        pos = np.zeros((R, N), np.int32)
        ptab = np.zeros((R, 1, W), np.float32)
    else:
        prev = (1 << (d - 1)) - 1
        pos = rng.randint(max(prev - 1, 0), prev + Kp, (R, N)).astype(np.int32)
        ptab = np.zeros((R, Kp, W), np.float32)
        ptab[..., 0] = rng.randint(0, 3, (R, Kp))
        ptab[:, 0, 0] = 2  # every tree splits a parent: rows move
        ptab[..., 1] = rng.randint(0, F, (R, Kp))
        ptab[..., 2] = rng.randint(0, B, (R, Kp))
        ptab[..., 3] = rng.randint(0, 2, (R, Kp))
        if cats:
            ptab[..., 4] = rng.rand(R, Kp) < 0.5
            ptab[..., 5:] = rng.rand(R, Kp, B) < 0.4
    gh = rng.randn(2 * R, N).astype(np.float32)
    gh[1::2] = np.abs(gh[1::2])
    if T is None:
        ptab = ptab[0]
    return (jnp.asarray(bins), jnp.asarray(pos), jnp.asarray(gh),
            jnp.asarray(ptab))


def _rows_major_level(bins, onehot, pos, gh, ptab, *, K, Kp, B, d, tr, sub):
    """The parent's untiled level (ISSUE 37's tree): the row-major i32 bins
    ``[n, F]`` by ``(tr, F)`` blocks, the resident one-hot's ``[2M, tr] @
    [tr, Fh B]`` and, for every other column, its ``[tr, B]`` one-hot by a
    lane broadcast of the column and ``[2M, tr] @ [tr, B]``, row tile by row
    tile; the rows routed first (exact), the channels the package's. The
    contract of ``_hoisted_level_pallas``."""
    from jax.experimental import pallas as pl

    n, F = bins.shape
    T = ptab.shape[0] if ptab.ndim == 3 else None
    if Kp > 0:
        tabs = [ptab] if T is None else [ptab[t] for t in range(T)]
        pos = jnp.concatenate([
            hk.partition_apply_xla(bins, pos[t:t + 1], tab, Kp=Kp, B=B, d=d)
            for t, tab in enumerate(tabs)])
    R = T or 1
    Kc = Kp if sub else K
    M = 2 * R * Kc
    Fh = 0 if onehot is None else onehot.shape[1] // B
    built, built_specs = hk._built_children(ptab, Kp=Kp, d=d, sub=sub)

    def kernel(bins_ref, *refs):
        oh_ref, refs = (refs[0], refs[1:]) if Fh else (None, refs)
        pos_ref, gh_ref, *built_ref, hist_ref = refs
        built_ref = built_ref[0] if built_ref else None

        @pl.when(pl.program_id(0) == 0)
        def _():
            hist_ref[...] = jnp.zeros_like(hist_ref)

        ghs4 = hk._route_and_channels(
            pos_ref, None, gh_ref, None, built_ref, None, T=T, K=K, Kp=0,
            B=0, prev_offset=0, offset=(1 << d) - 1)
        if Fh:
            out = jax.lax.dot_general(
                ghs4, oh_ref[:, :].astype(jnp.bfloat16),
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            hist_ref[:, :Fh * B] += out[:M] + out[M:]
        binsb = bins_ref[:, :]  # [tr, F]
        for f in range(Fh, F):
            iota_b = jax.lax.broadcasted_iota(jnp.int32, (tr, B), 1)
            oh = (binsb[:, f:f + 1] == iota_b).astype(jnp.bfloat16)
            out = jax.lax.dot_general(
                ghs4, oh, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            hist_ref[:, f * B:(f + 1) * B] += out[:M] + out[M:]

    oh_specs = [pl.BlockSpec((tr, Fh * B), lambda c: (c, 0))] if Fh else []
    hist = pl.pallas_call(
        kernel,
        grid=(n // tr,),
        in_specs=[pl.BlockSpec((tr, F), lambda c: (c, 0))] + oh_specs + [
            pl.BlockSpec((R, tr), lambda c: (0, c)),
            pl.BlockSpec((2 * R, tr), lambda c: (0, c))] + built_specs,
        out_specs=pl.BlockSpec((M, F * B), lambda c: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((M, F * B), jnp.float32),
        interpret=True,
    )(bins, *([onehot] if Fh else []), pos, gh, *built)
    if T is None:
        return pos, jnp.transpose(hist.reshape(2 * Kc, F, B), (1, 0, 2))
    return pos, jnp.transpose(hist.reshape(T, 2 * Kc, F, B), (0, 2, 1, 3))


LEVELS = [(0, False), (2, False), (2, True)]


@pytest.mark.parametrize("Fh", [0, 5, F])
@pytest.mark.parametrize("d,sub", LEVELS)
@pytest.mark.parametrize("T", [None, 4])
def test_untiled_kernels_are_the_row_major_layouts_bit_for_bit(
        mosaic_route, T, d, sub, Fh):
    """The streaming kernel with ``Fh`` of the 13 columns resident (none:
    the construct-only kernel) on the feature-major ``(16, tr)`` blocks,
    for one tree and four, directly and sibling-subtracting: the parent's
    row-major level's positions, and every histogram cell the same bits."""
    bins, pos, gh, ptab = _level_inputs(d, T)
    K = 1 << d
    kw = dict(K=K, Kp=K >> 1, B=B, d=d, tr=TR, sub=sub)
    binsT = hk._feature_major(bins, hk._SUBLANES, B)
    assert binsT.shape == (16, N) and bool((binsT[F:] == B).all())
    if Fh:
        onehot = hk._build_onehot_xla(bins[:, :Fh], B=B)
        got = hk._hoisted_level_pallas(binsT, onehot, pos, gh, ptab, F=F,
                                       **kw)
    else:
        onehot = None
        got = hk._fused_level_pallas(binsT, pos, gh, ptab, F=F, **kw)
    want = _rows_major_level(bins, onehot, pos, gh, ptab, **kw)
    Kc = (K >> 1) if sub else K
    assert got[1].shape == want[1].shape == (
        (F, 2 * Kc, B) if T is None else (T, F, 2 * Kc, B))
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(jnp.abs(got[1]).sum()) > 0.0
    if d:
        assert bool((got[0] != pos).any())


@pytest.mark.parametrize("B_,cats", [(B, False), (B, True), (300, True)])
def test_routing_on_the_feature_major_bins_at_an_untiled_width(
        mosaic_route, B_, cats):
    """The tree's last routing (``partition_apply``) at 13 columns, where
    no level is tiled: the routing kernel reads the ``(16, tr)`` blocks of
    the same feature-major array the levels read, with a numerical or a
    categorical table, and at 300 bins (past bf16's integers: the f32
    pick); the decisions are ``partition_apply_xla``'s exactly."""
    bins, pos, _, ptab = _level_inputs(4, cats=cats, seed=B_, B=B_)
    Kp = 8
    assert hk._tile_at(F, B_, 1) == 0
    want = hk.partition_apply_xla(bins, pos, ptab, Kp=Kp, B=B_, d=4)
    got = hk.partition_apply(bins, pos, ptab, Kp=Kp, B=B_, d=4, pallas=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    direct = hk._route_rows_pallas(hk._feature_major(bins, hk._SUBLANES, B_),
                                   pos, ptab, Kp=Kp, B=B_, d=4, tr=TR)
    np.testing.assert_array_equal(np.asarray(direct), np.asarray(want))
    assert bool((got != pos).any())


def _job(classes):
    rng = np.random.default_rng(5)
    X = rng.standard_normal((2048, F)).astype(np.float32)
    X[rng.random(X.shape) < 0.05] = np.nan
    params = {"tree_method": "tpu_hist", "max_depth": 4, "max_bin": 32,
              "seed": 3}
    if classes:
        y = rng.integers(0, classes, 2048).astype(np.float32)
        params.update(objective="multi:softmax", num_class=classes)
    else:
        y = (X[:, 0] + X[:, 1] > 0).astype(np.float32)
        params.update(objective="binary:logistic")
    d = xgb.DMatrix(X, label=y)
    bst = xgb.Booster(params, [d])
    bst.update_many(d, 0, 2, chunk=2)  # the scan path
    bst.update(d, 2)  # the per-round path
    return bytes(bst.save_raw("json"))


@pytest.mark.parametrize("classes", [0, 3])
def test_a_jobs_forest_is_the_row_major_layouts(mosaic_route, monkeypatch,
                                                classes):
    """A depth-4 job at 13 columns with 8 resident (a budget of 8 columns'
    one-hot at 2,048 rows and 32 bins), binary and three class trees grown
    together: its
    forest through the feature-major kernels is the forest whose untiled
    levels run the parent's row-major layout, byte for byte."""
    monkeypatch.setattr(hk, "hoist_budget_bytes", lambda: 2048 * 32 * 8)
    assert hk.hoist_plan(2048, F, 32, 4) == 8
    jax.clear_caches()
    got = _job(classes)
    calls = []

    def parent_level_call(bins, onehot, pos, gh, ptab, *, K, Kp, B, d, vma,
                          sub):
        n, F_ = bins.shape
        nodes = (ptab.shape[0] if ptab.ndim == 3 else 1) * (Kp if sub else K)
        width = 0 if onehot is None else onehot.shape[1]
        plan = hk.level_plan(n, F_, nodes, B, width, ptab.shape[-1])
        assert plan.kernel in ("hoisted", "construct")
        calls.append(plan.kernel)
        return _rows_major_level(
            bins, onehot if plan.kernel == "hoisted" else None, pos, gh,
            ptab, K=K, Kp=Kp, B=B, d=d, tr=plan.tr, sub=sub)

    monkeypatch.setattr(hk, "_level_call", parent_level_call)
    jax.clear_caches()  # the programs traced above hold the new kernels
    try:
        want = _job(classes)
    finally:
        jax.clear_caches()  # nor may a later test find the patched ones
    assert calls and set(calls) == {"hoisted"}
    assert got == want


def test_epsilon_tree_program_is_the_parents(mosaic_route, monkeypatch):
    """``epsilon_train``'s program (the scan chunk through
    ``Booster.update_many``) traced at its 2,000 columns, 128 bins and
    depth 6 on 4,096 rows: every level on the tiled kernel, and the jaxpr's
    text the parent's to the byte (ISSUE 37's tree: 1,428,803 bytes). The
    tiled kernel now runs the construct loop the untiled kernels share."""
    from xgboost_tpu.gbm import gbtree

    rng = np.random.RandomState(1)
    X = rng.randn(4096, 2000).astype(np.float32)
    y = rng.randint(0, 2, 4096).astype(np.float32)
    params = dict(objective="binary:logistic", tree_method="tpu_hist",
                  max_depth=6, max_bin=128, seed=1)
    orig = gbtree._scan_rounds_impl
    jitted = getattr(orig, "_guarded_jit", orig)
    traced = []

    class Traced(Exception):
        pass

    def tracing(*args, **kwargs):
        traced.append(jitted.trace(*args, **kwargs).jaxpr)
        raise Traced  # nothing of the program runs

    monkeypatch.setattr(gbtree, "_scan_rounds_impl", tracing)
    d = xgb.DMatrix(X, label=y)
    with pytest.raises(Traced):
        xgb.Booster(params, [d]).update_many(d, 0, 2, chunk=2)
    text = str(traced[0]).encode()
    assert text.count(b"name=_tiled_level_pallas") == 6
    assert b"_hoisted_level_pallas" not in text
    assert len(text) == 1_428_803
    assert hashlib.sha256(text).hexdigest() == (
        "ad36ba291fd37cf4ca8c6c8175cee2480eab3ee6b450b4c10dfbc7fd7fa09d7f")
