"""Hoisted-one-hot level kernel: layout + math equivalence on CPU.

The Mosaic kernel itself only compiles on TPU hardware; these tests pin
down everything around it — the [n, F*B] int8 layout contract of
``build_onehot``, the exact hi/lo-bf16 contraction the kernel performs
(emulated in XLA), and the [2K, F*B] -> [F, 2K, B] reshape the dispatcher
applies — against the segment-sum oracle ``fused_level_xla``. A TPU run
then only has to validate that Mosaic executes the same program
(docs/perf.md records that measurement).

Reference analog: gpu_hist's histogram kernel tests
(tests/cpp/tree/gpu_hist/test_histogram.cu) compare the device kernel to a
host-side oracle the same way.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xgboost_tpu.tree.hist_kernel import (
    build_onehot,
    fused_level_xla,
    hoist_budget_bytes,
)

_MASK_HI = np.int32(np.uint32(0xFFFF0000).view(np.int32))


def _split_hilo_xla(x):
    hi = jax.lax.bitcast_convert_type(
        jax.lax.bitcast_convert_type(x, jnp.int32) & _MASK_HI, jnp.float32)
    return hi, x - hi


def _hoisted_emulated(bins, pos, gh, onehot, *, K, B, d):
    """Pure-XLA twin of ``_hoisted_kernel``'s histogram half (post-
    partition): same grad-channel layout (rows on the lanes: ``pos``
    [1, n], ``gh`` [2, n], channels [4K, n]), same bf16 operands, same
    [2K, F*B] -> [F, 2K, B] reshape."""
    n, F = bins.shape
    offset = (1 << d) - 1
    local = pos[0] - offset
    ohseg = jax.nn.one_hot(jnp.where((local >= 0) & (local < K), local, K),
                           K + 1, dtype=jnp.float32, axis=0)[:K]  # [K, n]
    g, h = gh[0:1], gh[1:2]
    g_hi, g_lo = _split_hilo_xla(g)
    h_hi, h_lo = _split_hilo_xla(h)
    ghs4 = jnp.concatenate(
        [ohseg * g_hi, ohseg * h_hi, ohseg * g_lo, ohseg * h_lo], axis=0
    ).astype(jnp.bfloat16)  # [4K, n]
    out = jax.lax.dot_general(
        ghs4, onehot.astype(jnp.bfloat16), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [4K, F*B]
    hist2 = out[: 2 * K] + out[2 * K:]
    return jnp.transpose(hist2.reshape(2 * K, F, B), (1, 0, 2))


def _case(n=512, F=5, B=16, seed=0, missing_frac=0.1):
    rng = np.random.RandomState(seed)
    bins = rng.randint(0, B, size=(n, F)).astype(np.int32)
    miss = rng.rand(n, F) < missing_frac
    bins[miss] = B  # missing sentinel
    gh = rng.randn(2, n).astype(np.float32)  # row 0 g, row 1 h
    gh[1] = np.abs(gh[1])
    return jnp.asarray(bins), jnp.asarray(gh)


def test_build_onehot_layout():
    bins, _ = _case(n=64, F=3, B=8)
    oh = np.asarray(build_onehot(bins, B=8))
    assert oh.dtype == np.int8 and oh.shape == (64, 24)
    oh3 = oh.reshape(64, 3, 8)
    b = np.asarray(bins)
    for f in range(3):
        expect = (b[:, f, None] == np.arange(8)[None, :])
        np.testing.assert_array_equal(oh3[:, f, :], expect.astype(np.int8))
    # missing rows (bin == B) are all-zero -> drop out of histograms
    assert (oh3[b[:, 1] == 8, 1, :] == 0).all()


@pytest.mark.parametrize("d,K", [(0, 1), (2, 4)])
def test_hoisted_contraction_matches_segment_sum(d, K):
    bins, gh = _case(n=768, F=6, B=32, seed=3)
    n = bins.shape[0]
    rng = np.random.RandomState(7)
    offset = (1 << d) - 1
    pos = jnp.asarray(
        rng.randint(offset, offset + K, size=(1, n)).astype(np.int32))
    onehot = build_onehot(bins, B=32)
    got = _hoisted_emulated(bins, pos, gh, onehot, K=K, B=32, d=d)
    ptab = jnp.zeros((max(K >> 1, 1), 4), jnp.float32)  # Kp=0: no partition
    _, want = fused_level_xla(bins, pos, gh, ptab, K=K, Kp=0, B=32, d=d)
    # hi/lo bf16 two-term sums agree with exact f32 to ~2^-16 relative
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_hoisted_kernel_interpret_mode():
    """Run the real pallas_call body in interpret mode (CPU): this
    exercises ``_hoisted_kernel`` exactly as written (incl. the TPU bitcast
    hi/lo split, which interprets fine) against the segment-sum oracle.
    Hardware (Mosaic) validation happens in the bench session."""
    from xgboost_tpu.tree import hist_kernel as hk
    from jax.experimental import pallas as pl
    import functools

    bins, gh = _case(n=512, F=4, B=16, seed=5)
    pos = jnp.zeros((1, 512), jnp.int32)
    onehot = build_onehot(bins, B=16)
    ptab = jnp.zeros((1, 4), jnp.float32)
    kern = functools.partial(hk._hoisted_kernel, K=1, Kp=0, F=4, Fh=4, B=16,
                             prev_offset=0, offset=0)
    binsT = hk._feature_major(bins, hk._SUBLANES, 16)  # the [8, n] tiles
    pos_new, hist2 = pl.pallas_call(
        kern,
        grid=(2,),
        in_specs=[
            pl.BlockSpec((8, 256), lambda c: (0, c)),
            pl.BlockSpec((256, 64), lambda c: (c, 0)),
            pl.BlockSpec((1, 256), lambda c: (0, c)),
            pl.BlockSpec((2, 256), lambda c: (0, c)),
            pl.BlockSpec((1, 4), lambda c: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 256), lambda c: (0, c)),
            pl.BlockSpec((2, 64), lambda c: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, 512), jnp.int32),
            jax.ShapeDtypeStruct((2, 64), jnp.float32),
        ],
        interpret=True,
    )(binsT, onehot, pos, gh, ptab)
    hist = jnp.transpose(hist2.reshape(2, 4, 16), (1, 0, 2))
    _, want = fused_level_xla(bins, pos, gh, ptab, K=1, Kp=0, B=16, d=0)
    np.testing.assert_allclose(np.asarray(hist), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_hoist_budget_env(monkeypatch):
    from xgboost_tpu.tree.hist_kernel import can_hoist

    monkeypatch.setenv("XGBTPU_HOIST_BUDGET_MB", "1")
    assert hoist_budget_bytes() == 1024 * 1024
    # on CPU use_pallas() is False -> never hoist regardless of budget
    assert not can_hoist(1024, 4, 16)


def test_hoist_plan_partial(monkeypatch):
    """hoist_plan degrades to a feature PREFIX when the full expansion
    outgrows the HBM budget (the 256-bin / small-free-HBM cases), and to 0
    below the worthwhile minimum — never an OOM-destined full build."""
    from xgboost_tpu.tree import hist_kernel as hk

    monkeypatch.setattr(hk, "use_pallas", lambda: True)
    n, F, B = 1 << 20, 50, 64
    # generous budget: full hoist
    monkeypatch.setenv("XGBTPU_HOIST_BUDGET_MB", str(8 * 1024))
    assert hk.hoist_plan(n, F, B) == F
    # 1 GiB: 16 features fit (2^20 * 64 B/feature = 64 MiB each)
    monkeypatch.setenv("XGBTPU_HOIST_BUDGET_MB", "1024")
    assert hk.hoist_plan(n, F, B) == 16
    # below the minimum worthwhile prefix: no hoist
    monkeypatch.setenv("XGBTPU_HOIST_BUDGET_MB", "128")
    assert hk.hoist_plan(n, F, B) == 0
    # bin256 with a full budget: HBM would allow 32 features but VMEM
    # caps the streamed prefix — plan lands strictly between 0 and F
    monkeypatch.setenv("XGBTPU_HOIST_BUDGET_MB", str(8 * 1024))
    fh256 = hk.hoist_plan(n, F, 256)
    assert 0 < fh256 < F
    tr = hk._hoist_tr(fh256 * 256, 32, F, 256)
    assert tr > 0, "plan must be streamable at the deepest level"


def test_partial_hoist_kernel_interpret_mode():
    """REAL kernel body with Fh < F (stream 2 features, construct 2) in
    interpret mode against the segment-sum oracle — the partial-hoist
    compute path end to end."""
    import functools

    from jax.experimental import pallas as pl

    from xgboost_tpu.tree import hist_kernel as hk

    bins, gh = _case(n=512, F=4, B=16, seed=11)
    pos = jnp.zeros((1, 512), jnp.int32)
    Fh = 2
    onehot = build_onehot(bins[:, :Fh], B=16)  # [n, 32]
    ptab = jnp.zeros((1, 4), jnp.float32)
    kern = functools.partial(hk._hoisted_kernel, K=1, Kp=0, F=4, Fh=Fh,
                             B=16, prev_offset=0, offset=0)
    binsT = hk._feature_major(bins, hk._SUBLANES, 16)  # the [8, n] tiles
    pos_new, hist2 = pl.pallas_call(
        kern,
        grid=(2,),
        in_specs=[
            pl.BlockSpec((8, 256), lambda c: (0, c)),
            pl.BlockSpec((256, 32), lambda c: (c, 0)),
            pl.BlockSpec((1, 256), lambda c: (0, c)),
            pl.BlockSpec((2, 256), lambda c: (0, c)),
            pl.BlockSpec((1, 4), lambda c: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 256), lambda c: (0, c)),
            pl.BlockSpec((2, 64), lambda c: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, 512), jnp.int32),
            jax.ShapeDtypeStruct((2, 64), jnp.float32),
        ],
        interpret=True,
    )(binsT, onehot, pos, gh, ptab)
    hist = jnp.transpose(hist2.reshape(2, 4, 16), (1, 0, 2))
    _, want = fused_level_xla(bins, pos, gh, ptab, K=1, Kp=0, B=16, d=0)
    np.testing.assert_allclose(np.asarray(hist), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_partial_hoist_end_to_end_interpret(monkeypatch):
    """Full training through the public API with a forced PARTIAL hoist
    (interpret-mode kernels) must produce the same model as the XLA path."""
    import xgboost_tpu as xgb
    from xgboost_tpu.tree import hist_kernel as hk

    rng = np.random.RandomState(4)
    X = rng.randn(600, 6).astype(np.float32)
    y = (X @ rng.randn(6) > 0).astype(np.float32)
    params = {"objective": "binary:logistic", "tree_method": "tpu_hist",
              "max_depth": 3, "max_bin": 16, "eta": 0.3, "seed": 0}

    dtrain = xgb.DMatrix(X, label=y)
    bst_xla = xgb.train(params, dtrain, num_boost_round=3)
    want = bst_xla.predict(xgb.DMatrix(X))

    # force the pallas dispatch in interpret mode with a partial plan
    monkeypatch.setattr(hk, "use_pallas", lambda: True)
    monkeypatch.setattr(hk, "_INTERPRET", True)
    monkeypatch.setattr(hk, "hoist_plan",
                        lambda n_pad, F, B, max_depth=6: 4)  # 4 of 6
    d2 = xgb.DMatrix(X, label=y)
    binned = d2.get_binned(16, None)
    oh = binned.fused_onehot(3)
    assert oh is not None and oh.shape[1] == 4 * 16
    bst_p = xgb.train(params, d2, num_boost_round=3)
    got = bst_p.predict(xgb.DMatrix(X))
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_hoist_gates_agree():
    """The build gate must never accept a configuration the dispatch gate
    would then reject at some level (that would pin GiBs of HBM for zero
    streaming). Sweep the realistic grid and assert implication."""
    from xgboost_tpu.tree.hist_kernel import _hoist_tr

    for F in (10, 50, 100, 200):
        for B in (16, 64, 128, 256):
            for max_depth in (1, 4, 6, 8):
                deepest = _hoist_tr(F * B, 1 << (max_depth - 1), F)
                if deepest:
                    # monotone: every shallower level must also fit
                    for d in range(max_depth):
                        assert _hoist_tr(F * B, 1 << d, F) > 0, (F, B, d)
    # the headline configs stream at full depth; bin256 at F=50 does not
    assert _hoist_tr(50 * 64, 32, 50) > 0
    assert _hoist_tr(50 * 128, 32, 50) > 0
    assert _hoist_tr(50 * 256, 32, 50) == 0


def test_kernel_categorical_partition_interpret_mode():
    """The wide [Kp, 5+B] decision table (is_cat + right-going set) routes
    rows identically in the REAL kernel body (interpret mode) and the XLA
    twin partition_apply_xla — pinning the categorical branch of
    _partition_tile before hardware."""
    import functools

    from jax.experimental import pallas as pl

    from xgboost_tpu.tree import hist_kernel as hk

    rng = np.random.RandomState(2)
    n, F, B = 512, 4, 16
    Kp, K, d = 2, 4, 2
    bins = jnp.asarray(rng.randint(0, B + 1, size=(n, F)).astype(np.int32))
    gh = jnp.asarray(rng.randn(2, n).astype(np.float32))
    prev_off = (1 << (d - 1)) - 1
    pos = jnp.asarray(rng.randint(prev_off, prev_off + Kp,
                                  size=(1, n)).astype(np.int32))
    # two split nodes: one numerical, one categorical with a random set
    sets = rng.rand(Kp, B) < 0.4
    ptab = np.zeros((Kp, 5 + B), np.float32)
    ptab[:, 0] = 1.0  # is_split
    ptab[:, 1] = rng.randint(0, F, Kp)
    ptab[:, 2] = rng.randint(0, B, Kp)
    ptab[:, 3] = rng.randint(0, 2, Kp)
    ptab[:, 4] = [0.0, 1.0]  # node 1 categorical
    ptab[1, 5:] = sets[1]
    ptab_j = jnp.asarray(ptab)

    want = hk.partition_apply_xla(bins, pos, ptab_j, Kp=Kp, B=B, d=d)

    kern = functools.partial(hk._level_kernel, K=K, Kp=Kp, F=F, B=B,
                             prev_offset=prev_off, offset=(1 << d) - 1)
    binsT = hk._feature_major(bins, hk._SUBLANES, B)  # the [8, n] tiles
    pos_new, _ = pl.pallas_call(
        kern,
        grid=(2,),
        in_specs=[
            pl.BlockSpec((binsT.shape[0], 256), lambda c: (0, c)),
            pl.BlockSpec((1, 256), lambda c: (0, c)),
            pl.BlockSpec((2, 256), lambda c: (0, c)),
            pl.BlockSpec((Kp, 5 + B), lambda c: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 256), lambda c: (0, c)),
            pl.BlockSpec((F, 2 * K, B), lambda c: (0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, n), jnp.int32),
            jax.ShapeDtypeStruct((F, 2 * K, B), jnp.float32),
        ],
        interpret=True,
    )(binsT, pos, gh, ptab_j)
    np.testing.assert_array_equal(np.asarray(pos_new), np.asarray(want))


def test_build_onehot_pallas_matches_xla(monkeypatch):
    """The Pallas tile build (the only memory-safe path at headline scale:
    the XLA broadcast build materializes an s32 [n, F, B] intermediate, 4x
    the int8 output — 26 GB at 1M x 34 x 256) produces bit-identical
    output to the XLA build, across tile sizes and with missing bins."""
    from xgboost_tpu.tree import hist_kernel as hk

    monkeypatch.setattr(hk, "_INTERPRET", True)
    rng = np.random.RandomState(11)
    for n, F, B in [(1024, 5, 16), (512, 3, 256), (2048, 7, 64)]:
        # library narrow dtype: uint16 once bins (incl. the missing
        # sentinel B) outgrow int8 — an int8 cast would wrap bins >= 128
        # negative and the B=256 sentinel to 0, silently untesting the
        # upper half of the bin256 range
        dt = np.int8 if B + 1 <= 127 else np.uint16
        bins = rng.randint(0, B + 1, size=(n, F)).astype(dt)
        tr = hk._build_tr(n, F, B)
        assert tr and n % tr == 0
        got = np.asarray(hk._build_onehot_pallas(
            jnp.asarray(bins), B=B, tr=tr))
        want = np.asarray(hk._build_onehot_xla(jnp.asarray(bins), B=B))
        np.testing.assert_array_equal(got, want)


def test_build_tr_vmem_model():
    """Tile chooser: fits the double-buffered out tile in budget, honors
    divisibility, degrades to 0 for impossible widths."""
    from xgboost_tpu.tree import hist_kernel as hk

    assert hk._build_tr(750592, 50, 64) == 1024  # bin64 full hoist
    tr256 = hk._build_tr(750592, 34, 256)  # bin256 partial hoist
    assert tr256 in (256, 512) and 750592 % tr256 == 0
    assert hk._build_tr(1000, 5, 16) == 0  # not a multiple of 256
    assert hk._build_tr(1024, 4096, 256) == 0  # tile can never fit


def test_hoist_build_failure_degrades(monkeypatch):
    """A failing on-device one-hot build (e.g. a Mosaic reject of the int8
    tile store) must degrade to
    the construct path (fused_onehot -> None), latched so the build is not
    retried every call, instead of failing the fit."""
    import xgboost_tpu as xgb
    from xgboost_tpu.tree import hist_kernel as hk

    rng = np.random.RandomState(3)
    X = rng.randn(1024, 4).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    binned = xgb.DMatrix(X, label=y).get_binned(16, None)

    calls = {"n": 0}

    def boom(*a, **k):
        calls["n"] += 1
        raise RuntimeError("synthetic mosaic reject")

    monkeypatch.setattr(hk, "use_pallas", lambda: True)  # plan != 0 on CPU
    monkeypatch.setattr(hk, "build_onehot", boom)
    assert binned.fused_onehot(3) is None
    from xgboost_tpu.data.quantile import _onehot_health
    from xgboost_tpu.resilience import DISABLED

    assert _onehot_health.state() == DISABLED
    assert binned.fused_onehot(3) is None  # disabled: no per-call retry
    assert calls["n"] == 1


def test_hoist_budget_reads_memory_stats_and_never_guesses_on_tpu(
        monkeypatch):
    """The budget is 60% of the free HBM ``memory_stats`` reports, capped
    at 8 GiB; a TPU runtime that reports none raises instead of guessing
    (the allocation probe that used to stand in is gone)."""
    from xgboost_tpu.tree import hist_kernel as hk

    monkeypatch.delenv("XGBTPU_HOIST_BUDGET_MB", raising=False)
    free = 4 * 1024 ** 3
    monkeypatch.setattr(hk, "device_free_bytes", lambda: free)
    assert hk.hoist_budget_bytes() == int(free * 0.6)
    monkeypatch.setattr(hk, "device_free_bytes", lambda: 15 * 1024 ** 3)
    assert hk.hoist_budget_bytes() == 8 * 1024 ** 3
    # the CPU backend keeps no stats: the plan is 0 there before any
    # budget is asked for, and asking anyway gives the cap
    monkeypatch.setattr(hk, "device_free_bytes", lambda: None)
    assert hk.hoist_budget_bytes() == 8 * 1024 ** 3
    monkeypatch.setattr(hk.jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="memory_stats"):
        hk.hoist_budget_bytes()
    monkeypatch.setenv("XGBTPU_HOIST_BUDGET_MB", "1024")
    assert hk.hoist_budget_bytes() == 1024 ** 3
