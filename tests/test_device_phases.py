"""The round's device phases and the chunk's host steps carry the package's
own names in a profile (ISSUE 24).

Device side: every part of a boosting round sits under a
``jax.named_scope("xgb.<phase>")``, which becomes a component of each op's
``op_name`` metadata, in the lowered module and in the compiled one. Host
side: ``observability.trace.span(name)`` opens a
``jax.profiler.TraceAnnotation("xgb." + name)`` while a profiler session is
live, so the chunk's steps land on the profiler's clock. Neither changes
what a program computes."""

import glob
import os
import re
import sys
import time

import jax
import numpy as np
import pytest

import xgboost_tpu as xgb
from xgboost_tpu.gbm import gbtree
from xgboost_tpu.observability import trace
from xgboost_tpu.parallel import grow as pgrow
from xgboost_tpu.parallel import make_mesh, mesh_context
from xgboost_tpu.tree import hist_kernel as hk

PARAMS = {"objective": "binary:logistic", "max_depth": 3, "max_bin": 16,
          "eta": 0.5}
ROUND_SCOPES = ["xgb.gradient", "xgb.root", "xgb.level_hist",
                "xgb.split_eval", "xgb.partition", "xgb.finalize",
                "xgb.leaf_delta"]


def _data(n=512, F=6, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, F).astype(np.float32)
    y = ((X[:, 0] > .5) ^ (X[:, 3] > .5)).astype(np.float32)
    return X, y


def _op_names(lowered):
    """The scope paths of a program, before and after compilation."""
    mlir = lowered.as_text(debug_info=True)
    hlo = lowered.compile().as_text()
    return mlir, set(re.findall(r'op_name="([^"]+)"', hlo))


def _capture(module, attr, run):
    """Lower the program ``module.attr`` with the arguments ``run()`` calls
    it with (before the call: the scan donates its margin)."""
    orig = getattr(module, attr)
    jitted = getattr(orig, "_guarded_jit", orig)
    lowered = []

    def capturing(*args, **kwargs):
        if not lowered:
            lowered.append(jitted.lower(*args, **kwargs))
        return orig(*args, **kwargs)

    setattr(module, attr, capturing)
    try:
        run()
    finally:
        setattr(module, attr, orig)
    assert lowered, f"{attr} was not called"
    return _op_names(lowered[0])


@pytest.fixture(scope="module")
def one_chip_scan():
    """``_scan_rounds_impl`` at a tiny shape on the per-level route the
    chip takes (the Pallas kernels, their bodies interpreted)."""
    X, y = _data()
    d = xgb.DMatrix(X, label=y)
    bst = xgb.Booster(PARAMS, [d])
    saved = hk.use_pallas, hk._INTERPRET
    hk.use_pallas, hk._INTERPRET = (lambda: True), True
    try:
        return _capture(gbtree, "_scan_rounds_impl",
                        lambda: bst.update_many(d, 0, 2, chunk=2))
    finally:
        hk.use_pallas, hk._INTERPRET = saved


@pytest.fixture(scope="module")
def mesh_scan():
    """``_dist_scan_impl`` over four virtual devices."""
    X, y = _data()

    def run():
        with mesh_context(make_mesh(4)):
            d = xgb.DMatrix(X, label=y)
            xgb.Booster(PARAMS, [d]).update_many(d, 0, 2, chunk=2)

    return _capture(pgrow, "_dist_scan_impl", run)


def _assert_scope(texts, scope):
    mlir, op_names = texts
    assert scope + "/" in mlir, f"{scope} not in the lowered module"
    under = [n for n in op_names if f"/{scope}/" in n + "/"]
    assert under, f"{scope} in no op_name of the compiled program"


@pytest.mark.parametrize("scope", ROUND_SCOPES)
def test_one_chip_scan_carries_scope(one_chip_scan, scope):
    _assert_scope(one_chip_scan, scope)


@pytest.mark.parametrize("scope", ROUND_SCOPES + ["xgb.hist_psum"])
def test_mesh_scan_carries_scope(mesh_scan, scope):
    _assert_scope(mesh_scan, scope)


def test_one_chip_scan_has_no_psum_scope(one_chip_scan):
    assert "xgb.hist_psum" not in one_chip_scan[0]


def test_level_kernel_keeps_its_name_under_the_scope(one_chip_scan):
    """The benchmark finds the level kernels by the jitted function's name
    in the path; the scope goes in front of it and renames nothing."""
    _, op_names = one_chip_scan
    assert any(re.search(r"/xgb\.level_hist/jit\(_(hoisted|fused)_level_"
                         r"pallas\)", n) for n in op_names)


@pytest.mark.parametrize("build", ["_build_onehot_xla",
                                   "_build_onehot_pallas"])
def test_onehot_build_carries_scope(monkeypatch, build):
    """Inside the jitted builders, so that a caller outside any program
    (``BinnedMatrix.fused_onehot``) gets it too."""
    monkeypatch.setattr(hk, "_INTERPRET", True)
    bins = jax.ShapeDtypeStruct((256, 4), np.uint8)
    kwargs = {"B": 16} if build.endswith("xla") else {"B": 16, "tr": 256}
    lowered = getattr(hk, build)._guarded_jit.lower(bins, **kwargs)
    _assert_scope(_op_names(lowered), "xgb.onehot_build")


def test_predict_walk_carries_scope():
    X, y = _data()
    d = xgb.DMatrix(X, label=y)
    bst = xgb.train(PARAMS, d, 2)
    from xgboost_tpu import predictor

    f = bst._gbm.model.stacked()
    lowered = predictor._predict_margin_kernel.lower(
        jax.numpy.asarray(X), f.left, f.right, f.feature, f.cond,
        f.default_left, f.split_type, f.cat_bits, f.tree_group,
        jax.numpy.ones((f.left.shape[0],), np.float32),
        jax.numpy.zeros((len(X), 1), np.float32),
        n_groups=1, max_depth=int(f.max_depth), has_cats=False)
    _assert_scope(_op_names(lowered), "xgb.predict_walk")


# ---------------------------------------------------------------------------
# the kernels' instruction names, as the chip's compiler gives them
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def one_v5e_chip():
    """A described (not attached) v5e chip: the TPU compiler is installed
    here and compiles for it. Only in this fixture, in this file (one
    process holds the TPU library at a time)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler on this box
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _benchmark_summary():
    """``benchmark/reduce/summary.py``, loaded as the benchmark loads it."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "benchmark"))
    try:
        from bench_paths import load
    finally:
        sys.path.pop(0)
    return load("reduce/summary.py")


def _compiled_text(fn, *shapes, **static):
    return fn.lower(*shapes, **static).compile().as_text()


def _mosaic_lines(fn, *shapes, **static):
    """The Mosaic calls ``fn`` compiles to, as the compiled module's text
    has them (a profile's op line names an op by this text)."""
    return _mosaic_lines_of(_compiled_text(fn, *shapes, **static))


def _mosaic_lines_of(hlo):
    return [line.strip() for line in hlo.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


def _calls_of(lines):
    """{instruction name: op_name} of Mosaic calls' lines."""
    calls = {}
    for line in lines:
        name = re.match(r"(?:ROOT )?%([\w\-]+?)(?:\.\d+)? = ", line)
        calls[name.group(1)] = re.search(r'op_name="([^"]+)"',
                                         line).group(1)
    return calls


def _mosaic_calls(fn, *shapes, **static):
    return _calls_of(_mosaic_lines(fn, *shapes, **static))


def _feature_major(bins, B):
    """The tree program's widened bins as every Mosaic call of an untiled
    tree reads them (ISSUE 38): ``[Fp, n]`` i32, whole sublanes."""
    import jax.numpy as jnp

    return hk._feature_major(bins.astype(jnp.int32), hk._SUBLANES, B)


@pytest.mark.parametrize("kernel", ["_hoisted_level_pallas",
                                    "_build_onehot_pallas",
                                    "_predict_margin_pallas",
                                    "_route_rows_pallas"])
def test_scope_leaves_the_kernels_instruction_name(one_v5e_chip, kernel):
    """The TPU compiler names a Mosaic call after the last component of its
    path before ``pallas_call``. The benchmark finds the level kernels by
    that name, and the ledger's breakdowns carry the others: a scope must
    sit in front of the component that names the kernel. The routing
    kernel's name must hold no ``level``: it builds no histogram, and the
    benchmark's reduction would book it to the level histogram's time."""
    import jax.numpy as jnp

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_v5e_chip)

    n, F, B, K = 8192, 12, 256, 4
    if kernel == "_hoisted_level_pallas":
        def level(bins, onehot, pos, gh, ptab):
            with jax.named_scope("xgb.level_hist"):  # as grow_fused has it
                return hk._hoisted_level_pallas(
                    _feature_major(bins, B), onehot, pos, gh, ptab, F=F,
                    K=K, Kp=K >> 1, B=B, d=2, tr=hk._hoist_tr(F * B, K, F, B))

        calls = _mosaic_calls(
            jax.jit(level), S((n, F), jnp.uint8), S((n, F * B), jnp.int8),
            S((1, n), jnp.int32), S((2, n), jnp.float32),
            S((K >> 1, 4), jnp.float32))
        scope = "xgb.level_hist"
    elif kernel == "_build_onehot_pallas":
        calls = _mosaic_calls(hk._build_onehot_pallas._guarded_jit,
                              S((n, F), jnp.uint8), B=B,
                              tr=hk._build_tr(n, F, B))
        scope = "xgb.onehot_build"
    elif kernel == "_route_rows_pallas":
        Kp = 32  # the anchor's last level

        def route(bins, pos, ptab):
            with jax.named_scope("xgb.partition"):  # as grow_fused has it
                return hk._route_rows_pallas(_feature_major(bins, B), pos,
                                             ptab, Kp=Kp, B=B, d=6)

        lines = _mosaic_lines(
            jax.jit(route), S((n, 50), jnp.int32), S((1, n), jnp.int32),
            S((Kp, 4), jnp.float32))
        calls = _calls_of(lines)
        scope = "xgb.partition"
        # what the benchmark's reduction makes of the instruction
        summary = _benchmark_summary()
        text = lines[0].removeprefix("ROOT ")
        assert "level" not in kernel
        assert summary.kind_of(text) == "mosaic"
        assert not summary.is_level_kernel(text)
        assert summary.is_level_kernel(
            text.replace(kernel, "_hoisted_level_pallas"))
    else:
        from xgboost_tpu import predictor

        calls = _mosaic_calls(predictor._predict_margin_pallas,
                              S((1024, F), jnp.float32),
                              S((8, 8, 128), jnp.bfloat16),
                              S((8, 1), jnp.float32), steps=3)
        scope = "xgb.predict_walk"
    assert list(calls) == [kernel]
    assert f"/{scope}/" in calls[kernel]


# MSLR-WEB30K's shape (ISSUE 26): 2,270,296 rows padded to the row tile
_MSLR_ROWS, _MSLR_F, _MSLR_B, _MSLR_DEPTH = 2_271_232, 136, 256, 6


def test_hoist_plan_at_136_features_is_vmem_gated_to_12(monkeypatch):
    """The ``[2K, F*B]`` accumulator of the deepest level takes 8.9 of the
    hoisted step's 12 MiB: 12 features stream, at the 128-row tile, and
    the in-kernel construction alone does not fit."""
    monkeypatch.setattr(hk, "use_pallas", lambda: True)
    monkeypatch.setenv("XGBTPU_HOIST_BUDGET_MB", "8192")
    n, F, B = _MSLR_ROWS, _MSLR_F, _MSLR_B
    fh = hk.hoist_plan(n, F, B, _MSLR_DEPTH)
    assert fh == 12
    tiles = [hk._hoist_tr(fh * B, 1 << d, F, B) for d in range(_MSLR_DEPTH)]
    assert tiles == [512, 512, 512, 512, 512, 128]
    assert all(n % tr == 0 for tr in tiles)
    assert hk._hoist_tr((fh + 1) * B, 32, F, B) == 0
    assert not hk.pallas_level_fits(n, F, 32, B)
    assert hk.pallas_level_fits(n, F, 32, B, onehot_width=fh * B)
    assert hk.pallas_route_fits(n, F, 32, 4)


@pytest.mark.parametrize("kernel,d", [("_hoisted_level_pallas", 0),
                                      ("_hoisted_level_pallas", 5),
                                      ("_route_rows_pallas", 6)])
def test_kernels_compile_at_136_features_under_their_names(one_v5e_chip,
                                                           kernel, d):
    """The first and the deepest level (512- and 128-row tiles, 12 of 136
    features hoisted) and the tree's last routing at F 136, compiled for a
    described v5e: the chip's compiler takes them (VMEM, tiling) and names
    them as the benchmark's reduction expects."""
    import jax.numpy as jnp

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_v5e_chip)

    n, F, B, Fh = 8192, _MSLR_F, _MSLR_B, 12
    K, Kp = 1 << min(d, 5), (1 << d) >> 1
    if kernel == "_hoisted_level_pallas":
        tr = hk._hoist_tr(Fh * B, K, F, B)
        assert tr == (512 if d == 0 else 128)

        def level(bins, onehot, pos, gh, ptab):
            with jax.named_scope("xgb.level_hist"):
                return hk._hoisted_level_pallas(
                    _feature_major(bins, B), onehot, pos, gh, ptab, F=F,
                    K=K, Kp=Kp, B=B, d=d, tr=tr)

        calls = _mosaic_calls(
            jax.jit(level), S((n, F), jnp.uint8), S((n, Fh * B), jnp.int8),
            S((1, n), jnp.int32), S((2, n), jnp.float32),
            S((max(Kp, 1), 4), jnp.float32))
        scope = "xgb.level_hist"
    else:
        def route(bins, pos, ptab):
            with jax.named_scope("xgb.partition"):
                return hk._route_rows_pallas(_feature_major(bins, B), pos,
                                             ptab, Kp=Kp, B=B, d=d)

        calls = _mosaic_calls(jax.jit(route), S((n, F), jnp.int32),
                              S((1, n), jnp.int32), S((Kp, 4), jnp.float32))
        scope = "xgb.partition"
    assert list(calls) == [kernel]
    assert f"/{scope}/" in calls[kernel]


@pytest.mark.parametrize("kernel,F,Fh,d", [
    ("_hoisted_level_pallas", _MSLR_F, 12, 5),  # MSLR's deepest level
    ("_hoisted_level_pallas", 28, 7, 7),  # HIGGS's
    ("_fused_level_pallas", 12, 0, 3)])
def test_sibling_sub_kernels_keep_their_names_and_halve_the_output_rows(
        one_v5e_chip, kernel, F, Fh, d):
    """Sibling subtraction (ISSUE 27): a level below the root builds
    2^(d-1) nodes, so its f32 output has 2^d rows where the direct build
    has 2^(d+1), at the row tile the level above has; the Mosaic call
    keeps the name the benchmark's reduction books to the level histogram."""
    import jax.numpy as jnp

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_v5e_chip)

    n, B = 8192, _MSLR_B
    K, Kp = 1 << d, 1 << (d - 1)
    shapes = [S((n, F), jnp.uint8), S((1, n), jnp.int32),
              S((2, n), jnp.float32), S((Kp, 4), jnp.float32)]
    if kernel == "_hoisted_level_pallas":
        tr = hk._hoist_tr(Fh * B, Kp, F, B)
        assert tr == 512 and hk._hoist_tr(Fh * B, K, F, B) == 128

        def level(bins, pos, gh, ptab, onehot):
            with jax.named_scope("xgb.level_hist"):
                return hk._hoisted_level_pallas(
                    _feature_major(bins, B), onehot, pos, gh, ptab, F=F,
                    K=K, Kp=Kp, B=B, d=d, tr=tr, sub=True)

        shapes.append(S((n, Fh * B), jnp.int8))
        out = f"f32[{2 * Kp},{F * B}]"
    else:
        def level(bins, pos, gh, ptab):
            with jax.named_scope("xgb.level_hist"):
                return hk._fused_level_pallas(
                    _feature_major(bins, B), pos, gh, ptab, F=F, K=K, Kp=Kp,
                    B=B, d=d, sub=True)

        out = f"f32[{F},{2 * Kp},{B}]"
    lines = _mosaic_lines(jax.jit(level), *shapes)
    calls = _calls_of(lines)
    assert list(calls) == [kernel]
    assert "/xgb.level_hist/" in calls[kernel]
    assert out in lines[0].split(" custom-call(")[0], lines[0][:300]
    summary = _benchmark_summary()
    assert summary.is_level_kernel(lines[0].removeprefix("ROOT "))


# a round's trees in one pass (ISSUE 34): Cover Type's six levels
@pytest.mark.parametrize("d,T,tr,rows", [
    (0, 8, 256, 16), (1, 8, 256, 16), (2, 8, 128, 32), (3, 8, 128, 64),
    (4, 4, 128, 64), (5, 2, 128, 64), (3, 8, 0, 64)])
def test_level_calls_that_carry_trees_compile_under_their_names(
        one_v5e_chip, d, T, tr, rows):
    """The level call of T class trees at Cover Type's width (54 columns x
    256 bins, 33 resident; ``tr`` 0: no resident one-hot, a narrow matrix's
    construct-only kernel), compiled for a described v5e: positions
    ``s32[T, n]``, the accumulator ``2 T Kc`` rows, and the name the
    benchmark's reduction books to the level histogram."""
    import jax.numpy as jnp

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_v5e_chip)

    n, F, B, Fh = 8192, (54 if tr else 12), 256, (33 if tr else 0)
    K, Kp = 1 << d, (1 << d) >> 1
    Kc = Kp if d else K
    assert hk.level_trees(n, F, Kc, B, 8, Fh * B) == T
    shapes = [S((n, F), jnp.int32), S((T, n), jnp.int32),
              S((2 * T, n), jnp.float32), S((T, max(Kp, 1), 4), jnp.float32)]
    if tr:
        assert hk._hoist_tr(Fh * B, T * Kc, F, B) == tr
        shapes.append(S((n, Fh * B), jnp.int8))
        kernel, out = "_hoisted_level_pallas", f"f32[{rows},{F * B}]"
    else:
        kernel, out = "_fused_level_pallas", f"f32[{F},{rows},{B}]"

    def level(bins, pos, gh, ptab, onehot=None):
        with jax.named_scope("xgb.level_hist"):  # as grow_fused has it
            return hk.fused_level_trees(bins, pos, gh, ptab, K=K, Kp=Kp, B=B,
                                        d=d, onehot=onehot,
                                        sibling_sub=d > 0)

    lines = _mosaic_lines(jax.jit(level), *shapes)
    calls = _calls_of(lines)
    assert list(calls) == [kernel]
    assert "/xgb.level_hist/" in calls[kernel]
    head = lines[0].split(" custom-call(")[0]
    assert out in head and f"s32[{T},{n}]" in head, lines[0][:300]
    assert _benchmark_summary().is_level_kernel(
        lines[0].removeprefix("ROOT "))


# rows on the lanes (ISSUE 31): what the chip's compiler is handed
@pytest.mark.parametrize("kernel,F,Kp,W,B", [
    ("_hoisted_level_pallas", 50, 16, 4, 256),  # the anchor's level 5
    ("_fused_level_pallas", 12, 4, 5 + 64, 64),  # a categorical table
    ("_route_rows_pallas", 50, 32, 4, 256),  # the anchor's last routing
    ("_route_rows_pallas", 28, 128, 4, 256),  # HIGGS's
    ("_route_rows_pallas", hk._MAX_KERNEL_FEATURES, 128, 4, 256),
    ("_route_rows_pallas", 50, 128, 5 + 256, 256),  # categorical, bin256
    ("_route_rows_pallas", 136, 32, 4, 512)])  # bins past bf16's integers
def test_row_arrays_reach_the_kernels_lane_dense(one_v5e_chip, kernel, F, Kp,
                                                 W, B):
    """Positions go in and come out as ``s32[1, n]`` and gradients go in
    as ``f32[2, n]``: the compiler lays them out one and two sublanes deep
    (``T(1,128)``, ``T(2,128)``: 4 and 8 bytes a row in HBM) where
    ``[n, 1]`` and ``[n, 2]`` were padded to 128 lanes (512 bytes a row).
    The widest routing step ``pallas_route_fits`` admits, a categorical
    table and the f32 feature pick (``B > 256``) compile too."""
    import jax.numpy as jnp

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_v5e_chip)

    n, d = 8192, Kp.bit_length()
    shapes = [S((n, F), jnp.int32), S((1, n), jnp.int32),
              S((2, n), jnp.float32), S((Kp, W), jnp.float32)]
    if kernel == "_route_rows_pallas":
        assert hk.pallas_route_fits(n, F, Kp, W)
        del shapes[2]

        def fn(bins, pos, ptab):
            return hk._route_rows_pallas(_feature_major(bins, B), pos, ptab,
                                         Kp=Kp, B=B, d=d)
    elif kernel == "_fused_level_pallas":
        def fn(bins, pos, gh, ptab):
            return hk._fused_level_pallas(_feature_major(bins, B), pos, gh,
                                          ptab, F=F, K=2 * Kp,
                                          Kp=Kp, B=B, d=d, sub=True)
    else:
        Fh = 34
        shapes.append(S((n, Fh * B), jnp.int8))

        def fn(bins, pos, gh, ptab, onehot):
            return hk._hoisted_level_pallas(
                _feature_major(bins, B), onehot, pos, gh, ptab, F=F,
                K=2 * Kp, Kp=Kp, B=B, d=d,
                tr=hk._hoist_tr(Fh * B, Kp, F, B), sub=True)

    line, = _mosaic_lines(jax.jit(fn), *shapes)
    assert list(_calls_of([line])) == [kernel]
    result = line.split(" custom-call(")[0]
    assert f"s32[1,{n}]{{1,0:T(1,128)}}" in result, result
    operands = re.search(r"operand_layout_constraints=\{(.*?)\}, \w+=",
                         line).group(1)
    assert f"s32[1,{n}]{{1,0}}" in operands, operands
    if kernel != "_route_rows_pallas":
        assert f"f32[2,{n}]{{1,0}}" in operands, operands
    assert f"[{n},1]" not in line and f"[{n},2]" not in line


# ---------------------------------------------------------------------------
# host steps on the profiler's clock
# ---------------------------------------------------------------------------


@pytest.fixture()
def _no_chrome_trace(monkeypatch):
    monkeypatch.delenv("XGBTPU_TRACE", raising=False)
    trace.reset()
    yield
    trace.reset()


def _profile(tmp_path, fn):
    """Run ``fn`` under a profiler session set up as the benchmark's
    (python tracer off) and return {thread: [(name, start, end)]} of the
    ``xgb.`` events in ``/host:CPU``."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    threads = {}
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in line.events if e.name.startswith("xgb.")]
            if evs:
                threads[line.name] = sorted(evs, key=lambda t: t[1])
    return threads


def test_chunk_steps_nest_inside_scan_chunk_on_the_profilers_clock(
        tmp_path, _no_chrome_trace):
    X, y = _data()
    d = xgb.DMatrix(X, label=y)
    bst = xgb.Booster(PARAMS, [d])
    bst.update_many(d, 0, 2, chunk=2)  # trace and compile outside
    threads = _profile(tmp_path, lambda: bst.update_many(d, 2, 2, chunk=2))
    assert len(threads) == 1, threads  # the caller's thread, no other
    evs, = threads.values()
    by_name = {n: (s, e) for n, s, e in evs}
    assert [n for n, _, _ in evs if n != "xgb.local_rows"] == [
        "xgb.scan_chunk", "xgb.chunk.prepare", "xgb.chunk.dispatch",
        "xgb.chunk.commit", "xgb.chunk.admit"]
    lo, hi = by_name["xgb.scan_chunk"]
    steps = [by_name["xgb.chunk." + s]
             for s in ("prepare", "dispatch", "commit")]
    assert lo <= steps[0][0] and steps[-1][1] <= hi
    for (_, e0), (s1, _) in zip(steps, steps[1:]):
        assert e0 <= s1  # in time order, none inside another
    # the entry layer's own step follows the chunk, outside its span
    assert by_name["xgb.chunk.admit"][0] >= hi
    # the three steps are the chunk: what they leave out is span overhead
    covered = sum(e - s for s, e in steps)
    assert covered <= hi - lo
    # no Chrome event was recorded: XGBTPU_TRACE is unset
    assert trace.flush() is None


def test_span_records_on_both_clocks_with_bare_chrome_names(
        tmp_path, monkeypatch):
    out = tmp_path / "chrome.json"
    monkeypatch.setenv("XGBTPU_TRACE", str(out))
    trace.reset()

    def run():
        with trace.span("outer", k=1):
            with trace.span("chunk.inner"):
                pass

    threads = _profile(tmp_path / "prof", run)
    evs, = threads.values()
    assert [n for n, _, _ in evs] == ["xgb.outer", "xgb.chunk.inner"]
    trace.flush()
    names = [e["name"] for e in trace.load_trace(str(out))
             if e.get("ph") == "X"]
    assert sorted(names) == ["chunk.inner", "outer"]
    trace.reset()


def test_span_is_suppressed_while_jax_is_staging(tmp_path, monkeypatch):
    """jax 0.9 moved ``trace_state_clean``; the old lookup failed into
    "always host side" and a span inside a staged function was recorded
    once per compile."""
    monkeypatch.setenv("XGBTPU_TRACE", str(tmp_path / "chrome.json"))
    trace.reset()
    seen = []

    @jax.jit
    def staged(x):
        seen.append(trace.span("inside_jit"))
        return x + 1

    staged(1.0)
    assert seen == [trace._NOOP]
    assert not isinstance(trace.span("outside"), trace._NoopSpan)
    trace.reset()


def test_span_costs_under_5us_when_nothing_listens(_no_chrome_trace):
    assert not trace.enabled()
    assert not jax.profiler.TraceAnnotation.is_enabled()
    assert trace.span("chunk.prepare") is trace._NOOP

    def once(n=20000):
        t0 = time.perf_counter()
        for _ in range(n):
            with trace.span("chunk.prepare"):
                pass
        return (time.perf_counter() - t0) / n * 1e6

    once(2000)
    best = min(once() for _ in range(5))
    print(f"span() enter/exit, no profiler session, XGBTPU_TRACE unset: "
          f"{best:.2f} us")
    assert best < 5.0


def test_model_bytes_equal_with_and_without_a_profiler_session(
        tmp_path, _no_chrome_trace):
    X, y = _data()

    def fit():
        d = xgb.DMatrix(X, label=y)
        bst = xgb.Booster(dict(PARAMS, seed=7), [d])
        bst.update_many(d, 0, 4, chunk=2)
        return bytes(bst.save_raw("json"))

    plain = fit()
    profiled = []
    _profile(tmp_path, lambda: profiled.append(fit()))
    assert profiled == [plain]


# the level kernels tiled over features (ISSUE 35): Epsilon's width
_EPS_F, _EPS_B = 2000, 128


@pytest.mark.parametrize("F,B,depth", [(_EPS_F, _EPS_B, 6),
                                       (_EPS_F, 256, 6),
                                       (520, _EPS_B, 7)])
def test_wide_tree_compiles_on_the_tiled_kernels_under_their_names(
        one_v5e_chip, monkeypatch, F, B, depth):
    """A whole tree program over a matrix no untiled kernel takes, compiled
    for a described v5e at real widths (8,192 rows: the kernels' blocks do
    not depend on the row count): the chip's compiler takes every tiled
    level call (the tile's accumulator, the ``(128, tr)`` block of the
    feature-major bins) and the routing kernel at its 256-row tile; a level
    is one ``_tiled_level_pallas``, which the benchmark's
    reduction books to the level histogram by its name, behind one
    ``_route_rows_pallas`` under ``xgb.partition``, which it does not.
    Every one of them reads the feature-major ``s32[Fp, n]`` (ISSUE 36),
    which the program makes ONCE a tree: one fusion carries the
    transpose's name (XLA folds it into the widening and the pad)."""
    import jax.numpy as jnp

    from xgboost_tpu.tree import grow, grow_fused

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_v5e_chip)

    monkeypatch.setattr(hk, "use_pallas", lambda: True)
    n = 8192
    hlo = _compiled_text(
        grow_fused._grow_tree_fused_impl._guarded_jit,
        S((n, F), jnp.uint8 if B < 255 else jnp.uint16), S((n,), jnp.float32),
        S((n,), jnp.float32), S((F, B), jnp.float32), S((2,), jnp.uint32),
        S((), jnp.float32), S((), jnp.float32),
        cfg=grow.GrowParams(max_depth=depth))
    lines = _mosaic_lines_of(hlo)
    summary = _benchmark_summary()
    levels = [ln for ln in lines
              if summary.is_level_kernel(ln.removeprefix("ROOT "))]
    routes = [ln for ln in lines if ln not in levels]
    assert len(levels) == depth and len(routes) == depth
    Fp = -(-F // 128) * 128
    made = [ln for ln in hlo.splitlines() if " fusion(" in ln
            and '/xgb.level_hist/transpose"' in ln]
    assert len(made) == 1 and f" = s32[{Fp},{n}]" in made[0], made
    for ln in lines:
        assert f"operand_layout_constraints={{s32[{Fp},{n}]{{1,0}}, " in ln
    for d, ln in enumerate(levels):
        assert re.match(r"(?:ROOT )?%_tiled_level_pallas[.\d]* = ", ln), ln[:80]
        Kc = max(1 << d >> 1, 1)
        assert f"f32[{Fp},{2 * Kc},{B}]" in ln.split(" custom-call(")[0]
        assert "/xgb.level_hist/jit(_tiled_level_pallas)/" in ln
    for d, ln in enumerate(routes):
        assert re.match(r"(?:ROOT )?%_route_rows_pallas[.\d]* = ", ln), ln[:80]
        assert "xgb.partition/jit(_route_rows_pallas)/" in ln
        assert summary.kind_of(ln.removeprefix("ROOT ")) == "mosaic"


# the untiled kernels on the feature-major bins (ISSUE 38): the older cells'
# widths, resident columns and depths
@pytest.mark.parametrize("F,Fh,depth", [(50, 34, 6), (28, 7, 8), (136, 12, 6)])
def test_untiled_tree_reads_one_feature_major_array(one_v5e_chip,
                                                    monkeypatch, F, Fh,
                                                    depth):
    """A whole tree program at an older cell's width, resident one-hot and
    depth, compiled for a described v5e (8,192 rows): every level is one
    ``_hoisted_level_pallas`` and the tree's last routing one
    ``_route_rows_pallas``; every one of them reads the feature-major
    ``s32[Fp, n]``, padded to whole sublanes, which the program makes ONCE
    a tree (one fusion carries the transpose's name: XLA folds it into the
    widening and the pad), and no Mosaic call reads the bins row-major; a
    level call holds them in HBM."""
    import jax.numpy as jnp

    from xgboost_tpu.tree import grow, grow_fused

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_v5e_chip)

    monkeypatch.setattr(hk, "use_pallas", lambda: True)
    n, B = 8192, 256
    hlo = _compiled_text(
        grow_fused._grow_tree_fused_impl._guarded_jit,
        S((n, F), jnp.uint16), S((n,), jnp.float32), S((n,), jnp.float32),
        S((F, B), jnp.float32), S((2,), jnp.uint32), S((), jnp.float32),
        S((), jnp.float32), cfg=grow.GrowParams(max_depth=depth),
        onehot=S((n, Fh * B), jnp.int8))
    lines = _mosaic_lines_of(hlo)
    summary = _benchmark_summary()
    levels = [ln for ln in lines
              if summary.is_level_kernel(ln.removeprefix("ROOT "))]
    routes = [ln for ln in lines if ln not in levels]
    assert len(levels) == depth and len(routes) == 1
    Fp = hk._up(F, hk._SUBLANES)
    made = [ln for ln in hlo.splitlines() if " fusion(" in ln
            and '/xgb.level_hist/transpose"' in ln]
    assert len(made) == 1 and f" = s32[{Fp},{n}]" in made[0], made
    for ln in lines:
        assert f"operand_layout_constraints={{s32[{Fp},{n}]{{1,0}}, " in ln
        assert f"s32[{n},{F}]" not in ln
    for ln in levels:
        assert re.match(r"(?:ROOT )?%_hoisted_level_pallas[.\d]* = ", ln)
        # the bins held in HBM (``hist_kernel._in_hbm``): XLA may not keep
        # them in VMEM across the round
        assert ('"input_memory_space_colors":[{"operand_index":"0",'
                '"color":"0"') in ln
    assert re.match(r"(?:ROOT )?%_route_rows_pallas[.\d]* = ", routes[0])
