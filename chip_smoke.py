#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the main path once through the entry points a user
calls — ``xgb.train(tree_method="tpu_hist")`` -> ``Booster.predict`` /
``inplace_predict`` -> ``ModelServer`` — at the full width of the anchor
configuration (1M x 50 dense, ``binary:logistic``, depth 6; the rows of
``benchmark/generators/linear_logit.py``, seed 42), and checks what comes
out by the repo's own means: compiled Pallas kernels against
``fused_level_xla``, holdout AUC, routed-vs-XLA training, predict/serve
parity against the plain gather walk. Any exception or failed check ends the run non-zero; there is no
path that runs off the chip. The last line of stdout is one JSON object
``{"ok": true, "device": {...}}``.

    python chip_smoke.py                 # on the chip (through the chip tool)
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse   # CPU rehearsal

``--rehearse`` runs the same stages at a tiny size with the Pallas
kernels in interpret mode, so the command can be debugged before chip
time is spent. It requires ``JAX_PLATFORMS=cpu`` from the caller and
labels every line: nothing it prints is a chip result. ``--stages a,b``
runs a subset (the builder's tool for a four-chip host); the driver runs
the plain command.

Times printed are wall-clock observations of one run (first call =
compile + run, second call = warm), not metrics.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import threading
import time
from dataclasses import dataclass

STAGES = ("kernels", "wide", "train", "predict", "serve", "four_chips",
          "reference")

_TAG = ""  # set to the rehearsal label by main()


def say(msg: str = "") -> None:
    print(f"{_TAG}{msg}", flush=True)


class SmokeFailure(AssertionError):
    """A check of this script failed."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


@dataclass(frozen=True)
class Sizes:
    rows: int           # the anchor matrix
    cols: int
    kernel_rows: int    # slice for the compiled-kernel equivalence
    rounds: int         # bin64 rounds (each of: with eval, without)
    rounds256: int
    sub_rows: int       # routed-vs-xla reference subsample
    forest_rows: int    # subsample the wide forest is trained on
    forest_trees: int
    predict_rows: int
    serve_sizes: tuple
    serve_requests: int  # per client thread
    mesh_rounds: int
    auc_floor64: float
    auc_floor256: float


FULL = Sizes(rows=1_000_000, cols=50, kernel_rows=65_536, rounds=20,
             rounds256=5, sub_rows=100_000, forest_rows=32_768,
             forest_trees=500, predict_rows=100_000,
             serve_sizes=(1, 16, 256, 4096), serve_requests=8,
             mesh_rounds=10,
             # floors set from PR 21's chip run on the older rows (the AUC
             # observed there, less 0.015); the generator's rows read
             # 0.8612 and 0.7687 on the chip (CHANGES.md, PR 28)
             auc_floor64=0.84, auc_floor256=0.76)
TINY = Sizes(rows=4096, cols=8, kernel_rows=1024, rounds=3, rounds256=2,
             sub_rows=2048, forest_rows=1024, forest_trees=12,
             predict_rows=1500, serve_sizes=(1, 16, 256), serve_requests=3,
             mesh_rounds=2, auc_floor64=0.75, auc_floor256=0.70)

DEPTH = 6
SEED = 42


# ---------------------------------------------------------------------------
# bookkeeping: routes, degrade state, warnings
# ---------------------------------------------------------------------------

_WARNINGS: list = []


def _hook_warnings() -> None:
    """Record every ``console_logger.warning`` — the fallbacks on this
    path (one-hot build -> construct, pallas walk -> XLA walk, native
    containment) all log one before carrying on."""
    from xgboost_tpu.utils import console_logger

    orig = console_logger.warning

    def recording(*args):
        _WARNINGS.append(" ".join(str(a) for a in args))
        orig(*args)

    console_logger.warning = recording


def _decisions() -> dict:
    """{(op, impl): count} from ``dispatch_decisions_total``."""
    from xgboost_tpu.observability import REGISTRY

    fam = REGISTRY.get("dispatch_decisions_total")
    out: dict = {}
    if fam is not None:
        for labels, child in fam.series():
            key = (labels["op"], labels["impl"])
            out[key] = out.get(key, 0) + child.value
    return out


def _check_routes(stage: str, before: dict, expected: dict,
                  must_see: tuple = ()) -> None:
    """Fail if, during ``stage``, any op in ``expected`` resolved to an
    impl other than the stated one, or an op in ``must_see`` never
    resolved at all."""
    now = _decisions()
    seen: dict = {}
    for (op, impl), cnt in sorted(now.items()):
        d = cnt - before.get((op, impl), 0)
        if d > 0:
            seen.setdefault(op, {})[impl] = int(d)
    say(f"  routes[{stage}]: " + (", ".join(
        f"{op}={'/'.join(f'{i}x{c}' for i, c in impls.items())}"
        for op, impls in sorted(seen.items())) or "none resolved"))
    for op, want in expected.items():
        for impl in seen.get(op, {}):
            check(impl == want,
                  f"{stage}: op {op!r} resolved to {impl!r}, expected "
                  f"{want!r}")
    for op in must_see:
        check(op in seen, f"{stage}: op {op!r} never resolved")


def _check_health(stage: str) -> None:
    from xgboost_tpu.resilience import degrade

    snap = degrade.snapshot()
    bad = {k: v["worst"] for k, v in snap.items() if v["worst"] != "healthy"}
    check(not bad, f"{stage}: degraded capabilities {bad}")
    check(not _WARNINGS,
          f"{stage}: warning(s) logged: {_WARNINGS[:3]}")


@contextlib.contextmanager
def _pinned(spec: str):
    """``XGBTPU_DISPATCH`` with ``spec`` appended, for a deliberate
    off-preference run; an empty ``spec`` pins nothing."""
    prev = os.environ.get("XGBTPU_DISPATCH")
    if spec:
        os.environ["XGBTPU_DISPATCH"] = f"{prev},{spec}" if prev else spec
    try:
        yield
    finally:
        if prev is None:
            os.environ.pop("XGBTPU_DISPATCH", None)
        else:
            os.environ["XGBTPU_DISPATCH"] = prev


def _sync(x):
    import jax

    return jax.block_until_ready(x)


def _timed(fn):
    t0 = time.perf_counter()
    out = _sync(fn())
    return out, time.perf_counter() - t0


def anchor_data(rows: int, cols: int, seed: int):
    """(X, y) from the anchor configuration's generator, the file the
    benchmark's cells load (its default ``law_seed`` is the anchor's)."""
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "benchmark", "generators", "linear_logit.py")
    spec = importlib.util.spec_from_file_location("linear_logit", path)
    gen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gen)
    return gen.generate(rows=rows, cols=cols, seed=seed)


def _auc(bst, dmat) -> float:
    return float(bst.eval(dmat).rsplit(":", 1)[1])


def _params(max_bin: int) -> dict:
    return {"objective": "binary:logistic", "tree_method": "tpu_hist",
            "max_depth": DEPTH, "max_bin": max_bin, "eta": 0.1,
            "eval_metric": "auc", "seed": SEED}


# ---------------------------------------------------------------------------
# stage: compiled kernels == fused_level_xla
# ---------------------------------------------------------------------------


def _xla_oracle():
    """``fused_level_xla`` on host arrays, (pos, hist) back as numpy. Placed
    on the host CPU device where JAX has one: XLA's TPU compile of its
    scatter-add grows with the row count (67 s a shape at 64k rows on the
    v5e, for 0.03 s of run — CHANGES.md, PR 21), and where it runs does not
    matter to an oracle."""
    import jax
    import numpy as np

    from xgboost_tpu.tree import hist_kernel as hk

    try:
        dev = jax.devices("cpu")[0]
    except RuntimeError:  # JAX_PLATFORMS names no cpu: compile it on the chip
        dev = jax.devices()[0]
    say(f"  oracle fused_level_xla placed on {dev}")

    def oracle(bins_np, pos_np, gh_np, ptab_np, **kw):
        args = [jax.device_put(a, dev)
                for a in (bins_np, pos_np, gh_np, ptab_np)]
        pos_x, hist_x = hk.fused_level_xla(*args, **kw)
        return np.asarray(pos_x), np.asarray(hist_x)

    return oracle


def stage_kernels(sz: Sizes, X, routes: dict) -> None:
    """Each level kernel the anchor can route to, compiled, against
    ``fused_level_xla`` on a slice of the anchor: identical ``pos``,
    histograms within the 2^-16-relative class the hi/lo split promises
    (the comparison tests/test_hoisted.py makes in interpret mode). Below
    the root each runs twice: the direct build, and the child every parent
    marked with its sibling derived from the oracle's parent histogram
    (``*_sub``, tests/test_sibling_sub.py's comparison)."""
    import jax.numpy as jnp
    import numpy as np

    from xgboost_tpu.data.quantile import BinnedMatrix
    from xgboost_tpu.tree import hist_kernel as hk

    oracle = _xla_oracle()

    n, F = sz.kernel_rows, sz.cols
    n_anchor = -(-int(sz.rows * 0.75) // hk.TR) * hk.TR
    rng = np.random.RandomState(SEED)
    gh_np = rng.randn(2, n).astype(np.float32)  # row 0 g, row 1 h
    gh_np[1] = np.abs(gh_np[1]) + 0.05
    gh = jnp.asarray(gh_np)
    before = _decisions()
    for B in (64, 256):
        binned = BinnedMatrix.from_dense(X[:n], max_bin=B)
        bins = binned.bins  # narrow storage dtype
        bins_np = np.asarray(bins)
        bins32 = bins.astype(jnp.int32)
        binsT = hk._feature_major(bins32, hk._SUBLANES, B)  # as a tree has
        plan = hk.hoist_plan(n_anchor, F, B, DEPTH)
        say(f"  bin{B}: anchor hoist plan {plan}/{F} features "
            f"({n_anchor} rows)")
        check(plan > 0, f"bin{B}: the anchor does not hoist at all")
        # the anchor's own plan, plus a partial where the plan is full
        widths = [plan] + ([(F // 2) & ~1] if plan == F else [])
        onehots = {}
        for fh in widths:
            oh, cold = _timed(lambda: hk.build_onehot(bins[:, :fh], B=B))
            _, warm = _timed(lambda: hk.build_onehot(bins[:, :fh], B=B))
            want = hk._build_onehot_xla(bins[:4096, :fh], B=B)
            check(bool(jnp.array_equal(oh[:4096], want)),
                  f"bin{B}: one-hot build (Fh={fh}) != XLA build")
            check(int(oh.astype(jnp.int32).sum()) ==
                  int((bins_np[:, :fh] < B).sum()),
                  f"bin{B}: one-hot build (Fh={fh}) population wrong")
            onehots[fh] = oh
            say(f"  bin{B} onehot_build Fh={fh}: cold {cold:.2f}s "
                f"warm {warm:.4f}s  == XLA build")
        for d in (0, 3, 5):
            K, Kp = 1 << d, (1 << d) >> 1
            if d == 0:
                pos_np = np.zeros((1, n), np.int32)
                ptab_np = np.zeros((1, 4), np.float32)
            else:
                prev = (1 << (d - 1)) - 1
                pos_np = (prev + rng.randint(0, Kp, size=(1, n))
                          ).astype(np.int32)
                ptab_np = np.stack([
                    # is_split, and which child a subtracting level builds
                    ((rng.rand(Kp) < 0.85) * rng.randint(1, 3, Kp)
                     ).astype(np.float32),
                    rng.randint(0, F, Kp).astype(np.float32),
                    rng.randint(0, B - 1, Kp).astype(np.float32),
                    rng.randint(0, 2, Kp).astype(np.float32),
                ], axis=1)
            pos, ptab = jnp.asarray(pos_np), jnp.asarray(ptab_np)
            kw = dict(K=K, Kp=Kp, B=B, d=d)
            t0 = time.perf_counter()
            pos_x, hist_x = oracle(bins_np, pos_np, gh_np, ptab_np, **kw)
            t_x = time.perf_counter() - t0
            _, habs = oracle(bins_np, pos_np, np.abs(gh_np), ptab_np, **kw)
            # two bf16 terms carry ~16 significand bits per addend
            tol = {False: habs * 2.0 ** -15 + 1e-6}
            if d > 0:
                # the parents' histogram and, for a derived cell, the
                # tolerance of the parent's cell it was subtracted from
                up = dict(K=Kp, Kp=0, B=B, d=d - 1)
                parent = jnp.asarray(
                    oracle(bins_np, pos_np, gh_np, ptab_np, **up)[1])
                pabs = oracle(bins_np, pos_np, np.abs(gh_np), ptab_np,
                              **up)[1].reshape(F, 2, Kp, 1, B)
                tol[True] = np.broadcast_to(pabs, (F, 2, Kp, 2, B)).reshape(
                    F, 2 * K, B) * 2.0 ** -15 + 1e-6
            cands = {}
            for sub in ((False, True) if d > 0 else (False,)):
                Kc, tag = (Kp, "_sub") if sub else (K, "")
                cands["construct" + tag] = (
                    lambda sub=sub: hk._fused_level_pallas(
                        binsT, pos, gh, ptab, F=F, sub=sub, **kw))
                for fh in widths:
                    tr = hk._hoist_tr(fh * B, Kc, F, B)
                    check(tr > 0 and n % tr == 0, f"bin{B} d={d}: no "
                          f"hoisted row tile for Fh={fh} at {Kc} nodes")
                    name = ("hoisted_full" if fh == F
                            else f"hoisted_partial{fh}") + tag
                    cands[name] = (lambda oh=onehots[fh], tr=tr, sub=sub:
                                   hk._hoisted_level_pallas(
                                       binsT, oh, pos, gh, ptab, F=F, tr=tr,
                                       sub=sub, **kw))
            for name, fn in cands.items():
                (pos_p, hist_p), cold = _timed(fn)
                _, warm = _timed(fn)
                check(np.array_equal(np.asarray(pos_p), pos_x),
                      f"bin{B} d={d} {name}: pos differs from XLA")
                sub = name.endswith("_sub")
                if sub:
                    check(hist_p.shape == (F, 2 * Kp, B),
                          f"bin{B} d={d} {name}: not the built half")
                    hist_p = hk.derive_siblings(parent, hist_p, ptab)
                err = np.abs(np.asarray(hist_p) - hist_x)
                tol_c = tol[sub]
                check(bool((err <= tol_c).all()),
                      f"bin{B} d={d} {name}: histogram off by "
                      f"{float((err - tol_c).max()):.3e} beyond tolerance")
                say(f"  bin{B} d={d} {name}: cold {cold:.2f}s warm "
                    f"{warm:.4f}s  pos identical, max|dhist| "
                    f"{float(err.max()):.2e} (oracle {t_x:.2f}s)")
            if d > 0:
                # the routing kernel alone, as a tree's last level runs it
                def route():
                    return hk.partition_apply(bins32, pos, ptab, Kp=Kp, B=B,
                                              d=d, pallas=True)

                pos_r, cold = _timed(route)
                _, warm = _timed(route)
                check(np.array_equal(np.asarray(pos_r), pos_x),
                      f"bin{B} d={d} route_rows: pos differs from XLA")
                say(f"  bin{B} d={d} route_rows: cold {cold:.2f}s warm "
                    f"{warm:.4f}s  pos identical")
        del binned, bins, bins32, binsT, onehots
    _check_routes("kernels", before, routes,
                  must_see=("onehot_build", "level_partition",
                            "sketch_cuts", "bin_matrix"))


# ---------------------------------------------------------------------------
# stage: the tiled kernels at 2,000 columns == fused_level_xla
# ---------------------------------------------------------------------------

WIDE_COLS, WIDE_BINS = 2000, 128


def stage_wide(sz: Sizes, routes: dict) -> None:
    """A matrix no untiled kernel takes (2,000 columns at 128 bins, ISSUE
    35), on a few thousand rows: every level through ``fused_level``, which
    must find the tiled kernel by itself, against ``fused_level_xla`` as
    the kernels stage compares (positions identical, histograms in the
    hi/lo class; below the root also the built child with its sibling
    derived); the routing kernel at the width; and the sketch by column
    blocks against the whole-matrix program. Both kernels read the tree's
    feature-major bins (ISSUE 36: ``[2048, n]``, a column's one-hot
    ``[B, tr]``), which the dispatcher makes from the ``[n, 2000]`` array
    it is handed, as a tree's program does."""
    import jax.numpy as jnp
    import numpy as np

    from xgboost_tpu.data import quantile
    from xgboost_tpu.tree import hist_kernel as hk

    oracle = _xla_oracle()
    n, F, B = min(sz.kernel_rows, 8192), WIDE_COLS, WIDE_BINS
    rng = np.random.RandomState(SEED + 1)
    X = rng.randn(n, F).astype(np.float32)
    X[rng.rand(n, F) < 0.01] = np.nan
    before = _decisions()
    whole = quantile.BinnedMatrix.from_dense(X, max_bin=B)
    quantile._FORCE_BLOCK_COLS = 500
    try:
        blocks = quantile.BinnedMatrix.from_dense(X, max_bin=B)
    finally:
        quantile._FORCE_BLOCK_COLS = None
    check(np.array_equal(whole.cuts.values, blocks.cuts.values)
          and np.array_equal(np.asarray(whole.bins), np.asarray(blocks.bins)),
          "wide: column-blocked cuts or bins differ from the whole-matrix "
          "program's")
    say(f"  sketch + bins of {n}x{F} in 4 column blocks == whole matrix")
    bins_np = np.asarray(whole.bins)
    bins32 = whole.bins.astype(jnp.int32)
    gh_np = rng.randn(2, n).astype(np.float32)
    gh_np[1] = np.abs(gh_np[1]) + 0.05
    gh = jnp.asarray(gh_np)

    def level_inputs(d):
        Kp = (1 << d) >> 1
        if d == 0:
            return np.zeros((1, n), np.int32), np.zeros((1, 4), np.float32)
        prev = (1 << (d - 1)) - 1
        pos_np = (prev + rng.randint(0, Kp, size=(1, n))).astype(np.int32)
        ptab_np = np.stack([
            ((rng.rand(Kp) < 0.85) * rng.randint(1, 3, Kp)
             ).astype(np.float32),
            rng.randint(0, F, Kp).astype(np.float32),
            rng.randint(0, B - 1, Kp).astype(np.float32),
            rng.randint(0, 2, Kp).astype(np.float32)], axis=1)
        return pos_np, ptab_np

    def compare(tag, d, sub, pos_np, ptab_np):
        K, Kp = 1 << d, (1 << d) >> 1
        kw = dict(K=K, Kp=Kp, B=B, d=d)
        plan = hk.level_plan(n, F, Kp if sub else K, B)
        check(plan is not None and plan.kernel == "tiled",
              f"{tag}: the model does not send this level to the tiles: "
              f"{plan}")
        pos_x, hist_x = oracle(bins_np, pos_np, gh_np, ptab_np, **kw)
        _, habs = oracle(bins_np, pos_np, np.abs(gh_np), ptab_np, **kw)
        tol = habs * 2.0 ** -15 + 1e-6
        pos, ptab = jnp.asarray(pos_np), jnp.asarray(ptab_np)

        def call():
            return hk.fused_level(bins32, pos, gh, ptab, pallas=True,
                                  sibling_sub=sub, **kw)

        (pos_p, hist_p), cold = _timed(call)
        _, warm = _timed(call)
        check(np.array_equal(np.asarray(pos_p), pos_x),
              f"{tag}: pos differs from XLA")
        if sub:
            check(hist_p.shape == (F, 2 * Kp, B),
                  f"{tag}: not the built half")
            up = dict(K=Kp, Kp=0, B=B, d=d - 1)
            parent = jnp.asarray(oracle(bins_np, pos_np, gh_np, ptab_np,
                                        **up)[1])
            pabs = oracle(bins_np, pos_np, np.abs(gh_np), ptab_np,
                          **up)[1].reshape(F, 2, Kp, 1, B)
            tol = np.broadcast_to(pabs, (F, 2, Kp, 2, B)).reshape(
                F, 2 * K, B) * 2.0 ** -15 + 1e-6
            hist_p = hk.derive_siblings(parent, hist_p, ptab)
        err = np.abs(np.asarray(hist_p) - hist_x)
        check(bool((err <= tol).all()),
              f"{tag}: histogram off by {float((err - tol).max()):.3e} "
              "beyond tolerance")
        say(f"  {tag}: {plan.tiles} tiles of ({plan.ft}, {plan.tr}) "
            f"feature-major: cold {cold:.2f}s warm {warm:.4f}s  pos "
            f"identical, max|dhist| {float(err.max()):.2e}")

    Fp = hk._up(F, hk._FEATURE_TILE)
    # d=6 under subtraction builds 32 nodes: the most a tile's accumulator
    # holds at 128 bins
    for d in (0, 3, 5, 6):
        pos_np, ptab_np = level_inputs(d)
        for sub in ((False, True) if 0 < d < 6 else (d == 6,)):
            compare(f"wide {F}x{B} d={d}{'_sub' if sub else ''}", d, sub,
                    pos_np, ptab_np)
        if d:
            pos, ptab = jnp.asarray(pos_np), jnp.asarray(ptab_np)
            Kp = (1 << d) >> 1

            def route():
                return hk.partition_apply(bins32, pos, ptab, Kp=Kp, B=B, d=d,
                                          pallas=True)

            pos_r, cold = _timed(route)
            _, warm = _timed(route)
            want = oracle(bins_np, pos_np, gh_np, ptab_np, K=1 << d, Kp=Kp,
                          B=B, d=d)[0]
            check(np.array_equal(np.asarray(pos_r), want),
                  f"wide d={d} route_rows: pos differs from XLA")
            say(f"  wide d={d} route_rows (block ({Fp}, "
                f"{hk._route_tr(n, Fp, Kp, 4)}) feature-major): cold "
                f"{cold:.2f}s warm {warm:.4f}s  pos identical")
    _check_routes("wide", before, routes,
                  must_see=("level_hist", "level_partition", "sketch_cuts",
                            "bin_matrix"))


# ---------------------------------------------------------------------------
# stage: train at full width
# ---------------------------------------------------------------------------


def _say_hoist(dtrain, max_bin: int, F: int) -> None:
    """Which hoist plan the fit ran on: the resident one-hot's shape."""
    oh = dtrain.get_binned(max_bin)._onehot
    check(oh is not None, f"bin{max_bin}: training ran without a hoist")
    fh = oh.shape[1] // max_bin
    say(f"  bin{max_bin} resident one-hot {tuple(oh.shape)} int8 = "
        f"{oh.nbytes / 2**30:.2f} GiB, {fh}/{F} features hoisted")


def stage_train(sz: Sizes, xgb, X, y, routes: dict, rehearse: bool) -> None:
    n_tr = int(sz.rows * 0.75)
    dtest = xgb.DMatrix(X[n_tr:], label=y[n_tr:])
    before = _decisions()

    def with_eval(dtrain, params, rounds):
        res: dict = {}
        bst = xgb.train(params, dtrain, rounds, evals=[(dtest, "holdout")],
                        evals_result=res, verbose_eval=False)
        _sync(bst._caches[id(dtrain)].margin)
        return bst, res["holdout"]["auc"][-1]

    def without_consumer(dtrain, params, rounds):
        bst = xgb.train(params, dtrain, rounds)
        _sync(bst._caches[id(dtrain)].margin)
        return bst

    dtrain = xgb.DMatrix(X[:n_tr], label=y[:n_tr])
    p64 = _params(64)
    t0 = time.perf_counter()
    _, auc = with_eval(dtrain, p64, sz.rounds)
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    _, auc_w = with_eval(dtrain, p64, sz.rounds)
    warm = time.perf_counter() - t0
    say(f"  bin64 {sz.rounds}r with eval (per-round loop): cold "
        f"{cold:.2f}s (binning+compile+run) warm {warm:.2f}s  "
        f"holdout auc {auc:.4f}")
    check(abs(auc - auc_w) <= 1e-6,
          f"bin64: a repeated run gave AUC {auc_w!r}, not {auc!r}")
    _say_hoist(dtrain, 64, sz.cols)
    check(auc > sz.auc_floor64,
          f"bin64 holdout AUC {auc:.4f} <= floor {sz.auc_floor64}")

    t0 = time.perf_counter()
    bst = without_consumer(dtrain, p64, sz.rounds)
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    bst = without_consumer(dtrain, p64, sz.rounds)
    warm = time.perf_counter() - t0
    auc_s = _auc(bst, dtest)
    path = ("per-round loop: the scan path is TPU-only" if rehearse
            else "update_many scan")
    say(f"  bin64 {sz.rounds}r no consumer ({path}): cold {cold:.2f}s "
        f"warm {warm:.2f}s  holdout auc {auc_s:.4f}")
    check(abs(auc_s - auc) < 0.002,
          f"bin64: scan-path AUC {auc_s:.4f} vs per-round {auc:.4f}")
    if rehearse:
        # cover Booster.update_many itself; xgb.train reaches it on TPU only
        b2 = xgb.Booster(p64, [dtrain])
        b2.update_many(dtrain, 0, sz.rounds, chunk=sz.rounds)
        say(f"  bin64 Booster.update_many direct: holdout auc "
            f"{_auc(b2, dtest):.4f}")
    del bst, dtrain
    gc.collect()

    dtrain = xgb.DMatrix(X[:n_tr], label=y[:n_tr])
    p256 = _params(256)
    t0 = time.perf_counter()
    _, auc256 = with_eval(dtrain, p256, sz.rounds256)
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    with_eval(dtrain, p256, sz.rounds256)
    warm = time.perf_counter() - t0
    say(f"  bin256 {sz.rounds256}r with eval: cold {cold:.2f}s warm "
        f"{warm:.2f}s  holdout auc {auc256:.4f}")
    check(auc256 > sz.auc_floor256,
          f"bin256 holdout AUC {auc256:.4f} <= floor {sz.auc_floor256}")
    _say_hoist(dtrain, 256, sz.cols)
    del dtrain, dtest
    gc.collect()
    _check_routes("train", before, routes,
                  must_see=("level_hist", "sibling_sub", "level_partition",
                            "onehot_build", "predict_walk"))


# ---------------------------------------------------------------------------
# stage: predict at the forest width that failed before (T=512, Np=128)
# ---------------------------------------------------------------------------


def stage_predict(sz: Sizes, xgb, X, y, routes: dict, rehearse: bool):
    import jax.numpy as jnp
    import numpy as np

    from xgboost_tpu import predictor

    before = _decisions()
    dsub = xgb.DMatrix(X[:sz.forest_rows], label=y[:sz.forest_rows])
    t0 = time.perf_counter()
    if rehearse:
        # what xgb.train does with no consumer ON THE CHIP (the scan
        # path is TPU-only): chunked update_many, whose trees stay
        # device-resident in heap layout even after a save
        bst = xgb.Booster(_params(64), [dsub])
        bst.update_many(dsub, 0, sz.forest_trees)
    else:
        bst = xgb.train(_params(64), dsub, sz.forest_trees)
    _sync(bst._caches[id(dsub)].margin)
    say(f"  {sz.forest_trees}-tree forest on {sz.forest_rows} rows: "
        f"{time.perf_counter() - t0:.2f}s")
    forest = bst._gbm.model.stacked()
    T, Np = forest.left.shape
    say(f"  stacked forest: T={T} Np={Np} heap_layout={forest.heap_layout} "
        f"steps={forest.max_depth}")
    check(forest.heap_layout and not forest.has_cats,
          "forest is not a heap-layout numerical forest")
    check(predictor.pallas_walk_fits(T, Np),
          f"T={T} Np={Np} is outside the pallas walk's table gate")

    Xp = np.ascontiguousarray(X[-sz.predict_rows:])
    dm = xgb.DMatrix(Xp)
    m_dm, cold = _timed(lambda: bst.predict(dm, output_margin=True))
    # a fresh DMatrix: the first one's prediction cache would answer
    _, warm = _timed(
        lambda: bst.predict(xgb.DMatrix(Xp), output_margin=True))
    say(f"  Booster.predict(DMatrix) {sz.predict_rows} rows: cold "
        f"{cold:.2f}s warm {warm:.2f}s")
    m_ip, cold = _timed(
        lambda: bst.inplace_predict(Xp, predict_type="margin"))
    _, warm = _timed(lambda: bst.inplace_predict(Xp, predict_type="margin"))
    say(f"  inplace_predict {sz.predict_rows} rows: cold {cold:.2f}s "
        f"warm {warm:.2f}s")

    # the plain gather walk (_walk_leaves under _predict_margin_kernel)
    base = jnp.full((len(Xp), 1), bst._base_margin_val, jnp.float32)
    ref, t_ref = _timed(lambda: predictor._predict_margin_kernel(
        jnp.asarray(Xp), forest.left, forest.right, forest.feature,
        forest.cond, forest.default_left, forest.split_type,
        forest.cat_bits, forest.tree_group, jnp.ones((T,), jnp.float32),
        base, forest.n_groups, forest.max_depth, forest.has_cats))
    ref = np.asarray(ref)[:, 0]
    say(f"  gather-walk reference: {t_ref:.2f}s")
    for name, m in (("predict", m_dm), ("inplace_predict", m_ip)):
        m = np.asarray(m).reshape(-1)
        check(m.shape == ref.shape and bool(np.isfinite(m).all()),
              f"{name}: margins not finite [{len(ref)}]")
        err = float(np.abs(m - ref).max())
        check(err <= 1e-5, f"{name}: max |margin - gather walk| = {err:.3e}")
        say(f"  {name}: max |margin - gather walk| = {err:.2e}")
    _check_routes("predict", before, routes, must_see=("predict_walk",))
    return bst, Xp, ref


# ---------------------------------------------------------------------------
# stage: serve
# ---------------------------------------------------------------------------


def _serve_clients(server, sz: Sizes, Xp, want_all, sizes, tol: float):
    """``serve_requests`` requests from each of 4 client threads; every
    answer must be within ``tol`` of ``want_all`` on the same rows.
    Returns (requests answered, largest difference seen)."""
    import numpy as np

    n_threads = 4
    errors: list = []
    done = {"n": 0, "err": 0.0}
    lock = threading.Lock()

    def client(tid: int) -> None:
        try:
            rng = np.random.RandomState(tid)
            for r in range(sz.serve_requests):
                k = sizes[(tid + r) % len(sizes)]
                lo = int(rng.randint(0, len(Xp) - k + 1))
                got = server.predict("anchor", Xp[lo:lo + k])
                want = want_all[lo:lo + k]
                check(got.shape == want.shape,
                      f"served shape {got.shape} for rows {lo}:{lo + k}")
                err = float(np.abs(got - want).max())
                check(err <= tol, f"served answer for rows {lo}:{lo + k} "
                                  f"off by {err:.3e} (> {tol:g})")
                with lock:
                    done["n"] += 1
                    done["err"] = max(done["err"], err)
        except BaseException as e:  # re-raised on the main thread below
            errors.append(e)

    threads = [threading.Thread(target=client, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    check(done["n"] == n_threads * sz.serve_requests,
          f"only {done['n']} requests answered")
    return done["n"], done["err"]


def stage_serve(sz: Sizes, bst, Xp, ref_margin, routes: dict,
                loaded_walk: str, walk_pin: str) -> None:
    """``ModelServer`` twice: over the live Booster, whose forest is still
    device-resident in heap layout (the pallas walk), and over the same
    model loaded from its file, whose forest is re-stacked from host
    trees in compact breadth-first order — which the pallas walk does
    not take, so a loaded model is served by the XLA gather program
    (``loaded_walk``; the explicit exception of CHANGES.md, PR 21)."""
    import tempfile

    import numpy as np

    from xgboost_tpu.serving.server import ModelServer

    sig = (1.0 / (1.0 + np.exp(-ref_margin.astype(np.float64)))
           ).astype(np.float32)
    before = _decisions()
    with _pinned(walk_pin):
        t0 = time.perf_counter()
        server = ModelServer({"anchor": bst})
        say(f"  live Booster: load + warm {time.perf_counter() - t0:.2f}s")
        want_all = bst.inplace_predict(Xp)
        err = float(np.abs(want_all - sig).max())
        check(err <= 1e-5,
              f"inplace_predict vs sigmoid(gather walk): {err:.3e}")
        t0 = time.perf_counter()
        n, err = _serve_clients(server, sz, Xp, want_all, sz.serve_sizes,
                                tol=1e-6)
        wall = time.perf_counter() - t0
        server.close()
    say(f"  live Booster: {n} requests of {sz.serve_sizes} rows from 4 "
        f"threads in {wall:.2f}s, max |answer - inplace_predict| "
        f"{err:.1e}; closed cleanly")
    _check_routes("serve/live", before, routes, must_see=("predict_walk",))

    before = _decisions()
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "anchor.json")
        bst.save_model(path)
        t0 = time.perf_counter()
        server = ModelServer({"anchor": path})
        say(f"  loaded model: load + warm {time.perf_counter() - t0:.2f}s "
            f"({os.path.getsize(path) // 1024} KiB of JSON)")
        sizes = (sz.serve_sizes[1], sz.serve_sizes[-1])
        t0 = time.perf_counter()
        n, err = _serve_clients(server, sz, Xp, want_all, sizes, tol=1e-6)
        wall = time.perf_counter() - t0
        server.close()
    say(f"  loaded model: {n} requests of {sizes} rows in {wall:.2f}s, "
        f"max |answer - live model's| {err:.1e}; closed cleanly")
    _check_routes("serve/loaded", before,
                  dict(routes, predict_walk=loaded_walk),
                  must_see=("predict_walk",))


# ---------------------------------------------------------------------------
# stage: four chips
# ---------------------------------------------------------------------------


def stage_four_chips(sz: Sizes, xgb, X, y, routes: dict) -> None:
    """Row-sharded training over four chips: shards on four distinct
    devices, level kernels as routed under ``shard_map``, trees equal in
    structure to single-chip training on the same cuts (the
    distributed-vs-single oracle of tests/test_distributed.py)."""
    import jax
    import numpy as np

    from xgboost_tpu.parallel import make_mesh, mesh_context

    params = _params(64)
    d = xgb.DMatrix(X, label=y)
    binned = d.get_binned(64)  # exact cuts, shared by both runs
    t0 = time.perf_counter()
    b1 = xgb.train(params, d, sz.mesh_rounds)
    _sync(b1._caches[id(d)].margin)
    say(f"  single chip {sz.rows} rows {sz.mesh_rounds}r: "
        f"{time.perf_counter() - t0:.2f}s")

    before = _decisions()
    mesh = make_mesh(4)
    with mesh_context(mesh):
        t0 = time.perf_counter()
        b4 = xgb.train(params, d, sz.mesh_rounds)
        margin = _sync(b4._caches[id(d)].margin)
        cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        b4 = xgb.train(params, d, sz.mesh_rounds)
        margin = _sync(b4._caches[id(d)].margin)
        warm = time.perf_counter() - t0
    say(f"  four chips {sz.rows} rows {sz.mesh_rounds}r: cold {cold:.2f}s "
        f"warm {warm:.2f}s")

    def placed(name, arr, sharded=True):
        devs = sorted(s.device.id for s in arr.addressable_shards)
        rows = sorted({s.data.shape[0] for s in arr.addressable_shards})
        say(f"  {name}: shape {tuple(arr.shape)} on devices {devs}, "
            f"rows/shard {rows}")
        check(len(set(devs)) == 4, f"{name} sits on devices {devs}")
        if sharded:
            check(rows == [arr.shape[0] // 4],
                  f"{name} is not row-sharded four ways: {rows}")

    placed("bin shards", binned._fused_mesh[1])
    if routes:  # the pallas routes are on
        check(binned._onehot_mesh is not None
              and binned._onehot_mesh[1] is not None,
              "no hoisted one-hot was built under the mesh")
        placed("hoisted one-hot", binned._onehot_mesh[1])
    placed("margin cache", margin, sharded=False)
    _check_routes("four_chips", before, routes,
                  must_see=("level_hist", "level_partition", "onehot_build")
                  if routes else ())

    t1, t4 = b1._gbm.model.trees, b4._gbm.model.trees
    check(len(t1) == len(t4) == sz.mesh_rounds, "tree counts differ")
    for i, (a, b) in enumerate(zip(t1, t4)):
        check(np.array_equal(a.left_children, b.left_children)
              and np.array_equal(a.split_indices, b.split_indices),
              f"tree {i}: four-chip structure != single-chip")
        np.testing.assert_allclose(a.split_conditions, b.split_conditions,
                                   rtol=1e-4, atol=1e-5)
    say(f"  {len(t1)} trees: four-chip structure == single-chip "
        f"({jax.device_count()} devices)")


# ---------------------------------------------------------------------------
# stage: reference — routed vs level_hist pinned to xla
# ---------------------------------------------------------------------------


def stage_reference(sz: Sizes, xgb, X, y) -> None:
    """The same rounds on a subsample twice: once as routed, once with
    ``level_hist`` pinned to the plain XLA segment-sum. Runs last, after
    the route checks, because the pin is a deliberate off-route run."""
    import jax

    n = sz.sub_rows
    n_tr = int(n * 0.75)
    dtrain = xgb.DMatrix(X[:n_tr], label=y[:n_tr])
    dtest = xgb.DMatrix(X[n_tr:n], label=y[n_tr:n])
    params = _params(64)

    def run():
        res: dict = {}
        t0 = time.perf_counter()
        xgb.train(params, dtrain, sz.rounds, evals=[(dtest, "holdout")],
                  evals_result=res, verbose_eval=False)
        return res["holdout"]["auc"][-1], time.perf_counter() - t0

    before = _decisions()
    auc_r, t_r = run()
    _check_routes("reference/routed", before, {"level_hist": "pallas"},
                  must_see=("level_hist",))
    # routes are resolved at trace time: the pinned run must not be
    # answered by the program the routed run just compiled
    jax.clear_caches()
    with _pinned("level_hist=xla"):
        before = _decisions()
        auc_x, t_x = run()
        _check_routes("reference/xla", before, {"level_hist": "xla"},
                      must_see=("level_hist",))
    say(f"  {n} rows {sz.rounds}r: routed auc {auc_r:.4f} ({t_r:.2f}s)  "
        f"level_hist=xla auc {auc_x:.4f} ({t_x:.2f}s)")
    check(abs(auc_r - auc_x) <= 0.005,
          f"routed AUC {auc_r:.4f} vs XLA-reference AUC {auc_x:.4f}")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    global _TAG
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny CPU rehearsal, Pallas in interpret mode; "
                         "requires JAX_PLATFORMS=cpu from the caller")
    ap.add_argument("--stages", default=",".join(STAGES),
                    help=f"comma-separated subset of {STAGES}")
    args = ap.parse_args(argv)
    stages = [s for s in args.stages.split(",") if s]
    unknown = [s for s in stages if s not in STAGES]
    if unknown:
        ap.error(f"unknown stage(s) {unknown}")
    if args.rehearse:
        if os.environ.get("JAX_PLATFORMS") != "cpu":
            print("chip_smoke: --rehearse requires JAX_PLATFORMS=cpu set by "
                  "the caller", file=sys.stderr)
            return 2
        _TAG = "[CPU REHEARSAL - not a chip result] "

    t_start = time.perf_counter()
    import importlib.metadata as md

    import jax
    import jaxlib

    import xgboost_tpu as xgb
    from xgboost_tpu.config import compile_cache_dir, enable_compile_cache
    from xgboost_tpu.tree import hist_kernel as hk

    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = "not installed"
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    say(f"jax {jax.__version__}  jaxlib {jaxlib.__version__}  "
        f"libtpu {libtpu}")
    say(f"default_backend={jax.default_backend()}  "
        f"platform={dev.platform}  device_kind={dev.device_kind}  "
        f"device_count={device['count']}")
    if not args.rehearse and dev.platform != "tpu":
        print(f"chip_smoke: JAX found platform {dev.platform!r} "
              f"({dev.device_kind}), not a TPU; this script has no path "
              "that runs off the chip (see --rehearse)", file=sys.stderr)
        return 2
    say(f"compile cache: {enable_compile_cache() or 'off (CPU backend)'}  "
        f"(configured dir {compile_cache_dir()})")

    if args.rehearse:
        from xgboost_tpu import predictor

        # the CPU stand-ins for the chip: kernel bodies run interpreted,
        # the pallas routes are forced on, and the hoist budget comes
        # from the environment (no memory_stats off the chip)
        hk._INTERPRET = True
        hk.use_pallas = lambda: True
        predictor._INTERPRET = True
        os.environ.setdefault("XGBTPU_HOIST_BUDGET_MB", "64")
        sz = TINY
        # a CPU prefers its native data plane and native walker: the walk
        # is pinned where the chip would route it to pallas by itself
        data_plane, loaded_walk, walk_pin = "native", "native", \
            "predict_walk=pallas"
    else:
        sz = FULL
        data_plane, loaded_walk, walk_pin = "xla", "xla", ""
    check(hk.use_pallas(), "use_pallas() is false on this backend")
    check(hk._INTERPRET is bool(args.rehearse),
          "_INTERPRET must be off on the chip")
    _hook_warnings()

    # The expected route of each op on this path — stated, not discovered.
    routes = {"level_hist": "pallas", "level_partition": "pallas",
              "sibling_sub": "on", "onehot_build": "pallas",
              "leaf_delta": "pallas", "predict_walk": "pallas",
              "sketch_cuts": data_plane, "bin_matrix": data_plane}
    # (a model loaded from its file is served by ``loaded_walk`` instead:
    # stage_serve says why)

    t0 = time.perf_counter()
    X, y = anchor_data(sz.rows, sz.cols, SEED)
    say(f"anchor data {sz.rows}x{sz.cols} (seed {SEED}): "
        f"{time.perf_counter() - t0:.2f}s")

    served = None
    for stage in STAGES:
        if stage not in stages:
            say(f"[{stage}] not selected")
            continue
        t0 = time.perf_counter()
        say(f"[{stage}]")
        if stage == "kernels":
            stage_kernels(sz, X, routes)
        elif stage == "wide":
            stage_wide(sz, routes)
        elif stage == "train":
            with _pinned(walk_pin):
                stage_train(sz, xgb, X, y, routes, args.rehearse)
        elif stage == "predict":
            with _pinned(walk_pin):
                served = stage_predict(sz, xgb, X, y, routes,
                                       args.rehearse)
        elif stage == "serve":
            check(served is not None, "serve needs the predict stage")
            stage_serve(sz, *served, routes, loaded_walk, walk_pin)
            served = None
        elif stage == "four_chips":
            if len(jax.devices()) < 4:
                say(f"  {len(jax.devices())} device(s): the four-chip "
                    "stage needs four; stood down")
                continue
            if args.rehearse:
                # the interpreter cannot replay a kernel under
                # shard_map's vma check (tests/test_distributed.py):
                # rehearse placement and the oracle on the XLA route
                hk.use_pallas = lambda: False
                stage_four_chips(sz, xgb, X, y, {})
                hk.use_pallas = lambda: True
            else:
                stage_four_chips(sz, xgb, X, y, routes)
        elif stage == "reference":
            stage_reference(sz, xgb, X, y)
        _check_health(stage)
        gc.collect()
        say(f"[{stage}] passed in {time.perf_counter() - t0:.2f}s")

    from xgboost_tpu.analysis.retrace import retrace_counts

    say("recompiles_total: " + ", ".join(
        f"{k}={v}" for k, v in sorted(retrace_counts().items())))
    stats = dev.memory_stats() or {}
    say(f"memory_stats: bytes_limit={stats.get('bytes_limit')} "
        f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    from xgboost_tpu import native

    say(f"native libraries loaded: {list(native.loaded_libs()) or 'none'}")
    say(f"total {time.perf_counter() - t_start:.1f}s")
    result = {"ok": True, "device": device}
    if args.rehearse:
        result["rehearsal"] = True
    if set(stages) != set(STAGES):
        result["stages"] = stages
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
