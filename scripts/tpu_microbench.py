"""TPU microbench: hoisted one-hot kernel vs in-kernel construction.

Measures (single v5e chip, headline 1M x 50 shapes):
- the chip's real free HBM (memory_stats) — the hoist budget source;
- per-level times for the construct kernel vs the hoisted streaming
  kernel at bin64, partial hoist at bin256 (docs/perf.md table);
- whole-chunk update_many throughput at bin64 with a first-vs-last-chunks
  decay check (review r3 weak #4);
- shard_map + Mosaic on a 1-device mesh (the distributed kernel path).

Run it alone, as the one process of a chip-tool call. Every section is
independently fault-isolated: an OOM or Mosaic reject logs and moves on —
so this is a builder's exploration script, NOT a measuring command (a
measuring command fails when a stage fails: chip_smoke.py, and the
benchmark of ROADMAP Queue 1 item 1). It has not been run on the chip;
its timings sync with block_until_ready.
"""
import sys
import time
import traceback

import numpy as np


def log(msg):
    print(f"[{time.strftime('%H:%M:%S')}] {msg}", flush=True)


log("importing jax...")
import jax
import jax.numpy as jnp

log(f"backend: {jax.default_backend()} devices: {jax.devices()}")

from xgboost_tpu.config import enable_compile_cache

log(f"compile cache: {enable_compile_cache()}")

from xgboost_tpu.tree.hist_kernel import (
    build_onehot, device_free_bytes, fused_level, hoist_plan, _hoist_tr, TR,
)

N = 1_000_000
F = 50
rng = np.random.RandomState(42)


def drain(x):
    jax.block_until_ready(x)


def section(name):
    """Decorator: run a section, catch + log everything."""
    def deco(fn):
        log(f"=== {name} ===")
        try:
            fn()
        except Exception as e:
            traceback.print_exc()
            log(f"SECTION FAILED ({name}): {type(e).__name__}: {e}")
    return deco


@section("device memory")
def _mem():
    free = device_free_bytes()
    log(f"device_free_bytes: "
        f"{'unavailable' if free is None else f'{free/1e9:.2f} GB'}")
    try:
        s = jax.devices()[0].memory_stats()
        log(f"memory_stats: { {k: v for k, v in sorted(s.items())} }")
    except Exception as e:
        log(f"memory_stats unavailable: {e}")


def time_loop(fn, reps, drain_out):
    out = fn()
    drain(drain_out(out))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
    drain(drain_out(out))
    return (time.perf_counter() - t0) / reps


def level_bench(B, d, K, Kp, fh, reps=20):
    """One level's time; fh = hoisted feature count (0 = construct)."""
    n_pad = -(-N // TR) * TR
    bins = rng.randint(0, B, size=(n_pad, F)).astype(np.int32)
    bins_j = jnp.asarray(bins)
    gh = jnp.asarray(rng.randn(n_pad, 2).astype(np.float32))
    prev_off = (1 << (d - 1)) - 1 if d > 0 else 0
    pos = jnp.asarray(rng.randint(prev_off, prev_off + max(Kp, 1),
                                  size=(n_pad, 1)).astype(np.int32))
    ptab = jnp.asarray(
        np.stack([np.ones(max(Kp, 1), np.float32),
                  rng.randint(0, F, max(Kp, 1)).astype(np.float32),
                  rng.randint(0, B, max(Kp, 1)).astype(np.float32),
                  np.ones(max(Kp, 1), np.float32)], axis=1))
    onehot = None
    if fh:
        t0 = time.perf_counter()
        onehot = build_onehot(bins_j[:, :fh], B=B)
        drain(onehot[:1, :1])
        log(f"  build_onehot B={B} fh={fh}: {time.perf_counter()-t0:.2f}s "
            f"({n_pad*fh*B/1e9:.1f} GB)")

    def run():
        return fused_level(bins_j, pos, gh, ptab, K=K, Kp=Kp, B=B, d=d,
                           pallas=True, onehot=onehot)

    dt = time_loop(run, reps, lambda o: o[1])
    tag = f"hoisted fh={fh}" if fh else "construct"
    log(f"  level d={d} K={K} B={B} {tag}: {dt*1e3:.2f} ms")
    del onehot
    return dt


@section("per-level microbench, 1M x 50, bin64")
def _levels64():
    B = 64
    n_pad = -(-N // TR) * TR
    level_bench(B, d=5, K=32, Kp=16, fh=0)
    fh = hoist_plan(n_pad, F, B, 6)
    log(f"hoist_plan(bin64) -> fh={fh}")
    if fh:
        level_bench(B, d=5, K=32, Kp=16, fh=fh)
        level_bench(B, d=0, K=1, Kp=0, fh=fh)


@section("per-level microbench, bin256 (reference-default path)")
def _levels256():
    B = 256
    n_pad = -(-N // TR) * TR
    level_bench(B, d=5, K=32, Kp=16, fh=0, reps=10)
    fh = hoist_plan(n_pad, F, B, 6)
    log(f"hoist_plan(bin256) -> fh={fh}")
    if fh:
        level_bench(B, d=5, K=32, Kp=16, fh=fh, reps=10)


@section("whole-tree + chunk throughput, bin64")
def _chunks():
    import xgboost_tpu as xgb

    X = rng.randn(N, F).astype(np.float32)
    w = rng.randn(F).astype(np.float32)
    y = ((X @ w) * 0.5 + rng.randn(N) > 0).astype(np.float32)
    dtrain = xgb.DMatrix(X, label=y)
    params = {"objective": "binary:logistic", "tree_method": "tpu_hist",
              "max_depth": 6, "max_bin": 64, "eta": 0.1}
    t0 = time.perf_counter()
    bst = xgb.Booster(params, [dtrain])
    bst.update_many(dtrain, 0, 25, chunk=25)
    entry = bst._caches.get(id(dtrain))
    drain(entry.margin[:1, :1])
    log(f"warmup chunk (bin+compile+25r): {time.perf_counter()-t0:.1f}s")

    times = []
    for c in range(1, 20):
        t0 = time.perf_counter()
        bst.update_many(dtrain, c * 25, 25, chunk=25)
        entry = bst._caches.get(id(dtrain))
        drain(entry.margin[:1, :1])
        dt = time.perf_counter() - t0
        times.append(dt)
        log(f"chunk {c}: 25 rounds in {dt:.2f}s ({25/dt:.1f} r/s)")
    log(f"chunks 1-5 mean: {np.mean(times[:5]):.2f}s; "
        f"chunks 15-19 mean: {np.mean(times[-5:]):.2f}s "
        f"(decay check: within 5%? "
        f"{abs(np.mean(times[-5:])-np.mean(times[:5]))/np.mean(times[:5])*100:.1f}%)")
    proj = np.mean(times) * 20
    log(f"projected 500r at bin64: {proj:.1f}s (vs_baseline {36.01/proj:.2f})")


@section("1-device mesh: shard_map + Mosaic validation")
def _mesh():
    import xgboost_tpu as xgb
    from xgboost_tpu.parallel.grow import distributed_grow_tree_fused
    from xgboost_tpu.parallel.mesh import make_mesh

    n_small = 1 << 18  # modest rows: validate Mosaic-under-shard_map only
    X = rng.randn(n_small, F).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    dtrain = xgb.DMatrix(X, label=y)
    params = {"objective": "binary:logistic", "tree_method": "tpu_hist",
              "max_depth": 6, "max_bin": 64, "eta": 0.1}
    bst = xgb.Booster(params, [dtrain])
    bst._configure()  # _gbm is created lazily
    mesh1 = make_mesh(1)
    cfg = bst._gbm._grow_params()
    binned2 = dtrain.get_binned(64, None)
    binsf, n_pad2 = binned2.fused_bins_mesh(mesh1)
    onehot = binned2.fused_onehot_mesh(mesh1, 6)
    log(f"mesh onehot: {None if onehot is None else onehot.shape}")
    g = jnp.asarray(rng.randn(n_pad2).astype(np.float32))
    h = jnp.abs(jnp.asarray(rng.randn(n_pad2).astype(np.float32)))
    cut_vals = jnp.asarray(binned2.cuts.values)
    key = jax.random.PRNGKey(0)
    t0 = time.perf_counter()
    tree = distributed_grow_tree_fused(
        mesh1, binsf, g, h, cut_vals, key,
        jnp.float32(0.1), jnp.float32(0.0), cfg, onehot=onehot)
    drain(tree.leaf_value[:1])
    log(f"mesh(1) shard_map + Mosaic kernel: OK "
        f"(compile+1 tree {time.perf_counter()-t0:.1f}s)")
    t0 = time.perf_counter()
    for _ in range(10):
        tree = distributed_grow_tree_fused(
            mesh1, binsf, g, h, cut_vals, key,
            jnp.float32(0.1), jnp.float32(0.0), cfg, onehot=onehot)
    drain(tree.leaf_value[:1])
    log(f"mesh(1) tree: {(time.perf_counter()-t0)/10*1e3:.1f} ms")


log("done")
