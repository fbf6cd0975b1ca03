"""Load-time canary: prove each native ``.so`` in a forked subprocess
before its first in-process use (ISSUE 20 tentpole, part a).

PRs 13/15/17/19 moved the whole hot path into in-process C++ kernels, so
one bad library — a stale build, a miscompiled ``-march=native`` binary
on a new box, an OOB write under a fresh shape — used to take the
trainer down with a raw SIGSEGV. The reference never hard-requires an
impl (``gpu_hist`` unavailable falls back to ``hist``); this module is
the native half of that posture: a library that cannot survive a tiny
golden workload in a SACRIFICIAL child process never gets dlopened into
the trainer at all, and the per-library degrade capability
(``native_tree``, ``native_hist``, ``native_sketch``,
``native_serving`` — ``native/boundary.py``) routes dispatch onto the
XLA/per-level impls instead.

Protocol, per (library, build):

1. **Symbol refusal** (the NB604 ``nm -D`` probe promoted from lint time
   to load time): a library missing any registered handler symbol is
   refused outright — no subprocess, verdict ``refused``.
2. **Verdict cache**: ``<so>.canary.json`` records (host, mtime, size,
   sha256, verdict). Warm startup is ONE stat — host+mtime+size match
   trusts the cached verdict; an mtime-only change re-hashes and a
   matching sha256 refreshes the entry without re-running. Only a
   genuinely new build — or a verdict proven on another machine
   (``native.host_key``: the checkout travels with its ignored build
   products) — pays the subprocess.
3. **Golden run**: ``python -m xgboost_tpu.native.canary <lib> <so>``
   executes a tiny grow / hist+partition / sketch+bin / walk on
   count-valued inputs (integer-valued f32 — sums exact regardless of
   accumulation order, so the expected output bytes are knowable in
   numpy) against THIS ``.so``, registered under ``xgbtpu_canary_*``
   target names so the child never touches the production loaders. Exit
   0 = pass; exit 3 = output mismatch; a signal death = crash; a parent
   deadline (``XGBTPU_CANARY_TIMEOUT``, default 300 s) = timeout.
4. **Verdict**: anything but ``healthy`` degrades the library's
   capability for the process lifetime, counts
   ``native_faults_total{lib,kind}`` and drops the
   ``native_canary_state{lib}`` gauge to -1. ``healthy`` sets it to 1.

``XGBTPU_NATIVE_CANARY=0`` skips the whole protocol (emergency hatch +
the child's own recursion guard). The ``native_canary`` chaos site fires
INSIDE the child: ``crash`` aborts it (the SIGSEGV-equivalent the
acceptance criterion injects), ``timeout`` parks it, ``corrupt`` flips
the computed result so the parent sees a mismatch. The child also fires
``native_dispatch`` once before its golden run — a canary run IS a
native dispatch, so a ``native_dispatch:crash:1`` schedule dies in the
subprocess, never in the trainer.
"""

from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys
import time
from typing import Dict, Optional, Tuple

from . import boundary, host_key

_ENV_SKIP = "XGBTPU_NATIVE_CANARY"
_ENV_TIMEOUT = "XGBTPU_CANARY_TIMEOUT"

HEALTHY = "healthy"
REFUSED = "refused"
CRASH = "crash"
TIMEOUT = "timeout"
MISMATCH = "mismatch"
ERROR = "error"

#: lib name -> the handler symbols the loaders register (the refusal
#: set); single source of truth shared with the nm probe
LIB_SYMBOLS: Dict[str, Tuple[str, ...]] = {
    "tree_build": ("XgbtpuTreeGrow", "XgbtpuHbLevelSub",
                   "XgbtpuHbLevelQuant"),
    "hist_build": ("XgbtpuHbLevel", "XgbtpuHbPartition"),
    "sketch_bin": ("XgbtpuSketchCuts", "XgbtpuBinMatrixU8",
                   "XgbtpuBinMatrixU16"),
    "serving_walk": ("sv_predict_dense", "sv_predict_csr"),
}


def enabled() -> bool:
    return os.environ.get(_ENV_SKIP, "1") != "0"


def _timeout_s() -> float:
    try:
        return float(os.environ.get(_ENV_TIMEOUT, "300"))
    except ValueError:
        return 300.0


def _cache_path(so_path: str) -> str:
    return so_path + ".canary.json"


def _sha256(path: str) -> Optional[str]:
    try:
        h = hashlib.sha256()
        with open(path, "rb") as f:
            for chunk in iter(lambda: f.read(1 << 20), b""):
                h.update(chunk)
        return h.hexdigest()
    except OSError:
        return None


def _read_cache(so_path: str) -> Optional[dict]:
    try:
        with open(_cache_path(so_path), encoding="utf-8") as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def _write_cache(so_path: str, entry: dict) -> None:
    tmp = _cache_path(so_path) + ".tmp"
    try:
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(entry, f)
        os.replace(tmp, _cache_path(so_path))
    except OSError:
        pass  # an unwritable cache just means re-verifying next process


def cached_verdict(so_path: str) -> Optional[Tuple[str, str]]:
    """(verdict, detail) when the cache entry still describes this build,
    else None. Warm path: one stat (mtime+size match). An mtime-only
    drift re-hashes; a matching sha256 refreshes the entry in place."""
    entry = _read_cache(so_path)
    if not entry or entry.get("host") != host_key():
        return None
    try:
        st = os.stat(so_path)
    except OSError:
        return None
    if entry.get("size") != st.st_size:
        return None
    if entry.get("mtime") == st.st_mtime:
        return entry.get("verdict", ""), entry.get("detail", "")
    sha = _sha256(so_path)
    if sha is not None and sha == entry.get("sha256"):
        entry["mtime"] = st.st_mtime
        _write_cache(so_path, entry)
        return entry.get("verdict", ""), entry.get("detail", "")
    return None


def nm_symbols(so_path: str) -> Optional[set]:
    """Dynamic symbol table per ``nm -D``, or None when nm is unavailable
    / the file is unreadable (the probe stays silent — same posture as
    the lint-time NB604 probe it was promoted from)."""
    try:
        out = subprocess.run(
            ["nm", "-D", so_path], capture_output=True, timeout=30,
            check=True).stdout.decode(errors="replace")
        return {ln.split()[-1] for ln in out.splitlines() if ln.split()}
    except Exception:
        return None


def missing_symbols(lib: str, so_path: str) -> Tuple[str, ...]:
    syms = nm_symbols(so_path)
    if syms is None:
        return ()
    return tuple(s for s in LIB_SYMBOLS.get(lib, ()) if s not in syms)


def _gauge(lib: str, value: int) -> None:
    from ..observability.metrics import REGISTRY

    REGISTRY.gauge(
        "native_canary_state",
        "Load-time canary verdict per native library: "
        "1 passed, 0 unverified, -1 failed",
    ).labels(lib=lib).set(value)


def run_subprocess(lib: str, so_path: str) -> Tuple[str, str]:
    """One golden run of ``so_path`` in a sacrificial child. Returns
    (verdict, detail)."""
    repo_root = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env[_ENV_SKIP] = "0"  # the child must never recurse into proving
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    cmd = [sys.executable, "-m", "xgboost_tpu.native.canary", lib, so_path]
    t0 = time.monotonic()
    try:
        proc = subprocess.run(cmd, capture_output=True, cwd=repo_root,
                              timeout=_timeout_s(), env=env)
    except subprocess.TimeoutExpired:
        return TIMEOUT, f"no verdict after {_timeout_s():.0f}s"
    except Exception as e:  # missing interpreter etc.: inconclusive
        return ERROR, f"{type(e).__name__}: {e}"
    dt = time.monotonic() - t0
    tail = proc.stderr.decode(errors="replace")[-500:].strip()
    if proc.returncode == 0:
        return HEALTHY, f"golden run passed in {dt:.1f}s"
    if proc.returncode < 0:  # killed by signal: the contained SIGSEGV
        return CRASH, f"child died with signal {-proc.returncode}: {tail}"
    if proc.returncode == 3:
        return MISMATCH, tail or "golden output mismatch"
    return ERROR, f"child exit {proc.returncode}: {tail}"


def prove(lib: str, so_path: str) -> bool:
    """The loaders' gate: True only for a library whose current build is
    proven (or the canary is switched off). Every failure path degrades
    the library's capability and counts ``native_faults_total`` — the
    caller just returns None and dispatch re-routes."""
    if not enabled():
        return True
    if lib not in LIB_SYMBOLS:
        return True  # non-canaried library (fastparse/pagecache/c_api)
    _gauge(lib, 0)
    missing = missing_symbols(lib, so_path)
    if missing:
        verdict, detail = REFUSED, f"symbols missing: {missing}"
    else:
        cached = cached_verdict(so_path)
        if cached is not None:
            verdict, detail = cached
            detail = f"cached: {detail}"
        else:
            verdict, detail = run_subprocess(lib, so_path)
            st = None
            try:
                st = os.stat(so_path)
            except OSError:
                pass
            if st is not None and verdict != ERROR:
                # ERROR verdicts (no interpreter, spawn failure) describe
                # the HOST, not the build — never cache them
                _write_cache(so_path, {
                    "lib": lib, "host": host_key(),
                    "mtime": st.st_mtime, "size": st.st_size,
                    "sha256": _sha256(so_path), "verdict": verdict,
                    "detail": detail})
    if verdict == HEALTHY:
        _gauge(lib, 1)
        return True
    _gauge(lib, -1)
    boundary.record_native_fault(lib, verdict)
    boundary.degrade_lib(lib, kind_hint=verdict, detail=detail,
                         for_process=True)
    from ..utils import console_logger

    console_logger.warning(
        f"native canary refused {lib!r} ({so_path}): {verdict} — {detail}; "
        f"dispatch falls back to the XLA/per-level route")
    return False


# ---------------------------------------------------------------------------
# the child driver: golden checks against ONE .so, no production loaders
# ---------------------------------------------------------------------------


def _golden_serving(so_path: str, corrupt: bool) -> Optional[str]:
    import ctypes

    import numpy as np

    lib = ctypes.CDLL(so_path)
    c = ctypes
    lib.sv_predict_dense.argtypes = [
        c.c_void_p, c.c_int64, c.c_int64,
        c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p,
        c.c_void_p, c.c_void_p, c.c_int64, c.c_int64,
        c.c_void_p, c.c_void_p, c.c_int64,
    ]
    lib.sv_predict_dense.restype = c.c_int
    T, N, K, n, F = 2, 3, 1, 4, 1
    left = np.array([[1, -1, -1]] * T, np.int32)
    right = np.array([[2, -1, -1]] * T, np.int32)
    feature = np.zeros((T, N), np.int32)
    cond = np.array([[0.5, 1.0, 2.0], [0.5, 10.0, 20.0]], np.float32)
    default_left = np.array([[1, 0, 0]] * T, np.uint8)
    tree_group = np.zeros((T,), np.int32)
    tw = np.ones((T,), np.float32)
    X = np.array([[0.0], [1.0], [np.nan], [0.3]], np.float32)
    base = np.zeros((n, K), np.float32)
    out = np.empty((n, K), np.float32)

    def p(a):
        return a.ctypes.data

    rc = lib.sv_predict_dense(p(X), n, F, p(left), p(right), p(feature),
                              p(cond), p(default_left), p(tree_group),
                              p(tw), T, N, p(base), p(out), K)
    if rc != 0:
        return f"sv_predict_dense rc={rc}"
    # integer leaf values: the double accumulation is exact
    want = np.array([[11.0], [22.0], [11.0], [11.0]], np.float32)
    if corrupt:
        out = out + 1.0
    if out.tobytes() != want.tobytes():
        return f"walk margins {out.ravel().tolist()} != " \
               f"{want.ravel().tolist()}"
    return None


def _golden_hist(so_path: str, corrupt: bool) -> Optional[str]:
    import ctypes

    import numpy as np
    from jax import ffi as jffi

    lib = ctypes.CDLL(so_path)
    jffi.register_ffi_target(
        "xgbtpu_canary_hb_level", jffi.pycapsule(lib.XgbtpuHbLevel),
        platform="cpu")
    jffi.register_ffi_target(
        "xgbtpu_canary_hb_partition", jffi.pycapsule(lib.XgbtpuHbPartition),
        platform="cpu")
    import jax
    import jax.numpy as jnp

    n, F, B, K = 8, 2, 4, 1
    bins = np.array([[0, 1], [1, 2], [2, 3], [3, 0], [0, 2], [1, 3],
                     [2, 0], [4, 4]]).astype(np.uint8)
    g = np.array([1, -2, 3, -1, 2, 1, -3, 5], np.float32)
    h = np.array([1, 2, 1, 3, 2, 1, 2, 1], np.float32)
    gh = np.stack([g, h], axis=-1).astype(np.float32)
    pos = np.zeros((n, 1), np.int32)
    ptab = np.zeros((1, 4), np.float32)
    zero = np.zeros((), np.int32)
    pos_out, hist = boundary.ffi_call(
        "xgbtpu_canary_hb_level",
        (jax.ShapeDtypeStruct((n, 1), jnp.int32),
         jax.ShapeDtypeStruct((F, 2 * K, B), jnp.float32)),
        bins, pos, gh, ptab, zero, zero, K=K, Kp=0, B=B)
    want = np.zeros((F, 2 * K, B), np.float32)
    for i in range(n):  # count-valued g/h: sums exact in any order
        for f in range(F):
            bv = int(bins[i, f])
            if bv >= B:
                continue
            want[f, 0, bv] += g[i]
            want[f, K, bv] += h[i]
    got = np.asarray(hist)
    if corrupt:
        got = got + 1.0
    if got.tobytes() != want.tobytes():
        return "level histogram bytes diverged from the numpy reference"
    if np.asarray(pos_out).tobytes() != pos.tobytes():
        return "root-level pos_out mutated"

    ptab1 = np.array([[1.0, 0.0, 1.0, 1.0]], np.float32)  # split f0 @ bin 1
    pos2 = boundary.ffi_call(
        "xgbtpu_canary_hb_partition",
        jax.ShapeDtypeStruct((n, 1), jnp.int32),
        bins, pos, ptab1, Kp=1, B=B, prev_offset=0)
    bv0 = bins[:, 0].astype(np.int64)
    go_left = np.where(bv0 >= B, True, bv0 <= 1)
    want_pos = np.where(go_left, 1, 2).astype(np.int32).reshape(n, 1)
    if np.asarray(pos2).tobytes() != want_pos.tobytes():
        return "partition routing diverged from the decision table"
    return None


def _golden_tree(so_path: str, corrupt: bool) -> Optional[str]:
    import ctypes

    import numpy as np
    from jax import ffi as jffi

    lib = ctypes.CDLL(so_path)
    jffi.register_ffi_target(
        "xgbtpu_canary_tree_grow", jffi.pycapsule(lib.XgbtpuTreeGrow),
        platform="cpu")
    import jax
    import jax.numpy as jnp

    n, F, B, max_depth = 8, 1, 4, 1
    max_nodes = (1 << (max_depth + 1)) - 1
    mn = (max_nodes,)
    bins = np.array([[0], [0], [1], [1], [2], [2], [3], [3]], np.uint8)
    g = np.array([2, 2, 1, 1, -1, -1, -2, -2], np.float32)
    h = np.ones((n,), np.float32)
    gh = np.stack([g, h], axis=-1).astype(np.float32)
    cut_values = np.array([[0.5, 1.5, 2.5, 3.5]], np.float32)
    tree_mask = np.ones((F,), np.int32)
    G0 = np.float32(g.sum())
    H0 = np.float32(h.sum())
    out = boundary.ffi_call(
        "xgbtpu_canary_tree_grow",
        (jax.ShapeDtypeStruct((n, 1), jnp.int32),
         jax.ShapeDtypeStruct(mn, jnp.bool_),
         jax.ShapeDtypeStruct(mn, jnp.int32),
         jax.ShapeDtypeStruct(mn, jnp.int32),
         jax.ShapeDtypeStruct(mn, jnp.float32),
         jax.ShapeDtypeStruct(mn, jnp.bool_),
         jax.ShapeDtypeStruct(mn, jnp.float32),
         jax.ShapeDtypeStruct(mn, jnp.float32),
         jax.ShapeDtypeStruct(mn, jnp.float32),
         jax.ShapeDtypeStruct(mn, jnp.float32)),
        bins, gh, cut_values, tree_mask, G0, H0,
        max_depth=max_depth, B=B, sibling_sub=1, hist_acc=1,
        reg_lambda=np.float32(1.0), reg_alpha=np.float32(0.0),
        max_delta_step=np.float32(0.0), min_child_weight=np.float32(1.0))
    pos, is_split, feature, split_bin, split_cond = \
        (np.asarray(a) for a in out[:5])
    node_g, node_h = np.asarray(out[6]), np.asarray(out[7])
    if corrupt:
        node_g = node_g + 1.0
    # analytically-known round: gains 7.62 / 14.4 / 7.62 -> split @ bin 1;
    # count-valued g/h make every node stat an exact integer sum
    if not (bool(is_split[0]) and int(feature[0]) == 0
            and int(split_bin[0]) == 1):
        return (f"root split diverged: is_split={bool(is_split[0])} "
                f"feature={int(feature[0])} bin={int(split_bin[0])}")
    if float(split_cond[0]) != 1.5:
        return f"split_cond {float(split_cond[0])} != cut_values[0,1]"
    want_g = np.array([0.0, 6.0, -6.0], np.float32)
    want_h = np.array([8.0, 4.0, 4.0], np.float32)
    if node_g.tobytes() != want_g.tobytes() \
            or node_h.tobytes() != want_h.tobytes():
        return (f"node stats diverged: g={node_g.tolist()} "
                f"h={node_h.tolist()}")
    want_pos = np.where(bins[:, 0] <= 1, 1, 2).astype(np.int32)
    if pos.ravel().tobytes() != want_pos.tobytes():
        return f"leaf positions diverged: {pos.ravel().tolist()}"
    return None


def _golden_sketch(so_path: str, corrupt: bool) -> Optional[str]:
    import ctypes

    import numpy as np
    from jax import ffi as jffi

    lib = ctypes.CDLL(so_path)
    jffi.register_ffi_target(
        "xgbtpu_canary_sketch_cuts", jffi.pycapsule(lib.XgbtpuSketchCuts),
        platform="cpu")
    jffi.register_ffi_target(
        "xgbtpu_canary_bin_u8", jffi.pycapsule(lib.XgbtpuBinMatrixU8),
        platform="cpu")
    import jax
    import jax.numpy as jnp

    n, F, B = 8, 1, 4
    X = np.arange(1, n + 1, dtype=np.float32).reshape(n, F)
    w = np.ones((n,), np.float32)
    cuts, min_vals = boundary.ffi_call(
        "xgbtpu_canary_sketch_cuts",
        (jax.ShapeDtypeStruct((F, B), jnp.float32),
         jax.ShapeDtypeStruct((F,), jnp.float32)),
        X, w, B=B)
    cuts, min_vals = np.asarray(cuts), np.asarray(min_vals)
    if not np.isfinite(cuts).all() or (np.diff(cuts, axis=1) < 0).any():
        return f"sketch cuts not finite/monotone: {cuts.tolist()}"
    if not (min_vals[0] <= X.min() and cuts[0, B - 1] > X.max()):
        return f"sketch envelope wrong: min={min_vals.tolist()} " \
               f"cuts={cuts.tolist()}"
    # binning against FIXED cuts is pure searchsorted: exact golden bytes
    Xb = X.copy()
    Xb[7, 0] = np.nan
    fixed = np.array([[2.5, 4.5, 6.5, 100.0]], np.float32)
    bins = boundary.ffi_call(
        "xgbtpu_canary_bin_u8",
        jax.ShapeDtypeStruct((n, F), jnp.uint8), Xb, fixed)
    want = np.array([0, 0, 1, 1, 2, 2, 3, B], np.uint8).reshape(n, F)
    got = np.asarray(bins)
    if corrupt:
        got = (got + 1).astype(np.uint8)
    if got.tobytes() != want.tobytes():
        return f"bin matrix diverged: {got.ravel().tolist()}"
    return None


_GOLDEN = {
    "tree_build": _golden_tree,
    "hist_build": _golden_hist,
    "sketch_bin": _golden_sketch,
    "serving_walk": _golden_serving,
}


def _child_main(argv) -> int:
    if len(argv) != 3 or argv[1] not in _GOLDEN:
        sys.stderr.write(f"usage: canary <{'|'.join(_GOLDEN)}> <so_path>\n")
        return 2
    lib, so_path = argv[1], argv[2]
    from ..resilience import chaos
    from ..resilience.chaos import ChaosError

    corrupt = False
    try:
        chaos.hit("native_canary")
        chaos.hit("native_dispatch")  # a canary run IS a native dispatch
    except ChaosError as e:
        mode = getattr(e, "chaos_mode", "")
        if mode == "crash":
            os.abort()  # the scripted SIGSEGV-equivalent, contained here
        elif mode == "timeout":
            time.sleep(max(_timeout_s() * 4, 3600))
        elif mode == "corrupt":
            corrupt = True
        else:
            raise  # plain-kind schedules present as a child error
    detail = _GOLDEN[lib](so_path, corrupt)
    if detail is not None:
        sys.stderr.write(detail + "\n")
        return 3
    return 0


if __name__ == "__main__":  # pragma: no cover (subprocess entry)
    sys.exit(_child_main(sys.argv))
