"""Native (C++) runtime components, loaded via ctypes.

The reference keeps its data loaders, allocators and runtime in C++
(dmlc-core parsers, src/common/io.cc); the TPU build does the same for the
host-side pieces that sit outside the XLA compute path. The shared library
is built on demand with g++ (no pybind11 in the image — plain C ABI).
"""

from __future__ import annotations

import ctypes
import functools
import os
import subprocess
import threading
from typing import Optional, Tuple

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "fastparse.cpp")
_LIB_PATH = os.path.join(_HERE, "libfastparse.so")
_PC_SRC = os.path.join(_HERE, "pagecache.cpp")
_PC_LIB = os.path.join(_HERE, "libpagecache.so")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False
_pc_lib: Optional[ctypes.CDLL] = None
_pc_tried = False


def _san_mode() -> Optional[str]:
    """Sanitizer lane (the reference's CMake ``USE_SANITIZER`` analog):
    ``XGBTPU_SAN=1`` (or ``=address``) builds every native library with
    ASan+UBSan into ``.san.so`` artifacts; ``XGBTPU_SAN=thread`` builds
    TSan ``.tsan.so`` variants instead, so the data-race lane can watch
    the OpenMP kernels and the threaded prefetcher/checkpoint writers.
    Separate artifact suffixes mean no lane ever clobbers (or reuses)
    production builds. A sanitized library only *loads* under a
    preloaded process (``LD_PRELOAD=libasan.so`` / ``libtsan.so``) —
    plain processes get the usual graceful None fallback. See
    ``tests/test_sanitizer.py`` and docs/static_analysis.md."""
    v = os.environ.get("XGBTPU_SAN", "")
    if v in ("1", "address"):
        return "address"
    if v == "thread":
        return "thread"
    return None


def _san_enabled() -> bool:
    return _san_mode() is not None


_SAN_FLAGS = (
    "-fsanitize=address,undefined", "-fno-sanitize-recover=all",
    "-fno-omit-frame-pointer", "-g", "-Wall", "-Wextra", "-Werror",
)

# TSan and ASan are mutually exclusive in one binary, so the thread lane
# is its own artifact. No -Werror here: the lane must instrument the FFI
# kernels, and the jaxlib FFI headers themselves trip -Wsign-compare —
# warning hygiene is the address lane's job.
_TSAN_FLAGS = (
    "-fsanitize=thread", "-fno-omit-frame-pointer", "-g",
)


def _lib_variant(lib_path: str) -> str:
    """The artifact path for the active lane (``.san.so`` under the
    address lane, ``.tsan.so`` under the thread lane). Single source of
    truth for builders AND loaders."""
    mode = _san_mode()
    if mode and lib_path.endswith(".so"):
        return lib_path[:-3] + (".tsan.so" if mode == "thread"
                                else ".san.so")
    return lib_path


def _find_san_runtime(name: str) -> Optional[str]:
    try:
        out = subprocess.run(
            ["g++", f"-print-file-name={name}"],
            capture_output=True, timeout=30, check=True,
        ).stdout.decode().strip()
    except Exception:
        return None
    return out if out and os.path.sep in out else None


def find_libasan() -> Optional[str]:
    """Path of the toolchain's libasan runtime (for ``LD_PRELOAD`` when
    running a sanitized library under an uninstrumented Python), or None
    when the toolchain can't say."""
    return _find_san_runtime("libasan.so")


def find_libtsan() -> Optional[str]:
    """Path of the toolchain's libtsan runtime, for preloading the
    thread lane the same way (``LD_PRELOAD=libtsan.so``)."""
    return _find_san_runtime("libtsan.so")


@functools.lru_cache(maxsize=1)
def host_key() -> str:
    """Fingerprint of the machine a ``-march=native`` build (and a canary
    verdict) is valid on: architecture plus the first CPU's model and
    feature flags. The checkout is copied between machines with its
    ignored build products in it, so neither an mtime nor a verdict proven
    elsewhere may be trusted across a change of this key."""
    import hashlib
    import platform

    parts = [platform.machine()]
    try:
        with open("/proc/cpuinfo", encoding="utf-8") as f:
            for line in f:
                if not line.strip():
                    break  # first processor block only
                if line.split(":")[0].strip() in (
                        "vendor_id", "model name", "flags", "Features"):
                    parts.append(line.strip())
    except OSError:
        parts.append(platform.processor())
    return hashlib.sha256("\n".join(parts).encode()).hexdigest()[:16]


def _build_key(src: str, flags: list) -> str:
    """What a built library is a function of: source bytes, compiler
    flags, and the host (``host_key``)."""
    import hashlib

    h = hashlib.sha256()
    with open(src, "rb") as f:
        h.update(f.read())
    h.update("\0".join(flags).encode())
    h.update(host_key().encode())
    return h.hexdigest()


def _compile(src: str, lib_path: str, extra: list, timeout: int = 120) -> bool:
    """Build ``lib_path`` from ``src`` when stale (single-sourced
    staleness + existence logic for all the on-demand libraries).
    True when a usable library exists afterwards. Stale means the
    ``<lib>.build.json`` stamp beside the library does not carry this
    (source, flags, host) key: a library that arrived with the checkout
    from another machine, or predates a source or flag change, is rebuilt
    here rather than trusted by mtime. Under a sanitizer lane the caller
    passes a ``.san.so``/``.tsan.so`` path (via ``_lib_variant``) and the
    lane's flags are appended here."""
    import json

    if not os.path.exists(src):
        return os.path.exists(lib_path)  # prebuilt-only deployment
    mode = _san_mode()
    if mode == "address":
        extra = list(extra) + list(_SAN_FLAGS)
    elif mode == "thread":
        extra = list(extra) + list(_TSAN_FLAGS)
    key = _build_key(src, extra)
    stamp = lib_path + ".build.json"
    if os.path.exists(lib_path):
        try:
            with open(stamp, encoding="utf-8") as f:
                if json.load(f).get("key") == key:
                    return True
        except (OSError, ValueError):
            pass
    cmd = ["g++", "-shared", "-fPIC", "-o", lib_path, src] + extra
    try:
        # ``native_load`` chaos site: a scripted fault here exercises the
        # graceful every-caller-falls-back-to-None contract of the
        # on-demand native builds (resilience tentpole)
        from ..resilience import chaos

        chaos.hit("native_load")
        subprocess.run(cmd, check=True, capture_output=True, timeout=timeout)
    except Exception:
        return False
    tmp = stamp + ".tmp"
    try:
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump({"key": key}, f)
        os.replace(tmp, stamp)
    except OSError:
        pass  # an unwritable stamp just means rebuilding next process
    return True


def _build_failed(lib_name: str, detail: str) -> None:
    """Account a native build/load failure (ISSUE 20 satellite): bumps
    ``native_build_failures_total{lib}`` and — for the canaried kernel
    libraries — degrades the library's capability for the process, so a
    box without a toolchain resolves every op to the XLA impls instead
    of raising (or re-probing) at call sites. Never raises: accounting
    must not break the graceful None contract of the loaders."""
    try:
        from . import boundary

        boundary.record_build_failure(lib_name, detail)
    except Exception:
        pass


def loaded_libs() -> tuple:
    """Names of the kernel libraries ALREADY dlopened into this process
    (memo reads only — never triggers a build). The containment layer
    uses this as ground truth for 'native code can be running': dispatch
    decisions are only recorded at trace time, so a jit-cache-reused
    program runs native kernels without leaving a fresh decision."""
    with _lock:
        out = []
        if _tb_lib is not None:
            out.append("tree_build")
        if _hb_lib is not None:
            out.append("hist_build")
        if _sb_lib is not None:
            out.append("sketch_bin")
        if _sv_lib is not None:
            out.append("serving_walk")
        return tuple(out)


def _prove(lib_name: str, lib_path: str) -> bool:
    """Load-time canary gate (ISSUE 20 tentpole): the library must pass
    its golden run in a forked subprocess (``canary.prove`` — cached per
    build) before this process dlopens it. A refused/crashed/mismatched
    build degrades the capability and the loader returns None."""
    from . import canary

    return canary.prove(lib_name, lib_path)


def get_pagecache_lib() -> Optional[ctypes.CDLL]:
    """Load (building on demand) the native page cache; None if unavailable
    (callers fall back to plain numpy file IO)."""
    global _pc_lib, _pc_tried
    with _lock:
        if _pc_lib is not None or _pc_tried:
            return _pc_lib
        _pc_tried = True
        lp = _lib_variant(_PC_LIB)
        if not _compile(_PC_SRC, lp,
                        ["-O3", "-std=c++17", "-pthread", "-ffp-contract=off"]):
            return None
        try:
            lib = ctypes.CDLL(lp)
        except OSError:
            return None
        lib.pc_write.argtypes = [ctypes.c_char_p, ctypes.c_void_p,
                                 ctypes.c_longlong]
        lib.pc_write.restype = ctypes.c_int
        lib.pc_open.argtypes = [ctypes.c_char_p, ctypes.c_longlong,
                                ctypes.POINTER(ctypes.c_longlong),
                                ctypes.c_int]
        lib.pc_open.restype = ctypes.c_void_p
        lib.pc_read.argtypes = [ctypes.c_void_p, ctypes.c_longlong,
                                ctypes.c_void_p]
        lib.pc_read.restype = ctypes.c_int
        lib.pc_close.argtypes = [ctypes.c_void_p]
        lib.pc_close.restype = None
        _pc_lib = lib
        return _pc_lib


def get_lib() -> Optional[ctypes.CDLL]:
    """Load (building if needed) the native parser; None if unavailable."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        lp = _lib_variant(_LIB_PATH)
        if not _compile(_SRC, lp,
                        ["-O3", "-march=native", "-ffp-contract=off"]):
            return None
        try:
            lib = ctypes.CDLL(lp)
        except OSError:
            return None
        lib.fp_libsvm_dims.argtypes = [
            ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int32),
        ]
        lib.fp_libsvm_dims.restype = ctypes.c_int
        lib.fp_libsvm_parse.argtypes = (
            [ctypes.c_char_p] + [ctypes.c_void_p] * 5 + [ctypes.c_int64] * 2
        )
        lib.fp_libsvm_parse.restype = ctypes.c_int
        lib.fp_csv_dims.argtypes = [
            ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64),
        ]
        lib.fp_csv_dims.restype = ctypes.c_int
        lib.fp_csv_parse.argtypes = [
            ctypes.c_char_p, ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
        ]
        lib.fp_csv_parse.restype = ctypes.c_int
        _lib = lib
        return _lib


def load_svmlight_native(path: str) -> Optional[Tuple[np.ndarray, np.ndarray, Optional[np.ndarray]]]:
    """Native libsvm load -> (X dense NaN-missing, y, qid|None); None if the
    native library is unavailable."""
    lib = get_lib()
    if lib is None:
        return None
    n_rows = ctypes.c_int64()
    n_entries = ctypes.c_int64()
    max_col = ctypes.c_int64()
    has_qid = ctypes.c_int32()
    if lib.fp_libsvm_dims(path.encode(), ctypes.byref(n_rows), ctypes.byref(n_entries),
                          ctypes.byref(max_col), ctypes.byref(has_qid)) != 0:
        return None
    n, e, mc = n_rows.value, n_entries.value, max_col.value
    rows = np.empty(e, np.int64)
    cols = np.empty(e, np.int32)
    vals = np.empty(e, np.float32)
    labels = np.empty(n, np.float32)
    qids = np.empty(n, np.int64) if has_qid.value else None
    rc = lib.fp_libsvm_parse(
        path.encode(),
        rows.ctypes.data_as(ctypes.c_void_p),
        cols.ctypes.data_as(ctypes.c_void_p),
        vals.ctypes.data_as(ctypes.c_void_p),
        labels.ctypes.data_as(ctypes.c_void_p),
        qids.ctypes.data_as(ctypes.c_void_p) if qids is not None else None,
        n, e,
    )
    if rc != 0:
        return None
    X = np.full((n, mc + 1 if mc >= 0 else 0), np.nan, np.float32)
    if e:
        X[rows, cols] = vals
    return X, labels, qids


def load_csv_native(path: str) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """Native CSV load (first column = label) -> (X, y); None if unavailable."""
    lib = get_lib()
    if lib is None:
        return None
    n_rows = ctypes.c_int64()
    n_cols = ctypes.c_int64()
    if lib.fp_csv_dims(path.encode(), ctypes.byref(n_rows), ctypes.byref(n_cols)) != 0:
        return None
    n, c = n_rows.value, n_cols.value
    out = np.empty((n, c), np.float32)
    if lib.fp_csv_parse(path.encode(), out.ctypes.data_as(ctypes.c_void_p), n, c) != 0:
        return None
    y = out[:, 0].copy()
    X = np.ascontiguousarray(out[:, 1:])
    return X, y


_SV_SRC = os.path.join(_HERE, "serving_walk.cpp")
_SV_LIB = os.path.join(_HERE, "libservingwalk.so")
_sv_lib: Optional[ctypes.CDLL] = None
_sv_tried = False


def get_serving_lib() -> Optional[ctypes.CDLL]:
    """Load (building on demand) the native serving forest walker
    (``serving_walk.cpp`` — the cpu_predictor.cc block-of-rows analog);
    None when unavailable (callers fall back to the XLA walk)."""
    global _sv_lib, _sv_tried
    with _lock:
        if _sv_lib is not None or _sv_tried:
            return _sv_lib
        _sv_tried = True
        lp = _lib_variant(_SV_LIB)
        sv_flags = ["-O3", "-march=native", "-ffp-contract=off"]
        ok = _compile(_SV_SRC, lp, sv_flags + ["-fopenmp"])
        if not ok:  # toolchains without OpenMP: single-threaded walker
            ok = _compile(_SV_SRC, lp, sv_flags)
        if not ok:
            _build_failed("serving_walk", "build failed")
            return None
        if not _prove("serving_walk", lp):
            return None
        try:
            lib = ctypes.CDLL(lp)
        except OSError as e:
            _build_failed("serving_walk", f"dlopen: {e}")
            return None
        c = ctypes
        lib.sv_predict_dense.argtypes = [
            c.c_void_p, c.c_int64, c.c_int64,  # X, n, F
            c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p,
            c.c_void_p, c.c_void_p, c.c_int64, c.c_int64,  # ...T, N
            c.c_void_p, c.c_void_p, c.c_int64,  # base, out, K
        ]
        lib.sv_predict_dense.restype = c.c_int
        lib.sv_predict_csr.argtypes = [
            c.c_void_p, c.c_void_p, c.c_void_p, c.c_int64, c.c_int64,
            c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p,
            c.c_void_p, c.c_void_p, c.c_int64, c.c_int64,
            c.c_void_p, c.c_void_p, c.c_int64,
        ]
        lib.sv_predict_csr.restype = c.c_int
        _sv_lib = lib
        return _sv_lib


def serving_lib_available() -> bool:
    """Availability probe for the kernel dispatch registry
    (``dispatch/ops.py``, op ``predict_walk`` impl ``native``): whether
    the SoA forest walker builds/loads on this host. First call pays the
    on-demand build; afterwards it is a memo read. (The ``level_hist``
    impl probes through ``tree.hist_kernel._ensure_ffi`` instead — load
    and XLA target registration are one step there.)"""
    return get_serving_lib() is not None


_HB_SRC = os.path.join(_HERE, "hist_build.cpp")
_HB_LIB = os.path.join(_HERE, "libhistbuild.so")
_hb_lib: Optional[ctypes.CDLL] = None
_hb_tried = False


def get_hist_lib() -> Optional[ctypes.CDLL]:
    """Load (building on demand) the native level-histogram + partition
    kernel (``hist_build.cpp`` — the GHistBuilder analog the CPU training
    fallback dispatches as an XLA FFI custom call; ``tree/hist_kernel.py``
    registers the exported ``XgbtpuHbLevel``/``XgbtpuHbPartition`` handler
    symbols). None when the toolchain is unavailable, the build fails or
    the canary refuses it (callers fall back to the XLA segment_sum
    path)."""
    global _hb_lib, _hb_tried
    with _lock:
        if _hb_lib is not None or _hb_tried:
            return _hb_lib
        import jax

        inc = jax.ffi.include_dir()  # a moved JAX API raises; not a build problem
        _hb_tried = True
        lp = _lib_variant(_HB_LIB)
        if not _compile(_HB_SRC, lp,
                        ["-O3", "-march=native", "-std=c++17",
                         "-ffp-contract=off", f"-I{inc}"]):
            _build_failed("hist_build", "build failed")
            return None
        if not _prove("hist_build", lp):
            return None
        try:
            _hb_lib = ctypes.CDLL(lp)
        except OSError as e:
            _build_failed("hist_build", f"dlopen: {e}")
            return None
        return _hb_lib


_TB_SRC = os.path.join(_HERE, "tree_build.cpp")
_TB_LIB = os.path.join(_HERE, "libtreebuild.so")
_tb_lib: Optional[ctypes.CDLL] = None
_tb_tried = False


def get_tree_lib() -> Optional[ctypes.CDLL]:
    """Load (building on demand) the whole-tree native grow kernel
    (``tree_build.cpp`` — one custom call per boosting round; the
    ``tree_grow`` dispatch op resolves to it on CPU and
    ``tree/tree_kernel.py`` registers the exported ``XgbtpuTreeGrow`` /
    ``XgbtpuHbLevelSub`` handler symbols as XLA FFI targets). Built with
    ``-ffp-contract=off`` — the split-eval port is bit-identical to the
    XLA ``_level_update`` only without FMA contraction — and with OpenMP
    when the toolchain has it (falls back to single-threaded). None when
    the toolchain is unavailable, the build fails or the canary refuses
    it (callers keep the per-level path)."""
    global _tb_lib, _tb_tried
    with _lock:
        if _tb_lib is not None or _tb_tried:
            return _tb_lib
        import jax

        inc = jax.ffi.include_dir()  # a moved JAX API raises; not a build problem
        _tb_tried = True
        lp = _lib_variant(_TB_LIB)
        flags = ["-O3", "-march=native", "-std=c++17",
                 "-ffp-contract=off", f"-I{inc}"]
        ok = _compile(_TB_SRC, lp, flags + ["-fopenmp"])
        if not ok:  # toolchains without OpenMP: single-threaded kernel
            ok = _compile(_TB_SRC, lp, flags)
        if not ok:
            _build_failed("tree_build", "build failed")
            return None
        if not _prove("tree_build", lp):
            return None
        try:
            _tb_lib = ctypes.CDLL(lp)
        except OSError as e:
            _build_failed("tree_build", f"dlopen: {e}")
            return None
        return _tb_lib


_SB_SRC = os.path.join(_HERE, "sketch_bin.cpp")
_SB_LIB = os.path.join(_HERE, "libsketchbin.so")
_sb_lib: Optional[ctypes.CDLL] = None
_sb_tried = False


def get_sketch_lib() -> Optional[ctypes.CDLL]:
    """Load (building on demand) the native quantile-sketch + binning
    kernel (``sketch_bin.cpp`` — the data-plane fast path the ``sketch_cuts``
    / ``bin_matrix`` dispatch ops resolve to on CPU; ``data/quantile.py``
    registers the exported ``XgbtpuSketchCuts``/``XgbtpuBinMatrixU8``/
    ``XgbtpuBinMatrixU16`` handler symbols as XLA FFI targets). None when
    the toolchain is unavailable, the build fails or the canary refuses
    it (callers fall back to the XLA sort/searchsorted path)."""
    global _sb_lib, _sb_tried
    with _lock:
        if _sb_lib is not None or _sb_tried:
            return _sb_lib
        import jax

        inc = jax.ffi.include_dir()  # a moved JAX API raises; not a build problem
        _sb_tried = True
        lp = _lib_variant(_SB_LIB)
        if not _compile(_SB_SRC, lp,
                        ["-O3", "-march=native", "-std=c++17",
                         "-ffp-contract=off", f"-I{inc}"]):
            _build_failed("sketch_bin", "build failed")
            return None
        if not _prove("sketch_bin", lp):
            return None
        try:
            _sb_lib = ctypes.CDLL(lp)
        except OSError as e:
            _build_failed("sketch_bin", f"dlopen: {e}")
            return None
        return _sb_lib


_CAPI_SRC = os.path.join(_HERE, "c_api.cpp")
_CAPI_LIB = os.path.join(_HERE, "libxgbtpu.so")
_capi_path: Optional[str] = None
_capi_tried = False


def build_capi() -> Optional[str]:
    """Build (if stale) and return the path of the embedded-interpreter C
    API library ``libxgbtpu.so`` (reference ABI: include/xgboost/c_api.h).
    None when the toolchain or Python embedding flags are unavailable.
    Returns the PATH rather than a loaded CDLL: C hosts dlopen it
    themselves, and the ctypes test loads it explicitly."""
    global _capi_path, _capi_tried
    with _lock:
        if _capi_path is not None or _capi_tried:
            return _capi_path
        _capi_tried = True
        import sysconfig

        repo_root = os.path.dirname(os.path.dirname(_HERE))
        paths = sysconfig.get_paths()
        site = paths.get("purelib", "")
        inc = paths["include"]
        libdir = sysconfig.get_config_var("LIBDIR") or ""
        pyver = sysconfig.get_config_var("LDVERSION") or \
            sysconfig.get_config_var("VERSION") or ""
        lp = _lib_variant(_CAPI_LIB)
        if not _compile(_CAPI_SRC, lp,
                        ["-O2", "-std=c++17", "-ffp-contract=off", f"-I{inc}",
                         f'-DXGBTPU_ROOT="{repo_root}"',
                         f'-DXGBTPU_SITE="{site}"',
                         f"-L{libdir}", f"-lpython{pyver}",
                         f"-Wl,-rpath,{libdir}", "-ldl", "-lm"],
                        timeout=180):
            return None
        _capi_path = lp if os.path.exists(lp) else None
        return _capi_path
