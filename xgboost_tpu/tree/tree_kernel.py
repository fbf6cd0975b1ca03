"""Whole-tree native grow kernel wrappers (the ``tree_grow`` dispatch op).

``native/tree_build.cpp`` runs the ENTIRE depth loop of one boosting round
in a single XLA FFI custom call — per-level partition, histogram build
(with sibling subtraction), split eval and heap update — returning the
finalized heap arrays ``_finalize_jit`` consumes plus the leaf-level row
positions. The in-core CPU round drops from ~2 dispatches per level
(``fused_level`` + ``_level_update_jit``) to ONE host round-trip per round.

Three FFI entries are registered together (they share the C++ core loops,
so their histograms are bit-identical by construction):

* ``xgbtpu_tree_grow`` — the whole-tree kernel (``tree_grow_native``).
* ``xgbtpu_hb_level_sub`` — ONE level of the same partition + sibling-
  subtraction machinery (``fused_level_sub_native``): one level of the
  round replayed on its own, bit-identical to the fused kernel's output;
  ``tests/test_tree_grow.py`` holds the subtraction core to it.
* ``xgbtpu_hb_level_quant`` — ONE level of the quantized-gradient engine
  (``fused_level_quant_native``, ISSUE 19): the same replay for a
  round run with ``hist_acc=quant``, carrying the previous level's
  int64 histogram across calls as packed int32 word pairs (x64 stays
  off; an f32 carry would drop bits past 24-bit sums).

Route selection lives in the dispatch registry (``dispatch/ops.py``, ops
``tree_grow`` / ``sibling_sub`` / ``hist_acc``); the
``XGBTPU_SIBLING_SUB=0`` kill switch maps to a ``sibling_sub=off`` pin
there, and pinning BOTH ``sibling_sub=off`` and ``hist_acc=float`` makes
the kernel bit-identical to the per-level native path (see
tree_build.cpp's contract comment).
"""

from __future__ import annotations

import threading

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "tree_grow_native", "fused_level_sub_native",
    "fused_level_quant_native", "tree_ffi_ready",
]

_ffi_lock = threading.Lock()
_ffi_state = {"registered": None}  # None = not tried, True/False = result


def tree_ffi_ready() -> bool:
    """Build/load ``libtreebuild.so`` and register its FFI handlers with
    XLA (once per process). The ``tree_grow`` registry impl's availability
    probe. False when the library is unavailable (no toolchain, failed
    build, canary refusal); a JAX API error propagates."""
    with _ffi_lock:
        if _ffi_state["registered"] is None:
            from ..native import get_tree_lib

            lib = get_tree_lib()
            if lib is not None:
                jax.ffi.register_ffi_target(
                    "xgbtpu_tree_grow", jax.ffi.pycapsule(lib.XgbtpuTreeGrow),
                    platform="cpu")
                jax.ffi.register_ffi_target(
                    "xgbtpu_hb_level_sub",
                    jax.ffi.pycapsule(lib.XgbtpuHbLevelSub), platform="cpu")
                jax.ffi.register_ffi_target(
                    "xgbtpu_hb_level_quant",
                    jax.ffi.pycapsule(lib.XgbtpuHbLevelQuant), platform="cpu")
            _ffi_state["registered"] = lib is not None
        return _ffi_state["registered"]


def tree_grow_native(bins, gh, cut_values, tree_mask, G0, H0, *,
                     max_depth: int, B: int, sibling_sub: bool,
                     hist_acc: str, split):
    """One boosting round's depth loop as a single custom call. ``gh`` is
    the grower's ``[2, n]`` (rows on the lanes); the C ABI keeps the rows
    major, so it goes in transposed and ``pos`` comes back reshaped.

    Returns ``(pos, is_split, feature, split_bin, split_cond, default_left,
    node_g, node_h, node_w, loss_chg)`` — ``pos`` [1, n] i32 already routed
    into the LEAF level (the driver's final ``partition_apply`` is folded
    in), the rest heap arrays of ``max_nodes = 2^(max_depth+1) - 1``
    matching ``_level_update``'s state contract bit-for-bit (sub off +
    hist_acc float). ``hist_acc`` selects the histogram core:
    ``"quant"`` runs the fixed-point integer engine (per-node row lists,
    packed int32 lanes, int64 merge — thread-count invariant by
    construction), ``"float"`` the r17 f32 core (the bit-identity kill
    switch). Scalar split params travel as f32 attributes — the same
    f64 -> f32 rounding XLA applies to Python float constants at trace
    time."""
    from ..native import boundary

    n, F = bins.shape
    max_nodes = (1 << (max_depth + 1)) - 1
    mn = (max_nodes,)
    pos, *heap = boundary.ffi_call(
        "xgbtpu_tree_grow",
        (jax.ShapeDtypeStruct((n, 1), jnp.int32),
         jax.ShapeDtypeStruct(mn, jnp.bool_),     # is_split
         jax.ShapeDtypeStruct(mn, jnp.int32),     # feature
         jax.ShapeDtypeStruct(mn, jnp.int32),     # split_bin
         jax.ShapeDtypeStruct(mn, jnp.float32),   # split_cond
         jax.ShapeDtypeStruct(mn, jnp.bool_),     # default_left
         jax.ShapeDtypeStruct(mn, jnp.float32),   # node_g
         jax.ShapeDtypeStruct(mn, jnp.float32),   # node_h
         jax.ShapeDtypeStruct(mn, jnp.float32),   # node_w
         jax.ShapeDtypeStruct(mn, jnp.float32)),  # loss_chg
        bins, gh.T, cut_values, tree_mask.astype(jnp.int32),
        G0.astype(jnp.float32), H0.astype(jnp.float32),
        max_depth=int(max_depth), B=int(B),
        sibling_sub=int(bool(sibling_sub)),
        hist_acc=int(hist_acc == "quant"),
        reg_lambda=np.float32(split.reg_lambda),
        reg_alpha=np.float32(split.reg_alpha),
        max_delta_step=np.float32(split.max_delta_step),
        min_child_weight=np.float32(split.min_child_weight))
    return (pos.reshape(1, n), *heap)


def fused_level_sub_native(bins, pos, gh, ptab, prev_hist, *, K: int,
                           Kp: int, B: int, d: int):
    """Same contract as ``fused_level_native`` — ``pos`` [1, n] and ``gh``
    [2, n] in, (new pos [1, n] i32, hist [F, 2K, B] f32) out, rows-major
    only across the C ABI — but building only the smaller child of each sibling
    pair and deriving the other as parent − child from ``prev_hist`` (the
    previous level's [F, 2Kp, B]). Only valid at ``d >= 1``. This is one
    level of the whole-tree kernel with subtraction on, replayed alone:
    it shares tree_build.cpp's core loops, so its histogram matches the
    in-kernel one bit-for-bit (the tests' reference for that core)."""
    from ..native import boundary

    n, F = bins.shape
    prev_offset = jnp.int32((1 << (d - 1)) - 1)
    offset = jnp.int32((1 << d) - 1)
    pos_new, hist = boundary.ffi_call(
        "xgbtpu_hb_level_sub",
        (jax.ShapeDtypeStruct((n, 1), jnp.int32),
         jax.ShapeDtypeStruct((F, 2 * K, B), jnp.float32)),
        bins, pos.reshape(n, 1), gh.T, ptab, prev_hist, prev_offset, offset,
        K=K, Kp=Kp, B=B)
    return pos_new.reshape(1, n), hist


def fused_level_quant_native(bins, pos, gh, ptab, prev_hist_q, *, K: int,
                             Kp: int, B: int, d: int, sibling_sub: bool):
    """ONE level of the quantized-gradient histogram engine (hist_acc =
    quant), replayed alone: quantiser recomputed from the full
    ``gh`` (identical to the whole-tree kernel's per-round computation),
    partition, per-node row lists, packed-integer accumulation and (with
    ``sibling_sub``) EXACT integer sibling derivation from
    ``prev_hist_q``. ``pos`` [1, n] and ``gh`` [2, n] as everywhere above
    the C ABI. Returns ``(new pos [1, n] i32, hist_q [F, 2K, B, 2]
    i32, hist_f [F, 2K, B] f32)`` — ``hist_q`` is the level's int64
    histogram as packed little-endian int32 word pairs (carried between
    levels so no f32 rounding ever touches the running sums; jax x64
    stays off), ``hist_f`` the dequantized view ``_level_update_jit``
    consumes. At the root pass ``Kp=0`` with an empty ``prev_hist_q``
    ([F, 0, B, 2]); partition and derive are skipped there."""
    from ..native import boundary

    n, F = bins.shape
    prev_offset = jnp.int32((1 << max(d - 1, 0)) - 1)
    offset = jnp.int32((1 << d) - 1)
    pos_new, hist_q, hist_f = boundary.ffi_call(
        "xgbtpu_hb_level_quant",
        (jax.ShapeDtypeStruct((n, 1), jnp.int32),
         jax.ShapeDtypeStruct((F, 2 * K, B, 2), jnp.int32),
         jax.ShapeDtypeStruct((F, 2 * K, B), jnp.float32)),
        bins, pos.reshape(n, 1), gh.T, ptab, prev_hist_q, prev_offset,
        offset, K=K, Kp=Kp, B=B, sibling_sub=int(bool(sibling_sub)))
    return pos_new.reshape(1, n), hist_q, hist_f
