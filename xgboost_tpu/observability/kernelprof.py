"""Intra-round grow profiler: per-depth × per-op attribution on demand.

The flight recorder (PR 6) can say a round spent 95% of its wall in
``grow`` — and nothing more. This module answers the next question
(ROADMAP item 1: where does the grow dispatch itself go?) without
touching the production path: on **sampled rounds only**
(``XGBTPU_KERNEL_PROF=every=N`` or ``rounds=a,b,c``; off by default),
the in-core grower runs an instrumented mirror of the fused driver that
routes every kernel dispatch through the ``dispatch.invoke`` seam and
brackets it with a completion sync (``jax.block_until_ready``),
producing a per-round ``grow_detail`` record:

- per-depth × per-op wall time (``level_hist`` / ``level_update`` /
  ``level_partition`` / ``finalize`` / ``leaf_delta`` / ``prep``), with
  the resolved impl (pallas / XLA / native) attached from
  ``dispatch.last_decisions()`` — all impls covered uniformly because
  the bracket sits at the seam, not at any call site;
- a **host-blocked vs in-flight** split per bucket: time until the
  dispatch returned to the host (tracing + program launch) vs time until
  the result was actually ready;
- the **inter-dispatch gap** (host time between one op's completion and
  the next op's dispatch — the Python/driver overhead a fused program
  doesn't pay);
- ``host_syncs_total{site=op}`` — every deliberate completion sync,
  counted from the same seam. The RH204 lint statically walks the
  round-loop files and would flag these syncs there; they live HERE (and
  in ``dispatch/core.py``), outside its scope, which is the point: the
  production round loop stays statically sync-free, and profiled rounds
  opt in at one audited seam.

Sampled rounds stay **bit-identical** to unsampled ones: the mirror
reuses the exact shared level machinery (``fused_level`` /
``_level_update_jit`` / ``partition_apply`` / ``_finalize_jit`` /
``leaf_delta``) the fused program is built from — only sync points are
added, math untouched. This leans on the same cross-driver identity the
repo already pins (scanned ≡ unrolled, PR 13; paged ≡ streaming, PR 15)
and is pinned end-to-end by ``tests/test_kernelprof.py`` (model bytes
equal with profiling on vs off).

Single-dispatch rounds (ISSUE 17): when the production round runs the
whole-tree native kernel (``tree_grow`` resolves to ``native``), there
is exactly ONE dispatch to bracket — useless for attribution. The
mirror therefore replays the round per-level, and when sibling
subtraction is on it substitutes ``fused_level_sub_native`` at depth
>= 1 — the FFI entry that shares tree_build.cpp's partition + build +
subtract core loops — retaining the previous level's histogram between
calls, so the replayed histograms (and hence the whole round) match the
fused kernel's output bit-for-bit while every level still lands in its
own ``level_hist`` bucket. The record carries ``route`` and
``sibling_sub`` so a reader knows the numbers describe a per-level
replay of a one-dispatch round. On the Pallas level route the production
program subtracts siblings too (``grow_fused``, ISSUE 27); the mirror
does not grow a second implementation of that and builds every node,
which its report says: ``sibling_sub=off (mirror)``.

The record feeds the flight record as ``grow_detail`` (rendered by
``python -m xgboost_tpu grow-report``) and each bracket is emitted as a
``cat="grow"`` Chrome span, so the substages nest under the existing
``round`` span in the merged Perfetto trace and ``trace-report`` grows a
``grow`` category row for free.

Import discipline: this module imports ONLY stdlib at module scope —
``gbm/gbtree.py`` and ``training.py`` import it eagerly, and the tree /
dispatch / jax machinery must not load (or cycle) before first use.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

__all__ = [
    "should_sample", "arm", "active", "disarm",
    "grow_tree_fused_profiled", "format_grow_detail", "format_grow_diff",
    "main",
]

_ENV = "XGBTPU_KERNEL_PROF"

#: instrumented-driver name stamped into every record — a reader can
#: tell these numbers came from the unrolled host-driven mirror, not
#: from inside the production fused program
DRIVER = "instrumented-unrolled"


# ---------------------------------------------------------------------------
# sampling grammar: every=N | rounds=a,b,c
# ---------------------------------------------------------------------------


def _parse(spec: str) -> Tuple[str, Any]:
    kind, sep, val = spec.partition("=")
    if not sep:
        raise ValueError(spec)
    kind = kind.strip()
    if kind == "every":
        n = int(val)
        if n < 1:
            raise ValueError(spec)
        return ("every", n)
    if kind == "rounds":
        rounds = frozenset(int(x) for x in val.split(",") if x.strip())
        if not rounds or min(rounds) < 0:
            raise ValueError(spec)
        return ("rounds", rounds)
    raise ValueError(spec)


# plan memo, lock-guarded: keyed on the RAW env value so a monkeypatched
# spec re-parses and the steady state is one dict hit per round
_PLAN_LOCK = threading.Lock()
_PLAN_MEMO: Dict[str, Optional[Tuple[str, Any]]] = {}


def _plan() -> Optional[Tuple[str, Any]]:
    spec = os.environ.get(_ENV)
    if not spec:
        return None
    with _PLAN_LOCK:
        if spec in _PLAN_MEMO:
            return _PLAN_MEMO[spec]
    try:
        plan: Optional[Tuple[str, Any]] = _parse(spec)
    except (ValueError, TypeError):
        plan = None
        from ..utils import console_logger

        console_logger.warning(
            f"{_ENV}={spec!r} is malformed (grammar: every=N or "
            f"rounds=a,b,c — docs/observability.md); profiler stays off")
    with _PLAN_LOCK:
        if len(_PLAN_MEMO) > 64:
            _PLAN_MEMO.clear()
        _PLAN_MEMO[spec] = plan
    return plan


def should_sample(round_idx: int) -> bool:
    """Whether round ``round_idx`` is a sampled (profiled) round. With
    the env unset this is one ``os.environ`` read — the whole cost an
    unprofiled run pays per round (pinned ≤2% of a round by
    tests/test_kernelprof.py)."""
    plan = _plan()
    if plan is None:
        return False
    kind, val = plan
    if kind == "every":
        return round_idx % val == 0
    return round_idx in val


# ---------------------------------------------------------------------------
# the per-round profile (armed on the training thread)
# ---------------------------------------------------------------------------


class _Profile:
    """Accumulator for ONE sampled round (all trees of the round)."""

    __slots__ = ("round_idx", "buckets", "host_syncs", "trees", "depth",
                 "route", "sibling_sub", "hist_acc", "quant_scales",
                 "_last_done_ns")

    def __init__(self, round_idx: int) -> None:
        self.round_idx = int(round_idx)
        # (op, depth) -> aggregated bucket; depth -1 = pre-level prep
        self.buckets: Dict[Tuple[str, int], Dict[str, Any]] = {}
        self.host_syncs = 0
        self.trees = 0
        self.depth = -1
        # production route the mirror replayed ("tree_grow" = the round
        # would run as ONE native dispatch; "level" = per-level program)
        self.route = "level"
        self.sibling_sub = False
        # resolved hist_acc impl on the tree_grow route ("quant" /
        # "float"); quant_scales carries the round's quantiser grid
        # exponents {"g_exp": Eg, "h_exp": Eh} (dequantize = * 2^-E)
        self.hist_acc = "float"
        self.quant_scales: Optional[Dict[str, int]] = None
        self._last_done_ns = 0

    def record(self, op: str, depth: int, impl: str,
               host_ns: int, inflight_ns: int, gap_ns: int) -> None:
        b = self.buckets.get((op, depth))
        if b is None:
            b = self.buckets[(op, depth)] = {
                "op": op, "depth": depth, "impl": impl, "count": 0,
                "wall_s": 0.0, "host_s": 0.0, "inflight_s": 0.0,
                "gap_s": 0.0}
        b["count"] += 1
        b["impl"] = impl
        b["wall_s"] += (host_ns + inflight_ns) / 1e9
        b["host_s"] += host_ns / 1e9
        b["inflight_s"] += inflight_ns / 1e9
        b["gap_s"] += gap_ns / 1e9
        self.host_syncs += 1

    def to_record(self) -> Dict[str, Any]:
        ops = [dict(b,
                    wall_s=round(b["wall_s"], 6),
                    host_s=round(b["host_s"], 6),
                    inflight_s=round(b["inflight_s"], 6),
                    gap_s=round(b["gap_s"], 6))
               for _, b in sorted(self.buckets.items(),
                                  key=lambda kv: (kv[0][1], kv[0][0]))]
        return {
            "round": self.round_idx,
            "driver": DRIVER,
            "route": self.route,
            "sibling_sub": self.sibling_sub,
            "hist_acc": self.hist_acc,
            "quant_scales": self.quant_scales,
            "trees": self.trees,
            "host_syncs": self.host_syncs,
            "sum_s": round(sum(b["wall_s"] for b in ops), 6),
            "gap_s": round(sum(b["gap_s"] for b in ops), 6),
            "ops": ops,
        }


_TLS = threading.local()


def arm(round_idx: int) -> _Profile:
    """Open a profile for the sampled round on THIS thread; the in-core
    grower (``gbtree._boost_fused``) routes to the instrumented driver
    while one is armed."""
    prof = _Profile(round_idx)
    _TLS.profile = prof
    return prof


def active() -> bool:
    return getattr(_TLS, "profile", None) is not None


def disarm() -> Optional[Dict[str, Any]]:
    """Close the armed profile and return its ``grow_detail`` record —
    or ``None`` when nothing was profiled (not armed, or the round ran a
    path the instrumented driver does not cover: paged / mesh / scan)."""
    prof = getattr(_TLS, "profile", None)
    _TLS.profile = None
    if prof is None or not prof.buckets:
        return None
    return prof.to_record()


# ---------------------------------------------------------------------------
# the bracket hook (installed at the dispatch.invoke seam)
# ---------------------------------------------------------------------------


def _hook(prof: _Profile) -> Callable[[str, Callable, tuple, dict], Any]:
    import jax

    from .. import dispatch
    from . import trace as _trace
    from .metrics import REGISTRY

    counter = REGISTRY.counter(
        "host_syncs_total",
        "Deliberate host round-trips (completion syncs) by site — "
        "nonzero only on kernel-profiled rounds")

    def run(op: str, fn: Callable, args: tuple, kwargs: dict) -> Any:
        t0 = time.perf_counter_ns()
        gap_ns = (t0 - prof._last_done_ns) if prof._last_done_ns else 0
        out = fn(*args, **kwargs)
        t1 = time.perf_counter_ns()  # dispatch returned to the host
        jax.block_until_ready(out)  # the deliberate sync the seam owns
        t2 = time.perf_counter_ns()
        prof._last_done_ns = t2
        counter.labels(site=op).inc()
        impl = dispatch.last_decisions().get(op, "xla")
        prof.record(op, prof.depth, impl, t1 - t0, t2 - t1, gap_ns)
        _trace.emit(f"grow/{op}", t0, t2, cat="grow",
                    depth=prof.depth, impl=impl)
        return out

    return run


# ---------------------------------------------------------------------------
# the instrumented driver (mirror of grow_tree_fused's unrolled loop)
# ---------------------------------------------------------------------------

# lock-guarded lazy init of the jitted prologue (heavy imports deferred
# until the first sampled round)
_PREP_LOCK = threading.Lock()
_PREP_JIT: Optional[Callable] = None


def _prep_fn() -> Callable:
    global _PREP_JIT
    with _PREP_LOCK:
        if _PREP_JIT is None:
            import jax
            import jax.numpy as jnp

            from ..analysis.retrace import guard_jit
            from ..tree.grow import _sample_features_exact, apply_row_sampling
            from ..tree.grow_fused import _init_state

            def _prep(grad, hess, key, feature_weights, cfg, F, B):
                # op-for-op mirror of _grow_tree_fused_impl's prologue
                # (one program, so the f32 reduction order of the root
                # totals matches the fused program's)
                k_sub, k_ctree, k_level = jax.random.split(key, 3)
                grad, hess = apply_row_sampling(cfg, k_sub, grad, hess)
                gh = jnp.stack([grad, hess], axis=-1)
                if cfg.colsample_bytree < 1.0:
                    tree_mask = _sample_features_exact(
                        k_ctree, F, cfg.colsample_bytree, feature_weights)
                else:
                    tree_mask = jnp.ones((F,), bool)
                G0 = grad.sum()
                H0 = hess.sum()
                st = _init_state(cfg, F, G0, H0, B)
                return gh, tree_mask, k_level, st

            _PREP_JIT = guard_jit(_prep, name="kernelprof_prep",
                                  static_argnames=("cfg", "F", "B"))
        return _PREP_JIT


#: fixed-point quantiser width — MUST match kQBits in native/tree_build.cpp
_KQBITS = 18


def _quant_scales(gh) -> Dict[str, int]:
    """The sampled round's quantiser grid exponents, mirroring
    tree_build.cpp's ``compute_qscale``: per-lane max of finite |x|,
    ``E = kQBits − frexp-exponent`` (quantize = ``llrint(x * 2^E)``,
    dequantize = ``* 2^−E``). Recorded in the grow_detail record so a
    reader can see the grid the integer engine ran on."""
    import numpy as np

    a = np.abs(np.asarray(gh, dtype=np.float64))
    a = np.where(np.isfinite(a), a, 0.0)
    out: Dict[str, int] = {}
    for idx, name in ((0, "g_exp"), (1, "h_exp")):
        m = float(a[:, idx].max()) if a.size else 0.0
        out[name] = int(_KQBITS - np.frexp(m)[1]) if m > 0.0 else 0
    return out


def grow_tree_fused_profiled(bins, grad, hess, cut_values, key, eta, gamma,
                             cfg, feature_weights=None, onehot=None):
    """Instrumented mirror of ``grow_tree_fused`` for a sampled round:
    the same unrolled level loop, driven from the host so every kernel
    dispatch can be bracketed at the ``dispatch.invoke`` seam. Falls back
    to the production program when no profile is armed or under a mesh
    (the mirror is single-process by design). Bit-identity with the
    production drivers rests on reusing their exact level machinery —
    see the module docstring."""
    from ..tree import grow_fused as _gf

    prof = getattr(_TLS, "profile", None)
    if prof is None or cfg.axis_name is not None:
        return _gf.grow_tree_fused(bins, grad, hess, cut_values, key,
                                   eta, gamma, cfg, feature_weights, onehot)

    import jax
    import jax.numpy as jnp

    from .. import dispatch
    from ..tree import hist_kernel as _hk
    from . import trace as _trace

    pallas = _gf._pallas_flag(cfg)
    max_depth = cfg.max_depth
    # Which route would the PRODUCTION program take? Resolved with the
    # original bins dtype (the pallas path widens to i32 below). When
    # the answer is the whole-tree kernel, the mirror replays per-level
    # with the sibling-subtraction FFI entry at d >= 1 (bit-identical by
    # shared C++ core loops — see module docstring).
    route = ("tree_grow"
             if _gf._use_tree_grow(cfg, bool(pallas), max_depth,
                                   str(bins.dtype))
             else "level")
    sub_on = False
    quant_on = False
    if route == "tree_grow":
        plat = jax.default_backend()
        sub_on = dispatch.resolve(
            "sibling_sub", dispatch.Ctx(platform=plat)).impl == "on"
        quant_on = dispatch.resolve(
            "hist_acc", dispatch.Ctx(platform=plat)).impl == "quant"
    prof.route = route
    prof.sibling_sub = sub_on
    prof.hist_acc = "quant" if quant_on else "float"
    if pallas:
        bins = bins.astype(jnp.int32)
    n, F = bins.shape
    B = cut_values.shape[1]
    prof.trees += 1
    # start the gap clock at mirror entry so the setup before the first
    # bracket (route resolution, span entry) lands in prep's gap column
    # instead of vanishing from the attribution
    prof._last_done_ns = time.perf_counter_ns()
    prev = dispatch.set_invoke_hook(_hook(prof))
    try:
        with _trace.span("grow_tree", fused=True, instrumented=True,
                         depth=max_depth, features=int(F)):
            prof.depth = -1
            gh, tree_mask, k_level, st = dispatch.invoke(
                "prep", _prep_fn(), grad, hess, key, feature_weights,
                cfg=cfg, F=int(F), B=int(B))
            pos = jnp.zeros((n, 1), jnp.int32)
            prev_hist = None
            # the quant route carries the previous level's int64
            # histogram as packed int32 word pairs — empty at the root
            prev_q = jnp.zeros((F, 0, B, 2), jnp.int32)
            if quant_on:
                prof.quant_scales = _quant_scales(gh)
            for d in range(max_depth):
                prof.depth = d
                K = 1 << d
                if route == "tree_grow" and quant_on:
                    # quant engine for EVERY level (root included): the
                    # sampled round's histograms must match the fused
                    # kernel's integer accumulation bit-for-bit, and the
                    # int64 carry never passes through f32
                    from ..tree import tree_kernel as _tk

                    pos, prev_q, histC = dispatch.invoke(
                        "level_hist", _tk.fused_level_quant_native, bins,
                        pos, gh, st.ptab, prev_q, K=K, Kp=K >> 1, B=B,
                        d=d, sibling_sub=sub_on)
                elif route == "tree_grow" and sub_on and d >= 1:
                    from ..tree import tree_kernel as _tk

                    pos, histC = dispatch.invoke(
                        "level_hist", _tk.fused_level_sub_native, bins,
                        pos, gh, st.ptab, prev_hist, K=K, Kp=K >> 1, B=B,
                        d=d)
                else:
                    pos, histC = dispatch.invoke(
                        "level_hist", _hk.fused_level, bins, pos, gh,
                        st.ptab, K=K, Kp=K >> 1, B=B, d=d, pallas=pallas,
                        onehot=onehot, axis_name=None)
                prev_hist = histC
                st = dispatch.invoke(
                    "level_update", _gf._level_update_jit, st, histC,
                    cut_values, tree_mask, k_level, cfg=cfg, d=d)
            prof.depth = max_depth
            if max_depth > 0:
                pos = dispatch.invoke(
                    "level_partition", _hk.partition_apply, bins, pos,
                    st.ptab, Kp=1 << (max_depth - 1), B=B, d=max_depth,
                    pallas=pallas)
            keep, leaf_value = dispatch.invoke(
                "finalize", _gf._finalize_jit, st, jnp.float32(eta),
                jnp.float32(gamma), cfg=cfg)
            pad_nodes = max(128, 1 << (cfg.max_nodes - 1).bit_length())
            delta = dispatch.invoke(
                "leaf_delta", _hk.leaf_delta, pos, leaf_value, pad_nodes,
                pallas=pallas)
    finally:
        dispatch.set_invoke_hook(prev)

    return _gf.GrownTree(
        keep=keep, feature=st.feature, split_bin=st.split_bin,
        split_cond=st.split_cond, default_left=st.default_left,
        node_g=st.node_g, node_h=st.node_h, node_weight=st.node_w,
        loss_chg=st.loss_chg, leaf_value=leaf_value, delta=delta,
        cat_set=st.cat_set,
    )


# ---------------------------------------------------------------------------
# grow-report: render grow_detail records from a flight sink
# ---------------------------------------------------------------------------


def format_grow_detail(rec: Dict[str, Any],
                       grow_s: Optional[float] = None) -> str:
    """Render one ``grow_detail`` record as the per-depth × per-op table.
    ``grow_s`` (the round's ``stages.grow``) adds the coverage line —
    the acceptance contract is substages summing to within 10% of it."""
    route = rec.get("route")
    route_note = ""
    if route:
        route_note = f", route={route}"
        if route == "tree_grow":
            # per-level replay of a one-dispatch production round; the
            # resolved hist_acc impl picks the replay flavour, and the
            # quant flavour shows the round's quantiser grid
            if rec.get("hist_acc") == "quant":
                route_note += " (quant replay"
                qs = rec.get("quant_scales") or {}
                if qs:
                    route_note += (f", scales g=2^-{qs.get('g_exp')}"
                                   f" h=2^-{qs.get('h_exp')}")
                route_note += ")"
            elif rec.get("sibling_sub"):
                route_note += " (sibling-sub replay)"
            else:
                route_note += " (per-level replay)"
        elif any(b.get("op") == "level_hist" and b.get("impl") == "pallas"
                 for b in rec.get("ops", ())):
            # the program's Pallas level loop builds one child of every
            # split (grow_fused); this mirror builds every node
            route_note += ", sibling_sub=off (mirror)"
    lines = [
        f"round {rec.get('round')}: grow detail "
        f"({rec.get('driver')}, {rec.get('trees')} tree(s){route_note})",
        f"  {'depth':>5} {'op':<16} {'impl':<8} {'count':>5} "
        f"{'wall':>10} {'host':>10} {'inflight':>10} {'gap':>9}",
    ]

    def ms(v: float) -> str:
        return f"{v * 1e3:.3f}ms"

    for b in rec.get("ops", ()):
        depth = b.get("depth", -1)
        lines.append(
            f"  {('prep' if depth < 0 else depth)!s:>5} {b['op']:<16} "
            f"{b.get('impl', '?'):<8} {b.get('count', 0):>5} "
            f"{ms(b['wall_s']):>10} {ms(b.get('host_s', 0.0)):>10} "
            f"{ms(b.get('inflight_s', 0.0)):>10} "
            f"{ms(b.get('gap_s', 0.0)):>9}")
    total = f"  substages {ms(rec.get('sum_s', 0.0))}, " \
            f"dispatch gap {ms(rec.get('gap_s', 0.0))}, " \
            f"host syncs {rec.get('host_syncs', 0)}"
    if grow_s:
        total += (f"; stages.grow {ms(grow_s)} "
                  f"(substages = {100.0 * rec.get('sum_s', 0.0) / grow_s:.1f}%)")
    lines.append(total)
    return "\n".join(lines)


def _aggregate_ops(recs: List[Dict[str, Any]]) -> Tuple[
        Dict[Tuple[int, str], Dict[str, Any]], List[int]]:
    """Sum per-(depth, op) wall seconds across sampled round records —
    the input to the ``--diff`` table. Returns ``(buckets, rounds)``."""
    agg: Dict[Tuple[int, str], Dict[str, Any]] = {}
    rounds: List[int] = []
    for r in recs:
        gd = r.get("grow_detail", {})
        rounds.append(gd.get("round", r.get("round", -1)))
        for b in gd.get("ops", ()):
            key = (b.get("depth", -1), b.get("op", "?"))
            cur = agg.setdefault(key, {"wall_s": 0.0, "count": 0,
                                       "impl": b.get("impl", "?")})
            cur["wall_s"] += b.get("wall_s", 0.0)
            cur["count"] += b.get("count", 0)
            cur["impl"] = b.get("impl", cur["impl"])
    return agg, rounds


def format_grow_diff(agg_a: Dict[Tuple[int, str], Dict[str, Any]],
                     rounds_a: List[int], label_a: str,
                     agg_b: Dict[Tuple[int, str], Dict[str, Any]],
                     rounds_b: List[int], label_b: str) -> str:
    """Render the A-vs-B per-depth × per-op table with a delta column
    (B − A; negative = B faster). Rows missing on one side show '-' —
    e.g. a depth the other run never grew, or an op only one route
    dispatches."""
    lines = [
        f"grow detail diff: A = {label_a} (rounds {sorted(set(rounds_a))}) "
        f"vs B = {label_b} (rounds {sorted(set(rounds_b))})",
        f"  {'depth':>5} {'op':<16} {'impl':<16} {'A wall':>10} "
        f"{'B wall':>10} {'delta':>10}",
    ]

    def ms(v: Optional[float]) -> str:
        return "-" if v is None else f"{v * 1e3:.3f}ms"

    tot_a = tot_b = 0.0
    changed = 0
    for depth, op in sorted(set(agg_a) | set(agg_b)):
        a = agg_a.get((depth, op))
        b = agg_b.get((depth, op))
        wa = a["wall_s"] if a else None
        wb = b["wall_s"] if b else None
        tot_a += wa or 0.0
        tot_b += wb or 0.0
        ia = a["impl"] if a else "-"
        ib = b["impl"] if b else "-"
        impl = ia if ia == ib else f"{ia}->{ib}"
        delta = "-" if (wa is None or wb is None) else ms(wb - wa)
        # rows whose resolved impl changed between the runs get a
        # visible marker — a reader scanning a long table should not
        # have to eyeball the impl column to spot a route flip
        mark = ""
        if ia != ib and a is not None and b is not None:
            mark = " *"
            changed += 1
        lines.append(
            f"  {('prep' if depth < 0 else depth)!s:>5} {op:<16} "
            f"{impl:<16} {ms(wa):>10} {ms(wb):>10} {delta:>10}{mark}")
    lines.append(f"  substages A {ms(tot_a)}, B {ms(tot_b)}, "
                 f"delta {ms(tot_b - tot_a)}")
    if changed:
        lines.append(f"  * = resolved impl changed between runs "
                     f"({changed} row(s))")
    return "\n".join(lines)


def _iter_flight_lines(path: str) -> List[Dict[str, Any]]:
    """Parse a flight.jsonl tolerantly: torn/partial lines (SIGKILL
    mid-write) are skipped, not fatal — the PR-6 precedent."""
    out: List[Dict[str, Any]] = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if isinstance(rec, dict):
                out.append(rec)
    return out


def _find_flight_files(arg: str) -> List[str]:
    if os.path.isdir(arg):
        import glob as _glob

        hits = sorted(
            _glob.glob(os.path.join(arg, "obs", "rank*", "flight.jsonl"))
            or _glob.glob(os.path.join(arg, "flight.jsonl")))
        return hits
    return [arg]


def main(argv: List[str]) -> int:
    usage = ("usage: python -m xgboost_tpu grow-report "
             "<flight.jsonl|run-dir> [--round N] | "
             "grow-report --diff <A> <B> [--round N]")
    if not argv or argv[0] in ("-h", "--help"):
        print(usage, file=sys.stderr)
        return 0 if argv else 1
    want_round: Optional[int] = None
    if "--round" in argv:
        i = argv.index("--round")
        try:
            want_round = int(argv[i + 1])
        except (IndexError, ValueError):
            print(usage, file=sys.stderr)
            return 1
        argv = argv[:i] + argv[i + 2:]
    if "--diff" in argv:
        rest = [a for a in argv if a != "--diff"]
        if len(rest) != 2:
            print(usage, file=sys.stderr)
            return 1
        sides = []
        for arg in rest:
            recs: List[Dict[str, Any]] = []
            for path in _find_flight_files(arg):
                try:
                    recs.extend(
                        r for r in _iter_flight_lines(path)
                        if r.get("t") == "round" and "grow_detail" in r)
                except OSError as e:
                    print(f"{path}: {e}", file=sys.stderr)
                    return 1
            if want_round is not None:
                recs = [r for r in recs if r.get("round") == want_round]
            if not recs:
                print(f"{arg}: no sampled grow_detail records found "
                      f"(profiler arms via {_ENV}=every=N|rounds=a,b,c)",
                      file=sys.stderr)
                return 1
            sides.append((arg, recs))
        (la, ra), (lb, rb) = sides
        agg_a, rounds_a = _aggregate_ops(ra)
        agg_b, rounds_b = _aggregate_ops(rb)
        print(format_grow_diff(agg_a, rounds_a, la, agg_b, rounds_b, lb))
        return 0
    paths = _find_flight_files(argv[0])
    if not paths:
        print(f"{argv[0]}: no flight.jsonl found", file=sys.stderr)
        return 1
    rc = 0
    shown = 0
    for path in paths:
        try:
            recs = _iter_flight_lines(path)
        except OSError as e:
            print(f"{path}: {e}", file=sys.stderr)
            rc = 1
            continue
        sampled = [r for r in recs
                   if r.get("t") == "round" and "grow_detail" in r]
        if want_round is not None:
            sampled = [r for r in sampled if r.get("round") == want_round]
        for r in sampled:
            print(format_grow_detail(
                r["grow_detail"], r.get("stages", {}).get("grow")))
            print()
            shown += 1
    if not shown:
        print("no sampled grow_detail records found "
              f"(profiler arms via {_ENV}=every=N|rounds=a,b,c)",
              file=sys.stderr)
        return 1
    return rc
