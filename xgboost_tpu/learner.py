"""Learner / Booster: the API core.

Reference: ``src/learner.cc`` — ``LearnerConfiguration::Configure``
(:250-357, lazy one-time objective/GBM/metric creation),
``LearnerImpl::UpdateOneIter`` (:1060 — PredictRaw -> GetGradient ->
DoBoost), ``BoostOneIter`` (:1088 custom objective), ``EvalOneIter``
(:1105), LearnerIO JSON model save/load (:659-994), plus the Python
``Booster`` facade (python-package/xgboost/core.py). Here the two layers
collapse into one class: there is no C API boundary to cross — the Python
object IS the learner, and device state (prediction caches) lives in JAX
arrays.
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from .data.dmatrix import DMatrix
from .gbm import create_booster
from .metric import create_metric
from .objective import create_objective
from .observability import REGISTRY as _REGISTRY, trace as _trace
from .params import LearnerParam
from .registry import BOOSTERS, OBJECTIVES
from .utils import Monitor, console_logger, fault

__all__ = ["Booster"]

_VERSION = [2, 0, 0]  # this framework's model version triplet


def _multiprocess_mesh_active() -> bool:
    """True only when training would run the COLLECTIVE multi-process path:
    several processes AND an active ``mesh_context``. A program that merely
    initialized jax.distributed (e.g. for its own IO) but trains mesh-less
    per-process boosters takes the normal local paths. Shares the metric
    layer's predicate so routing and reductions cannot disagree."""
    from .parallel.mesh import collective_active

    return collective_active()


class _PredCache:
    """Versioned prediction cache (reference: PredictionContainer,
    include/xgboost/predictor.h:242 — tracks how many trees are already
    folded into the cached margin)."""

    def __init__(self) -> None:
        self.margin: Optional[jax.Array] = None  # [n, K]
        self.num_trees: int = 0
        # whether the cached margin may have come from the predict_walk
        # dispatch route's NATIVE walker (double accumulation — off by
        # ~1 ulp from the device path): the TRAINING margin read
        # (_cached_margin) must never consume such an entry, or resumed
        # runs would stop being bit-identical to uninterrupted ones
        self.native: bool = False


class Booster:
    """A trained (or training) gradient-boosted model."""

    def __init__(
        self,
        params: Optional[Dict[str, Any]] = None,
        cache: Optional[Sequence[DMatrix]] = None,
        model_file: Optional[Union[str, bytes, os.PathLike]] = None,
    ):
        self.lparam = LearnerParam()
        self._extra_params: Dict[str, Any] = {}
        self._gbm = None
        self._obj = None
        self._metrics: List = []
        self._base_margin_val: float = 0.0
        self._caches: Dict[int, _PredCache] = {}
        self._cache_refs: Dict[int, DMatrix] = {}
        # stacked-forest snapshots keyed by (num_trees, resolved
        # iteration_range): repeated predicts — the serving pattern — must
        # not re-stack/re-pad trees per request (see _forest_snapshot).
        # Lock-guarded: a multi-threaded serving frontend hits this from
        # concurrent inplace_predict calls (lock recreated on unpickle via
        # __setstate__ -> __init__)
        self._forest_snapshots: "OrderedDict" = OrderedDict()
        self._forest_snapshots_lock = threading.Lock()
        self.attributes_: Dict[str, str] = {}
        self.best_iteration: Optional[int] = None
        self.best_score: Optional[float] = None
        # bounded in-flight window for pipelined update_many chunks
        # (pipeline.RoundPipeline, created lazily; never pickled)
        self._pipeline = None
        self.monitor = Monitor("Booster")
        if params:
            self._apply_params(dict(params))
        if cache:
            for d in cache:
                self._caches[id(d)] = _PredCache()
                self._cache_refs[id(d)] = d
        if model_file is not None:
            self.load_model(model_file)

    # ------------------------------------------------------------------
    # configuration (lazy, like reference Configure())
    # ------------------------------------------------------------------
    def _apply_params(self, params: Dict[str, Any]) -> None:
        unknown = self.lparam.update(params)
        self._extra_params.update(unknown)
        # shared keys consumed by the learner-level ParamSet but ALSO read
        # by the tree layer (see LearnerParam.FIELDS note): forward them
        for k in ("max_delta_step",):
            if k in params:
                self._extra_params[k] = params[k]
        if self.lparam.validate_parameters:
            self._validate_unknown()

    def _validate_unknown(self) -> None:
        """validate_parameters (reference: learner.cc:351) — flag keys no
        component recognized."""
        from .params import GBLinearParam, GBTreeParam, TrainParam

        known = set()
        for P in (GBTreeParam, TrainParam, GBLinearParam):
            known.update(P.FIELDS)
            for f in P.FIELDS.values():
                known.update(f.aliases)
        bad = [k for k in self._extra_params if k not in known]
        if bad:
            raise ValueError(f"Unknown parameters: {bad}")

    def set_param(self, params, value=None) -> None:
        if isinstance(params, str):
            params = {params: value}
        elif isinstance(params, (list, tuple)):
            params = dict(params)
        self._apply_params(dict(params))
        if self._gbm is not None:
            for k, v in params.items():
                try:
                    self._gbm.set_param(k, v)
                except Exception:
                    pass
            if self._obj is not None and hasattr(self._obj, "params"):
                self._obj.params = self.lparam
        self._metrics = []  # re-resolve on next eval

    def _configure(self) -> None:
        if self._obj is None:
            self._obj = create_objective(self.lparam.objective, self.lparam)
        if self._gbm is None:
            n_groups = self._obj.n_targets()
            self._gbm = create_booster(self.lparam.booster, n_groups, self._extra_params)
        base = self.lparam.base_score
        if base is None:
            base = self._obj.default_base_score()
        self._base_margin_val = float(self._obj.prob_to_margin(float(base)))

    @property
    def n_groups(self) -> int:
        self._configure()
        return self._gbm.n_groups

    # ------------------------------------------------------------------
    # margins & caches
    # ------------------------------------------------------------------
    def _base_margin_for(self, dmat: DMatrix, n: int) -> jax.Array:
        K = self.n_groups
        bm = dmat.info.base_margin
        if bm is not None and bm.size:
            b = jnp.asarray(bm, jnp.float32)
            return b.reshape(n, K) if b.ndim != 2 else b
        return jnp.full((n, K), self._base_margin_val, jnp.float32)

    def _cached_margin(self, dtrain: DMatrix) -> jax.Array:
        """PredictRaw with cache (reference learner.cc:1075)."""
        entry = self._caches.setdefault(id(dtrain), _PredCache())
        self._cache_refs.setdefault(id(dtrain), dtrain)
        n = dtrain.num_row()
        if self._gbm.name == "dart":
            # dropout changes old-tree weights: always a fresh dropped pass
            base = self._base_margin_for(dtrain, n)
            return self._gbm.training_margin(dtrain.data, base)
        # native_ok=False: gradients must stay byte-stable regardless of
        # how eval/predict walks are routed (ISSUE 15)
        return self._predict_margin(dtrain, native_ok=False)

    # ------------------------------------------------------------------
    # training
    # ------------------------------------------------------------------
    def update(self, dtrain: DMatrix, iteration: int, fobj=None) -> None:
        """One boosting iteration (reference UpdateOneIter learner.cc:1060)."""
        self._configure()
        if fobj is None and _multiprocess_mesh_active():
            # multi-process MESH boosting only exists as scan chunks
            # (per-round deltas stay device-sharded, gbtree.boost_one_round
            # raises) — a single round IS a 1-chunk scan, so train()'s
            # per-round loop with eval/early-stop composes with dsplit=row
            # directly. Multi-process WITHOUT an active mesh is per-process
            # local training and takes the normal path.
            self.update_many(dtrain, iteration, 1, chunk=1)
            return
        before = self._num_trees()
        with _trace.span("update", iteration=iteration):
            self._update(dtrain, iteration, fobj)
        _REGISTRY.counter(
            "rounds_total", "Boosting rounds dispatched").inc()
        self._count_trees("round", before)

    def _num_trees(self) -> int:
        return getattr(getattr(self._gbm, "model", None), "num_trees", 0)

    def _count_trees(self, path: str, before: int) -> None:
        """``trees_grown_total``: the trees that entered the model since
        ``before``, G x ``num_parallel_tree`` a round: by a scan chunk
        (``path="scan"``) or by one ``update`` (``path="round"``). A
        round of ``process_type=update`` grows none: it re-stats the
        model's own trees, taking them out and putting them back."""
        if getattr(self._gbm, "_is_update_process", False):
            return
        _REGISTRY.counter(
            "trees_grown_total", "Trees grown into the model by path"
        ).labels(path=path).inc(self._num_trees() - before)

    def _update(self, dtrain: DMatrix, iteration: int, fobj=None) -> None:
        fault.begin_version(iteration)
        fault.inject("gradient")
        if fobj is not None:
            margin = self._cached_margin(dtrain)
            pred = np.asarray(margin)
            if pred.shape[1] == 1:
                pred = pred[:, 0]
            grad, hess = fobj(pred, dtrain)
            self.boost(dtrain, grad, hess)
            return
        from .utils import observer

        with self.monitor.section("GetGradient"), \
                _trace.span("round.gradient", iteration=iteration):
            margin = self._cached_margin(dtrain)
            grad, hess = self._gradient(dtrain, margin, iteration)
        if observer.enabled():
            observer.observe("margin", margin, iteration)
            observer.observe("grad", grad, iteration)
            observer.observe("hess", hess, iteration)
        with _trace.span("round.boost", iteration=iteration):
            self._do_boost(dtrain, grad, hess, iteration)
        self.monitor.maybe_print()

    def _gradient(self, dtrain: DMatrix, margin, iteration: int):
        # the scope names what is traced under it; a jitted objective
        # (ranking) opens ``xgb.gradient`` inside its own program
        with jax.named_scope("xgb.gradient"):
            return self._obj.gradient_of(margin, dtrain.info, iteration)

    def gradient(self, dtrain: DMatrix, iteration: int = 0):
        """``(grad, hess)`` that ``update(dtrain, iteration)`` would boost
        on, as device arrays: the objective at the cached training margin.
        Changes nothing."""
        self._configure()
        return self._gradient(dtrain, self._cached_margin(dtrain), iteration)

    def update_many(self, dtrain: DMatrix, start_iteration: int,
                    num_rounds: int, chunk: int = 25) -> None:
        """``num_rounds`` boosting rounds with ONE device dispatch per
        ``chunk`` rounds (a ``lax.scan`` over the fused round program,
        ``gbm/gbtree.py:boost_rounds_scan``) — same trees as calling
        ``update`` per round (identical RNG keys). Falls back to the per-round path whenever the
        configuration is outside the scan-safe envelope (ranking/survival
        objectives, DART, lossguide, categorical, external memory, custom
        objective); multiclass (one tree per group per scanned round) and
        mesh training (the chunk scan runs inside one shard_map) are
        supported."""
        self._configure()
        binned = None
        if (
            self._gbm.name == "gbtree"
            and not getattr(self._gbm, "needs_iteration_sketch", False)
            and not getattr(self._gbm, "needs_local_sketch", False)
            and not getattr(self._gbm, "needs_exact_cuts", False)
            and dtrain.info.label is not None
        ):
            binned = dtrain.get_binned(self._gbm.train_param.max_bin,
                                       dtrain.info.weight)
        if binned is None or not self._gbm.scan_rounds_supported(
                binned, self._obj, self.n_groups):
            if _multiprocess_mesh_active():
                raise NotImplementedError(
                    "this configuration is outside the multi-process scan "
                    "envelope (ranking/survival/DART/lossguide/categorical/"
                    "external-memory/custom objectives are single-process); "
                    "see docs/distributed.md")
            for i in range(start_iteration, start_iteration + num_rounds):
                self.update(dtrain, i)
            return
        from .observability import flight as _flight
        from .pipeline import RoundPipeline, completion_probe

        if self._pipeline is None:
            self._pipeline = RoundPipeline()
        entry = self._caches.setdefault(id(dtrain), _PredCache())
        done = 0
        while done < num_rounds:
            k = min(chunk, num_rounds - done)
            # one flight record per chunk (rounds=k): the scan path's
            # dispatch cadence is per-chunk, so that is the granularity
            # the recorder can honestly time. Under train()'s per-round
            # loop (mesh: update -> 1-chunk scan) the begin is NESTED and
            # owned stays False: the outer loop already times the whole
            # update as "grow", so noting it here too would double-count.
            owned = _flight.RECORDER.begin_round(
                start_iteration + done, rounds=k)
            # profiling is independent of the recorder: owned is False
            # both for a nested begin (outer loop already ticks) AND when
            # XGBTPU_FLIGHT=0 — the profiler window must still open then
            if owned or not _flight.enabled():
                _flight.profile_tick(start_iteration + done)
            try:
                fault.begin_version(start_iteration + done)
                fault.inject("gradient")
                fault.inject("grow")
                margin = self._cached_margin(dtrain)
                # detach before the chunk donates the carried margin: an
                # abort mid-chunk must not leave a deleted buffer in the
                # cache (see _do_boost)
                entry.margin = None
                info = dtrain.info
                before = self._num_trees()
                _t0 = time.perf_counter()
                # the label goes up inside the chunk's ``chunk.prepare``
                # step (gbtree), where a profile shows what it costs
                margin = self._gbm.boost_rounds_scan(
                    binned, self._obj, info.label, info.weight, margin,
                    start_iteration + done, k,
                    feature_weights=info.feature_weights,
                )
                if owned:
                    _flight.note("grow", time.perf_counter() - _t0)
                entry.margin = margin
                entry.num_trees = self._gbm.model.num_trees
                # pipelined chunks (ISSUE 13): the dispatch above is
                # async — admit its output and only block once more than
                # XGBTPU_PIPELINE_DEPTH chunks are in flight, so chunk
                # i+1's host work (gradient staging, dispatch) overlaps
                # chunk i's device execution with a pinned memory
                # watermark. An async fault surfaces here attributed to
                # the chunk's first round (sync time -> 'sync' stage).
                try:
                    # the one place this layer can block
                    with _trace.span("chunk.admit",
                                     start=start_iteration + done):
                        self._pipeline.admit(start_iteration + done,
                                             completion_probe(margin))
                except BaseException:
                    self._pipeline.abandon()  # younger chunks are dead too
                    raise
                _REGISTRY.counter(
                    "rounds_total", "Boosting rounds dispatched").inc(k)
                self._count_trees("scan", before)
                done += k
            finally:
                _flight.RECORDER.end_round()

    def boost(self, dtrain: DMatrix, grad, hess) -> None:
        """Custom-objective boost (reference BoostOneIter learner.cc:1088)."""
        self._configure()
        grad = jnp.asarray(np.asarray(grad, np.float32))
        hess = jnp.asarray(np.asarray(hess, np.float32))
        self._do_boost(dtrain, grad, hess, iteration=self.num_boosted_rounds())

    def _do_boost(self, dtrain: DMatrix, grad, hess, iteration: int) -> None:
        fault.inject("grow")
        entry = self._caches.setdefault(id(dtrain), _PredCache())
        if self._gbm.name in ("gbtree", "dart"):
            if getattr(self._gbm, "_is_update_process", False):
                # process_type=update / updater=refresh: re-stat existing
                # trees on this data, no new trees (updater_refresh.cc:162)
                with self.monitor.section("Refresh"):
                    self._gbm.refresh_one_round(
                        dtrain.data, grad, hess, iteration
                    )
                entry.margin = None  # leaf values changed
                self._forest_snapshots.clear()  # same num_trees, new leaves
                return
            if getattr(self._gbm, "needs_local_sketch", False):
                # updater=grow_local_histmaker: per-node re-sketched cuts,
                # grown from RAW values — no global quantized matrix
                # (updater_histmaker.cc:753)
                if self._gbm.name != "gbtree":
                    raise NotImplementedError(
                        "grow_local_histmaker is a gbtree updater")
                if getattr(dtrain, "data_is_reconstructed", False):
                    # a QuantileDMatrix's .data is bin-reconstructed (at
                    # most max_bin distinct values/feature): re-sketching
                    # it would silently lose exactly the sub-bin
                    # resolution this updater exists for. The reference's
                    # QuantileDMatrix is likewise hist-only.
                    raise NotImplementedError(
                        "grow_local_histmaker needs TRUE raw values; a "
                        "QuantileDMatrix only holds quantized bins — "
                        "construct a DMatrix instead")
                try:
                    X_raw = dtrain.data  # paged matrices refuse this
                except NotImplementedError:
                    X_raw = None
                if X_raw is None:
                    raise NotImplementedError(
                        "grow_local_histmaker needs in-memory data for "
                        "per-node re-sketching")
                if dtrain.categorical_features():
                    raise NotImplementedError(
                        "grow_local_histmaker supports numerical features "
                        "only (the reference's local maker predates "
                        "categorical support)")
                margin_cache = entry.margin
                entry.margin = None  # donated below; see the gbtree branch
                with self.monitor.section("BoostOneRound"):
                    _, new_margin = self._gbm.local_boost_one_round(
                        X_raw, grad, hess, iteration, margin_cache,
                        feature_weights=dtrain.info.feature_weights)
                if new_margin is not None:
                    entry.margin = new_margin
                    entry.num_trees = self._gbm.model.num_trees
                else:
                    entry.margin = None
                return
            with self.monitor.section("GetBinned"):
                if getattr(self._gbm, "needs_iteration_sketch", False):
                    # approx: fresh hessian-weighted cuts every round
                    # (updater_histmaker.cc per-iteration proposal). hess is
                    # already instance-weight-scaled by the objective, so it
                    # is the complete sketch weight. Reuses the cached
                    # get_binned path's categorical + distributed-sketch
                    # machinery via the uncached builder.
                    if not hasattr(dtrain, "build_binned"):
                        raise NotImplementedError(
                            "tree_method='approx' needs in-memory data for "
                            "per-iteration re-sketching; use tpu_hist for "
                            "external-memory matrices"
                        )
                    hw = np.asarray(hess, np.float32)
                    if hw.ndim == 2:
                        hw = hw.sum(axis=1)
                    binned = dtrain.build_binned(
                        self._gbm.train_param.max_bin, hw
                    )
                elif getattr(self._gbm, "needs_exact_cuts", False):
                    # exact: one bin per distinct value (colmaker candidate
                    # set, updater_colmaker.cc:367)
                    if not hasattr(dtrain, "get_binned_exact"):
                        raise NotImplementedError(
                            "tree_method='exact' needs in-memory data; "
                            "use tpu_hist for external-memory matrices"
                        )
                    binned = dtrain.get_binned_exact()
                else:
                    binned = dtrain.get_binned(self._gbm.train_param.max_bin, dtrain.info.weight)
            fw = dtrain.info.feature_weights
            # detach the cache entry for the duration of the round: the
            # margin buffer is DONATED into the round's margin update, and
            # an abort mid-round (chaos fault, watchdog, Ctrl-C) must not
            # leave a deleted array reachable through the cache (the
            # incremental catch-up in _predict_margin would read it)
            margin_cache = entry.margin
            entry.margin = None
            with self.monitor.section("BoostOneRound"):
                _, new_margin = self._gbm.boost_one_round(
                    binned, grad, hess, iteration, margin_cache,
                    feature_weights=fw,
                )
            if new_margin is not None:
                entry.margin = new_margin
                entry.num_trees = self._gbm.model.num_trees
            else:
                entry.margin = None  # DART: invalidate
        else:  # gblinear
            self._gbm.boost_one_round(dtrain.data, grad, hess, iteration)
            entry.margin = None

    # ------------------------------------------------------------------
    # evaluation
    # ------------------------------------------------------------------
    def _resolve_metrics(self) -> List:
        self._configure()
        if not self._metrics:
            names = list(self.lparam.eval_metric)
            if not names and not self.lparam.disable_default_eval_metric:
                names = [self._obj.default_metric()]
            self._metrics = [create_metric(n) for n in names]
            for m in self._metrics:
                # metrics that share objective configuration (aft-nloglik's
                # distribution/scale — the reference configures the metric
                # with the same AFTParam, survival_metric.cu) read it here
                m.lparam = self.lparam
        return self._metrics

    def eval_set(self, evals, iteration: int = 0, feval=None, output_margin: bool = True) -> str:
        self._configure()
        fault.inject("eval")
        evals = list(evals)
        with _trace.span("eval", iteration=iteration, n_sets=len(evals),
                         rows=sum(d.num_row() for d, _ in evals)):
            return self._eval_set(evals, iteration, feval)

    def _eval_set(self, evals, iteration: int, feval=None) -> str:
        parts = [f"[{iteration}]"]
        for dmat, name in evals:
            # the per-eval-round walk rides the predict_walk dispatch
            # route (native on CPU) — ISSUE 15 tentpole (d)
            margin = self._predict_margin(dmat, native_ok=True)
            preds = self._obj.eval_transform(margin[:, 0] if self.n_groups == 1 else margin)
            info = dmat.info
            for metric in self._resolve_metrics():
                val = metric.evaluate(
                    preds,
                    jnp.asarray(info.label) if info.label is not None else jnp.zeros(dmat.num_row()),
                    info.weight,
                    group_ptr=info.group_ptr,
                    label_lower=info.label_lower_bound,
                    label_upper=info.label_upper_bound,
                )
                parts.append(f"{name}-{metric.name}:{val:.6f}")
            if feval is not None:
                m = np.asarray(margin)
                fname, fval = feval(m[:, 0] if m.shape[1] == 1 else m, dmat)
                parts.append(f"{name}-{fname}:{fval:.6f}")
        return "\t".join(parts)

    def eval(self, data: DMatrix, name: str = "eval", iteration: int = 0) -> str:
        return self.eval_set([(data, name)], iteration)

    # ------------------------------------------------------------------
    # prediction
    # ------------------------------------------------------------------
    def _data_blocks(self, dmat: DMatrix, blk: int = 65536):
        """Yield (lo, hi, X_block) over a matrix's rows WITHOUT densifying
        the whole thing: disk-backed matrices stream quantized pages
        (reconstructed from cut midpoints — the reference's page-streamed
        predict, cpu_predictor.cc:266), CSR-backed ones densify row
        blocks, plain ones yield their array once."""
        n = dmat.num_row()
        paged = getattr(dmat, "_paged", None)
        if paged is not None:
            self._warn_foreign_paged(dmat, paged)
            for k in range(paged.n_pages):
                lo = k * paged.page_rows
                yield lo, lo + paged.rows_of(k), jnp.asarray(
                    paged.float_page(k))
        elif getattr(dmat, "_sparse", None) is not None and dmat._data is None:
            for lo in range(0, n, blk):
                hi = min(lo + blk, n)
                yield lo, hi, dmat._sparse.dense_rows(lo, hi)
        else:
            yield 0, n, dmat.data

    def _warn_foreign_paged(self, dmat: DMatrix, paged) -> None:
        """Page-streamed predict reconstructs features from cut MIDPOINTS,
        which routes exactly only through split thresholds drawn from the
        SAME cuts (data/external.py:midpoints). A foreign booster — loaded
        from file or trained on other data — can flip decisions near
        thresholds, so walking it over a paged matrix gets a loud warning
        (reference cpu_predictor.cc:266 streams raw pages and has no such
        approximation). Checked once per (matrix, model-size) pair: every
        internal-node threshold must be a member of the matrix's own cut
        set for its feature."""
        if self._gbm.name not in ("gbtree", "dart"):
            return
        key = (id(dmat), self._gbm.model.num_trees)
        if getattr(self, "_paged_cuts_checked", None) == key:
            return
        self._paged_cuts_checked = key
        forest = self._gbm.model.stacked()
        if forest.left.shape[0] == 0:
            return
        left = np.asarray(forest.left)
        feat = np.asarray(forest.feature)
        cond = np.asarray(forest.cond, np.float32)
        internal = left >= 0
        if not internal.any():
            return
        cuts = np.asarray(paged.cuts.values, np.float32)  # [F, B]
        f = feat[internal].ravel()
        c = cond[internal].ravel()
        ok = np.zeros(f.shape[0], bool)
        for fi in np.unique(f):
            sel = f == fi
            if not 0 <= int(fi) < cuts.shape[0]:
                continue  # model splits on a feature the matrix lacks:
                # definitely foreign, leave ok=False for these nodes
            ok[sel] = np.isin(c[sel], cuts[int(fi)])
        if not ok.all():
            import warnings

            warnings.warn(
                "predict on an external-memory matrix with a booster whose "
                f"split thresholds are not drawn from this matrix's cuts "
                f"({int((~ok).sum())}/{ok.size} internal nodes foreign): "
                "page-streamed features are reconstructed from cut "
                "midpoints, so decisions near thresholds may flip. "
                "Predict from an in-memory DMatrix for exact results.",
                UserWarning, stacklevel=4)

    def _forest_snapshot(self, iteration_range=None):
        """(StackedForest, tree_weights) for the current model restricted to
        ``iteration_range`` (None or (0, 0) = all rounds), LRU-cached keyed
        by (num_trees, resolved range). The stacking/padding work — host
        tree walks, pow2 padding, device transfer — happens once per model
        version, not once per predict call: this is what lets a serving
        loop issue thousands of ``inplace_predict`` calls without touching
        the tree store (reference analog: gbtree keeps its device model
        resident across PredictBatch calls, gpu_predictor.cu)."""
        self._configure()
        if iteration_range is not None and tuple(iteration_range) == (0, 0):
            iteration_range = None
        cur = self._gbm.model.num_trees
        if iteration_range is None:
            rkey = None
        else:
            lo, hi = iteration_range
            if hi == 0:
                hi = self.num_boosted_rounds()
            rkey = (int(lo), int(hi))
        key = (cur, rkey)
        with self._forest_snapshots_lock:
            hit = self._forest_snapshots.get(key)
            if hit is not None:
                self._forest_snapshots.move_to_end(key)
                _REGISTRY.counter(
                    "predict_forest_snapshot_hits_total",
                    "Predicts served from a cached stacked forest").inc()
                return hit
        _REGISTRY.counter(
            "predict_forest_snapshot_misses_total",
            "Stacked-forest (re)builds for predict").inc()
        tw = self._gbm.tree_weights()
        if rkey is None:
            forest = self._gbm.model.stacked()
        else:
            lo, hi = rkey
            forest = self._gbm.model.slice(lo, hi).stacked()
            if tw is not None:
                per_round = max(1, self._gbm.n_groups) * \
                    self._gbm.gbtree_param.num_parallel_tree
                tw = tw[lo * per_round: hi * per_round]
        with self._forest_snapshots_lock:
            self._forest_snapshots[key] = (forest, tw)
            while len(self._forest_snapshots) > 4:
                self._forest_snapshots.popitem(last=False)
        return forest, tw

    def _predict_margin(self, dmat: DMatrix, iteration_range=None,
                        native_ok: bool = False) -> jax.Array:
        """``native_ok`` (ISSUE 15 tentpole (d)): the EVAL path
        (``_eval_set``) routes its per-round walks through the
        ``predict_walk`` kernel dispatch op — the same table the serving
        plane resolves, which on CPU picks the native SoA walker
        (order-of-magnitude faster than the XLA gather walk; pin away
        with ``XGBTPU_DISPATCH=predict_walk=xla``). Everything else —
        the training margin read (``_cached_margin``) AND the public
        ``predict`` path — keeps ``native_ok=False``: the native walker
        accumulates in double (≈1 ulp off the device path), gradients
        must stay byte-stable so resumed runs remain bit-identical, and
        ``predict`` results must be bit-stable regardless of
        prediction-cache state (cached margins are device-accumulated;
        tests/test_c_api.py pins fresh-load vs cached equality)."""
        self._configure()
        n = dmat.num_row()
        base = self._base_margin_for(dmat, n)
        from .predictor import predict_margin as _pm_xla
        from .predictor import walk_margin as _pm_walk

        _pm = _pm_walk if native_ok else _pm_xla
        # conservative taint marker for cache entries the dispatch route
        # MAY have filled through the native walker. Deliberately NOT
        # keyed on the backend: device platforms route to the native
        # walker too when pallas_predict is degraded (the dispatch
        # table's reason="degraded" fallback), and an untainted native
        # fill there would feed ~1-ulp-off margins to _cached_margin.
        # The cost of over-tainting is one XLA recompute if a
        # native_ok=False reader ever consumes such an entry — rare
        # (training keeps dtrain's cache current itself).
        _taints = native_ok
        if iteration_range is not None and self._gbm.name in ("gbtree", "dart"):
            stacked, tw = self._forest_snapshot(iteration_range)
            parts = [_pm(stacked, X, base[blo:bhi], tw)
                     for blo, bhi, X in self._data_blocks(dmat)]
            return jnp.concatenate(parts, axis=0) if len(parts) > 1 else parts[0]
        # cache fast path for full-model predictions, with INCREMENTAL
        # catch-up: only trees not yet folded into the cache are walked
        # (reference: gbtree.cc:519 'cache hit? only new trees applied').
        # DART is excluded — dropout rescales old trees every round.
        entry = self._caches.get(id(dmat))
        cur = self._gbm.model.num_trees if hasattr(self._gbm, "model") else -1
        if (entry is not None and entry.margin is not None
                and entry.num_trees == cur
                and (native_ok or not entry.native)):
            return entry.margin
        K = self.n_groups
        per_round = max(1, K) * (
            self._gbm.gbtree_param.num_parallel_tree
            if hasattr(self._gbm, "gbtree_param")
            else 1
        )
        if (
            entry is not None
            and self._gbm.name == "gbtree"
            and entry.margin is not None
            and 0 < entry.num_trees < cur
            and (native_ok or not entry.native)
            # far behind (e.g. predicting after a long training run with no
            # intermediate evals): one full pass beats replaying per-round
            and cur - entry.num_trees <= 16 * per_round
        ):
            model = self._gbm.model
            while entry.num_trees < cur:
                hi = min(entry.num_trees + per_round, cur)
                # stacked_slice keeps device trees on device — no host
                # materialization from inside the eval loop; data streams
                # in blocks (pages / CSR row blocks / one dense array), so
                # out-of-core eval sets catch up in O(new trees) too
                sub = model.stacked_slice(entry.num_trees, hi)
                parts = [
                    _pm(sub, X, jnp.zeros((bhi - blo, K), jnp.float32))
                    for blo, bhi, X in self._data_blocks(dmat)
                ]
                delta = (jnp.concatenate(parts, axis=0) if len(parts) > 1
                         else parts[0])
                entry.margin = entry.margin + delta
                entry.num_trees = hi
                entry.native = entry.native or _taints
            return entry.margin
        if cur == 0:
            # empty model: don't touch dmat.data (streaming matrices
            # reconstruct raw values lazily — the zero-tree margin is base)
            margin = base
        elif native_ok and self._gbm.name in ("gbtree", "dart"):
            # full pass through the dispatch-routed walker (the gbm's own
            # predict stays on the XLA walk — gradient numerics)
            stacked = self._gbm.model.stacked()
            tw = self._gbm.tree_weights()
            parts = [_pm_walk(stacked, X, base[blo:bhi], tw)
                     for blo, bhi, X in self._data_blocks(dmat)]
            margin = (jnp.concatenate(parts, axis=0) if len(parts) > 1
                      else parts[0] if parts else base)
        else:
            # stream whatever the matrix is backed by: quantized disk
            # pages, CSR row blocks, or one dense array (_data_blocks)
            parts = [self._gbm.predict(X, base[blo:bhi])
                     for blo, bhi, X in self._data_blocks(dmat)]
            margin = (jnp.concatenate(parts, axis=0) if len(parts) > 1
                      else parts[0] if parts else base)
        if entry is not None and self._gbm.name == "gbtree":
            entry.margin = margin
            entry.num_trees = cur
            entry.native = _taints
        return margin

    def predict(
        self,
        data: DMatrix,
        output_margin: bool = False,
        pred_leaf: bool = False,
        pred_contribs: bool = False,
        approx_contribs: bool = False,
        pred_interactions: bool = False,
        validate_features: bool = True,
        training: bool = False,
        iteration_range: Optional[Tuple[int, int]] = None,
        strict_shape: bool = False,
        ntree_limit: int = 0,
    ) -> np.ndarray:
        with _trace.span("predict", rows=data.num_row()):
            return self._predict(
                data, output_margin, pred_leaf, pred_contribs,
                approx_contribs, pred_interactions, validate_features,
                training, iteration_range, strict_shape, ntree_limit)

    def _predict(
        self,
        data: DMatrix,
        output_margin: bool = False,
        pred_leaf: bool = False,
        pred_contribs: bool = False,
        approx_contribs: bool = False,
        pred_interactions: bool = False,
        validate_features: bool = True,
        training: bool = False,
        iteration_range: Optional[Tuple[int, int]] = None,
        strict_shape: bool = False,
        ntree_limit: int = 0,
    ) -> np.ndarray:
        self._configure()
        if ntree_limit and iteration_range is None:
            per_round = max(1, self.n_groups) * (
                self._gbm.gbtree_param.num_parallel_tree
                if hasattr(self._gbm, "gbtree_param")
                else 1
            )
            iteration_range = (0, max(1, ntree_limit // per_round))
        if self._gbm.name == "gblinear":
            if pred_leaf:
                raise ValueError(
                    "gblinear does not support prediction of leaf index")
            if pred_interactions:
                # linear models have no interaction effects: zeros with
                # the contribs' shape convention (gblinear.cc:214)
                n = data.num_row()
                F = self.num_features()
                K = max(1, self.n_groups)
                shape = (n, F + 1, F + 1) if K == 1 else (n, K, F + 1,
                                                          F + 1)
                return np.zeros(shape, np.float32)
            if pred_contribs:
                return self._gblinear_contribs(data)
        if pred_leaf:
            parts = [np.asarray(self._gbm.predict_leaf(X))
                     for _, _, X in self._data_blocks(data)]
            return np.concatenate(parts, axis=0) if len(parts) > 1 else parts[0]
        if pred_contribs or pred_interactions:
            from .interpret import predict_contribs, predict_interactions

            if pred_interactions:
                return predict_interactions(self, data)
            return predict_contribs(self, data, approx=approx_contribs)
        margin = self._predict_margin(data, iteration_range)
        if output_margin:
            out = margin
        else:
            out = self._obj.pred_transform(margin[:, 0] if self.n_groups == 1 else margin)
        out = np.asarray(out)
        if out.ndim == 2 and out.shape[1] == 1 and not strict_shape:
            out = out[:, 0]
        return out

    def _inplace_normalize(self, data, missing):
        """Raw input -> [n, F] float32 with NaN missing, with the minimum
        copying the dtype/missing semantics allow. Returns None for inputs
        the zero-copy path does not understand (those take the DMatrix
        fallback)."""
        if hasattr(data, "tocsr") and hasattr(data, "nnz"):
            # scipy CSR/CSC/COO: normalize stored values (user sentinel ->
            # NaN; absent entries are missing) but keep the CSR structure —
            # the native serving walker consumes it without densification
            # (same semantics as DMatrix ingestion, data/sparse.py)
            from .data.sparse import CSRStorage

            return CSRStorage(data, missing)
        if isinstance(data, np.ndarray) and data.ndim == 2:
            X = data
            if X.dtype != np.float32:
                X = X.astype(np.float32)
            if missing is not None and not (
                isinstance(missing, float) and np.isnan(missing)
            ):
                X = np.where(X == missing, np.nan, X)
            return np.ascontiguousarray(X)
        if isinstance(data, (list, tuple)):
            return self._inplace_normalize(
                np.asarray(data, np.float32), missing)
        return None

    def inplace_predict(self, data, iteration_range=None,
                        predict_type="value", missing=np.nan,
                        base_margin=None, validate_features=True,
                        strict_shape=False):
        """In-place predict from raw arrays — no DMatrix, no quantile work,
        no copy of the input beyond the device transfer (reference:
        ``XGBoosterPredictFromDense/CSR``, c_api.cc:833, and core.py
        ``Booster.inplace_predict``).

        Serving-grade: rows pad up to a power-of-two bucket and the
        compiled program is cached per (bucket, forest-shape, output-kind)
        with an LRU bound, so a stream of ragged batch sizes never
        recompiles (``predictor/serving.py``; cache counters live in the
        observability registry). The stacked forest itself is snapshotted
        per (num_trees, iteration_range) on this Booster. ``predict_type``
        is ``"value"`` (transformed, fused into the program) or
        ``"margin"``; anything else raises — leaf/contribution outputs go
        through :meth:`predict`."""
        self._configure()
        if predict_type not in ("value", "margin"):
            raise ValueError(
                f"inplace_predict supports predict_type 'value' and "
                f"'margin', got {predict_type!r}; use Booster.predict for "
                "leaf/contribution outputs")
        if iteration_range is not None and tuple(iteration_range) == (0, 0):
            iteration_range = None
        X = (self._inplace_normalize(data, missing)
             if self._gbm.name in ("gbtree", "dart") else None)
        if X is None:
            d = DMatrix(data, missing=missing)
            if base_margin is not None:
                d.set_base_margin(base_margin)
            return self.predict(
                d, output_margin=(predict_type == "margin"),
                iteration_range=iteration_range, strict_shape=strict_shape)
        n, F = X.shape
        if validate_features:
            # _num_feature() from a loaded model is max(split index)+1 — a
            # LOWER bound on the training width — so only narrower inputs
            # are definitely wrong (the walk would gather out of range)
            nf = self._num_feature()
            if nf and F < nf:
                raise ValueError(
                    f"feature count mismatch: model needs >= {nf} "
                    f"features, input has {F}")
        K = self.n_groups
        if base_margin is not None:
            base = np.asarray(base_margin, np.float32).reshape(n, K)
        else:
            base = np.full((n, K), self._base_margin_val, np.float32)
        forest, tw = self._forest_snapshot(iteration_range)
        from .predictor.serving import predict_serving

        transform = (None if predict_type == "margin"
                     else self._obj.pred_transform)
        out = predict_serving(forest, X, base, tw, transform=transform)
        if out.ndim == 2 and out.shape[1] == 1 and not strict_shape:
            out = out[:, 0]
        elif strict_shape and out.ndim == 1:
            out = out.reshape(n, 1)
        return out

    # ------------------------------------------------------------------
    # model IO (XGBoost-JSON-schema-compatible layout, doc/model.schema)
    # ------------------------------------------------------------------
    def save_json(self) -> dict:
        self._configure()
        # feature metadata: live training data wins, else whatever a loaded
        # model carried (so load -> save preserves names, like reference
        # LearnerIO)
        fn, ft = self._feature_meta()
        learner = {
            "feature_names": list(fn),
            "feature_types": list(ft),
            "learner_model_param": {
                "base_score": str(
                    self.lparam.base_score
                    if self.lparam.base_score is not None
                    else self._obj.default_base_score()
                ),
                "num_class": str(self.lparam.num_class),
                "num_feature": str(self._num_feature()),
            },
            "objective": {"name": self._obj.name},
            "gradient_booster": self._gbm.save_json(),
            "attributes": dict(self.attributes_),
        }
        return {"version": _VERSION, "learner": learner}

    def _num_feature(self) -> int:
        for d in self._cache_refs.values():
            return d.num_col()
        # a loaded model's learner_model_param carries the exact training
        # width (reference LearnerModelParam::num_feature) — prefer it
        # over the max-split-index lower bound, so serving-side width
        # validation can be exact after a save/load round trip
        if getattr(self, "_loaded_num_feature", 0):
            return int(self._loaded_num_feature)
        if getattr(self._gbm, "model", None) and self._gbm.model.trees:
            return int(max(t.split_indices.max(initial=0) for t in self._gbm.model.trees) + 1)
        return 0

    def save_raw(self, raw_format: str = "json") -> bytes:
        return json.dumps(self.save_json()).encode()

    def save_model(self, fname: Union[str, os.PathLike]) -> None:
        with open(fname, "w") as f:
            json.dump(self.save_json(), f)

    def load_json(self, j: dict) -> None:
        learner = j["learner"]
        lmp = learner["learner_model_param"]
        self.lparam.update(
            {
                "base_score": float(lmp["base_score"]),
                "num_class": int(lmp.get("num_class", 0)),
                "objective": learner["objective"]["name"],
            }
        )
        self._obj = None
        self._gbm = None
        self._configure()
        gb = learner["gradient_booster"]
        name = gb.get("name", "gbtree")
        if name != self.lparam.booster:
            self.lparam.update({"booster": name})
            self._gbm = None
            self._configure()
        self._gbm.load_json(gb)
        self.attributes_ = dict(learner.get("attributes", {}))
        try:
            self._loaded_num_feature = int(lmp.get("num_feature", 0))
        except (TypeError, ValueError):
            self._loaded_num_feature = 0
        self._loaded_feature_names = list(learner.get("feature_names", []))
        self._loaded_feature_types = list(learner.get("feature_types", []))
        self._caches.clear()
        self._forest_snapshots.clear()

    def load_model(self, fname: Union[str, bytes, os.PathLike]) -> None:
        if isinstance(fname, (bytes, bytearray)):
            self.load_json(json.loads(fname.decode()))
            return
        with open(fname) as f:
            self.load_json(json.load(f))

    def __getstate__(self):
        # full pickle round-trip incl. config (reference:
        # XGBoosterSerializeToBuffer / test_pickling.py)
        state = {
            "model": self.save_json() if self._gbm is not None else None,
            "lparam": self.lparam.to_dict(),
            # which keys the user actually set: replaying to_dict() through
            # update() would mark every DEFAULT explicit, breaking
            # explicitness-gated defaults (Poisson's max_delta_step 0.7)
            "lparam_explicit": sorted(self.lparam._explicit),
            "extra": dict(self._extra_params),
            "attributes": dict(self.attributes_),
        }
        return state

    def __setstate__(self, state):
        self.__init__()
        self.lparam.update({k: v for k, v in state["lparam"].items() if v is not None})
        self.lparam._explicit = set(
            state.get("lparam_explicit", state["lparam"]))
        self._extra_params = dict(state["extra"])
        self.attributes_ = dict(state["attributes"])
        if state["model"] is not None:
            self.load_json(state["model"])

    def copy(self) -> "Booster":
        import copy as _copy

        return _copy.deepcopy(self)

    def __copy__(self):
        return self.copy()

    def __deepcopy__(self, memo):
        b = Booster()
        b.__setstate__(json.loads(json.dumps(self.__getstate__(), default=float)))
        return b

    # ------------------------------------------------------------------
    # introspection
    # ------------------------------------------------------------------
    def num_boosted_rounds(self) -> int:
        self._configure()
        if self._gbm.name in ("gbtree", "dart"):
            per_round = max(1, self.n_groups) * self._gbm.gbtree_param.num_parallel_tree
            return self._gbm.model.num_trees // per_round
        return getattr(self._gbm, "n_rounds", 0)

    def num_features(self) -> int:
        return self._num_feature()

    def attr(self, key: str) -> Optional[str]:
        return self.attributes_.get(key)

    def set_attr(self, **kwargs) -> None:
        for k, v in kwargs.items():
            if v is None:
                self.attributes_.pop(k, None)
            else:
                self.attributes_[k] = str(v)

    def attributes(self) -> Dict[str, str]:
        return dict(self.attributes_)

    # ------------------------------------------------------------------
    # feature metadata properties + config IO (reference core.py
    # Booster.feature_names/feature_types, save_config/load_config —
    # XGBoosterSaveJsonConfig / learner.cc:SaveConfig)
    # ------------------------------------------------------------------
    @property
    def feature_names(self) -> Optional[List[str]]:
        return self._feature_meta()[0] or None

    @feature_names.setter
    def feature_names(self, names) -> None:
        self._loaded_feature_names = list(names) if names else []
        for d in self._cache_refs.values():
            d.feature_names = list(names) if names else None

    @property
    def feature_types(self) -> Optional[List[str]]:
        return self._feature_meta()[1] or None

    @feature_types.setter
    def feature_types(self, types) -> None:
        self._loaded_feature_types = list(types) if types else []

    def save_config(self) -> str:
        """JSON string of the learner's configuration (reference
        XGBoosterSaveJsonConfig). Covers the learner-level ParamSet, the
        booster/tree params, and the objective — enough for load_config to
        reconstruct an equivalently-configured Booster."""
        self._configure()
        cfg = {
            "version": list(_VERSION),
            "learner": {
                "learner_train_param": self.lparam.to_dict(),
                "gradient_booster": {
                    "name": self._gbm.name,
                    "params": dict(self._extra_params),
                },
                "objective": {"name": self._obj.name},
            },
        }
        return json.dumps(cfg)

    def load_config(self, config: str) -> None:
        c = json.loads(config)
        learner = c.get("learner", {})
        self._apply_params(dict(learner.get("learner_train_param", {})))
        gb = learner.get("gradient_booster", {})
        if gb.get("name"):
            self._apply_params({"booster": gb["name"]})
        self._apply_params(dict(gb.get("params", {})))
        obj = learner.get("objective", {})
        if obj.get("name"):
            self._apply_params({"objective": obj["name"]})
        # rebuild lazily with the new configuration
        if self._gbm is not None:
            for k, v in {**gb.get("params", {})}.items():
                try:
                    self._gbm.set_param(k, v)
                except Exception:
                    pass
        self._metrics = []

    def get_split_value_histogram(self, feature: str, fmap: str = "",
                                  bins: Optional[int] = None,
                                  as_pandas: bool = True):
        """Histogram of a feature's used split values (reference
        ``core.py:2508`` — it regexes the text dump; here the SoA trees are
        read directly). Categorical-split features raise like the
        reference."""
        self._configure()
        names = self._parse_fmap(fmap) or self._feature_meta()[0]
        try:
            fidx = int(feature[1:]) if (not names and feature.startswith("f")
                                        and feature[1:].isdigit()) \
                else names.index(feature)
        except (ValueError, AttributeError):
            raise ValueError(f"unknown feature: {feature!r}")
        values: List[float] = []
        is_cat = False
        for t in self._gbm.model.trees:
            internal = t.left_children != -1
            mask = internal & (t.split_indices == fidx)
            if t.split_type is not None and bool(
                    (np.asarray(t.split_type)[mask] != 0).any()):
                is_cat = True
                continue
            values.extend(float(v) for v in t.split_conditions[mask])
        if not values and is_cat:
            raise ValueError(
                "Split value historgam doesn't support categorical split."
            )
        n_unique = len(np.unique(values))
        bins = max(min(n_unique, bins) if bins is not None else n_unique, 1)
        nph = np.histogram(values, bins=bins)
        nph = np.column_stack((nph[1][1:], nph[0]))
        nph = nph[nph[:, 1] > 0]
        if as_pandas:
            try:
                import pandas as pd

                return pd.DataFrame(nph, columns=["SplitValue", "Count"])
            except ImportError:
                pass
        return nph

    def _feature_meta(self):
        """(feature_names, feature_types) from the first cached matrix
        carrying ANY feature metadata — both fields from the SAME source so
        they always describe one schema — falling back to what a loaded
        model carried."""
        for d in self._cache_refs.values():
            if d.feature_names or getattr(d.info, "feature_types", None):
                return (list(d.feature_names or []),
                        list(d.info.feature_types or []))
        return (list(getattr(self, "_loaded_feature_names", []) or []),
                list(getattr(self, "_loaded_feature_types", []) or []))

    @staticmethod
    def _parse_fmap_full(fmap: str
                         ) -> Optional[Tuple[List[str], List[str]]]:
        """featmap.txt parsing ('<id> <name> <type>' per line — reference
        core.py FeatureMap); (names, types) or None when no file is given.
        Types follow the reference vocabulary: i / q / int / float / c.
        A nonexistent path is an error, matching the reference
        (tests/python/test_basic.py::test_dump expects ValueError)."""
        if not fmap:
            return None
        if not os.path.exists(fmap):
            raise ValueError(f"No such featmap file: {fmap!r}")
        names: Dict[int, str] = {}
        types: Dict[int, str] = {}
        with open(fmap) as f:
            for line in f:
                ps = line.split()
                if len(ps) >= 2:
                    names[int(ps[0])] = ps[1]
                    if len(ps) >= 3:
                        types[int(ps[0])] = ps[2]
        if not names:
            return None
        n = max(names) + 1
        return ([names.get(i, f"f{i}") for i in range(n)],
                [types.get(i, "q") for i in range(n)])

    @classmethod
    def _parse_fmap(cls, fmap: str) -> Optional[List[str]]:
        parsed = cls._parse_fmap_full(fmap)
        return parsed[0] if parsed else None

    def get_dump(self, fmap: str = "", with_stats: bool = False, dump_format: str = "text") -> List[str]:
        """Per-tree dump strings in the reference's generator formats
        (src/tree/tree_model.cc: text :235, json :362 — the per-node
        nodeid/split/children structure downstream parsers consume — and
        ``dot``/``dot:{attrs-json}`` :550). featmap types drive the same
        per-type formatting ('i' indicator, 'int' ceil'd threshold)."""
        self._configure()
        parsed = self._parse_fmap_full(fmap)
        names, types = parsed if parsed else (None, None)
        if not names:
            meta_names, meta_types = self._feature_meta()
            names = meta_names or None
            types = types or (meta_types or None)
        if self._gbm.name == "gblinear":
            # one dump string: bias then per-feature weights
            # (gblinear_model.h:99 DumpModel)
            w = np.asarray(self._gbm.weights)  # [F+1, K], last row = bias
            bias, wt = w[-1], w[:-1]
            if dump_format == "json":
                return [json.dumps(
                    {"bias": [float(b) for b in bias],
                     "weight": [float(v) for row in wt for v in row]},
                    indent=2)]
            lines = ["bias:"] + [f"{float(b):.6g}" for b in bias] + \
                ["weight:"] + [f"{float(v):.6g}" for row in wt for v in row]
            return ["\n".join(lines) + "\n"]
        out = []
        for t in self._gbm.model.trees:
            if dump_format == "json":
                out.append(t.dump_json_ref(names, with_stats, types))
            elif dump_format == "text":
                out.append(t.dump_text(names, with_stats, types))
            elif dump_format.startswith("dot"):
                attrs = None
                if dump_format.startswith("dot:"):
                    attrs = json.loads(dump_format[4:])
                out.append(t.dump_dot(names, types, attrs))
            else:
                raise ValueError(f"Unknown dump format: {dump_format!r}")
        return out

    def dump_model(self, fout, fmap: str = "", with_stats: bool = False, dump_format: str = "text") -> None:
        dumps = self.get_dump(fmap, with_stats, dump_format)
        with open(fout, "w") as f:
            if dump_format == "json":
                f.write("[\n" + ",\n".join(dumps) + "\n]")
            else:
                for i, d in enumerate(dumps):
                    f.write(f"booster[{i}]:\n{d}\n")

    def _gblinear_contribs(self, data: DMatrix) -> np.ndarray:
        """Per-feature linear contributions (gblinear.cc:176
        PredictContribution): present entries contribute x_f * w_f
        (missing contribute 0), and the last column is bias + base
        margin. [n, F+1], or [n, K, F+1] for multiple output groups."""
        w = np.asarray(self._gbm.weights)  # [F+1, K]
        X = np.asarray(data.data, np.float32)
        n, F = X.shape
        K = w.shape[1]
        Xz = np.nan_to_num(X, nan=0.0)
        base = self._base_margin_val
        out = np.empty((n, K, F + 1), np.float32)
        for g in range(K):
            out[:, g, :F] = Xz * w[None, :F, g].reshape(1, F)
            out[:, g, F] = w[F, g] + base
        return out[:, 0, :] if K == 1 else out

    def get_score(self, fmap: str = "", importance_type: str = "weight") -> Dict[str, float]:
        """Feature importances (reference: CalcFeatureScore learner.cc)."""
        self._configure()
        if self._gbm.name == "gblinear":
            # reference gblinear.cc:240: only 'weight' is defined, and the
            # scores ARE the per-feature coefficients (bias excluded)
            if importance_type != "weight":
                raise ValueError(
                    "gblinear only has `weight` defined for feature "
                    "importance")
            w = np.asarray(self._gbm.weights)[:-1]  # [F, K]
            names = self._parse_fmap(fmap) or self._feature_meta()[0] or None

            def lname(f: int) -> str:
                return names[f] if names and f < len(names) else f"f{f}"

            if w.shape[1] == 1:
                return {lname(f): float(w[f, 0]) for f in range(w.shape[0])}
            return {f"{lname(f)}_g{g}": float(w[f, g])
                    for f in range(w.shape[0]) for g in range(w.shape[1])}
        gain: Dict[int, float] = {}
        cover: Dict[int, float] = {}
        weight: Dict[int, float] = {}
        for t in self._gbm.model.trees:
            internal = t.left_children != -1
            for f, g, c in zip(
                t.split_indices[internal], t.loss_changes[internal], t.sum_hessian[internal]
            ):
                f = int(f)
                weight[f] = weight.get(f, 0.0) + 1.0
                gain[f] = gain.get(f, 0.0) + float(g)
                cover[f] = cover.get(f, 0.0) + float(c)
        names = self._parse_fmap(fmap) or self._feature_meta()[0] or None

        def nm(f: int) -> str:
            return names[f] if names and f < len(names) else f"f{f}"

        if importance_type == "weight":
            return {nm(f): v for f, v in weight.items()}
        if importance_type == "total_gain":
            return {nm(f): v for f, v in gain.items()}
        if importance_type == "total_cover":
            return {nm(f): v for f, v in cover.items()}
        if importance_type == "gain":
            return {nm(f): gain[f] / weight[f] for f in gain}
        if importance_type == "cover":
            return {nm(f): cover[f] / weight[f] for f in cover}
        raise ValueError(f"Unknown importance_type: {importance_type}")

    def get_fscore(self, fmap: str = "") -> Dict[str, float]:
        return self.get_score(fmap, "weight")

    def __getitem__(self, val) -> "Booster":
        """Layer slicing (reference: Learner::Slice)."""
        self._configure()
        if self._gbm.name == "gblinear":
            # reference gbm.h:70: the base GradientBooster::Slice fails;
            # only tree boosters implement it
            raise ValueError("Slice is not supported by current booster.")
        if isinstance(val, int):
            val = slice(val, val + 1)
        start = val.start or 0
        stop = val.stop if val.stop is not None else self.num_boosted_rounds()
        step = val.step or 1
        self._configure()
        out = self.copy()
        out._gbm.model = out._gbm.model.slice(start, stop, step)
        out._caches.clear()
        out._forest_snapshots.clear()
        return out

    def trees_to_dataframe(self, fmap: str = ""):
        import pandas as pd

        self._configure()
        if self._gbm.name not in ("gbtree", "dart"):
            raise ValueError(
                "This method is not defined for Booster type "
                f"{self._gbm.name}")
        rows = []
        for ti, t in enumerate(self._gbm.model.trees):
            for i in range(t.num_nodes):
                leaf = t.left_children[i] == -1
                rows.append(
                    {
                        "Tree": ti,
                        "Node": i,
                        "ID": f"{ti}-{i}",
                        "Feature": "Leaf" if leaf else f"f{t.split_indices[i]}",
                        "Split": None if leaf else float(t.split_conditions[i]),
                        "Yes": None if leaf else f"{ti}-{t.left_children[i]}",
                        "No": None if leaf else f"{ti}-{t.right_children[i]}",
                        "Missing": None
                        if leaf
                        else (
                            f"{ti}-{t.left_children[i]}"
                            if t.default_left[i]
                            else f"{ti}-{t.right_children[i]}"
                        ),
                        "Gain": float(t.split_conditions[i]) if leaf else float(t.loss_changes[i]),
                        "Cover": float(t.sum_hessian[i]),
                    }
                )
        return pd.DataFrame(rows)
