"""Distributed tree growth: shard_map over the row axis.

This is the TPU realization of the reference's inter-node data-parallel
strategy (SURVEY.md §2.11 item 3): each device holds a row shard, the model
is replicated, and the only hot-loop synchronization is the per-level
histogram AllReduce — ``jax.lax.psum`` inside ``grow_tree`` (the analog of
``SyncHistogramDistributed`` hist/histogram.h:201 and ``AllReduceHist``
updater_gpu_hist.cu:526). Histogram size is independent of row count, so
collective cost stays constant as data scales — the same property the
reference's design relies on.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..tree.grow import GrowParams, HeapTree, grow_tree
from ..tree.grow_fused import GrownTree, grow_tree_fused
from ..tree.grow_lossguide import AllocTree, grow_tree_lossguide
from .mesh import ROW_AXIS


def _row_sharded_call(mesh, grower, out_specs, args, feature_weights):
    """shard_map a grower: rows sharded, cuts/key/feature_weights
    replicated. feature_weights joins the traced args only when present so
    the None default stays bit-identical with the single-device path."""
    in_specs = [P(ROW_AXIS, None), P(ROW_AXIS), P(ROW_AXIS), P(None, None), P()]
    if feature_weights is not None:
        in_specs.append(P())
        args = args + (feature_weights,)
    fn = jax.shard_map(
        grower,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=out_specs,
        check_vma=True,
    )
    return fn(*args)


def distributed_grow_tree(
    mesh: Mesh,
    bins: jax.Array,  # [n, F] row-sharded (n divisible by mesh size)
    grad: jax.Array,  # [n] row-sharded
    hess: jax.Array,
    cut_values: jax.Array,  # [F, B] replicated
    key: jax.Array,
    cfg: GrowParams,
    feature_weights: Optional[jax.Array] = None,  # [F] replicated
) -> HeapTree:
    """Grow one tree over row shards. Tree tensors come back replicated
    (bitwise identical on every device — the property the reference asserts
    with gpu_hist's debug_synchronize, updater_gpu_hist.cu:49); row
    positions stay sharded."""
    import dataclasses

    from ..observability import comms, trace

    cfg_dist = dataclasses.replace(cfg, axis_name=ROW_AXIS)

    # Build the out_specs programmatically from HeapTree._fields so the
    # spec can never drift from the NamedTuple definition: every tree
    # tensor comes back replicated, only per-row positions stay sharded.
    out_specs = HeapTree(
        **{f: (P(ROW_AXIS) if f == "positions" else P()) for f in HeapTree._fields}
    )
    comms.record_grow_collectives(cfg.max_depth, bins.shape[1],
                                  cut_values.shape[1])
    with trace.span("distributed_grow_tree", depth=cfg.max_depth):
        return _row_sharded_call(
            mesh, partial(grow_tree, cfg=cfg_dist), out_specs,
            (bins, grad, hess, cut_values, key), feature_weights,
        )


def distributed_grow_tree_fused(
    mesh: Mesh,
    bins: jax.Array,  # [n_pad, F] int32 row-sharded (missing == B padding)
    grad: jax.Array,  # [n_pad] row-sharded (pad rows zero)
    hess: jax.Array,
    cut_values: jax.Array,  # [F, B] replicated
    key: jax.Array,
    eta: float,
    gamma: float,
    cfg: GrowParams,
    feature_weights: Optional[jax.Array] = None,
    onehot: Optional[jax.Array] = None,  # [n_pad, Fh*B] int8 row-sharded
) -> GrownTree:
    """The fused fast-path grower over row shards: per-level histograms and
    root totals are psum'd inside ``grow_tree_fused`` (the reference's two
    collective sites, hist/histogram.h:201 + InitRoot); tree tensors come
    back replicated, the per-row cache delta stays sharded.

    ``onehot`` is the PRE-BUILT row-sharded hoisted expansion
    (``BinnedMatrix.fused_onehot_mesh`` — one build per (fit, mesh), not
    one per tree; review r4 weak #5): it enters the shard_map as a
    row-sharded operand, so each device streams its own resident shard."""
    import dataclasses

    from ..observability import comms

    comms.record_grow_collectives(cfg.max_depth, bins.shape[1],
                                  cut_values.shape[1])
    cfg_dist = dataclasses.replace(cfg, axis_name=ROW_AXIS)
    out_specs = GrownTree(
        **{f: (P(ROW_AXIS) if f == "delta" else P()) for f in GrownTree._fields}
    )
    use_oh = onehot is not None and not cfg.has_categorical

    def grower(bins_s, g_s, h_s, cuts_s, key_s, eta_s, gamma_s, *rest):
        rest = list(rest)
        oh_s = rest.pop(0) if use_oh else None
        fw = rest.pop(0) if rest else None
        return grow_tree_fused(bins_s, g_s, h_s, cuts_s, key_s, eta_s,
                               gamma_s, cfg=cfg_dist, feature_weights=fw,
                               onehot=oh_s)

    in_specs = [P(ROW_AXIS, None), P(ROW_AXIS), P(ROW_AXIS), P(None, None),
                P(), P(), P()]
    args = (bins, grad, hess, cut_values, key, eta, gamma)
    if use_oh:
        in_specs.append(P(ROW_AXIS, None))
        args = args + (onehot,)
    if feature_weights is not None:
        in_specs.append(P())
        args = args + (feature_weights,)
    fn = jax.shard_map(
        grower, mesh=mesh, in_specs=tuple(in_specs), out_specs=out_specs,
        check_vma=True,
    )
    return fn(*args)


def distributed_grow_tree_lossguide(
    mesh: Mesh,
    bins: jax.Array,  # [n, F] row-sharded
    grad: jax.Array,
    hess: jax.Array,
    cut_values: jax.Array,  # [F, B] replicated
    key: jax.Array,
    cfg: GrowParams,
    max_leaves: int,
    feature_weights: Optional[jax.Array] = None,  # [F] replicated
) -> AllocTree:
    """Lossguide growth over row shards: per-step child histograms are
    psum'd, the priority queue runs identically on every device (the
    single-best-candidate argmax is deterministic on the reduced
    histograms), so tree tensors come back replicated."""
    import dataclasses

    from ..observability import comms

    # lossguide reduces one [F, 2, B] child-pair histogram per expansion
    # step (max_leaves - 1 splits) rather than whole levels
    comms.record(
        "psum_hist",
        max(max_leaves - 1, 1) * bins.shape[1] * 2 * cut_values.shape[1] * 4,
        n_ops=max(max_leaves - 1, 1),
    )
    cfg_dist = dataclasses.replace(cfg, axis_name=ROW_AXIS)
    out_specs = AllocTree(
        **{f: (P(ROW_AXIS) if f == "positions" else P()) for f in AllocTree._fields}
    )
    return _row_sharded_call(
        mesh, partial(grow_tree_lossguide, cfg=cfg_dist, max_leaves=max_leaves),
        out_specs, (bins, grad, hess, cut_values, key), feature_weights,
    )


def distributed_boost_rounds_scan(
    mesh: Mesh,
    obj,  # scan-safe objective (elementwise/rowwise gradient)
    bins: jax.Array,  # [n_pad, F] row-sharded narrow-int bins
    label: jax.Array,  # [n_pad] row-sharded (pad rows arbitrary)
    weight: Optional[jax.Array],  # [n_pad] row-sharded or None
    margin: jax.Array,  # [n_pad, K] row-sharded
    iters: jax.Array,  # [R] int32 iteration numbers
    cut_values: jax.Array,  # [F, B] replicated
    eta: jax.Array,
    gamma: jax.Array,
    feature_weights: Optional[jax.Array],
    seed_base: jax.Array,  # uint32
    n: int,  # real (unpadded) global row count
    cfg: GrowParams,
    onehot: Optional[jax.Array] = None,  # [n_pad, Fh*B] row-sharded, cached
    fh_plan: Optional[int] = None,  # caller's frozen synced plan
):
    """A chunk of boosting rounds over row shards as ONE program: the
    ``lax.scan`` of (gradient -> fused tree -> margin update) runs inside a
    single ``shard_map``, with the per-level histogram / root-total psums
    inside ``grow_tree_fused`` (hist/histogram.h:201's collective). Returns
    (sharded margin [n_pad, K], replicated stacked trees [R, K, ...]).

    Gradients are computed per shard (scan-safe objectives are rowwise);
    rows past ``n`` (padding) get their gradients masked to zero every
    round — the fixed-shape analog of the reference's empty-worker
    handling."""
    from ..gbm.gbtree import _obj_fingerprint
    from ..observability import comms
    from .mesh import local_device_count, replicate

    # one fused tree per group per scanned round, each with the per-level
    # histogram psums + root-total psum of grow_tree_fused
    comms.record_grow_collectives(
        cfg.max_depth, bins.shape[1], cut_values.shape[1],
        n_trees=int(iters.shape[0]) * margin.shape[1],
    )
    n_procs = jax.process_count()
    if n_procs > 1:
        # the r // d_local shard->process attribution below requires the
        # mesh device order to be process-major contiguous blocks of equal
        # size — true for make_mesh(jax.devices()); anything else would
        # SILENTLY mis-mask padding rows, so verify loudly
        pidx = [d.process_index for d in mesh.devices.flat]
        dl = local_device_count(mesh)
        ok = (len(pidx) == dl * n_procs and all(
            pidx[i] == i // dl for i in range(len(pidx))))
        if not ok:
            raise ValueError(
                "multi-process mesh must list devices process-major with "
                f"equal per-process counts; got process order {pidx}"
            )
        # per-process real row counts (the validity mask must know where
        # each PROCESS's padding tail starts — real rows are not a global
        # prefix under load_row_split ingestion), plus explicit replication
        # of the small operands: multi-process programs only accept global
        # arrays
        from .. import collective

        n_arr = jnp.asarray(collective.process_allgather(
            np.asarray(n, np.int32), site="row_counts"))
        rep = lambda x: None if x is None else replicate(  # noqa: E731
            jnp.asarray(x), mesh)
        iters, cut_values, eta, gamma, feature_weights, seed_base, n_arr = (
            rep(iters), rep(cut_values), rep(eta), rep(gamma),
            rep(feature_weights), rep(seed_base), rep(n_arr))
    else:
        n_arr = jnp.asarray([n], jnp.int32)
    if cfg.has_categorical:
        onehot, fh = None, 0
    elif onehot is not None:
        # the caller's cached per-fit expansion (BinnedMatrix.
        # fused_onehot_mesh): its width IS the (already process-synced)
        # plan, and passing it as an operand means chunks — per ROUND
        # under train()'s chunk=1 routing — never replan (a blocking
        # allgather) or rebuild (multi-GB of HBM writes)
        fh = onehot.shape[1] // cut_values.shape[1]
    elif fh_plan is not None:
        # the caller's frozen plan with no resident expansion (plan 0, or
        # a standalone caller managing its own build): no per-chunk
        # allgather, no free-HBM drift flipping this jit static arg
        fh = fh_plan
    else:
        from ..tree.hist_kernel import hoist_plan_synced

        # no caller plan (direct/test callers): per-shard plan decided
        # OUTSIDE the jit and agreed across processes (min over ranks) —
        # it is baked statically into the traced SPMD program, and ranks
        # can see different free HBM. The shard_fn then builds per
        # dispatch.
        D = mesh.devices.size
        fh = hoist_plan_synced(margin.shape[0] // D, bins.shape[1],
                               cut_values.shape[1], cfg.max_depth)
    return _dist_scan_impl(
        bins, label, weight, margin, iters, cut_values, eta, gamma,
        feature_weights, seed_base, n_arr, onehot, mesh=mesh, obj=obj,
        obj_fp=_obj_fingerprint(obj), cfg=cfg,
        d_local=local_device_count(mesh), fh=fh,
    )


@partial(jax.jit, static_argnames=("mesh", "obj", "obj_fp", "cfg",
                                   "d_local", "fh"))
def _dist_scan_impl(bins, label, weight, margin, iters, cut_values, eta,
                    gamma, feature_weights, seed_base, n_arr, onehot, *,
                    mesh, obj, obj_fp, cfg, d_local, fh):
    import dataclasses

    import jax.numpy as jnp
    import jax.tree_util as jtu

    from ..gbm.gbtree import round_seed_traced

    from ..tree.hist_kernel import build_onehot

    cfg_dist = dataclasses.replace(cfg, axis_name=ROW_AXIS)
    D = mesh.devices.size
    n_pad, K = margin.shape
    rows_local = n_pad // D
    B = cut_values.shape[1]

    def shard_fn(bins_s, label_s, weight_s, m_s, fw, n_a, oh_s):
        r = jax.lax.axis_index(ROW_AXIS)
        # shard r belongs to process r // d_local; its real-row budget is
        # that process's count, measured within the process's block
        q = r % d_local
        n_own = n_a[r // d_local]
        valid = (q * rows_local
                 + jax.lax.broadcasted_iota(jnp.int32, (rows_local, 1), 0)[:, 0]
                 ) < n_own
        validf = valid.astype(jnp.float32)
        if oh_s is not None:
            onehot_s = oh_s
        else:
            onehot_s = (build_onehot(bins_s[:, :fh], B=B, vma=(ROW_AXIS,))
                        if fh else None)

        # the same ``xgb.<phase>`` scopes as the one-chip scan body
        # (gbtree._scan_rounds_impl); the tree's own are in grow_tree_fused
        def body(m_loc, i):
            with jax.named_scope("xgb.gradient"):
                m = m_loc[:, 0] if K == 1 else m_loc
                g, h = obj.get_gradient(m, label_s, weight_s, i)
            trees = []
            for k in range(K):
                with jax.named_scope("xgb.gradient"):
                    gk = (g[:, k] if g.ndim == 2 else g) * validf
                    hk = (h[:, k] if h.ndim == 2 else h) * validf
                seed = round_seed_traced(seed_base, i, k)
                key = jax.random.PRNGKey(seed.astype(jnp.int32))
                t = grow_tree_fused(bins_s, gk, hk, cut_values, key, eta,
                                    gamma, cfg_dist, feature_weights=fw,
                                    onehot=onehot_s)
                with jax.named_scope("xgb.leaf_delta"):
                    m_loc = m_loc.at[:, k].add(t.delta)
                trees.append(t._replace(delta=jnp.zeros((0,), jnp.float32)))
            return m_loc, jtu.tree_map(lambda *xs: jnp.stack(xs), *trees)

        return jax.lax.scan(body, m_s, iters)

    tree_specs = GrownTree(**{f: P() for f in GrownTree._fields})
    in_specs = [P(ROW_AXIS, None), P(ROW_AXIS)]
    args = [bins, label]
    if weight is not None:
        in_specs.append(P(ROW_AXIS))
        args.append(weight)
    else:
        in_specs.append(None)
        args.append(None)
    in_specs.append(P(ROW_AXIS, None))
    args.append(margin)
    if feature_weights is not None:
        in_specs.append(P())
        args.append(feature_weights)
    else:
        in_specs.append(None)
        args.append(None)
    in_specs.append(P())
    args.append(n_arr)
    if onehot is not None:
        in_specs.append(P(ROW_AXIS, None))
        args.append(onehot)
    else:
        in_specs.append(None)
        args.append(None)
    fn = jax.shard_map(
        shard_fn, mesh=mesh, in_specs=tuple(in_specs),
        out_specs=(P(ROW_AXIS, None), tree_specs),
        check_vma=True,
    )
    return fn(*args)
