"""Distributed quantile sketch: per-shard summaries + all_gather merge.

The TPU analog of the reference's cross-worker sketch AllReduce
(``HostSketchContainer::AllReduce`` quantile.cc:270; GPU
``SketchContainer::AllReduce`` quantile.cu:510): every shard compresses its
rows into a fixed-size weighted summary (value, weight) per feature — the
moral equivalent of a pruned WQSummary — the summaries are all_gathered
over the mesh, merged by a weighted-CDF pass, and every device reads off
identical cuts. Summary size is ``OVERSAMPLE * max_bin`` per feature, so
accuracy matches a GK sketch with eps ~ 1/(OVERSAMPLE * max_bin) per shard.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..collective import psum as _coll_psum
from ..data.quantile import HistogramCuts
from .mesh import ROW_AXIS

OVERSAMPLE = 8


@partial(jax.jit, static_argnames=("max_bin",))
def _local_summary(X: jax.Array, weights: jax.Array, max_bin: int):
    """[n_local, F] -> per-feature summary (values [F, S], weights [F, S])."""
    S = OVERSAMPLE * max_bin
    Xt = X.T
    valid = ~jnp.isnan(Xt)
    big = jnp.float32(np.finfo(np.float32).max)
    keys = jnp.where(valid, Xt, big)
    order = jnp.argsort(keys, axis=1)
    svals = jnp.take_along_axis(keys, order, axis=1)
    w = jnp.where(valid, weights[None, :], 0.0)
    sw = jnp.take_along_axis(w, order, axis=1)
    cdf = jnp.cumsum(sw, axis=1)
    total = cdf[:, -1:]
    levels = (jnp.arange(1, S + 1, dtype=jnp.float32) / S) * total
    idx = jax.vmap(lambda c, l: jnp.searchsorted(c, l, side="left"))(cdf, levels)
    idx = jnp.clip(idx, 0, Xt.shape[1] - 1)
    vals = jnp.take_along_axis(svals, idx, axis=1)  # [F, S]
    wts = jnp.broadcast_to(total / S, vals.shape)
    # features with no valid rows: zero weights
    wts = jnp.where(total > 0, wts, 0.0)
    vals = jnp.where(total > 0, vals, 0.0)
    # also carry per-feature max for the sentinel cut
    n_valid = valid.sum(axis=1)
    fmax = jnp.where(n_valid > 0, jnp.take_along_axis(svals, (n_valid - 1)[:, None], axis=1)[:, 0], 0.0)
    fmin = jnp.where(n_valid > 0, svals[:, 0], 0.0)
    return vals, wts, fmax, fmin


@partial(jax.jit, static_argnames=("max_bin",))
def _merge_summaries(vals: jax.Array, wts: jax.Array, fmax: jax.Array, fmin: jax.Array, max_bin: int):
    """[D, F, S] gathered summaries -> [F, max_bin] global cuts."""
    D, F, S = vals.shape
    v = jnp.transpose(vals, (1, 0, 2)).reshape(F, D * S)
    w = jnp.transpose(wts, (1, 0, 2)).reshape(F, D * S)
    order = jnp.argsort(v, axis=1)
    sv = jnp.take_along_axis(v, order, axis=1)
    sw = jnp.take_along_axis(w, order, axis=1)
    cdf = jnp.cumsum(sw, axis=1)
    total = cdf[:, -1:]
    levels = (jnp.arange(1, max_bin, dtype=jnp.float32) / max_bin) * total
    idx = jax.vmap(lambda c, l: jnp.searchsorted(c, l, side="left"))(cdf, levels)
    idx = jnp.clip(idx, 0, D * S - 1)
    interior = jnp.take_along_axis(sv, idx, axis=1)
    gmax = fmax.max(axis=0)
    gmin = jnp.where(jnp.any(wts.sum(axis=2) > 0, axis=0), fmin.min(axis=0), 0.0)
    sentinel = gmax + jnp.maximum(1.0, jnp.abs(gmax))
    any_valid = (total[:, 0] > 0)
    interior = jnp.where(any_valid[:, None], interior, 0.0)
    cuts = jnp.concatenate([interior, sentinel[:, None]], axis=1)
    return cuts, gmin


def distributed_compute_cuts(
    mesh: Mesh,
    X: jax.Array,  # [n, F] row-sharded dense float32/NaN
    max_bin: int = 256,
    weights: Optional[jax.Array] = None,
) -> HistogramCuts:
    from ..observability import comms, trace

    n, F = X.shape
    if weights is None:
        weights = jnp.ones((n,), jnp.float32)
    # per-device volume of the summary merge: four all_gathers (vals/wts
    # [F, S] + fmax/fmin [F]) over D shards, plus the two psum-broadcasts
    # of the [F, max_bin] cuts — the quantile.cc:270 AllReduce site
    D = mesh.devices.size
    S = OVERSAMPLE * max_bin
    comms.record("all_gather_sketch", D * (2 * F * S + 2 * F) * 4, n_ops=4)
    comms.record("psum_hist", 2 * F * max_bin * 4, n_ops=2)

    def shard_fn(Xs, ws):
        vals, wts, fmax, fmin = _local_summary(Xs, ws, max_bin)
        g_vals = jax.lax.all_gather(vals, ROW_AXIS)  # [D, F, S]
        g_wts = jax.lax.all_gather(wts, ROW_AXIS)
        g_max = jax.lax.all_gather(fmax, ROW_AXIS)
        g_min = jax.lax.all_gather(fmin, ROW_AXIS)
        cuts, mins = _merge_summaries(g_vals, g_wts, g_max, g_min, max_bin)
        # every shard computed identical cuts, but the VMA type system
        # cannot credit that through all_gather; an exact rank-0
        # psum-broadcast (the reference's tree-sync site,
        # updater_sync.cc:20) makes the replication provable so shard_map
        # verifies it (check_vma on)
        r = jax.lax.axis_index(ROW_AXIS)

        def bcast0(a):
            return _coll_psum(jnp.where(r == 0, a, jnp.zeros_like(a)),
                              ROW_AXIS)

        return bcast0(cuts), bcast0(mins)

    # the set-up stage, closed on the cuts on the host
    with trace.stage("sketch", distributed=True, rows=n, features=F,
                     max_bin=max_bin):
        cuts, min_vals = jax.shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=(P(ROW_AXIS, None), P(ROW_AXIS)),
            out_specs=(P(), P()),
        )(X, weights)
        return HistogramCuts(values=np.asarray(cuts),
                             min_vals=np.asarray(min_vals))
