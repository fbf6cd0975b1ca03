"""GBTree / DART boosters.

Reference: ``src/gbm/gbtree.{h,cc}`` — ``DoBoost`` (gbtree.cc:219) slices
per-group gradients, ``BoostNewTrees`` (:319) runs the updater chain, and
``CommitModel`` (:364) appends trees + updates the prediction cache; DART
subclass at gbtree.cc:637-1020 (drop/normalize logic mirrored here line by
line from DropTrees:914 / NormalizeTrees:963).
"""

from __future__ import annotations

import dataclasses as _dc
import functools
import time as _time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..observability import REGISTRY as _REGISTRY, trace as _trace
from ..params import GBTreeParam, TrainParam
from ..predictor import StackedForest, predict_leaf, predict_margin, stack_forest
from ..registry import BOOSTERS
from ..analysis.retrace import guard_jit
from ..tree.grow import GrowParams, grow_tree, leaf_value_map, prune_heap
from ..tree.grow_fused import (GrownTree, _pallas_flag, grow_tree_fused,
                               grow_trees_one_pass)
from ..tree.model import RegTree
from ..tree.param import SplitParams
from ..utils import console_logger


def _hist_seconds():
    return _REGISTRY.histogram(
        "hist_build_seconds",
        "Host-side wall time of one tree build dispatch "
        "(hist + split + partition)")


@functools.partial(guard_jit, name="margin_add", static_argnames=("k",),
                   donate_argnames=("m",))
def _margin_add_jit(m, delta, *, k=None):
    # the scan bodies book the margin's add to the same phase
    with jax.named_scope("xgb.leaf_delta"):
        delta = delta[: m.shape[0]]  # the grower's row padding
        if k is None:
            return m + delta
        return m.at[:, k].add(delta)


@functools.partial(guard_jit, name="pad_gh", static_argnames=("n_pad",))
def _pad_gh(g, h, *, n_pad):
    """A round's gradient and hessian padded to the grower's row tile:
    padded rows belong to no query and no leaf, and weigh nothing."""
    with jax.named_scope("xgb.gradient"):  # as the scan bodies book it
        pad = jnp.zeros((n_pad - g.shape[0],), jnp.float32)
        return jnp.concatenate([g, pad]), jnp.concatenate([h, pad])


def _margin_add(margin_cache, delta, k):
    """Per-round prediction-cache update with the OLD margin donated: the
    round's cache buffer is updated in place instead of allocating a fresh
    [n, K] every round (ISSUE 13 donation tentpole). The caller must treat
    the passed-in cache as dead (every call site rebinds)."""
    if margin_cache.ndim == 2:
        return _margin_add_jit(margin_cache, delta, k=k)
    return _margin_add_jit(margin_cache, delta)


class _PendingTree:
    """A tree still living on device as heap-layout arrays (GrownTree minus
    the per-row delta). RegTree materialization is deferred until model IO
    or host introspection needs it — each device->host sync costs more than
    an entire tree build, so the training loop never pays it."""

    __slots__ = ("keep", "feature", "split_bin", "split_cond", "default_left",
                 "node_weight", "loss_chg", "node_h", "leaf_value", "eta",
                 "max_depth", "cat_set", "cat_mask")

    def __init__(self, g: GrownTree, eta: float, max_depth: int,
                 cat_mask=None):
        self.keep = g.keep
        self.feature = g.feature
        self.split_bin = g.split_bin
        self.split_cond = g.split_cond
        self.default_left = g.default_left
        self.node_weight = g.node_weight
        self.loss_chg = g.loss_chg
        self.node_h = g.node_h
        self.leaf_value = g.leaf_value
        self.eta = eta
        self.max_depth = max_depth
        # categorical metadata ([max_nodes, B] right-going sets + [F] bool
        # feature mask); None for pure-numerical trees
        self.cat_set = g.cat_set if cat_mask is not None else None
        self.cat_mask = cat_mask


class _PendingChunk:
    """A whole scan-chunk of trees held as the scan's native [R, K, N]
    device arrays. Slicing R*K per-tree views out of these on device was
    measured to matter: ~11 arrays x rounds tiny dispatches per chunk and
    thousands of live buffers by round 500 (the prime suspect for the
    round-3 rounds/s decay, review Weak #4) — so the chunk is stored
    as-is and trees are carved out lazily, on host, one bulk transfer per
    field per chunk."""

    __slots__ = ("fields", "R", "K", "eta", "max_depth", "cat_mask",
                 "_host")

    FIELDS = ("keep", "feature", "split_bin", "split_cond", "default_left",
              "node_weight", "loss_chg", "node_h", "leaf_value")

    def __init__(self, stacked: GrownTree, R: int, K: int, eta: float,
                 max_depth: int, cat_mask=None):
        self.fields = {f: getattr(stacked, f) for f in self.FIELDS}
        # a categorical job's chunk also carries each split's right-going
        # category set, [R, K, N, B]
        if cat_mask is not None:
            self.fields["cat_set"] = stacked.cat_set
        self.R, self.K = R, K
        self.eta, self.max_depth = eta, max_depth
        self.cat_mask = cat_mask
        self._host = None

    @property
    def n_nodes(self) -> int:
        return int(self.fields["keep"].shape[2])

    def host(self):
        """One bulk device->host transfer per field, cached."""
        if self._host is None:
            self._host = {f: np.asarray(a) for f, a in self.fields.items()}
        return self._host

    def flat(self, f: str) -> jax.Array:
        """[R*K, N] device view in tree order (r-major, k inner) — a free
        reshape, never a per-tree slice."""
        a = self.fields[f]
        return a.reshape(a.shape[0] * a.shape[1], a.shape[2])


class _ChunkRef:
    """Per-tree placeholder into a _PendingChunk (plain python — creating
    one performs zero device operations)."""

    __slots__ = ("chunk", "r", "k")

    def __init__(self, chunk: _PendingChunk, r: int, k: int):
        self.chunk = chunk
        self.r = r
        self.k = k

    @property
    def flat_index(self) -> int:
        return self.r * self.chunk.K + self.k

    @property
    def max_depth(self) -> int:
        return self.chunk.max_depth

    @property
    def n_nodes(self) -> int:
        return self.chunk.n_nodes

    @property
    def cat_mask(self):
        return self.chunk.cat_mask


class _PendingAllocChunk:
    """Lossguide twin of _PendingChunk: a scan chunk of allocation-ordered
    trees held as the scan's [R, K, M] device outputs (alloc fields +
    on-device keep/leaf_value); per-tree carving happens on host, one bulk
    transfer per field per chunk."""

    __slots__ = ("fields", "R", "K", "eta", "gamma", "max_depth",
                 "cat_mask", "_host")

    ALLOC_FIELDS = ("left", "right", "feature", "split_bin", "split_cond",
                    "default_left", "node_weight", "loss_chg", "node_h",
                    "cat_set", "n_nodes", "depth")

    def __init__(self, alloc_stacked, keep, leaf_value, R, K, eta, gamma,
                 max_depth, cat_mask):
        self.fields = {f: getattr(alloc_stacked, f)
                       for f in self.ALLOC_FIELDS}
        self.fields["keep"] = keep
        self.fields["leaf_value"] = leaf_value
        self.R, self.K = R, K
        self.eta, self.gamma = eta, gamma
        self.max_depth = max_depth
        self.cat_mask = cat_mask
        self._host = None

    def host(self):
        """Bulk transfer of exactly what RegTree.from_alloc consumes (keep/
        leaf_value/depth serve only the DEVICE stacker; cat_set only when
        categorical)."""
        if self._host is None:
            skip = {"keep", "leaf_value", "depth"}
            if self.cat_mask is None:
                skip.add("cat_set")
            self._host = {f: np.asarray(a)
                          for f, a in self.fields.items() if f not in skip}
        return self._host

    def flat(self, f: str) -> jax.Array:
        """[R*K, M] device view in tree order — a free reshape."""
        a = self.fields[f]
        return a.reshape(a.shape[0] * a.shape[1], *a.shape[2:])


class _AllocChunkRef:
    """Per-tree placeholder into a _PendingAllocChunk."""

    __slots__ = ("chunk", "r", "k")

    def __init__(self, chunk: _PendingAllocChunk, r: int, k: int):
        self.chunk = chunk
        self.r = r
        self.k = k

    @property
    def flat_index(self) -> int:
        return self.r * self.chunk.K + self.k

    @property
    def cat_mask(self):
        return self.chunk.cat_mask


def _pad_stack(arrs, n_cols: int, col_pad: int, row_pad: int, fill, dtype):
    """Stack 1-D per-tree arrays into a [row_pad, col_pad] device matrix:
    per-array pad to ``n_cols`` then to pow2 ``col_pad`` columns and
    ``row_pad`` rows (compile-reuse bucketing). Single home for the padding
    policy used by every device stacker/materializer in this module."""
    arrs = [a if a.shape[0] == n_cols
            else jnp.pad(a, (0, n_cols - a.shape[0]), constant_values=fill)
            for a in arrs]
    s = jnp.stack(arrs)
    if n_cols != col_pad:
        s = jnp.pad(s, ((0, 0), (0, col_pad - n_cols)), constant_values=fill)
    if s.shape[0] != row_pad:
        s = jnp.pad(s, ((0, row_pad - s.shape[0]), (0, 0)),
                    constant_values=fill)
    return s.astype(dtype)


class _PendingAllocTree:
    """A lossguide tree still on device (allocation-ordered arrays +
    on-device prune/leaf results). RegTree materialization via
    ``RegTree.from_alloc`` is deferred like ``_PendingTree``."""

    __slots__ = ("left", "right", "feature", "split_bin", "split_cond",
                 "default_left", "node_weight", "loss_chg", "node_h",
                 "cat_set", "keep", "leaf_value", "n_nodes", "depth",
                 "eta", "gamma", "max_depth", "cat_mask")

    def __init__(self, alloc, keep, leaf_value, eta, gamma, max_depth,
                 cat_mask):
        self.left = alloc.left
        self.right = alloc.right
        self.feature = alloc.feature
        self.split_bin = alloc.split_bin
        self.split_cond = alloc.split_cond
        self.default_left = alloc.default_left
        self.node_weight = alloc.node_weight
        self.loss_chg = alloc.loss_chg
        self.node_h = alloc.node_h
        self.cat_set = alloc.cat_set
        self.n_nodes = alloc.n_nodes
        self.depth = alloc.depth
        self.keep = keep
        self.leaf_value = leaf_value
        self.eta = eta
        self.gamma = gamma
        self.max_depth = max_depth
        self.cat_mask = cat_mask


def _materialize_pending_alloc(pending: List[_PendingAllocTree]) -> List[RegTree]:
    """Bulk host conversion of device lossguide trees (pad to common width,
    stack per field, one transfer per field)."""
    if not pending:
        return []
    fields = ("left", "right", "feature", "split_cond", "default_left",
              "node_weight", "loss_chg", "node_h", "split_bin", "n_nodes")
    sizes = [t.left.shape[0] for t in pending]
    Mmax = max(sizes)

    def stack(f):
        arrs = [getattr(t, f) for t in pending]
        if f == "n_nodes":
            return np.asarray(jnp.stack(arrs))
        arrs = [a if a.shape[0] == Mmax
                else jnp.pad(a, (0, Mmax - a.shape[0]),
                             constant_values=(-1 if f in ("left", "right")
                                              else 0))
                for a in arrs]
        return np.asarray(jnp.stack(arrs))

    st = {f: stack(f) for f in fields}
    cat_sets = None
    if any(t.cat_mask is not None for t in pending):
        cat_sets = [np.asarray(t.cat_set) for t in pending]
    out = []
    for i, t in enumerate(pending):
        m = sizes[i]
        tree, _ = RegTree.from_alloc(
            st["left"][i][:m], st["right"][i][:m], st["feature"][i][:m],
            st["split_cond"][i][:m], st["default_left"][i][:m],
            st["node_weight"][i][:m], st["loss_chg"][i][:m],
            st["node_h"][i][:m], int(st["n_nodes"][i]), eta=t.eta,
            min_split_loss=t.gamma, split_bin=st["split_bin"][i][:m],
            cat_features=t.cat_mask,
            cat_set=cat_sets[i] if cat_sets is not None else None,
        )
        out.append(tree)
    return out


def _pack_cat_bits(cat_set: jax.Array) -> jax.Array:
    """[T, M, B] bool right-going sets -> [T, M, W] uint32 bitfields
    (common/bitfield.h CatBitField layout), W pow2-padded."""
    T, M, B = cat_set.shape
    W = max(1, -(-B // 32))
    W = 1 << (W - 1).bit_length()
    if B != W * 32:
        cat_set = jnp.pad(cat_set, ((0, 0), (0, 0), (0, W * 32 - B)))
    bits = cat_set.reshape(T, M, W, 32).astype(jnp.uint32)
    weights = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))
    return (bits * weights).sum(axis=-1, dtype=jnp.uint32)


def _stack_device_alloc(pending: List[_PendingAllocTree], tree_info,
                        n_groups: int) -> StackedForest:
    """Stacked forest from device lossguide trees — explicit child arrays
    (allocation order), pruned topology applied via ``keep``. Uses the
    XLA walk (not the heap pallas kernel). One scalar readback for the
    walk depth bound."""
    T = len(pending)
    Tp = 1 << (T - 1).bit_length() if T > 1 else 1
    M = max(t.left.shape[0] for t in pending)
    Mp = max(1, 1 << (M - 1).bit_length())

    def stack(get, fill, dtype):
        return _pad_stack([get(t) for t in pending], M, Mp, Tp, fill, dtype)

    keep = stack(lambda t: t.keep, False, bool)
    left = jnp.where(keep, stack(lambda t: t.left, -1, jnp.int32), -1)
    right = jnp.where(keep, stack(lambda t: t.right, -1, jnp.int32), -1)
    cond = jnp.where(keep,
                     stack(lambda t: t.split_cond, 0.0, jnp.float32),
                     stack(lambda t: t.leaf_value, 0.0, jnp.float32))
    feature = stack(lambda t: t.feature, 0, jnp.int32)
    has_cats = any(t.cat_mask is not None for t in pending)
    if has_cats:
        catf = [jnp.asarray(t.cat_mask) if t.cat_mask is not None
                else jnp.zeros(int(t.feature.max()) + 1, bool)
                for t in pending]
        st_rows = [cf[jnp.clip(t.feature, 0, cf.shape[0] - 1)]
                   for cf, t in zip(catf, pending)]
        split_type = jnp.stack(
            [r if r.shape[0] == M else jnp.pad(r, (0, M - r.shape[0]))
             for r in st_rows]
        )
        if M != Mp:
            split_type = jnp.pad(split_type, ((0, 0), (0, Mp - M)))
        if Tp != T:
            split_type = jnp.pad(split_type, ((0, Tp - T), (0, 0)))
        split_type = split_type & keep
        css = [t.cat_set for t in pending]
        B = max(c.shape[1] for c in css)
        css = [jnp.pad(c, ((0, M - c.shape[0]), (0, B - c.shape[1])))
               for c in css]
        cat_all = jnp.stack(css)
        if M != Mp:
            cat_all = jnp.pad(cat_all, ((0, 0), (0, Mp - M), (0, 0)))
        if Tp != T:
            cat_all = jnp.pad(cat_all, ((0, Tp - T), (0, 0), (0, 0)))
        cat_bits = _pack_cat_bits(cat_all)
    else:
        split_type = jnp.zeros((Tp, Mp), bool)
        cat_bits = jnp.zeros((Tp, Mp, 1), jnp.uint32)
    md = int(jnp.max(jnp.stack([jnp.max(t.depth) for t in pending]))) + 1
    group = np.zeros(Tp, np.int32)
    group[:T] = np.asarray(tree_info, np.int32)
    return StackedForest(
        left=left, right=right, feature=feature, cond=cond,
        default_left=stack(lambda t: t.default_left, False, bool),
        split_type=split_type, cat_bits=cat_bits,
        tree_group=jnp.asarray(group), max_depth=max(md, 1),
        n_groups=n_groups, has_cats=has_cats, heap_layout=False,
    )


def _materialize_pending(pending: List[_PendingTree]) -> List[RegTree]:
    """Convert device trees to host RegTrees in a handful of bulk transfers
    (one stacked array per field) instead of per-tree round trips."""
    if not pending:
        return []
    fields = ("keep", "feature", "split_cond", "default_left", "node_weight",
              "loss_chg", "node_h", "split_bin")
    sizes = [t.keep.shape[0] for t in pending]
    Nmax = max(sizes)

    def stack(f):
        # trees can differ in max_nodes if max_depth changed between rounds;
        # pad (zeros => leaves) to the common width before stacking
        arrs = [getattr(t, f) for t in pending]
        arrs = [a if a.shape[0] == Nmax else jnp.pad(a, (0, Nmax - a.shape[0]))
                for a in arrs]
        return np.asarray(jnp.stack(arrs))

    stacked = {f: stack(f) for f in fields}
    cat_ix = [i for i, t in enumerate(pending) if t.cat_mask is not None]
    cat_sets = {}
    if cat_ix:
        # one bulk transfer for every categorical set, like the scalar
        # fields: pad to the common [Nmax, Bmax] then stack
        Bmax = max(pending[i].cat_set.shape[1] for i in cat_ix)
        padded = [
            jnp.pad(pending[i].cat_set,
                    ((0, Nmax - pending[i].cat_set.shape[0]),
                     (0, Bmax - pending[i].cat_set.shape[1])))
            for i in cat_ix
        ]
        host_sets = np.asarray(jnp.stack(padded))
        cat_sets = {i: host_sets[j] for j, i in enumerate(cat_ix)}
    out = []
    for i, t in enumerate(pending):
        m = sizes[i]
        out.append(RegTree.from_heap(
            stacked["keep"][i][:m], stacked["feature"][i][:m],
            stacked["split_cond"][i][:m], stacked["default_left"][i][:m],
            stacked["node_weight"][i][:m], stacked["loss_chg"][i][:m],
            stacked["node_h"][i][:m], eta=t.eta,
            split_bin=stacked["split_bin"][i][:m],
            cat_features=t.cat_mask,
            cat_set=cat_sets.get(i)[:m] if i in cat_sets else None,
        ))
    return out


# (the _PendingTree-only device stacker was subsumed by _stack_device_mixed,
# which handles pure, chunk-backed, and mixed pending lists with one padding
# policy — see below)


class GBTreeModel:
    """Tree collection + group ids (reference: ``src/gbm/gbtree_model.h``).

    Trees grown by the fused TPU path are kept on device (``_PendingTree``)
    and materialized to host ``RegTree`` lazily; host-origin trees (JSON
    load, lossguide path) are stored directly."""

    def __init__(self, n_groups: int = 1, num_parallel_tree: int = 1):
        self.n_groups = n_groups
        self.num_parallel_tree = max(1, num_parallel_tree)
        self._entries: List[Any] = []  # RegTree | _PendingTree
        self.tree_info: List[int] = []
        self._stacked: Optional[StackedForest] = None
        self._stacked_count: int = -1

    def add(self, tree: RegTree, group: int) -> None:
        self._entries.append(tree)
        self.tree_info.append(group)
        self._stacked = None

    def add_device(self, grown: GrownTree, eta: float, group: int,
                   max_depth: int, cat_mask=None) -> None:
        self._entries.append(_PendingTree(grown, eta, max_depth, cat_mask))
        self.tree_info.append(group)
        self._stacked = None

    def add_device_chunk(self, stacked: GrownTree, R: int,
                         groups_per_round, eta: float,
                         max_depth: int, cat_mask=None) -> _PendingChunk:
        """Append a whole scan-chunk ([R, T, N] stacked heap arrays, T
        trees per round) as R*T trees WITHOUT slicing per-tree device
        arrays (see _PendingChunk). ``groups_per_round`` lists each tree
        slot's output group in the per-round order (group-major, parallel
        trees inner — matching boost_one_round / BoostNewTrees).
        ``cat_mask`` ([F] bool) where the job has categorical columns: the
        trees then keep their category sets. Returns the chunk."""
        T = len(groups_per_round)
        chunk = _PendingChunk(stacked, R, T, eta, max_depth, cat_mask)
        for r in range(R):
            for idx, grp in enumerate(groups_per_round):
                self._entries.append(_ChunkRef(chunk, r, idx))
                self.tree_info.append(int(grp))
        self._stacked = None
        return chunk

    def add_device_alloc_chunk(self, alloc_stacked, keep, leaf_value,
                               R: int, K: int, eta: float, gamma: float,
                               max_depth: int, cat_mask) -> None:
        """Lossguide twin of add_device_chunk: a whole scan chunk appended
        without slicing per-tree device arrays."""
        chunk = _PendingAllocChunk(alloc_stacked, keep, leaf_value, R, K,
                                   eta, gamma, max_depth, cat_mask)
        for r in range(R):
            for k in range(K):
                self._entries.append(_AllocChunkRef(chunk, r, k))
                self.tree_info.append(k)
        self._stacked = None

    def add_device_alloc(self, alloc, keep, leaf_value, eta: float,
                         gamma: float, group: int, max_depth: int,
                         cat_mask) -> None:
        self._entries.append(_PendingAllocTree(
            alloc, keep, leaf_value, eta, gamma, max_depth, cat_mask
        ))
        self.tree_info.append(group)
        self._stacked = None

    @property
    def trees(self) -> List[RegTree]:
        heap_ix = [i for i, e in enumerate(self._entries)
                   if isinstance(e, _PendingTree)]
        alloc_ix = [i for i, e in enumerate(self._entries)
                    if isinstance(e, _PendingAllocTree)]
        ref_any = any(isinstance(e, (_ChunkRef, _AllocChunkRef))
                      for e in self._entries)
        if ref_any:
            _materialize_chunk_refs(self._entries)
            _materialize_alloc_chunk_refs(self._entries)
        if heap_ix:
            converted = _materialize_pending(
                [self._entries[i] for i in heap_ix]
            )
            for i, t in zip(heap_ix, converted):
                self._entries[i] = t
        if alloc_ix:
            converted = _materialize_pending_alloc(
                [self._entries[i] for i in alloc_ix]
            )
            for i, t in zip(alloc_ix, converted):
                self._entries[i] = t
        if heap_ix or alloc_ix or ref_any:
            # a device-stacked forest uses raw device node ids; after
            # materialization node ids are BFS-compacted — rebuild so
            # pred_leaf etc. are consistent with the saved model
            self._stacked = None
        return self._entries

    @property
    def num_trees(self) -> int:
        return len(self._entries)

    def stacked(self) -> StackedForest:
        if self._stacked is not None and self._stacked_count == len(self._entries):
            return self._stacked
        self._stacked = self.stacked_slice(0, len(self._entries))
        self._stacked_count = len(self._entries)
        return self._stacked

    def stacked_slice(self, lo: int, hi: int) -> StackedForest:
        """Stacked forest over trees [lo, hi) WITHOUT materializing pending
        device trees when the slice is uniformly device-resident — neither
        the incremental prediction-cache catch-up nor per-round DART
        repredicts may trigger host syncs mid-training (gbtree.cc:519)."""
        ents = self._entries[lo:hi]
        if ents and all(
            isinstance(e, (_PendingTree, _ChunkRef))
            and getattr(e, "cat_mask", None) is None
            for e in ents
        ):
            # (categorical pending trees fall through to host
            # materialization — their bitset packing lives in RegTree)
            return _stack_device_mixed(ents, self.tree_info[lo:hi],
                                       self.n_groups)
        if ents and all(isinstance(e, _PendingAllocTree) for e in ents):
            return _stack_device_alloc(ents, self.tree_info[lo:hi],
                                       self.n_groups)
        if ents and all(
            isinstance(e, (_PendingAllocTree, _AllocChunkRef))
            and getattr(e, "cat_mask", None) is None
            for e in ents
        ):
            return _stack_device_alloc_mixed(ents, self.tree_info[lo:hi],
                                             self.n_groups)
        trees = self.trees[lo:hi]
        return stack_forest(trees, self.tree_info[lo:hi], self.n_groups)

    def slice(self, begin: int, end: int, step: int = 1) -> "GBTreeModel":
        out = GBTreeModel(self.n_groups, self.num_parallel_tree)
        # layered slicing: rounds -> trees_per_round trees (gbtree slicing
        # semantics operate on boosting rounds; one round appends
        # n_groups * num_parallel_tree trees — gbtree.cc:326)
        trees = self.trees
        per_round = max(1, self.n_groups) * self.num_parallel_tree
        for r in range(begin, end, step):
            for t in range(r * per_round, min((r + 1) * per_round, len(trees))):
                out.add(trees[t], self.tree_info[t])
        return out


def _cat_cfg(cfg: GrowParams, binned, tp) -> Tuple[GrowParams, Any]:
    """Apply the one-hot vs optimal-partition gate (reference UseOneHot,
    evaluate_splits.h: one-hot when n_cats < max_cat_to_onehot) to a grow
    config. Single home for the rule so the fused and lossguide growers
    cannot diverge. Returns (cfg, cat_mask or None)."""
    cats = tuple(getattr(binned, "categorical", ()))
    if not cats:
        return cfg, None
    counts = tuple(getattr(binned, "cat_counts", ())) or (0,) * len(cats)
    onehot_f = tuple(f for f, c in zip(cats, counts)
                     if c < tp.max_cat_to_onehot)
    part_f = tuple(f for f, c in zip(cats, counts)
                   if c >= tp.max_cat_to_onehot)
    cfg = _dc.replace(cfg, categorical=onehot_f, cat_partition=part_f)
    return cfg, cfg.cat_mask_np(binned.n_features)


def _count_splits(chunk: _PendingChunk, cfg: GrowParams, F: int) -> None:
    """A categorical chunk's splits, by kind, summed on the host from the
    program's output once a chunk: ``splits_total`` (every split the
    chunk's trees keep) and ``cat_splits_total{kind}``, ``onehot`` for a
    column below ``max_cat_to_onehot`` categories, ``partition`` for one
    at or above it. The read waits for the chunk and fetches the arrays
    its trees are materialized from (``_PendingChunk.host``)."""
    h = chunk.host()
    kind = np.zeros(F, np.int8)
    kind[list(cfg.categorical)] = 1
    kind[list(cfg.cat_partition)] = 2
    split_kind = kind[h["feature"][h["keep"]]]
    _REGISTRY.counter(
        "splits_total",
        "Splits the trees of a categorical job's scan chunks keep",
    ).inc(int(split_kind.size))
    fam = _REGISTRY.counter(
        "cat_splits_total",
        "Categorical splits of a job's scan chunks, by kind: onehot (one "
        "category right) or partition (a sorted prefix of categories right)")
    fam.labels(kind="onehot").inc(int((split_kind == 1).sum()))
    fam.labels(kind="partition").inc(int((split_kind == 2).sum()))


def round_seed_py(seed: int, iteration: int, k: int = 0,
                  ptree: int = 0) -> int:
    """Per-tree RNG seed (python-int path). The traced twin
    ``round_seed_traced`` MUST stay in lockstep — the scan paths' identity
    with per-round training depends on it."""
    return (seed * 1000003 + iteration * 131 + k * 17 + ptree) & 0x7FFFFFFF


def round_seed_traced(seed_base_u32, i, k: int = 0, ptree: int = 0):
    """Traced twin of ``round_seed_py`` for scan bodies: ``seed_base_u32``
    is uint32((seed * 1000003) & 0xFFFFFFFF); the 31-bit mask reads only
    low bits, which uint32 arithmetic preserves, so the two formulas agree
    bit for bit."""
    return (seed_base_u32 + i.astype(jnp.uint32) * jnp.uint32(131)
            + jnp.uint32(k * 17 + ptree)) & jnp.uint32(0x7FFFFFFF)


def _round_in_one_pass(cfg: GrowParams, trees: int) -> bool:
    """Whether a scanned round's ``trees`` (class trees x
    ``num_parallel_tree``) are grown level by level together, a level
    kernel call carrying several trees' gradient channels over one pass of
    the rows (``grow_fused.grow_trees_one_pass``), in place of one after
    another: where there is more than one and the Mosaic level kernels
    run. Observed in the job, chosen by nobody; a job that grows one tree
    a round traces the class loop's program and nothing else, and so does
    a categorical job (its trees' routing tables carry category sets)."""
    return trees > 1 and _pallas_flag(cfg) and not cfg.has_categorical


def _lossguide_on_chip(cfg: GrowParams, F: int, B: int) -> bool:
    """Whether a lossguide job's trees take the chip path
    (``grow_tree_lossguide_rows``: the rows' data grouped by leaf and
    moved by a Mosaic partition, the smaller child's histogram by the
    Mosaic node kernel), resolved through the
    ``lossguide_step`` row of the dispatch table. A TPU job that cannot
    (categorical splits, interaction constraints, a mesh, a width the node
    kernel does not take) stays on the plain reference, which passes over
    every row at every step, and says so once."""
    from ..dispatch import Ctx, resolve
    from ..dispatch.core import _warn_once

    dec = resolve("lossguide_step", Ctx(
        platform=jax.default_backend(), pallas=bool(_pallas_flag(cfg)),
        sharded=cfg.axis_name is not None or _mesh_active(),
        has_cats=cfg.has_categorical, interaction=cfg.has_interaction,
        features=int(F), bins=int(B)))
    if dec.impl != "pallas" and dec.reason != "pinned" \
            and jax.default_backend() == "tpu":
        _warn_once(
            f"lossguide_off_chip:{F}:{B}:{cfg.has_categorical}:"
            f"{cfg.has_interaction}",
            "grow_policy='lossguide': categorical splits, interaction "
            "constraints, a mesh or this width keep the trees on the plain "
            "reference grower, one pass over every row a split; expect it "
            "to be slow")
    return dec.impl == "pallas"


def _mesh_active() -> bool:
    from ..parallel.mesh import current_mesh

    mesh = current_mesh()
    return mesh is not None and mesh.devices.size > 1


def _obj_fingerprint(obj) -> tuple:
    """Hashable snapshot of the scalar params an objective can read at
    trace time. Part of the scan's static jit key so mutating params via
    set_param between update_many calls retraces instead of silently
    reusing gradients compiled with the old values."""
    p = getattr(obj, "params", None)
    fields = getattr(p, "FIELDS", None)
    if p is None or not fields:
        return ()
    return tuple(
        (k, v) for k in sorted(fields)
        for v in (getattr(p, k, None),)
        if isinstance(v, (int, float, str, bool, type(None)))
    )


@functools.partial(guard_jit, name="scan_rounds",
                   static_argnames=("obj", "obj_fp", "cfg", "n", "n_pad",
                                    "n_groups", "n_parallel"),
                   donate_argnames=("m_pad",))
def _scan_rounds_impl(binsf, label, weight, m_pad, iters, cut_vals, eta,
                      gamma, fw, seed_base, onehot=None, *, obj, obj_fp,
                      cfg, n, n_pad, n_groups, n_parallel=1):
    """Multi-round boosting as one program: scan body = gradient -> fused
    tree(s) -> margin update (one tree per output group, like DoBoost's
    per-group gradient slicing, gbtree.cc:219). Cache key includes the
    objective INSTANCE (its params are read at trace time) and the static
    grow config; equal-length chunks reuse the compile. The carried margin
    is DONATED (ISSUE 13: async executor + donation): each chunk's margin
    buffer is reused in place instead of re-allocated, so the steady-state
    live-buffer count is flat across a whole training run — the caller's
    input margin is dead after the call (update_many re-points the cache
    at the returned one)."""
    K = n_groups
    one_pass = _round_in_one_pass(cfg, K * n_parallel)

    def pad0(v):
        if n_pad == n:
            return v
        return jnp.concatenate([v, jnp.zeros((n_pad - n,), jnp.float32)])

    def body_one_pass(m_pad, i):
        """The round's trees grown together: the class loop's gradients,
        keys and margin updates around one ``grow_trees_one_pass``."""
        with jax.named_scope("xgb.gradient"):
            m = m_pad[:n, 0] if K == 1 else m_pad[:n]
            g, h = obj.get_gradient(m, label, weight, i)
        gks, hks, keys, groups = [], [], [], []
        for k in range(K):
            with jax.named_scope("xgb.gradient"):
                gk = pad0(g[:, k] if g.ndim == 2 else g)
                hk = pad0(h[:, k] if h.ndim == 2 else h)
            for pt in range(n_parallel):
                seed = round_seed_traced(seed_base, i, k, pt)
                keys.append(jax.random.PRNGKey(seed.astype(jnp.int32)))
                gks.append(gk)
                hks.append(hk)
                groups.append(k)
        grown = grow_trees_one_pass(binsf, gks, hks, cut_vals, keys, eta,
                                    gamma, cfg, feature_weights=fw,
                                    onehot=onehot)
        for k, t in zip(groups, grown):
            with jax.named_scope("xgb.leaf_delta"):
                m_pad = m_pad.at[:, k].add(t.delta)
        stacked = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs),
            *[t._replace(delta=jnp.zeros((0,), jnp.float32)) for t in grown])
        return m_pad, stacked

    def body(m_pad, i):
        with jax.named_scope("xgb.gradient"):
            m = m_pad[:n, 0] if K == 1 else m_pad[:n]
            g, h = obj.get_gradient(m, label, weight, i)
        trees = []
        for k in range(K):
            with jax.named_scope("xgb.gradient"):
                gk = pad0(g[:, k] if g.ndim == 2 else g)
                hk = pad0(h[:, k] if h.ndim == 2 else h)
            for pt in range(n_parallel):
                # bit-identical to boost_one_round's python-int key
                # formula: the 31-bit mask reads only low bits
                seed = round_seed_traced(seed_base, i, k, pt)
                key = jax.random.PRNGKey(seed.astype(jnp.int32))
                t = grow_tree_fused(binsf, gk, hk, cut_vals, key, eta,
                                    gamma, cfg, feature_weights=fw,
                                    onehot=onehot)
                with jax.named_scope("xgb.leaf_delta"):
                    m_pad = m_pad.at[:, k].add(t.delta)
                trees.append(
                    t._replace(delta=jnp.zeros((0,), jnp.float32)))
        stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)
        return m_pad, stacked

    return jax.lax.scan(body_one_pass if one_pass else body, m_pad, iters)


@functools.partial(guard_jit, name="scan_rounds_lossguide",
                   static_argnames=("obj", "obj_fp", "cfg", "n_groups",
                                    "max_leaves", "rows_path"),
                   donate_argnames=("m_cur",))
def _scan_rounds_lossguide_impl(bins, label, weight, m_cur, iters, cut_vals,
                                eta, gamma, fw, seed_base, *, obj, obj_fp,
                                cfg, n_groups, max_leaves, rows_path=False):
    """Lossguide variant of the multi-round scan: body = gradient ->
    allocation-ordered growth -> on-device prune / leaf values / delta
    (finalize_alloc) -> margin update. Per-row positions are stripped from
    the stacked outputs (only the delta uses them). ``rows_path``: the
    trees grow on the chip path (``grow_tree_lossguide_rows``, its bins
    widened and packed once a chunk, here), and the stacked outputs end
    with each tree's node row counts; else on the plain reference
    (``grow_tree_lossguide``)."""
    from ..tree.grow_lossguide import (
        chip_bins,
        finalize_alloc,
        grow_tree_lossguide,
        grow_tree_lossguide_rows,
    )

    K = n_groups
    n = bins.shape[0]
    if rows_path:
        binsT, packed = chip_bins(bins, cut_vals.shape[1])

    def body(m_cur, i):
        with jax.named_scope("xgb.gradient"):
            m = m_cur[:, 0] if K == 1 else m_cur
            g, h = obj.get_gradient(m, label, weight, i)
        outs = []
        for k in range(K):
            with jax.named_scope("xgb.gradient"):
                gk = g[:, k] if g.ndim == 2 else g
                hk = h[:, k] if h.ndim == 2 else h
            seed = round_seed_traced(seed_base, i, k)
            key = jax.random.PRNGKey(seed.astype(jnp.int32))
            if rows_path:
                alloc, rows = grow_tree_lossguide_rows(
                    binsT, packed, gk, hk, cut_vals, key, cfg, max_leaves, n,
                    fw)
            else:
                alloc = grow_tree_lossguide(bins, gk, hk, cut_vals, key, cfg,
                                            max_leaves, fw)
            keep, lv, delta = finalize_alloc(alloc, eta, gamma)
            with jax.named_scope("xgb.leaf_delta"):
                m_cur = m_cur.at[:, k].add(delta)
            out = (alloc._replace(positions=jnp.zeros((0,), jnp.int32)),
                   keep, lv)
            outs.append(out + (rows,) if rows_path else out)
        stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *outs)
        return m_cur, stacked

    return jax.lax.scan(body, m_cur, iters)


def _chunked_field2d(entries: List[Any], ref_type, name: str, Np: int,
                     Tp: int, fill, dtype) -> jax.Array:
    """[Tp, Np] device matrix of one per-tree field over a mixed pending
    list: consecutive ``ref_type`` refs into the same chunk contribute ONE
    reshape+slice of the chunk's [R*K, ...] view; plain pending trees
    contribute their own array. Shared by both mixed stackers so the
    run-detection/padding policy has a single home."""
    T = len(entries)
    segs = []
    i = 0
    while i < T:
        e = entries[i]
        if isinstance(e, ref_type):
            c, start = e.chunk, e.flat_index
            j = i + 1
            while (j < T and isinstance(entries[j], ref_type)
                   and entries[j].chunk is c
                   and entries[j].flat_index == start + (j - i)):
                j += 1
            seg = c.flat(name)[start:start + (j - i)]
            i = j
        else:
            seg = getattr(e, name)[None]
            i += 1
        if seg.shape[1] != Np:
            seg = jnp.pad(seg, ((0, 0), (0, Np - seg.shape[1])),
                          constant_values=fill)
        segs.append(seg)
    s = segs[0] if len(segs) == 1 else jnp.concatenate(segs)
    if s.shape[0] != Tp:
        s = jnp.pad(s, ((0, Tp - s.shape[0]), (0, 0)), constant_values=fill)
    return s.astype(dtype)


def _stack_device_alloc_mixed(entries: List[Any], tree_info,
                              n_groups: int) -> StackedForest:
    """Device-stacked forest over a mixture of _PendingAllocTree and
    _AllocChunkRef entries (numerical-only — categorical lossguide never
    reaches the scan path): consecutive refs into one chunk contribute one
    reshape+slice, like _stack_device_mixed for the depthwise twin."""
    T = len(entries)
    Tp = 1 << (T - 1).bit_length() if T > 1 else 1

    def width(e):
        if isinstance(e, _AllocChunkRef):
            return int(e.chunk.fields["left"].shape[2])
        return int(e.left.shape[0])

    M = max(width(e) for e in entries)
    Mp = max(1, 1 << (M - 1).bit_length())

    def field2d(name, fill, dtype):
        return _chunked_field2d(entries, _AllocChunkRef, name, Mp, Tp,
                                fill, dtype)

    keep = field2d("keep", False, bool)
    left = jnp.where(keep, field2d("left", -1, jnp.int32), -1)
    right = jnp.where(keep, field2d("right", -1, jnp.int32), -1)
    cond = jnp.where(keep, field2d("split_cond", 0.0, jnp.float32),
                     field2d("leaf_value", 0.0, jnp.float32))
    # Static depth bound — reading fields["depth"] here would force a
    # device->host sync inside the no-sync catch-up path (ADVICE r4). When
    # cfg max_depth is 0 (unbounded lossguide), a tree over M=2L-1 alloc
    # slots has depth <= L-1 = (M-1)//2; an over-estimate only costs walk
    # iterations, never correctness.
    def depth_bound(e):
        cap = e.chunk.max_depth if isinstance(e, _AllocChunkRef) else e.max_depth
        return cap if cap and cap > 0 else (width(e) - 1) // 2

    md = 1 + max(depth_bound(e) for e in entries)
    group = np.zeros(Tp, np.int32)
    group[:T] = np.asarray(tree_info, np.int32)
    return StackedForest(
        left=left, right=right,
        feature=field2d("feature", 0, jnp.int32), cond=cond,
        default_left=field2d("default_left", False, bool),
        split_type=jnp.zeros((Tp, Mp), bool),
        cat_bits=jnp.zeros((Tp, Mp, 1), jnp.uint32),
        tree_group=jnp.asarray(group), max_depth=max(md, 1),
        n_groups=n_groups, has_cats=False, heap_layout=False,
    )


def _materialize_alloc_chunk_refs(entries: List[Any]) -> None:
    """Replace every _AllocChunkRef (in place) with a host RegTree; one
    bulk transfer per field per chunk, numpy slicing per tree. from_alloc
    re-runs the gamma prune host-side exactly like the per-tree
    materializer (_materialize_pending_alloc)."""
    for i, e in enumerate(entries):
        if not isinstance(e, _AllocChunkRef):
            continue
        h = e.chunk.host()
        c = e.chunk
        r, k = e.r, e.k
        tree, _ = RegTree.from_alloc(
            h["left"][r, k], h["right"][r, k], h["feature"][r, k],
            h["split_cond"][r, k], h["default_left"][r, k],
            h["node_weight"][r, k], h["loss_chg"][r, k], h["node_h"][r, k],
            int(h["n_nodes"][r, k]), eta=c.eta, min_split_loss=c.gamma,
            split_bin=h["split_bin"][r, k], cat_features=c.cat_mask,
            cat_set=(h["cat_set"][r, k] if c.cat_mask is not None else None),
        )
        entries[i] = tree


def _materialize_chunk_refs(entries: List[Any]) -> None:
    """Replace every _ChunkRef in ``entries`` (in place) with a host
    RegTree; each distinct chunk pays one bulk transfer per field and the
    per-tree carving is numpy slicing."""
    for i, e in enumerate(entries):
        if not isinstance(e, _ChunkRef):
            continue
        h = e.chunk.host()
        r, k = e.r, e.k
        entries[i] = RegTree.from_heap(
            h["keep"][r, k], h["feature"][r, k], h["split_cond"][r, k],
            h["default_left"][r, k], h["node_weight"][r, k],
            h["loss_chg"][r, k], h["node_h"][r, k], eta=e.chunk.eta,
            split_bin=h["split_bin"][r, k], cat_features=e.cat_mask,
            cat_set=h["cat_set"][r, k] if e.cat_mask is not None else None,
        )


def _stack_device_mixed(entries: List[Any], tree_info, n_groups: int
                        ) -> StackedForest:
    """Stacked forest directly from device heap trees — no host transfer.
    Heap layout is itself a valid node indexing (children of i at
    2i+1/2i+2); leaves carry their governing (pruned) leaf value; the tree
    list is padded to a power of two so the predictor recompiles only
    log2(T) times over a training run. Handles any mixture of _PendingTree
    and _ChunkRef entries: consecutive refs into the same chunk contribute
    ONE reshape+slice of the chunk's [R*K, N] arrays (a handful of device
    ops per chunk) instead of per-tree slices."""
    T = len(entries)
    Tp = 1 << (T - 1).bit_length() if T > 1 else 1
    N = max(e.n_nodes if isinstance(e, _ChunkRef) else e.keep.shape[0]
            for e in entries)
    Np = max(1, 1 << (N - 1).bit_length())
    md = max(e.max_depth for e in entries)

    def field2d(name, fill, dtype):
        return _chunked_field2d(entries, _ChunkRef, name, Np, Tp, fill,
                                dtype)

    keep = field2d("keep", False, bool)
    iota = jnp.arange(Np, dtype=jnp.int32)[None, :]
    cond = jnp.where(keep, field2d("split_cond", 0.0, jnp.float32),
                     field2d("leaf_value", 0.0, jnp.float32))
    group = np.zeros(Tp, np.int32)
    group[:T] = np.asarray(tree_info, np.int32)
    return StackedForest(
        left=jnp.where(keep, 2 * iota + 1, -1),
        right=jnp.where(keep, 2 * iota + 2, -1),
        feature=field2d("feature", 0, jnp.int32),
        cond=cond,
        default_left=field2d("default_left", False, bool),
        split_type=jnp.zeros((Tp, Np), bool),
        cat_bits=jnp.zeros((Tp, Np, 1), jnp.uint32),
        tree_group=jnp.asarray(group),
        max_depth=max(md, 1),
        n_groups=n_groups,
        has_cats=False,
        heap_layout=True,
    )


@BOOSTERS.register("gbtree")
class GBTree:
    """Boosting orchestration over the tpu_hist grower."""

    name = "gbtree"

    def __init__(self, n_groups: int, params: Dict[str, Any]):
        self.n_groups = max(1, n_groups)
        self.gbtree_param = GBTreeParam()
        rest = self.gbtree_param.update(dict(params))
        self.train_param = TrainParam()
        self.train_param.update(rest)
        self.model = GBTreeModel(self.n_groups, self.gbtree_param.num_parallel_tree)
        self._configure_method()

    #: updater registry names the tree path honors (reference:
    #: tree_updater.h registry; every grow_* maps onto the tpu_hist grower
    #: the way the reference maps them onto updater sequences,
    #: gbtree.cc:158-190)
    _KNOWN_UPDATERS = {
        "grow_quantile_histmaker": "grow", "grow_histmaker": "grow",
        "grow_local_histmaker": "grow", "grow_colmaker": "grow",
        "grow_gpu_hist": "grow", "grow_fast_histmaker": "grow",
        "distcol": "grow", "prune": "prune", "refresh": "refresh",
        "sync": "sync",
    }

    def _configure_method(self) -> None:
        tm = self.gbtree_param.tree_method
        # every quantile-hist family method maps onto the tpu_hist grower;
        # exact is realized as exact binning (cuts at every distinct value,
        # compute_exact_cuts) + the same fixed-shape level program — the
        # colmaker candidate set without its data-dependent column scans
        if tm not in ("auto", "exact", "hist", "gpu_hist", "tpu_hist",
                      "approx"):
            raise ValueError(f"Unknown tree_method: {tm}")
        # explicit updater sequence overrides tree_method (gbtree.cc:158):
        # grow_* -> the fused grower; refresh -> the refresh pass; unknown
        # names are an error, not a silent no-op
        self._updater_seq = []
        if self.gbtree_param.updater:
            for name in str(self.gbtree_param.updater).split(","):
                name = name.strip()
                if name and name not in self._KNOWN_UPDATERS:
                    raise ValueError(f"Unknown updater: {name!r}")
                if name:
                    self._updater_seq.append(name)
            roles = {self._KNOWN_UPDATERS[u] for u in self._updater_seq}
            if "prune" in self._updater_seq and "grow" not in roles \
                    and "refresh" not in roles:
                # prune-only sequences (re-prune an existing model without
                # growing) are a distinct reference behavior we don't have;
                # gamma pruning is built into the growers
                raise NotImplementedError(
                    "standalone updater='prune' is not supported; pruning "
                    "runs inside every grower (gamma)"
                )
        if self.train_param.sampling_method not in ("uniform", "gradient_based"):
            raise ValueError(
                f"Unknown sampling_method: {self.train_param.sampling_method}"
            )
        if self.gbtree_param.process_type not in ("default", "update"):
            raise ValueError(
                f"Unknown process_type: {self.gbtree_param.process_type}"
            )
        if not self.train_param.single_precision_histogram:
            console_logger.warning(
                "single_precision_histogram=False (float64 histograms) is "
                "not available on TPU; using deterministic hi/lo bf16 "
                "accumulation (~f32 precision)"
            )
        if self.train_param.is_explicit("sketch_eps"):
            console_logger.warning(
                "sketch_eps is superseded by max_bin on the tpu_hist sketch "
                "(reference hist makes the same substitution)"
            )
        if self.train_param.is_explicit("sparse_threshold"):
            console_logger.warning(
                "sparse_threshold has no effect: the TPU quantized matrix is "
                "dense ELLPACK-style (missing encoded as a null bin)"
            )
        if self.gbtree_param.predictor not in (
            "auto", "cpu_predictor", "gpu_predictor", "tpu_predictor"
        ):
            raise ValueError(f"Unknown predictor: {self.gbtree_param.predictor}")
        if self.gbtree_param.is_explicit("predictor") and (
            self.gbtree_param.predictor in ("cpu_predictor", "gpu_predictor")
        ):
            console_logger.warning(
                "predictor=%s requested; the TPU stacked-forest predictor "
                "is always used" % self.gbtree_param.predictor
            )

    @property
    def _is_update_process(self) -> bool:
        return (
            self.gbtree_param.process_type == "update"
            or "refresh" in getattr(self, "_updater_seq", [])
        )

    @property
    def needs_exact_cuts(self) -> bool:
        """tree_method='exact' / updater='grow_colmaker': train on the
        exact-greedy candidate set (one bin per distinct value,
        ``compute_exact_cuts``) instead of quantile cuts — the TPU
        realization of ``src/tree/updater_colmaker.cc``."""
        return (
            self.gbtree_param.tree_method == "exact"
            or "grow_colmaker" in getattr(self, "_updater_seq", [])
        )

    @property
    def needs_local_sketch(self) -> bool:
        """``updater='grow_local_histmaker'``: per-NODE hessian-weighted
        cut re-proposal every level (``src/tree/updater_histmaker.cc:753``
        CQHistMaker / registration :25) — the grower re-sketches each
        expand node's rows and evaluates it against its OWN cuts
        (``tree/grow_local.py``), unlike the global per-iteration proposal
        of ``approx``."""
        return "grow_local_histmaker" in getattr(self, "_updater_seq", [])

    @property
    def needs_iteration_sketch(self) -> bool:
        """tree_method='approx': the reference's histmaker re-proposes the
        candidate cuts EVERY iteration from hessian-weighted sketches
        (``src/tree/updater_histmaker.cc:639`` SerializeReducer AllReduce of
        per-iteration WXQSketches); hist/tpu_hist sketch once. The learner
        rebuilds the quantized matrix per round with hessian weights when
        this is set."""
        return (
            self.gbtree_param.tree_method == "approx"
            or "grow_histmaker" in getattr(self, "_updater_seq", [])
        )

    def _lossguide_max_leaves(self) -> int:
        """Default leaf budget: bounded by depth when small, else a fixed
        255 cap. The growers size their ``[2 * max_leaves - 1]`` node
        tensors (and, on the chip path, a ``[F, 3, B]`` histogram a node)
        by it; a tree takes at most ``max_leaves - 1`` steps, one split a
        step, and stops early when no leaf has gain left. Users wanting
        more set max_leaves explicitly, as the reference requires for
        lossguide."""
        tp = self.train_param
        if tp.max_leaves:
            return tp.max_leaves
        if 0 < tp.max_depth <= 8:
            return 1 << tp.max_depth
        return 255

    def _grow_params(self, axis_name: Optional[str] = None) -> GrowParams:
        tp = self.train_param
        from ..native import boundary as _boundary

        return GrowParams(
            native_caps=_boundary.cap_snapshot(),
            max_depth=tp.max_depth,
            subsample=tp.subsample,
            sampling_method=tp.sampling_method,
            colsample_bytree=tp.colsample_bytree,
            colsample_bylevel=tp.colsample_bylevel,
            colsample_bynode=tp.colsample_bynode,
            split=SplitParams(
                reg_lambda=tp.reg_lambda,
                reg_alpha=tp.reg_alpha,
                max_delta_step=tp.max_delta_step,
                min_child_weight=tp.min_child_weight,
                min_split_loss=tp.gamma,
            ),
            monotone=tuple(int(c) for c in tp.monotone_constraints),
            interaction=tuple(
                tuple(int(f) for f in grp) for grp in tp.interaction_constraints
            ),
            axis_name=axis_name,
        )

    def set_param(self, key: str, value: Any) -> None:
        rest = self.gbtree_param.update({key: value})
        self.train_param.update(rest)
        if key in ("updater", "process_type", "tree_method",
                   "sampling_method"):
            self._configure_method()  # refresh the updater sequence/flags

    # ------------------------------------------------------------------
    def boost_one_round(
        self,
        binned,
        grad: jax.Array,  # [n, K]
        hess: jax.Array,
        iteration: int,
        margin_cache: Optional[jax.Array],  # [n, K] updated in place-ish
        feature_weights: Optional[jax.Array] = None,
    ) -> Tuple[List[RegTree], Optional[jax.Array]]:
        """One boosting round: K groups x num_parallel_tree new trees.
        Returns (new trees, updated margin cache). The cache update is the
        UpdatePredictionCache fast path — leaf values gathered at each row's
        final grower position, no predictor pass (gbtree.cc:219).

        Under an active mesh (``mesh_context``), rows are sharded over the
        mesh and trees grow via the shard_map'd growers with psum'd
        histograms — the reference's inter-node data-parallel strategy
        (dsplit=row, histogram.h:201) with zero changes above this layer."""
        from ..parallel.mesh import current_mesh

        tp = self.train_param
        cfg = self._grow_params()
        mesh = current_mesh()
        use_mesh = mesh is not None and mesh.devices.size > 1
        if use_mesh and jax.process_count() > 1:
            # covers EVERY per-round branch (fused, lossguide, legacy):
            # per-round margin deltas stay device-sharded across processes
            raise NotImplementedError(
                "multi-process training runs through update_many (scan) "
                "chunks; see docs/distributed.md"
            )
        cats = tuple(getattr(binned, "categorical", ()))
        lossguide_pol = tp.grow_policy == "lossguide"
        # fast path: fused per-level kernels, device-resident trees, zero
        # host syncs per round (depthwise incl. categorical; mesh-aware)
        if not lossguide_pol:
            return self._boost_fused(binned, grad, hess, iteration,
                                     margin_cache, feature_weights)
        if getattr(binned, "is_paged", False):
            raise NotImplementedError(
                "external-memory matrices support depthwise numerical "
                "training only (reference external memory has the same "
                "hist-only restriction)"
            )
        cfg, cat_mask = _cat_cfg(cfg, binned, tp)
        cuts = binned.cuts
        cut_vals = jnp.asarray(cuts.values)
        lossguide = tp.grow_policy == "lossguide"
        if lossguide:
            max_leaves = self._lossguide_max_leaves()
            on_chip = _lossguide_on_chip(cfg, binned.n_features,
                                         cuts.max_bin)
            if on_chip:
                from ..tree.grow_lossguide import chip_bins

                binsT, packed = chip_bins(binned.bins, cuts.max_bin)
        new_trees: List[RegTree] = []
        if use_mesh:
            from ..parallel.grow import (
                distributed_grow_tree,
                distributed_grow_tree_lossguide,
            )
            from ..parallel.mesh import shard_rows

            bins_sh, n_pad = binned.sharded(mesh)
            n_rows = binned.n_rows

            def _shard_gh(v: jax.Array) -> jax.Array:
                if n_pad != n_rows:
                    v = jnp.concatenate(
                        [v, jnp.zeros((n_pad - n_rows,), v.dtype)]
                    )
                return shard_rows(v, mesh)

        for k in range(self.n_groups):
            g = grad[:, k] if grad.ndim == 2 else grad
            h = hess[:, k] if hess.ndim == 2 else hess
            if use_mesh:
                g, h = _shard_gh(g), _shard_gh(h)
            for ptree in range(self.gbtree_param.num_parallel_tree):
                key = jax.random.PRNGKey(
                    round_seed_py(tp.seed, iteration, k, ptree)
                )
                fw = (
                    jnp.asarray(feature_weights)
                    if feature_weights is not None
                    else None
                )
                if lossguide:
                    from ..tree.grow_lossguide import (
                        finalize_alloc,
                        grow_tree_lossguide,
                        grow_tree_lossguide_rows,
                    )

                    t0 = _time.perf_counter()
                    with _trace.span("build_tree", iteration=iteration,
                                     group=k, policy="lossguide"):
                        if use_mesh:
                            alloc = distributed_grow_tree_lossguide(
                                mesh, bins_sh, g, h, cut_vals, key, cfg,
                                max_leaves, fw
                            )
                        elif on_chip:
                            alloc, _ = grow_tree_lossguide_rows(
                                binsT, packed, g, h, cut_vals, key, cfg,
                                max_leaves, binned.n_rows, fw)
                        else:
                            alloc = grow_tree_lossguide(
                                binned.bins, g, h, cut_vals, key, cfg,
                                max_leaves, fw
                            )
                    _hist_seconds().observe(_time.perf_counter() - t0)
                    # on-device prune/leaf-values/delta: the lossguide round
                    # performs zero host syncs, like the fused depthwise path
                    keep, lv, delta_full = finalize_alloc(
                        alloc, jnp.float32(tp.eta), jnp.float32(tp.gamma)
                    )
                    self.model.add_device_alloc(
                        alloc, keep, lv, tp.eta, tp.gamma, k, tp.max_depth,
                        cat_mask,
                    )
                    new_trees.append(alloc)
                    if margin_cache is not None:
                        delta = delta_full
                        if use_mesh and delta.shape[0] != binned.n_rows:
                            delta = delta[: binned.n_rows]
                        margin_cache = _margin_add(margin_cache, delta, k)
                    continue
                else:
                    t0 = _time.perf_counter()
                    with _trace.span("build_tree", iteration=iteration,
                                     group=k):
                        if use_mesh:
                            heap = distributed_grow_tree(
                                mesh, bins_sh, g, h, cut_vals, key, cfg, fw
                            )
                        else:
                            heap = grow_tree(binned.bins, g, h, cut_vals,
                                             key, cfg, fw)
                    _hist_seconds().observe(_time.perf_counter() - t0)
                    is_split = np.asarray(heap.is_split)
                    loss_chg = np.asarray(heap.loss_chg)
                    pruned = prune_heap(is_split, loss_chg, tp.gamma)
                    tree = RegTree.from_heap(
                        pruned,
                        np.asarray(heap.feature),
                        np.asarray(heap.split_cond),
                        np.asarray(heap.default_left),
                        np.asarray(heap.node_weight),
                        loss_chg,
                        np.asarray(heap.node_h),
                        eta=tp.eta,
                        split_bin=np.asarray(heap.split_bin),
                        cat_features=cat_mask,
                        cat_set=(
                            np.asarray(heap.cat_set) if cfg.has_categorical else None
                        ),
                    )
                    lmap_np = leaf_value_map(pruned, np.asarray(heap.node_weight), tp.eta)
                    positions = heap.positions
                self.model.add(tree, k)
                new_trees.append(tree)
                if margin_cache is not None:
                    delta = jnp.asarray(lmap_np)[positions]
                    if use_mesh and delta.shape[0] != binned.n_rows:
                        delta = delta[: binned.n_rows]  # drop inert padding
                    margin_cache = _margin_add(margin_cache, delta, k)
        return new_trees, margin_cache

    # ------------------------------------------------------------------
    def local_boost_one_round(self, X, grad, hess, iteration, margin_cache,
                              feature_weights=None):
        """One boosting round via the LOCAL histmaker
        (``updater='grow_local_histmaker'``): trees grow on RAW values with
        per-node re-sketched cuts (``tree/grow_local.py``) instead of the
        global quantized matrix. Same model/caching contract as the legacy
        ``boost_one_round`` loop."""
        from ..parallel.mesh import current_mesh
        from ..tree.grow_local import grow_tree_local

        tp = self.train_param
        mesh = current_mesh()
        if mesh is not None and mesh.devices.size > 1:
            raise NotImplementedError(
                "grow_local_histmaker is single-process/single-device; "
                "use tree_method='tpu_hist' under a mesh")
        if tp.grow_policy == "lossguide":
            raise NotImplementedError(
                "grow_local_histmaker is depthwise (the reference's "
                "histmaker family has no lossguide variant)")
        cfg = self._grow_params()
        X = jnp.asarray(X, jnp.float32)
        new_trees: List[RegTree] = []
        for k in range(self.n_groups):
            g = grad[:, k] if grad.ndim == 2 else grad
            h = hess[:, k] if hess.ndim == 2 else hess
            for ptree in range(self.gbtree_param.num_parallel_tree):
                key = jax.random.PRNGKey(
                    round_seed_py(tp.seed, iteration, k, ptree))
                fw = (jnp.asarray(feature_weights)
                      if feature_weights is not None else None)
                heap = grow_tree_local(X, g, h, key, cfg, tp.max_bin, fw)
                is_split = np.asarray(heap.is_split)
                loss_chg = np.asarray(heap.loss_chg)
                pruned = prune_heap(is_split, loss_chg, tp.gamma)
                tree = RegTree.from_heap(
                    pruned,
                    np.asarray(heap.feature),
                    np.asarray(heap.split_cond),
                    np.asarray(heap.default_left),
                    np.asarray(heap.node_weight),
                    loss_chg,
                    np.asarray(heap.node_h),
                    eta=tp.eta,
                    split_bin=np.asarray(heap.split_bin),
                )
                lmap_np = leaf_value_map(pruned, np.asarray(heap.node_weight),
                                         tp.eta)
                self.model.add(tree, k)
                new_trees.append(tree)
                if margin_cache is not None:
                    delta = jnp.asarray(lmap_np)[heap.positions]
                    margin_cache = _margin_add(margin_cache, delta, k)
        return new_trees, margin_cache

    # ------------------------------------------------------------------
    def refresh_one_round(self, X, grad, hess, iteration):
        """``process_type=update`` / ``updater=refresh``: recompute node
        statistics — and leaf values when ``refresh_leaf`` — of the existing
        model's trees against the current data/gradients, adding NO new
        trees (reference: ``src/tree/updater_refresh.cc:162``,
        ``TreeProcessType`` ``src/gbm/gbtree.h:42``)."""
        from ..predictor import predict_leaf as _pl
        from ..predictor import stack_forest as _sf
        from ..tree.param import calc_weight

        per_round = max(1, self.n_groups) * self.gbtree_param.num_parallel_tree
        if not hasattr(self, "_update_queue") or self._update_queue is None:
            trees = self.model.trees
            if not trees:
                raise ValueError(
                    "process_type=update requires an existing model "
                    "(pass xgb_model / load_model first)"
                )
            self._update_queue = list(zip(trees, self.model.tree_info))
            self.model = GBTreeModel(self.n_groups,
                                     self.gbtree_param.num_parallel_tree)
        if not self._update_queue:
            raise ValueError(
                "num_boost_round exceeds the number of trees to update "
                "(reference gbtree.cc process_type=update contract)"
            )
        batch = self._update_queue[:per_round]
        self._update_queue = self._update_queue[per_round:]
        tp = self.train_param
        p = self._grow_params().split
        eta = tp.eta
        Xj = jnp.asarray(X, jnp.float32)
        new_trees = []
        for slot, (tree, group) in enumerate(batch):
            g = grad[:, group] if grad.ndim == 2 else grad
            h = hess[:, group] if hess.ndim == 2 else hess
            leaves = np.asarray(
                _pl(_sf([tree], [group], self.n_groups), Xj)
            )[:, 0]
            nn = tree.num_nodes
            G = np.zeros(nn, np.float64)
            H = np.zeros(nn, np.float64)
            np.add.at(G, leaves, np.asarray(g, np.float64))
            np.add.at(H, leaves, np.asarray(h, np.float64))
            # push leaf sums up; BFS ids => parents precede children
            for i in range(nn - 1, 0, -1):
                par = tree.parents[i]
                G[par] += G[i]
                H[par] += H[i]
            tree.sum_hessian = H.astype(np.float32)
            w = np.asarray(
                calc_weight(jnp.asarray(G, jnp.float32),
                            jnp.asarray(H, jnp.float32), p)
            )
            tree.base_weights = (eta * w).astype(np.float32)
            # refresh loss_chg too: gain(L) + gain(R) - gain(self) on the
            # NEW stats for internal nodes, 0 for leaves
            # (updater_refresh.cc:148-151; pinned by the golden fixture —
            # CalcGain's min_child_weight zero rule included)
            from ..tree.param import calc_gain

            gains = np.asarray(calc_gain(jnp.asarray(G, jnp.float32),
                                         jnp.asarray(H, jnp.float32), p))
            internal = tree.left_children != -1
            lc = np.where(internal, tree.left_children, 0)
            rc = np.where(internal, tree.right_children, 0)
            tree.loss_changes = np.where(
                internal, gains[lc] + gains[rc] - gains, 0.0
            ).astype(np.float32)
            if tp.refresh_leaf:
                leaf_mask = tree.left_children == -1
                tree.split_conditions = np.where(
                    leaf_mask, eta * w, tree.split_conditions
                ).astype(np.float32)
            self.model.add(tree, group)
            new_trees.append(tree)
        return new_trees, None

    # ------------------------------------------------------------------
    def _boost_fused(
        self, binned, grad, hess, iteration,
        margin_cache, feature_weights=None,
    ):
        """Fast-path round: ``grow_tree_fused`` builds each tree, its gamma
        pruning / leaf values / prediction-cache delta all on device; the
        tree is stored as device arrays and materialized lazily."""
        from ..parallel.mesh import current_mesh, shard_rows

        tp = self.train_param
        cfg, cat_mask = _cat_cfg(self._grow_params(), binned, tp)
        mesh = current_mesh()
        use_mesh = mesh is not None and mesh.devices.size > 1
        if use_mesh and cfg.has_categorical:
            raise NotImplementedError(
                "categorical training under a mesh is not supported yet "
                "(the distributed sketch's categorical identity-cut path "
                "is untested); train single-device or drop feature_types"
            )
        n = binned.n_rows
        cut_vals = jnp.asarray(binned.cuts.values)
        fw = (jnp.asarray(feature_weights)
              if feature_weights is not None else None)
        paged = getattr(binned, "is_paged", False)
        if paged and use_mesh:
            raise NotImplementedError(
                "external-memory + mesh training is not supported yet; "
                "shard rows across processes instead (docs/distributed.md)"
            )
        if paged and cfg.has_categorical:
            raise NotImplementedError(
                "external-memory matrices support numerical training only "
                "(reference external memory has the same restriction)"
            )
        if paged:
            from ..tree.grow_fused import grow_tree_fused_paged

            def grow_one(g, h, key):
                return grow_tree_fused_paged(
                    binned, g, h, cut_vals, key,
                    float(tp.eta), float(tp.gamma), cfg,
                    feature_weights=fw,
                )
        elif use_mesh:
            from ..parallel.grow import distributed_grow_tree_fused

            binsf, n_pad = binned.fused_bins_mesh(mesh)
            onehot_mesh = (None if cfg.has_categorical
                           else binned.fused_onehot_mesh(mesh, tp.max_depth))

            def grow_one(g, h, key):
                if n_pad != n:
                    pad = jnp.zeros((n_pad - n,), jnp.float32)
                    g = jnp.concatenate([g, pad])
                    h = jnp.concatenate([h, pad])
                g, h = shard_rows(g, mesh), shard_rows(h, mesh)
                return distributed_grow_tree_fused(
                    mesh, binsf, g, h, cut_vals, key,
                    jnp.float32(tp.eta), jnp.float32(tp.gamma), cfg, fw,
                    onehot=onehot_mesh,
                )
        else:
            binsf, n_pad = binned.fused_bins()
            onehot = binned.fused_onehot(tp.max_depth)

            def grow_one(g, h, key):
                if n_pad != n:
                    g, h = _pad_gh(g, h, n_pad=n_pad)
                elif self.gbtree_param.num_parallel_tree > 1:
                    # hess is DONATED into the grow program; parallel trees
                    # re-pass the same slice, so each call needs its own
                    # buffer to give up
                    h = jnp.copy(h)
                return grow_tree_fused(
                    binsf, g, h, cut_vals, key,
                    float(tp.eta), float(tp.gamma), cfg, fw, onehot,
                )

        new_trees = []
        hist_seconds = _hist_seconds()
        for k in range(self.n_groups):
            g = grad[:, k] if grad.ndim == 2 else grad
            h = hess[:, k] if hess.ndim == 2 else hess
            for ptree in range(self.gbtree_param.num_parallel_tree):
                key = jax.random.PRNGKey(
                    round_seed_py(tp.seed, iteration, k, ptree)
                )
                t0 = _time.perf_counter()
                with _trace.span("build_tree", iteration=iteration, group=k,
                                 ptree=ptree):
                    grown = grow_one(g, h, key)
                hist_seconds.observe(_time.perf_counter() - t0)
                self.model.add_device(grown, tp.eta, k, tp.max_depth,
                                      cat_mask)
                new_trees.append(grown)
                if margin_cache is not None:
                    margin_cache = _margin_add(margin_cache, grown.delta, k)
        return new_trees, margin_cache

    def scan_rounds_supported(self, binned, obj, n_groups: int) -> bool:
        """Whether ``boost_rounds_scan`` can run: the fused depthwise
        path with a scan-safe (jax-traceable, groupless-state) objective;
        one tree per output group per round. A matrix with categorical
        columns is admitted depthwise on one device; lossguide or a mesh
        keeps it on the per-round path."""
        tp = self.train_param
        npt_ok = self.gbtree_param.num_parallel_tree == 1 or (
            tp.grow_policy != "lossguide" and not _mesh_active()
        )
        cats_ok = not tuple(getattr(binned, "categorical", ())) or (
            tp.grow_policy != "lossguide" and not _mesh_active()
        )
        return (
            self.name == "gbtree"
            and npt_ok
            and not self._is_update_process
            and getattr(obj, "scan_safe", False)
            and cats_ok
            and not getattr(binned, "is_paged", False)
            and (tp.grow_policy != "lossguide" or not _mesh_active())
        )

    def boost_rounds_scan(
        self,
        binned,
        obj,
        label: jax.Array,  # [n]
        weight,  # [n] or None
        margin: jax.Array,  # [n, 1]
        start_iteration: int,
        num_rounds: int,
        feature_weights=None,
    ) -> jax.Array:
        """``num_rounds`` boosting rounds as ONE compiled program: a
        ``lax.scan`` whose body is gradient -> fused tree build -> margin
        update, with per-tree heap arrays stacked as scan outputs. One
        dispatch replaces ~10 x num_rounds host round-trips — the
        whole-training-loop-on-device design point the reference cannot
        reach (its DoBoost crosses Python/C/driver boundaries every round,
        ``gbtree.cc:219``). Per-round RNG keys reproduce ``boost_one_round``
        exactly; results match the per-round path to float-fusion noise.
        Under an active mesh the whole chunk runs inside one shard_map
        (distributed_boost_rounds_scan)."""
        from ..tree.hist_kernel import feature_tile

        t0 = _time.perf_counter()
        per_round = self.n_groups * self.gbtree_param.num_parallel_tree
        tile = feature_tile(binned.n_features, binned.cuts.max_bin,
                            self.train_param.max_depth) \
            if _pallas_flag(None) else 0
        with _trace.span("scan_chunk", start=start_iteration,
                         rounds=num_rounds, groups=self.n_groups,
                         trees=per_round * num_rounds,
                         features=binned.n_features, feature_tile=tile):
            out = self._boost_rounds_scan_impl(
                binned, obj, label, weight, margin, start_iteration,
                num_rounds, feature_weights)
        _REGISTRY.histogram(
            "scan_chunk_seconds",
            "Host-side wall time of one fused multi-round scan dispatch",
        ).observe(_time.perf_counter() - t0)
        return out

    def _boost_rounds_scan_impl(
        self,
        binned,
        obj,
        label: jax.Array,
        weight,
        margin: jax.Array,
        start_iteration: int,
        num_rounds: int,
        feature_weights=None,
    ) -> jax.Array:
        from ..parallel.mesh import current_mesh, shard_rows

        tp = self.train_param
        # a categorical job's one-hot / partition columns (the same rule
        # as the per-round path's); a numerical job's config is unchanged
        cfg, cat_mask = _cat_cfg(self._grow_params(), binned, tp)
        mesh = current_mesh()
        use_mesh = mesh is not None and mesh.devices.size > 1
        n = binned.n_rows
        if tp.grow_policy == "lossguide":
            assert not use_mesh  # eligibility gate keeps mesh off this path
            return self._scan_lossguide(binned, obj, label, weight, margin,
                                        start_iteration, num_rounds,
                                        feature_weights)
        # the chunk's three host steps, children of the caller's
        # ``scan_chunk`` span: what the host does before the program is
        # called, the call itself (tracing and compiling, when they happen,
        # are in it), and what it does with the result
        with _trace.span("chunk.prepare"):
            if use_mesh:
                binsf, n_pad = binned.fused_bins_mesh(mesh)
            else:
                binsf, n_pad = binned.fused_bins()
            cut_vals = jnp.asarray(binned.cuts.values)
            fw = (jnp.asarray(feature_weights)
                  if feature_weights is not None else None)
            eta = jnp.float32(tp.eta)
            gamma = jnp.float32(tp.gamma)
            label = jnp.asarray(label, jnp.float32)
            weight_j = (jnp.asarray(weight, jnp.float32)
                        if weight is not None else None)
            seed_base = jnp.uint32(
                np.uint32((tp.seed * 1000003) & 0xFFFFFFFF))

            K = self.n_groups
            m_pad = margin
            if n_pad != n:
                m_pad = jnp.concatenate(
                    [m_pad, jnp.zeros((n_pad - n, K), jnp.float32)])
            iters = jnp.arange(start_iteration, start_iteration + num_rounds,
                               dtype=jnp.int32)
            if use_mesh:
                # the mesh path shards label/weight alongside the padded
                # rows
                if n_pad != n:
                    label = jnp.concatenate(
                        [label, jnp.zeros((n_pad - n,), jnp.float32)])
                    if weight_j is not None:
                        weight_j = jnp.concatenate(
                            [weight_j, jnp.zeros((n_pad - n,), jnp.float32)])
                label = shard_rows(label, mesh)
                if weight_j is not None:
                    weight_j = shard_rows(weight_j, mesh)
                m_pad = shard_rows(m_pad, mesh)
                onehot = binned.fused_onehot_mesh(mesh, tp.max_depth)
                fh_plan = binned.hoist_plan_mesh(mesh, tp.max_depth)
                groups = list(range(K))
            else:
                onehot = binned.fused_onehot(tp.max_depth)
                npt = self.gbtree_param.num_parallel_tree
                groups = [k for k in range(K) for _ in range(npt)]
        with _trace.span("chunk.dispatch"):
            if use_mesh:
                from ..parallel.grow import distributed_boost_rounds_scan

                m_pad, stacked = distributed_boost_rounds_scan(
                    mesh, obj, binsf, label, weight_j, m_pad, iters,
                    cut_vals, eta, gamma, fw, seed_base, n, cfg,
                    onehot=onehot, fh_plan=fh_plan,
                )
            else:
                m_pad, stacked = _scan_rounds_impl(
                    binsf, label, weight_j, m_pad, iters, cut_vals, eta,
                    gamma, fw, seed_base, onehot, obj=obj,
                    obj_fp=_obj_fingerprint(obj), cfg=cfg, n=n, n_pad=n_pad,
                    n_groups=K, n_parallel=npt,
                )
        with _trace.span("chunk.commit", groups=K,
                         trees=len(groups) * num_rounds):
            if use_mesh:
                from ..parallel.mesh import local_rows

                # back to THIS process's rows (identity single-process):
                # the margin cache, evals, and predictions are process-local
                m_pad = local_rows(m_pad)
            chunk = self.model.add_device_chunk(stacked, num_rounds, groups,
                                                tp.eta, tp.max_depth,
                                                cat_mask)
        if cat_mask is not None:
            # waits for the chunk, outside the commit span, as the
            # lossguide chunk's row counters do
            _count_splits(chunk, cfg, binned.n_features)
        return m_pad[:n]

    def _scan_lossguide(self, binned, obj, label, weight, margin,
                        start_iteration, num_rounds, feature_weights):
        """A lossguide chunk, in the three host steps of the depthwise
        chunk's. The chip path's trees report every node's row count:
        ``node_hist_rows_total`` (the rows their node histograms were asked
        to build) and ``node_partition_rows_total`` (the rows their
        partitions streamed) are fed from it once a chunk, after
        ``chunk.commit`` (a read that waits for the chunk, so the next
        chunk's dispatch does not overlap this one's run)."""
        tp = self.train_param
        with _trace.span("chunk.prepare"):
            cfg = self._grow_params()
            max_leaves = self._lossguide_max_leaves()
            rows_path = _lossguide_on_chip(cfg, binned.n_features,
                                           binned.cuts.max_bin)
            K = self.n_groups
            cut_vals = jnp.asarray(binned.cuts.values)
            fw = (jnp.asarray(feature_weights)
                  if feature_weights is not None else None)
            label_j = jnp.asarray(label, jnp.float32)
            weight_j = (jnp.asarray(weight, jnp.float32)
                        if weight is not None else None)
            seed_base = np.uint32((tp.seed * 1000003) & 0xFFFFFFFF)
            iters = jnp.arange(start_iteration, start_iteration + num_rounds,
                               dtype=jnp.int32)
        with _trace.span("chunk.dispatch"):
            m_cur, stacked = _scan_rounds_lossguide_impl(
                binned.bins, label_j, weight_j, margin, iters, cut_vals,
                jnp.float32(tp.eta), jnp.float32(tp.gamma), fw,
                jnp.uint32(seed_base), obj=obj, obj_fp=_obj_fingerprint(obj),
                cfg=cfg, n_groups=K, max_leaves=max_leaves,
                rows_path=rows_path,
            )
        with _trace.span("chunk.commit", groups=K, trees=K * num_rounds):
            self.model.add_device_alloc_chunk(
                stacked[0], stacked[1], stacked[2], num_rounds, K,
                tp.eta, tp.gamma, tp.max_depth, cat_mask=None,
            )
        if rows_path:
            from ..tree.grow_lossguide import node_work

            # waits for the chunk, outside the commit span: its time is the
            # device's, not the commit's
            streamed, built = node_work(stacked[0].left, stacked[0].right,
                                        stacked[3])
            _REGISTRY.counter(
                "node_hist_rows_total",
                "Rows the leaf-wise node histograms were asked to build "
                "(the smaller children's)",
            ).inc(built)
            _REGISTRY.counter(
                "node_partition_rows_total",
                "Rows the leaf-wise partitions streamed (the split leaves')",
            ).inc(streamed)
        return m_cur

    # ------------------------------------------------------------------
    def training_margin(self, X, base_margin: jax.Array) -> jax.Array:
        """Margin used to compute this round's gradients (DART overrides to
        apply dropout)."""
        return predict_margin(self.model.stacked(), X, base_margin)

    def tree_weights(self) -> Optional[jax.Array]:
        return None

    def predict(self, X, base_margin: jax.Array) -> jax.Array:
        return predict_margin(self.model.stacked(), X, base_margin, self.tree_weights())

    def predict_leaf(self, X) -> jax.Array:
        # leaf ids must match the (BFS-compacted) saved model, not the
        # device heap layout: force materialization before stacking
        _ = self.model.trees
        return predict_leaf(self.model.stacked(), X)

    # ------------------------------------------------------------------
    def save_json(self) -> dict:
        return {
            "name": self.name,
            "model": {
                "gbtree_model_param": {
                    "num_trees": str(self.model.num_trees),
                    # persisted so round-slicing semantics survive a JSON
                    # round trip (reference GBTreeModelParam)
                    "num_parallel_tree": str(self.gbtree_param.num_parallel_tree),
                    "size_leaf_vector": "0",
                },
                "trees": [t.to_json(i) for i, t in enumerate(self.model.trees)],
                "tree_info": list(self.model.tree_info),
            },
        }

    def load_json(self, j: dict) -> None:
        m = j["model"]
        npt = int(m.get("gbtree_model_param", {}).get("num_parallel_tree", 0)) or (
            self.gbtree_param.num_parallel_tree
        )
        self.gbtree_param.num_parallel_tree = npt
        self.model = GBTreeModel(self.n_groups, npt)
        for tj, info in zip(m["trees"], m["tree_info"]):
            self.model.add(RegTree.from_json(tj), int(info))


@BOOSTERS.register("dart")
class Dart(GBTree):
    """DART dropout booster (reference: gbtree.cc:637-1020)."""

    name = "dart"

    def __init__(self, n_groups: int, params: Dict[str, Any]):
        super().__init__(n_groups, params)
        self.weight_drop: List[float] = []
        self._idx_drop: List[int] = []
        self._rng = np.random.RandomState(self.train_param.seed)

    def _drop_trees(self) -> None:
        """reference DropTrees (gbtree.cc:914)."""
        p = self.gbtree_param
        self._idx_drop = []
        if p.skip_drop > 0.0 and self._rng.uniform() < p.skip_drop:
            return
        W = self.weight_drop
        if not W:
            return
        if p.sample_type == "weighted":
            sw = sum(W)
            for i, wi in enumerate(W):
                if self._rng.uniform() < p.rate_drop * len(W) * wi / max(sw, 1e-30):
                    self._idx_drop.append(i)
            if p.one_drop and not self._idx_drop:
                probs = np.asarray(W) / max(sum(W), 1e-30)
                self._idx_drop.append(int(self._rng.choice(len(W), p=probs)))
        else:
            for i in range(len(W)):
                if self._rng.uniform() < p.rate_drop:
                    self._idx_drop.append(i)
            if p.one_drop and not self._idx_drop:
                self._idx_drop.append(int(self._rng.randint(len(W))))

    def _normalize_trees(self, n_new: int) -> None:
        """reference NormalizeTrees (gbtree.cc:963)."""
        lr = self.train_param.eta / max(n_new, 1)
        k = len(self._idx_drop)
        if k == 0:
            self.weight_drop.extend([1.0] * n_new)
        elif self.gbtree_param.normalize_type == "forest":
            factor = 1.0 / (1.0 + lr)
            for i in self._idx_drop:
                self.weight_drop[i] *= factor
            self.weight_drop.extend([factor] * n_new)
        else:  # "tree"
            factor = k / (k + lr)
            for i in self._idx_drop:
                self.weight_drop[i] *= factor
            self.weight_drop.extend([1.0 / (k + lr)] * n_new)

    def tree_weights(self) -> Optional[jax.Array]:
        if not self.weight_drop:
            return None
        return jnp.asarray(np.asarray(self.weight_drop, np.float32))

    def training_margin(self, X, base_margin: jax.Array) -> jax.Array:
        self._drop_trees()
        tw = np.asarray(self.weight_drop, np.float32)
        if len(tw):
            tw = tw.copy()
            tw[self._idx_drop] = 0.0
            return predict_margin(self.model.stacked(), X, base_margin, jnp.asarray(tw))
        return predict_margin(self.model.stacked(), X, base_margin)

    def boost_one_round(self, binned, grad, hess, iteration, margin_cache,
                        feature_weights=None):
        # DART cannot use the incremental cache (dropout changes old trees'
        # weights every round) — reference also disables the cache for DART
        new_trees, _ = super().boost_one_round(
            binned, grad, hess, iteration, None, feature_weights
        )
        self._normalize_trees(len(new_trees))
        return new_trees, None

    def save_json(self) -> dict:
        j = super().save_json()
        j["name"] = "dart"
        j["model"] = {"gbtree": j["model"], "weight_drop": list(self.weight_drop)}
        return j

    def load_json(self, j: dict) -> None:
        inner = j["model"]["gbtree"]
        super().load_json({"model": inner})
        self.weight_drop = [float(x) for x in j["model"]["weight_drop"]]
