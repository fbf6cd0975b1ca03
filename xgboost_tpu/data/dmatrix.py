"""DMatrix and MetaInfo.

Reference equivalents: ``MetaInfo`` (``include/xgboost/data.h:47-185``),
``SimpleDMatrix`` (``src/data/simple_dmatrix.cc``), ``DeviceQuantileDMatrix``
(``src/data/iterative_device_dmatrix.h``), Python ``DMatrix``
(``python-package/xgboost/core.py:501``).

Host side keeps a canonical dense float32/NaN matrix; the quantized
device-resident form (BinnedMatrix, the ELLPACK analog) is built lazily on
first use by the hist updater and cached — mirroring the reference where
``GetBatches<GHistIndexMatrix>``/``EllpackPage`` materialize on first touch.
"""

from __future__ import annotations

import os
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from .adapters import dispatch_data
from .quantile import BinnedMatrix, HistogramCuts

__all__ = ["MetaInfo", "DMatrix", "QuantileDMatrix"]


class MetaInfo:
    """Labels, weights, groups, margins, survival bounds, feature metadata."""

    def __init__(self) -> None:
        self.label: Optional[np.ndarray] = None
        self.weight: Optional[np.ndarray] = None
        self.base_margin: Optional[np.ndarray] = None
        self.group_ptr: Optional[np.ndarray] = None  # [n_groups+1] int64 CSR-style
        self.label_lower_bound: Optional[np.ndarray] = None
        self.label_upper_bound: Optional[np.ndarray] = None
        self.feature_names: Optional[List[str]] = None
        self.feature_types: Optional[List[str]] = None
        self.feature_weights: Optional[np.ndarray] = None

    def num_groups(self) -> int:
        return 0 if self.group_ptr is None else len(self.group_ptr) - 1

    def slice(self, rindex: np.ndarray) -> "MetaInfo":
        out = MetaInfo()
        for name in ("label", "weight", "base_margin", "label_lower_bound", "label_upper_bound"):
            v = getattr(self, name)
            if v is not None:
                setattr(out, name, v[rindex])
        out.feature_names = self.feature_names
        out.feature_types = self.feature_types
        out.feature_weights = self.feature_weights
        # group structure does not survive arbitrary row slicing (same
        # limitation as the reference's SliceDMatrix for ranking)
        return out


def _group_ptr_from_sizes(sizes: np.ndarray) -> np.ndarray:
    ptr = np.zeros(len(sizes) + 1, dtype=np.int64)
    np.cumsum(sizes, out=ptr[1:])
    return ptr


def _group_ptr_from_qid(qid: np.ndarray) -> np.ndarray:
    if len(qid) == 0:
        return np.zeros(1, dtype=np.int64)
    change = np.nonzero(np.diff(qid))[0] + 1
    return np.concatenate([[0], change, [len(qid)]]).astype(np.int64)


class DMatrix:
    """In-memory data matrix + metadata, the universal training/predict input."""

    #: CSR storage when constructed from scipy sparse input (class-level
    #: default so subclasses bypassing __init__ read None)
    _sparse = None

    def __init__(
        self,
        data: Any,
        label: Any = None,
        *,
        weight: Any = None,
        base_margin: Any = None,
        missing: float = np.nan,
        feature_names: Optional[Sequence[str]] = None,
        feature_types: Optional[Sequence[str]] = None,
        group: Any = None,
        qid: Any = None,
        label_lower_bound: Any = None,
        label_upper_bound: Any = None,
        feature_weights: Any = None,
        enable_categorical: bool = False,
        nthread: Optional[int] = None,  # accepted for API compat; single-controller
    ) -> None:
        auto_names = auto_types = auto_label = auto_qid = None
        self.info = MetaInfo()
        if isinstance(data, (str, os.PathLike)) and self._looks_binary(
                os.fspath(data)):
            # save_binary round-trip: restores the full MetaInfo, not just
            # data+label, so handle it before the generic adapter sweep
            self._load_binary(data)
            self._finish_init(label, weight, base_margin, feature_names,
                              feature_types, group, qid, label_lower_bound,
                              label_upper_bound, feature_weights)
            return
        if hasattr(data, "tocsr") and hasattr(data, "nnz"):
            # scipy sparse stays sparse: no dense float materialization
            # (reference SparsePage storage, include/xgboost/data.h:260);
            # quantization streams column blocks (quantile.from_sparse)
            from .sparse import CSRStorage

            self._sparse: Optional["CSRStorage"] = CSRStorage(data, missing)
            self._data = None
        else:
            X, auto_names, auto_types, auto_label, auto_qid = dispatch_data(
                data, missing=missing, enable_categorical=enable_categorical
            )
            self._data: np.ndarray = X
            self._sparse = None
        if auto_names and not feature_names:
            self.info.feature_names = auto_names
        if auto_types and not feature_types:
            self.info.feature_types = auto_types
        if label is None and auto_label is not None:
            label = auto_label
        if qid is None and auto_qid is not None:
            qid = auto_qid
        self._finish_init(label, weight, base_margin, feature_names,
                          feature_types, group, qid, label_lower_bound,
                          label_upper_bound, feature_weights)

    def _finish_init(self, label, weight, base_margin, feature_names,
                     feature_types, group, qid, label_lower_bound,
                     label_upper_bound, feature_weights) -> None:
        """Apply explicit constructor metadata (wins over anything the
        adapter or a binary container supplied) and set up lazy caches."""
        if feature_names:
            self.info.feature_names = list(feature_names)
        if feature_types:
            self.info.feature_types = list(feature_types)
        if label is not None:
            self.set_label(label)
        if weight is not None:
            self.set_weight(weight)
        if base_margin is not None:
            self.set_base_margin(base_margin)
        if group is not None:
            self.set_group(group)
        if qid is not None:
            self.info.group_ptr = _group_ptr_from_qid(np.asarray(qid))
        if label_lower_bound is not None:
            self.info.label_lower_bound = np.asarray(label_lower_bound, dtype=np.float32)
        if label_upper_bound is not None:
            self.info.label_upper_bound = np.asarray(label_upper_bound, dtype=np.float32)
        if feature_weights is not None:
            self.info.feature_weights = np.asarray(feature_weights, dtype=np.float32)
        # lazily-built quantized views keyed by max_bin (analog of the
        # page cache in SimpleDMatrix::GetBatches)
        self._binned: Dict[int, BinnedMatrix] = {}

    # ---- metadata setters (reference: MetaInfo::SetInfo, data.cc) ----
    #: float fields settable through the reference's set_float_info API
    _FLOAT_INFO = ("label", "weight", "base_margin", "label_lower_bound",
                   "label_upper_bound", "feature_weights")

    def set_float_info(self, field: str, data: Any) -> None:
        """Reference core.py DMatrix.set_float_info parity."""
        if field not in self._FLOAT_INFO:
            raise ValueError(f"unknown float field: {field!r}")
        setattr(self.info, field, np.asarray(data, dtype=np.float32))

    def get_float_info(self, field: str) -> np.ndarray:
        if field not in self._FLOAT_INFO:
            raise ValueError(f"unknown float field: {field!r}")
        v = getattr(self.info, field)
        return np.asarray(v, np.float32) if v is not None else np.array([], np.float32)

    def set_uint_info(self, field: str, data: Any) -> None:
        if field == "group_ptr":
            self.info.group_ptr = np.asarray(data, np.int64)
        elif field == "group":
            self.set_group(data)
        else:
            raise ValueError(f"unknown uint field: {field!r}")

    def get_uint_info(self, field: str) -> np.ndarray:
        if field in ("group_ptr", "group"):
            gp = self.info.group_ptr
            return (np.asarray(gp, np.uint32) if gp is not None
                    else np.array([], np.uint32))
        raise ValueError(f"unknown uint field: {field!r}")

    def set_info(self, *, label=None, weight=None, base_margin=None,
                 group=None, qid=None, label_lower_bound=None,
                 label_upper_bound=None, feature_names=None,
                 feature_types=None, feature_weights=None) -> None:
        """Bulk metadata setter (reference core.py DMatrix.set_info)."""
        if label is not None:
            self.set_label(label)
        if weight is not None:
            self.set_weight(weight)
        if base_margin is not None:
            self.set_base_margin(base_margin)
        if group is not None:
            self.set_group(group)
        if qid is not None:
            self.info.group_ptr = _group_ptr_from_qid(
                np.asarray(qid))
        if label_lower_bound is not None:
            self.set_float_info("label_lower_bound", label_lower_bound)
        if label_upper_bound is not None:
            self.set_float_info("label_upper_bound", label_upper_bound)
        if feature_weights is not None:
            self.set_float_info("feature_weights", feature_weights)
        if feature_names is not None:
            self.feature_names = feature_names
        if feature_types is not None:
            self.info.feature_types = list(feature_types)

    def get_group(self) -> np.ndarray:
        """Per-group sizes (inverse of set_group)."""
        gp = self.info.group_ptr
        if gp is None:
            return np.array([], np.int64)
        return np.diff(np.asarray(gp, np.int64))

    def get_data(self):
        """Feature matrix as scipy CSR (reference DMatrix.get_data)."""
        import scipy.sparse as sp

        if self._sparse is not None and self._data is None:
            # pre-serving bug: this read .values/.indices/.indptr, which
            # CSRStorage never had — it wraps one scipy CSR (.csr)
            return sp.csr_matrix(self._sparse.csr, copy=True)
        X = np.asarray(self.data)
        mask = ~np.isnan(X)
        return sp.csr_matrix(np.where(mask, X, 0.0) * mask)

    def save_binary(self, fname, silent: bool = True) -> None:
        """Persist data + metadata for fast reload via ``DMatrix(fname)``
        (the reference's .buffer files; here an npz container). Written
        through an open handle so the file is exactly ``fname`` — np.savez
        on a *path* appends '.npz', which would break the reference-
        canonical ``save_binary('train.buffer')`` round-trip."""
        fields = {"data": np.asarray(self.data, np.float32)}
        for name in ("label", "weight", "base_margin", "group_ptr",
                     "label_lower_bound", "label_upper_bound",
                     "feature_weights"):
            v = getattr(self.info, name)
            if v is not None:
                fields[name] = np.asarray(v)
        fields["feature_names"] = np.asarray(
            [str(n) for n in (self.feature_names or [])])
        fields["feature_types"] = np.asarray(
            [str(t) for t in (self.info.feature_types or [])])
        with open(fname, "wb") as fh:
            np.savez(fh, **fields)

    @staticmethod
    def _looks_binary(uri: str) -> bool:
        path, _, fmt = uri.partition("?format=")
        return fmt == "binary" or path.endswith((".buffer", ".npz"))

    def _load_binary(self, uri: str) -> None:
        """Restore a save_binary container: data plus every persisted
        MetaInfo field (reference: SimpleDMatrix binary load,
        simple_dmatrix.cc SaveToLocalFile/LoadBinary round-trip)."""
        path = os.fspath(uri).partition("?format=")[0]
        with np.load(path, allow_pickle=False) as z:
            self._data = z["data"].astype(np.float32)
            self._sparse = None
            for name in ("label", "weight", "base_margin", "group_ptr",
                         "label_lower_bound", "label_upper_bound",
                         "feature_weights"):
                # legacy containers wrote empty arrays as the "unset"
                # sentinel — keep those None, not set-but-empty
                if name in z.files and z[name].size:
                    setattr(self.info, name, np.asarray(z[name]))
            # any key beyond data is optional: third-party npz files (a
            # bare {"data": ...}) and legacy containers both load
            if "feature_names" in z.files:
                names = [str(x) for x in z["feature_names"]]
                self.info.feature_names = names or None
            if "feature_types" in z.files:
                types = [str(x) for x in z["feature_types"]]
                self.info.feature_types = types or None

    def set_label(self, label: Any) -> None:
        self.info.label = np.asarray(label, dtype=np.float32).reshape(-1)

    def set_weight(self, weight: Any) -> None:
        self.info.weight = np.asarray(weight, dtype=np.float32).reshape(-1)

    def set_base_margin(self, margin: Any) -> None:
        self.info.base_margin = np.asarray(margin, dtype=np.float32)

    def set_group(self, group: Any) -> None:
        self.info.group_ptr = _group_ptr_from_sizes(np.asarray(group, dtype=np.int64))

    def get_label(self) -> np.ndarray:
        return self.info.label if self.info.label is not None else np.empty(0, np.float32)

    def get_weight(self) -> np.ndarray:
        return self.info.weight if self.info.weight is not None else np.empty(0, np.float32)

    def get_base_margin(self) -> np.ndarray:
        return (
            self.info.base_margin
            if self.info.base_margin is not None
            else np.empty(0, np.float32)
        )

    # ---- shape ----
    def num_row(self) -> int:
        if self._sparse is not None:
            return int(self._sparse.shape[0])
        return int(self._data.shape[0])

    def num_col(self) -> int:
        if self._sparse is not None:
            return int(self._sparse.shape[1])
        return int(self._data.shape[1])

    def num_nonmissing(self) -> int:
        if self._sparse is not None and self._data is None:
            return self._sparse.nnz
        return int(np.count_nonzero(~np.isnan(self.data)))

    @property
    def data(self) -> np.ndarray:
        """Dense [n, F] float32 with NaN missing. For sparse-constructed
        matrices this densifies ON FIRST TOUCH and caches — training and
        batch prediction never call it (they stream blocks); feature paths
        that need raw values wholesale (SHAP, gblinear, approx re-sketch,
        exact cuts) do."""
        if self._data is None and self._sparse is not None:
            self._data = self._sparse.toarray()
        return self._data

    @property
    def feature_names(self) -> Optional[List[str]]:
        return self.info.feature_names

    @feature_names.setter
    def feature_names(self, names: Optional[Sequence[str]]) -> None:
        self.info.feature_names = list(names) if names is not None else None

    @property
    def feature_types(self) -> Optional[List[str]]:
        return self.info.feature_types

    @feature_types.setter
    def feature_types(self, types: Optional[Sequence[str]]) -> None:
        self.info.feature_types = list(types) if types is not None else None

    # ---- quantized view ----
    def categorical_features(self) -> List[int]:
        ft = self.info.feature_types
        if not ft:
            return []
        return [i for i, t in enumerate(ft) if t in ("c", "categorical")]

    def get_binned(
        self, max_bin: int = 256, sketch_weights: Optional[np.ndarray] = None
    ) -> BinnedMatrix:
        """Build-or-fetch the quantized matrix for this max_bin (analog of
        ``GetBatches<GHistIndexMatrix>(BatchParam{max_bin})``)."""
        bm = self._binned.get(max_bin)
        if bm is None:
            from ..observability import trace

            # one span per COLD construction: the data-plane ingest cost
            # (sketch + quantize, routed through the sketch_cuts /
            # bin_matrix dispatch ops) — cache hits pay nothing
            with trace.span("dmatrix_build", rows=self.num_row(),
                            features=self.num_col(), max_bin=max_bin):
                bm = self.build_binned(max_bin, sketch_weights)
            self._binned[max_bin] = bm
        return bm

    def get_binned_exact(self, cap: int = 16384) -> BinnedMatrix:
        """Quantized view with cuts at EVERY distinct value — the exact
        candidate set tree_method='exact' trains on (colmaker semantics,
        ``src/tree/updater_colmaker.cc:367``; see
        ``quantile.compute_exact_cuts``). Cached under its own key."""
        bm = self._binned.get("exact")
        if bm is None:
            import jax

            if jax.process_count() > 1:
                raise NotImplementedError(
                    "tree_method='exact' is single-process only (each "
                    "process sees only its row shard, so globally exact "
                    "cuts cannot be built); use tpu_hist"
                )
            from .quantile import compute_exact_cuts

            cat = self.categorical_features()
            cuts = compute_exact_cuts(self.data, cap=cap, categorical=cat)
            if cat:
                self._validate_categorical(cat, cuts.max_bin)
            bm = BinnedMatrix.from_dense(
                self.data, max_bin=cuts.max_bin, cuts=cuts, categorical=cat
            )
            self._binned["exact"] = bm
        return bm

    def build_binned(
        self, max_bin: int = 256, sketch_weights: Optional[np.ndarray] = None
    ) -> BinnedMatrix:
        """UNCACHED quantized-matrix build — same categorical and
        distributed-sketch handling as ``get_binned``; used by the approx
        per-iteration re-sketch (updater_histmaker.cc) with fresh hessian
        weights every round."""
        if True:
            cat = self.categorical_features()
            if cat:
                self._validate_categorical(cat, max_bin)
            cuts = None
            from ..parallel.mesh import current_mesh

            mesh = current_mesh()
            if (self._sparse is not None and self._data is None
                    and not (mesh is not None and mesh.devices.size > 1)):
                # sparse fast path: column-blocked sketch + quantization,
                # no dense float detour (under a mesh the distributed
                # sketch needs the dense row shards — densify then)
                return BinnedMatrix.from_sparse(
                    self._sparse, max_bin=max_bin, weights=sketch_weights,
                    categorical=cat,
                )
            if mesh is not None and mesh.devices.size > 1:
                # distributed sketch: per-shard summaries merged by
                # all_gather (the quantile.cc:270 AllReduce site)
                import jax
                import jax.numpy as jnp

                from ..observability import trace
                from ..parallel.mesh import (global_pad_rows,
                                             local_device_count, shard_rows)
                from ..parallel.sketch import distributed_compute_cuts

                X = np.asarray(self.data, np.float32)
                # common per-process block (processes may hold ragged row
                # slices); NaN pad rows are sketch-inert
                n_pad = global_pad_rows(X.shape[0],
                                        max(1, local_device_count(mesh)))
                if n_pad != X.shape[0]:
                    X = np.concatenate(
                        [X, np.full((n_pad - X.shape[0], X.shape[1]), np.nan, np.float32)]
                    )
                w = sketch_weights
                if w is not None and len(w):
                    w = np.concatenate(
                        [np.asarray(w, np.float32),
                         np.zeros(n_pad - len(w), np.float32)]
                    )
                    w = shard_rows(jnp.asarray(w), mesh)
                with trace.stage("upload", cols=int(X.shape[1]),
                                 what="cuts", sharded=True):
                    Xs = jax.block_until_ready(
                        shard_rows(jnp.asarray(X), mesh))
                cuts = distributed_compute_cuts(
                    mesh, Xs, max_bin=max_bin, weights=w,
                )
                del Xs  # the float32 shards go before the bins are made
                if cat:
                    from .quantile import apply_categorical_identity

                    apply_categorical_identity(cuts.values, cuts.min_vals, cat)
            bm = BinnedMatrix.from_dense(
                self.data, max_bin=max_bin, weights=sketch_weights,
                categorical=cat, cuts=cuts,
            )
        return bm

    def _validate_categorical(self, cat: List[int], max_bin: int) -> None:
        """Categorical codes must be non-negative integers < max_bin: the
        identity binning and the predictor's exact-equality decision must
        agree, so out-of-range or fractional codes are an error (the
        reference likewise validates categories, common/categorical.h
        InvalidCat checks)."""
        for f in cat:
            if self._sparse is not None and self._data is None:
                # CSR-backed: read the column's stored values directly —
                # touching .data would densify the whole matrix and defeat
                # the sparse ingestion path
                col = self._sparse.column_values(f)
            else:
                col = self.data[:, f]
            valid = col[~np.isnan(col)]
            if valid.size == 0:
                continue
            if (valid < 0).any() or (valid != np.floor(valid)).any():
                raise ValueError(
                    f"categorical feature {f} has negative or non-integer codes"
                )
            mx = float(valid.max())
            if mx >= max_bin:
                raise ValueError(
                    f"categorical feature {f} has {int(mx) + 1} categories, "
                    f"exceeding max_bin={max_bin}; raise max_bin"
                )

    def slice(self, rindex: Any, allow_groups: bool = False) -> "DMatrix":
        """A new DMatrix holding the selected rows, with per-row metadata
        (label/weight/base_margin/survival bounds) and feature metadata
        sliced along (reference: ``core.py DMatrix.slice`` /
        ``XGDMatrixSliceDMatrix``). ``rindex`` is an integer index array
        or a boolean row mask; out-of-range indices raise. Ranking group
        structure does not survive arbitrary row slicing — matrices with
        groups refuse unless ``allow_groups=True`` drops it (the
        reference's ``XGDMatrixSliceDMatrixEx`` contract). Sparse-
        constructed matrices stay sparse: no densification to slice."""
        rindex = np.asarray(rindex)
        if rindex.dtype == np.bool_:
            rindex = np.nonzero(rindex)[0]
        rindex = rindex.astype(np.int64).ravel()
        n = self.num_row()
        if rindex.size and (rindex.min() < -n or rindex.max() >= n):
            raise IndexError(
                f"slice index out of range for {n} rows: "
                f"[{rindex.min()}, {rindex.max()}]")
        if self.info.group_ptr is not None and not allow_groups:
            raise ValueError(
                "slice does not support group structure; pass "
                "allow_groups=True to drop it")
        out = DMatrix.__new__(DMatrix)
        if self._sparse is not None and self._data is None:
            out._sparse = self._sparse.slice_rows(rindex)
            out._data = None
        else:
            out._data = np.asarray(self.data)[rindex]
        out.info = self.info.slice(rindex)
        out._binned = {}
        return out


class QuantileDMatrix(DMatrix):
    """Quantized-at-construction DMatrix (reference:
    ``DeviceQuantileDMatrix``/``IterativeDeviceDMatrix``): bins eagerly with
    either its own sketch or the cuts of a reference DMatrix (so validation
    sets share the training bin edges)."""

    def __init__(
        self,
        data: Any,
        label: Any = None,
        *,
        max_bin: int = 256,
        ref: Optional[DMatrix] = None,
        **kwargs: Any,
    ) -> None:
        super().__init__(data, label, **kwargs)
        self.max_bin = max_bin
        cuts: Optional[HistogramCuts] = None
        cat = self.categorical_features()
        if ref is not None and ref._binned:
            ref_bm = next(iter(ref._binned.values()))
            cuts = ref_bm.cuts
            if not cat:
                cat = list(ref_bm.categorical)
        if self._sparse is not None and self._data is None:
            self._binned[max_bin] = BinnedMatrix.from_sparse(
                self._sparse, max_bin=max_bin, weights=self.info.weight,
                cuts=cuts, categorical=cat,
            )
        else:
            self._binned[max_bin] = BinnedMatrix.from_dense(
                self._data, max_bin=max_bin, weights=self.info.weight,
                cuts=cuts, categorical=cat,
            )


def load_row_split(uri, rank: int, world: int, **kwargs) -> "DMatrix":
    """Load a rank's row shard of a text dataset — the multi-process
    ingestion helper for distributed training (reference:
    ``DMatrix::Load(..., load_row_split=true)`` /
    ``include/xgboost/data.h:512``: every worker parses the file and keeps
    the rows of its rank, round-robin by block). Use with
    ``parallel.init_distributed`` (docs/distributed.md)."""
    if not (0 <= rank < world):
        raise ValueError(f"rank {rank} outside [0, {world})")
    d = DMatrix(uri, **kwargs)
    if world == 1:
        return d
    idx = np.arange(rank, d.num_row(), world)
    out = d.slice(idx)
    # per-group data cannot be row-split blindly (reference raises too)
    if d.info.group_ptr is not None and len(d.info.group_ptr) > 2:
        raise ValueError(
            "load_row_split cannot split grouped (ranking) data; "
            "shard by query group instead"
        )
    return out
