"""Streaming ingestion: DataIter callbacks -> quantized matrix in 2 passes.

Reference: the ``DataIter`` callback protocol
(``python-package/xgboost/core.py:311``) feeding
``IterativeDeviceDMatrix::Initialize`` (``src/data/iterative_device_dmatrix.h:81``)
— pass 1 sketches every batch, pass 2 packs bins directly into the
device-resident quantized layout, never materializing a float CSR of the
full data (the GPU memory-saver; here the saved object is the dense float
matrix — bins are 1-2 bytes/entry vs 4).

The per-batch sketch merge reuses the SAME fixed-size summary + weighted-CDF
merge as the distributed sketch (parallel/sketch.py) — batches over time and
shards over a mesh are the same problem (quantile.cc:270's AllReduce treats
them identically).
"""

from __future__ import annotations

from typing import Any, List, Optional

import jax.numpy as jnp
import numpy as np

from ..parallel.sketch import _local_summary, _merge_summaries
from .adapters import dispatch_data
from .dmatrix import DMatrix, MetaInfo
from .quantile import BinnedMatrix, HistogramCuts, bin_matrix

__all__ = ["DataIter", "StreamingQuantileDMatrix"]


class DataIter:
    """User-subclassed batch iterator (reference core.py:311): implement
    ``next(input_data)`` calling ``input_data(data=..., label=..., ...)``
    once per batch and returning 1, or returning 0 at the end; and
    ``reset()`` to rewind."""

    def __init__(self, cache_prefix: Optional[str] = None):
        self.cache_prefix = cache_prefix

    def reset(self) -> None:  # pragma: no cover - interface
        raise NotImplementedError

    def next(self, input_data) -> int:  # pragma: no cover - interface
        raise NotImplementedError


class StreamingQuantileDMatrix(DMatrix):
    """QuantileDMatrix built from a DataIter without concatenating raw
    feature batches (2-pass: sketch, then pack)."""

    def __init__(self, it: DataIter, *, max_bin: int = 256, missing: float = np.nan):
        self.max_bin = max_bin
        current: List[dict] = []  # holds exactly ONE in-flight batch

        def input_data(data=None, label=None, weight=None, base_margin=None,
                       group=None, qid=None, **kw):
            X, *_ = dispatch_data(data, missing=missing)
            current.append(
                {"X": X, "label": label, "weight": weight,
                 "base_margin": base_margin, "group": group, "qid": qid}
            )
            return 1

        # ---- pass 1: stream + sketch each batch into a fixed summary;
        # raw floats are DROPPED batch by batch (peak host memory = one
        # batch + summaries — the IterativeDeviceDMatrix property,
        # iterative_device_dmatrix.h:81; review r2: the old version
        # concatenated every float batch, defeating its own purpose) ----
        it.reset()
        vals, wts, maxs, mins = [], [], [], []
        meta: List[dict] = []
        n_batches = 0
        while it.next(input_data):
            b = current.pop()
            X = b.pop("X")
            w = b["weight"]
            wj = (
                jnp.asarray(np.asarray(w, np.float32))
                if w is not None
                else jnp.ones((X.shape[0],), jnp.float32)
            )
            v, ww, mx, mn = _local_summary(jnp.asarray(X), wj, max_bin)
            vals.append(v)
            wts.append(ww)
            maxs.append(mx)
            mins.append(mn)
            meta.append(b)
            n_batches += 1
            del X  # float batch released here
        if not n_batches:
            raise ValueError("DataIter produced no batches")
        cuts_j, min_vals = _merge_summaries(
            jnp.stack(vals), jnp.stack(wts), jnp.stack(maxs), jnp.stack(mins), max_bin
        )
        cuts = HistogramCuts(values=np.asarray(cuts_j), min_vals=np.asarray(min_vals))

        # ---- pass 2: re-iterate, quantize each batch on arrival, keep
        # only the narrow-int bins (1-2 bytes/entry vs 4) ----
        it.reset()
        bin_parts: List[Any] = []
        n2 = 0
        while it.next(input_data):
            b = current.pop()
            bin_parts.append(bin_matrix(jnp.asarray(b["X"]), cuts))
            n2 += 1
        if n2 != n_batches:
            raise ValueError(
                f"DataIter yielded {n2} batches on the second pass vs "
                f"{n_batches} on the first — the iterator must be "
                "deterministic across reset() for 2-pass ingestion"
            )
        bins = jnp.concatenate(bin_parts)

        self._data = None  # no raw-float copy; reconstructed lazily
        self.info = MetaInfo()
        for field, setter in (
            ("label", "label"), ("weight", "weight"), ("base_margin", "base_margin"),
        ):
            parts = [b[field] for b in meta if b[field] is not None]
            if parts:
                setattr(self.info, setter, np.concatenate([np.asarray(p, np.float32) for p in parts]))
        qparts = [b["qid"] for b in meta if b["qid"] is not None]
        if qparts:
            from .dmatrix import _group_ptr_from_qid

            self.info.group_ptr = _group_ptr_from_qid(np.concatenate(qparts))
        self._binned = {max_bin: BinnedMatrix(cuts=cuts, bins=bins)}

    #: consumers needing TRUE raw values (e.g. grow_local_histmaker's
    #: per-node re-sketch) must refuse this matrix: ``data`` is quantized
    data_is_reconstructed = True

    @property
    def data(self):
        """Representative feature values reconstructed from bins (the
        EllpackDeviceAccessor::GetFvalue idea, ellpack_page.cuh:119): bin k
        of feature f maps to its lower cut edge, missing back to NaN. Only
        materialized when something actually needs raw values (predict on
        the training matrix, SHAP) — training itself runs on bins."""
        if self._data is None:
            bm = self._binned[self.max_bin]
            bins = np.asarray(bm.bins)
            cuts = bm.cuts
            n, F = bins.shape
            out = np.empty((n, F), np.float32)
            for f in range(F):
                lower = np.concatenate(
                    [[cuts.min_vals[f]], cuts.values[f][:-1]]
                ).astype(np.float32)
                k = bins[:, f]
                miss = k >= cuts.max_bin
                out[:, f] = lower[np.minimum(k, cuts.max_bin - 1)]
                out[miss, f] = np.nan
            self._data = out
        return self._data

    def num_row(self) -> int:
        return int(self._binned[self.max_bin].bins.shape[0])

    def num_col(self) -> int:
        return int(self._binned[self.max_bin].bins.shape[1])
