"""xgboost_tpu: a TPU-native gradient-boosted decision tree framework.

From-scratch JAX/XLA implementation of the capability surface of XGBoost
(reference surveyed in SURVEY.md): quantile binning, per-node gradient
histograms, and split evaluation run as fixed-shape XLA programs on TPU
(``tree_method='tpu_hist'``, the sibling of the reference's ``gpu_hist``),
with row-sharded data parallelism over TPU meshes via ``jax.lax.psum`` in
place of rabit/NCCL AllReduce.
"""

from .config import config_context, get_config, set_config  # noqa: F401
from .config import apply_debug_env as _apply_debug_env

# debug opt-ins (XGBTPU_DEBUG_NANS / XGBTPU_CHECK_TRACER_LEAKS -> jax
# debug flags) applied before any jit is built — docs/static_analysis.md
_apply_debug_env()
from .data.dmatrix import DMatrix, QuantileDMatrix, load_row_split  # noqa: F401
from .utils.timer import profiler_context  # noqa: F401
from .data.external import ExternalMemoryQuantileDMatrix  # noqa: F401
from .learner import Booster  # noqa: F401
from .training import cv, elastic_exit, elastic_train, train  # noqa: F401
from .plotting import plot_importance, plot_tree, to_graphviz  # noqa: F401
from .data.iterator import DataIter  # noqa: F401


def build_info() -> dict:
    """Build/runtime facts (reference: xgboost.build_info — compiler and
    feature flags; here the backend and kernel availability)."""
    import jax

    from .native import get_pagecache_lib
    from .tree.hist_kernel import use_pallas

    try:
        backend = jax.default_backend()
    except Exception:  # pragma: no cover - backend init failure
        backend = "uninitialized"
    return {
        "backend": backend,
        "pallas_kernels": use_pallas(),
        "native_pagecache": get_pagecache_lib() is not None,
        "devices": len(jax.devices()) if backend != "uninitialized" else 0,
    }

from . import callback  # noqa: F401
from . import collective  # noqa: F401
from . import collective as rabit  # noqa: F401  (legacy alias)
from . import observability  # noqa: F401  (span tracing + metrics registry)
from . import resilience  # noqa: F401  (failure policy / degrade / chaos)
from . import objective  # noqa: F401  (registers objectives)
from . import metric  # noqa: F401  (registers metrics)
from .gbm import GBTree, Dart, GBLinear  # noqa: F401

__version__ = "0.1.0"

__all__ = [
    "DMatrix",
    "QuantileDMatrix",
    "ExternalMemoryQuantileDMatrix",
    "load_row_split",
    "profiler_context",
    "Booster",
    "train",
    "cv",
    "callback",
    "observability",
    "resilience",
    "config_context",
    "set_config",
    "get_config",
    "ModelServer",
    "RequestError",
    "RequestShed",
    "__version__",
]


def __getattr__(name):
    # soft imports for the sklearn facade (mirrors python-package layout)
    if name in (
        "XGBModel",
        "XGBRegressor",
        "XGBClassifier",
        "XGBRanker",
        "XGBRFRegressor",
        "XGBRFClassifier",
    ):
        from . import sklearn as _sk

        return getattr(_sk, name)
    # serving front end (docs/serving.md "The model server"): soft import
    # so `import xgboost_tpu` doesn't pay for the server machinery.
    # import_module, not `from . import`: the latter re-enters this
    # __getattr__ while the submodule attribute is still unset
    if name in ("ModelServer", "RequestError", "RequestShed", "serving"):
        import importlib

        _serving = importlib.import_module(".serving", __name__)
        return _serving if name == "serving" else getattr(_serving, name)
    raise AttributeError(f"module 'xgboost_tpu' has no attribute '{name}'")
