"""ObjFunction base class (reference: ``include/xgboost/objective.h``,
task typing via ObjInfo ``include/xgboost/task.h:22``)."""

from __future__ import annotations

import enum
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..registry import OBJECTIVES


class Task(enum.Enum):
    REGRESSION = "regression"
    BINARY = "binary"
    CLASSIFICATION = "classification"
    RANKING = "ranking"
    SURVIVAL = "survival"


class ObjFunction:
    """Gradient/hessian provider. Shapes: margin [n] or [n, n_targets]."""

    task: Task = Task.REGRESSION
    name: str = ""
    #: elementwise, jax-traceable gradient with no group/bound state — safe
    #: to trace inside a multi-round lax.scan (Booster.update_many)
    scan_safe: bool = False

    def __init__(self, params=None):
        self.params = params

    def n_targets(self) -> int:
        return 1

    def get_gradient(
        self,
        margin: jax.Array,
        label: jax.Array,
        weight: Optional[jax.Array],
        iteration: int = 0,
        *,
        group_ptr: Optional[np.ndarray] = None,
        label_lower: Optional[jax.Array] = None,
        label_upper: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, jax.Array]:
        raise NotImplementedError

    def gradient_of(self, margin: jax.Array, info, iteration: int = 0
                    ) -> Tuple[jax.Array, jax.Array]:
        """The gradient a round boosts on, from the ``[n, K]`` training
        margin and the matrix's ``MetaInfo``: the per-round entry
        (``Booster.update``). The default sends up what ``get_gradient``
        reads; an objective that keeps per-matrix state on the device
        (ranking) overrides it."""
        def up(a):
            return jnp.asarray(a) if a is not None else None

        m = margin[:, 0] if margin.ndim == 2 and margin.shape[1] == 1 \
            else margin
        return self.get_gradient(
            m,
            up(info.label) if info.label is not None
            else jnp.zeros(margin.shape[0]),
            up(info.weight),
            iteration,
            group_ptr=info.group_ptr,
            label_lower=up(info.label_lower_bound),
            label_upper=up(info.label_upper_bound),
        )

    # margin -> user-facing prediction (reference: PredTransform)
    def pred_transform(self, margin: jax.Array) -> jax.Array:
        return margin

    # same but for evaluation-time predictions (softmax differs)
    def eval_transform(self, margin: jax.Array) -> jax.Array:
        return self.pred_transform(margin)

    # base_score (prob space) -> initial margin (reference: ProbToMargin)
    def prob_to_margin(self, base_score: float) -> float:
        return base_score

    def default_base_score(self) -> float:
        return 0.5

    def default_metric(self) -> str:
        return "rmse"


def create_objective(name: str, params=None) -> ObjFunction:
    obj = OBJECTIVES.create(name, params)
    obj.name = OBJECTIVES.resolve(name)
    return obj


def apply_weight(
    grad: jax.Array, hess: jax.Array, weight: Optional[jax.Array]
) -> Tuple[jax.Array, jax.Array]:
    if weight is None:
        return grad, hess
    if grad.ndim == 2:
        weight = weight[:, None]
    return grad * weight, hess * weight
