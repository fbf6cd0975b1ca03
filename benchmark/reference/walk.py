"""A plain numpy walk of a forest parsed from its saved JSON model file.

Independent of the package: it reads the published XGBoost JSON schema
(``learner.gradient_booster.model.trees[*]``), not the package's objects. A
row goes left where ``x < split_condition`` and by ``default_left`` where x
is NaN; a leaf's value is its ``split_conditions`` entry.
"""

import json

import numpy as np


class Forest:
    """Trees of one saved model, as parallel numpy arrays per tree."""

    def __init__(self, doc: dict) -> None:
        learner = doc["learner"]
        model = learner["gradient_booster"]["model"]
        self.trees = [
            {k: np.asarray(t[k], dt) for k, dt in (
                ("left_children", np.int64), ("right_children", np.int64),
                ("split_indices", np.int64), ("split_conditions", np.float32),
                ("default_left", np.bool_), ("base_weights", np.float32),
                ("sum_hessian", np.float32), ("loss_changes", np.float32))}
            for t in model["trees"]]
        self.tree_group = np.asarray(model["tree_info"], np.int64)
        lmp = learner["learner_model_param"]
        self.num_class = max(int(lmp.get("num_class", "0")), 1)
        self.base_score = float(lmp["base_score"])
        self.objective = learner["objective"]["name"]

    @classmethod
    def from_file(cls, path: str) -> "Forest":
        with open(path) as f:
            return cls(json.load(f))

    @classmethod
    def from_bytes(cls, raw: bytes) -> "Forest":
        return cls(json.loads(raw))

    def base_margin(self) -> float:
        """The margin every row starts from: the link of ``base_score``."""
        if self.objective in ("binary:logistic", "reg:logistic"):
            p = min(max(self.base_score, 1e-7), 1 - 1e-7)
            return float(np.log(p / (1 - p)))
        return self.base_score

    def leaves(self, X: np.ndarray, tree: int) -> np.ndarray:
        """Leaf node id each row of ``X`` reaches in ``tree``."""
        t = self.trees[tree]
        node = np.zeros(len(X), np.int64)
        rows = np.arange(len(X))
        while True:
            left = t["left_children"][node]
            active = left >= 0
            if not active.any():
                return node
            x = X[rows, t["split_indices"][node]]
            go_left = np.where(np.isnan(x), t["default_left"][node],
                               x < t["split_conditions"][node])
            nxt = np.where(go_left, left, t["right_children"][node])
            node = np.where(active, nxt, node)

    def margin(self, X: np.ndarray, trees: int | None = None) -> np.ndarray:
        """float32-accumulated margin [n, num_class] over the first
        ``trees`` trees (all by default), in tree order as the package adds."""
        X = np.asarray(X, np.float32)
        out = np.full((len(X), self.num_class), self.base_margin(), np.float32)
        n = len(self.trees) if trees is None else trees
        for i in range(n):
            leaf = self.leaves(X, i)
            out[:, self.tree_group[i]] += \
                self.trees[i]["split_conditions"][leaf]
        return out

    def predict(self, X: np.ndarray, predict_type: str = "value") -> np.ndarray:
        """What a caller is served, in float64: the margin, or for ``value``
        the objective's link of it (sigmoid, or softmax over the classes)."""
        m = self.margin(X).astype(np.float64)
        if predict_type == "margin":
            return m
        if predict_type != "value":
            raise ValueError(f"no reference for predict_type {predict_type!r}")
        if self.objective in ("binary:logistic", "reg:logistic"):
            return 1.0 / (1.0 + np.exp(-m))
        if self.objective == "multi:softprob":
            e = np.exp(m - m.max(axis=1, keepdims=True))
            return e / e.sum(axis=1, keepdims=True)
        raise ValueError(f"no reference link for {self.objective!r}")
