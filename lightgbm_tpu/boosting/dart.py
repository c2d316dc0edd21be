"""DART: Dropouts meet Multiple Additive Regression Trees.

Reference: `src/boosting/dart.hpp` — per iteration a random subset of
existing trees is dropped (weight-proportional unless uniform_drop), the
new tree is fit against the score without them, and dropped trees are
re-weighted to k/(k+1) (or the xgboost_dart_mode variant) so the ensemble
stays normalized (DroppingTrees dart.hpp:85-130, Normalize :140-180).
"""
from __future__ import annotations

import copy
from typing import List

import numpy as np

from .. import checkpoint as ckpt
from .gbdt import GBDT
from ..ops.predict import predict_value_binned


class DART(GBDT):
    def __init__(self, config):
        super().__init__(config)
        # DART reads back the CURRENT iteration's tree (normalization,
        # dart.hpp:85-130), so the base class's one-behind async tree
        # pipeline cannot apply
        self._supports_pipeline = False
        self.tree_weight: List[float] = []
        self.sum_weight = 0.0
        self._drop_rng = np.random.RandomState(config.boosting.drop_seed)
        self.drop_index: List[int] = []

    def model_name(self) -> str:
        return "dart"

    # ------------------------------------------------------------------
    # DART owns mutable cross-iteration state the base class doesn't:
    # the per-tree weight ledger (future drop probabilities are weight-
    # proportional) and the host drop RNG. Both must survive checkpoint/
    # resume and model-text round-trips or a restarted run diverges.
    def _checkpoint_extra(self) -> dict:
        return {
            "tree_weight": [float(w) for w in self.tree_weight],
            "sum_weight": float(self.sum_weight),
            "drop_rng": ckpt.encode_rng(self._drop_rng),
        }

    def _restore_extra(self, extra: dict) -> None:
        self.tree_weight = [float(w) for w in extra.get("tree_weight", [])]
        self.sum_weight = float(extra.get("sum_weight", 0.0))
        if "drop_rng" in extra:
            self._drop_rng = ckpt.decode_rng(extra["drop_rng"])
        self.drop_index = []

    def _extra_model_header(self, num_iteration: int = -1):
        # the drop ledger rides in the model text too (reference DART
        # cannot continue-train a loaded model for exactly this reason —
        # dart.hpp keeps the ledger in memory only); repr() round-trips
        # the doubles exactly. Truncated saves truncate the ledger.
        weights = self.tree_weight
        sum_weight = self.sum_weight
        if 0 < num_iteration < len(weights):
            weights = weights[:num_iteration]
            sum_weight = float(sum(weights))
        if not weights:
            return []
        # full saves emit the exact RUNNING sum (maintained incrementally
        # through _normalize; recomputing would change the f64 rounding)
        return ["tpu_dart_tree_weights=" + " ".join(
                    repr(float(w)) for w in weights),
                "tpu_dart_sum_weight=" + repr(float(sum_weight))]

    def load_model_from_string(self, text: str) -> None:
        super().load_model_from_string(text)
        self.tree_weight = []
        self.sum_weight = 0.0
        self.drop_index = []
        for line in text.splitlines():
            ls = line.strip()
            if ls.startswith("tpu_dart_tree_weights="):
                self.tree_weight = [float(w)
                                    for w in ls.split("=", 1)[1].split()]
            elif ls.startswith("tpu_dart_sum_weight="):
                self.sum_weight = float(ls.split("=", 1)[1])
            elif ls.startswith("Tree="):
                break

    def _tree_contribution(self, it: int, sign: float, on_valid: bool):
        """Add sign * tree(it) to train (and optionally valid) scores."""
        import jax.numpy as jnp
        k = self.num_tree_per_iteration
        for cls in range(k):
            tree = self.models[it * k + cls]
            if tree.num_leaves <= 1:
                continue
            t = copy.deepcopy(tree)
            t.leaf_value = t.leaf_value * sign
            dt = t.to_device()
            if not on_valid:
                self._score = self._score.at[cls].add(
                    predict_value_binned(dt, self._binned))
            else:
                for vi in range(len(self.valid_sets)):
                    self._valid_score[vi] = self._valid_score[vi].at[cls].add(
                        predict_value_binned(dt, self._valid_binned[vi]))

    def _dropping_trees(self):
        """Select and remove dropped trees from the train score
        (dart.hpp:85-130)."""
        cfg = self.config.boosting
        self.drop_index = []
        if self._drop_rng.rand() >= cfg.skip_drop:
            drop_rate = cfg.drop_rate
            if not cfg.uniform_drop:
                inv_avg = len(self.tree_weight) / self.sum_weight \
                    if self.sum_weight > 0 else 0.0
                if cfg.max_drop > 0 and self.sum_weight > 0:
                    drop_rate = min(drop_rate, cfg.max_drop * inv_avg / self.sum_weight)
                for i in range(self.iter_):
                    if self._drop_rng.rand() < drop_rate * self.tree_weight[i] * inv_avg:
                        self.drop_index.append(i)
            else:
                if cfg.max_drop > 0 and self.iter_ > 0:
                    drop_rate = min(drop_rate, cfg.max_drop / self.iter_)
                for i in range(self.iter_):
                    if self._drop_rng.rand() < drop_rate:
                        self.drop_index.append(i)
        for i in self.drop_index:
            self._tree_contribution(i, -1.0, on_valid=False)
        kdrop = len(self.drop_index)
        # drop activity in the run log / counters: a DART run whose
        # ledger drifted is diagnosed from dropped-per-iteration deltas
        from .. import telemetry
        telemetry.counter_add("boosting/dart_dropped_trees", kdrop)
        if not cfg.xgboost_dart_mode:
            self.shrinkage_rate = cfg.learning_rate / (1.0 + kdrop)
        else:
            self.shrinkage_rate = cfg.learning_rate if kdrop == 0 else \
                cfg.learning_rate / (cfg.learning_rate + kdrop)

    def _normalize(self):
        """Re-weight dropped trees (dart.hpp:140-180)."""
        cfg = self.config.boosting
        kdrop = float(len(self.drop_index))
        for i in self.drop_index:
            if not cfg.xgboost_dart_mode:
                factor = kdrop / (kdrop + 1.0)
            else:
                factor = kdrop / (kdrop + cfg.learning_rate)
            # valid scores still hold the full tree: adjust by (factor-1)
            k = self.num_tree_per_iteration
            for cls in range(k):
                tree = self.models[i * k + cls]
                tree.leaf_value = tree.leaf_value * factor
                tree.internal_value = tree.internal_value * factor
            self._tree_contribution_scaled(i, (factor - 1.0) / factor, on_valid=True)
            # train score had the tree fully removed: add back factor*tree
            self._tree_contribution(i, 1.0, on_valid=False)
            if not cfg.uniform_drop:
                if not cfg.xgboost_dart_mode:
                    self.sum_weight -= self.tree_weight[i] / (kdrop + 1.0)
                else:
                    self.sum_weight -= self.tree_weight[i] / (kdrop + cfg.learning_rate)
                self.tree_weight[i] *= factor

    def _tree_contribution_scaled(self, it: int, rel_sign: float, on_valid: bool):
        """Add rel_sign * current-tree-values to valid scores (used after the
        tree's stored values were already rescaled)."""
        import jax.numpy as jnp
        k = self.num_tree_per_iteration
        for cls in range(k):
            tree = self.models[it * k + cls]
            if tree.num_leaves <= 1:
                continue
            t = copy.deepcopy(tree)
            t.leaf_value = t.leaf_value * rel_sign
            dt = t.to_device()
            for vi in range(len(self.valid_sets)):
                self._valid_score[vi] = self._valid_score[vi].at[cls].add(
                    predict_value_binned(dt, self._valid_binned[vi]))

    def train_one_iter(self, gradients=None, hessians=None) -> bool:
        self._dropping_trees()
        stop = super().train_one_iter(gradients, hessians)
        if not stop:
            self.tree_weight.append(self.shrinkage_rate)
            self.sum_weight += self.shrinkage_rate
            self._normalize()
            # _normalize rescales EXISTING trees' leaf values in place —
            # a stacked forest cached after the append would be stale
            self._bump_model_version()
        else:
            # restore dropped trees to the train score
            for i in self.drop_index:
                self._tree_contribution(i, 1.0, on_valid=False)
        return stop
