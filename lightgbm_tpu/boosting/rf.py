"""Random forest mode.

Reference: `src/boosting/rf.hpp` — bagged trees with no shrinkage;
gradients are computed ONCE from the zero score (rf.hpp:83-89), every
iteration refits against them on a fresh bag, and the ensemble output is
the average over iterations (average_output_, rf.hpp:22 + score updates at
:120-140). Requires bagging and feature_fraction < 1.
"""
from __future__ import annotations

import numpy as np

from .. import log
from .gbdt import GBDT


class RF(GBDT):
    def __init__(self, config):
        super().__init__(config)
        cfg = config.boosting
        if not (cfg.bagging_freq > 0 and 0.0 < cfg.bagging_fraction < 1.0):
            log.fatal("RF mode requires bagging "
                      "(bagging_freq > 0 and bagging_fraction in (0,1))")
        if not (0.0 < config.tree.feature_fraction < 1.0):
            log.fatal("RF mode requires feature_fraction in (0, 1)")
        self.average_output = True

    def model_name(self) -> str:
        return "tree"  # reference RF also serializes as 'tree' with average_output

    def init(self, train_data, objective, metric_names=()):
        super().init(train_data, objective, metric_names)
        self.shrinkage_rate = 1.0
        if objective is None:
            log.fatal("RF mode requires an objective function")
        # RF fits against gradients of the ZERO score (rf.hpp:83-89); undo
        # any boost_from_average the base init applied so the averaged
        # ensemble output is not offset by bias/T
        if self.init_score_bias != 0.0:
            self._score = self._score - self.init_score_bias
            self.init_score_bias = 0.0
        self._pending_bias = 0.0
        # gradients from the zero score, fixed for all iterations
        import jax.numpy as jnp
        k = self.num_tree_per_iteration
        zero = jnp.zeros((k, self._n_pad), jnp.float32)
        g, h = self.objective.get_gradients(zero.reshape(-1))
        # RF never recomputes gradients, so a NaN label would poison
        # EVERY tree — check the one batch that matters up front
        self._raise_if_nonfinite(self._nonfinite_probe(g, h), 0)
        self._rf_grad = g
        self._rf_hess = h

    def _checkpoint_extra(self) -> dict:
        """RF needs no extra checkpoint state: `_rf_grad`/`_rf_hess` are
        rebuilt bit-identically by init() (gradients of the zero score),
        and its bagging masks are stateless like the base class's."""
        return {}

    def train_one_iter(self, gradients=None, hessians=None) -> bool:
        from ..testing import faults
        faults.inject("backend.grow")
        import jax.numpy as jnp
        k = self.num_tree_per_iteration
        n_pad = self._n_pad
        grad = self._rf_grad.reshape(k, n_pad)
        hess = self._rf_hess.reshape(k, n_pad)

        bag = self._bagging_weights(self.iter_, grad, hess)
        row_weight = self._row_weight_from_bag(bag)

        from .. import telemetry
        from ..tree import Tree
        from ..ops.predict import predict_value_binned
        could_split_any = False
        t_before = float(self.iter_)
        for cls in range(k):
            mask = self._feature_mask()
            # phase spans match the base class's so RF iterations show
            # up under the same tree/grow..tree/extract accounting
            with telemetry.span("tree/grow"):
                state = self._grow(grad[cls], hess[cls], row_weight, mask)
            with telemetry.span("tree/extract"):
                tree = Tree.from_grower_state(state, self.train_data)
            if tree.num_leaves > 1:
                could_split_any = True
                # running average: score_{t+1} = (score_t * t + tree) / (t+1)
                leaf_vals = jnp.asarray(tree.leaf_value, jnp.float32)
                contrib = leaf_vals[jnp.clip(state.leaf_id, 0, tree.num_leaves - 1)]
                self._score = self._score.at[cls].set(
                    (self._score[cls] * t_before + contrib) / (t_before + 1.0))
                dtree = tree.to_device()
                for vi in range(len(self.valid_sets)):
                    vadd = predict_value_binned(dtree, self._valid_binned[vi])
                    self._valid_score[vi] = self._valid_score[vi].at[cls].set(
                        (self._valid_score[vi][cls] * t_before + vadd) / (t_before + 1.0))
            self.models.append(tree)
        self._bump_model_version()
        self.iter_ += 1
        if not could_split_any:
            for _ in range(k):
                self.models.pop()
            self.iter_ -= 1
            log.warning("Stopped training: no more valid splits")
            return True
        return False
