"""Many-model sweep training: K boosters, one compiled program, lockstep.

Host half of the vmapped sweep (device half: learner/sweep.SweepGrower).
`engine.train_sweep` drives this:

- `validate_sweep_params` checks up front that every param dict agrees
  on every knob that is not on the per-model allowlist — the
  shape-affecting ones (max_bin, num_leaves, max_depth, bundling, ...)
  decide the compiled program's shapes, so a divergence must surface as
  a LightGBMError naming the key, not as an XLA shape failure half a
  compile later.
- `SweepTrainer` builds ONE device-resident dataset + grower schedule
  (through a lead GBDT init), stacks the per-model knobs into traced
  [K] arrays, and steps all K boosting loops in lockstep with one
  dispatch per iteration and ZERO host syncs in the loop (small tree
  states stay on device until `finish()`).
- `finish()` materializes each model's trees, applies the serial stop
  rule per model (training truncates at the first iteration where no
  class tree could split — later lockstep iterations are discarded, so
  the ensemble matches what `engine.train` would have kept), folds the
  boost-from-average bias into each model's first splitting tree, and
  returns real `Booster` objects built through the model-text path (the
  loaded-booster invariants are test-enforced; tree text round-trips
  exactly).

Every model's trees are BYTE-IDENTICAL to training that config alone
(tests/test_sweep.py asserts `model_to_string()` equality, including
bagging/GOSS sampling, multiclass, and heterogeneous learning rates).
"""
from __future__ import annotations

from typing import Any, Dict, List, Sequence

import numpy as np

from .. import log, telemetry
from ..config import Config, key_alias_transform
from ..learner.grow import GrowParams
from ..learner.schedule import subtract_cache_fits
from ..learner.sweep import (MODE_BAGGING, MODE_GOSS, MODE_PLAIN,
                             SweepGrower, SweepModelParams)
from ..objectives import create_objective
from ..tree import Tree
from . import create_boosting
from .gbdt import (_SMALL_STATE_KEYS, _HostState, _K_EPSILON,
                   feature_fraction_mask)

# knobs that may differ across the models of one sweep: they enter the
# compiled program as TRACED per-model values (learner/grow.GrowParams,
# shrinkage, sampling seeds/rates) or as host-side per-model state
# (feature_fraction masks). Everything else must agree — most of the
# rest is shape-affecting (max_bin, num_leaves, max_depth, bundling,
# num_class, bagging_freq, ...) or changes the shared dataset/binning.
SWEEP_VARIABLE_PARAMS = frozenset({
    "learning_rate",
    "lambda_l1", "lambda_l2", "min_gain_to_split",
    "min_data_in_leaf", "min_sum_hessian_in_leaf",
    "bagging_fraction", "bagging_seed",
    "feature_fraction", "feature_fraction_seed",
    "top_rate", "other_rate",
    # cosmetic / sweep-bookkeeping: never reaches the compiled program
    "verbosity",
})

_MISSING = object()


def _agreement_key(v):
    """Type-tolerant comparison key: 255 and 255.0 (or "255") are the
    same effective config value — Config.from_params parses them
    identically — so they must not be refused as divergent. Booleans
    stay distinct from their numeric forms."""
    if v is _MISSING:
        return ("missing",)
    if isinstance(v, bool):
        return ("bool", v)
    try:
        return ("num", float(v))
    except (TypeError, ValueError):
        return ("str", str(v))


def validate_sweep_params(params_list: Sequence[Dict[str, Any]]
                          ) -> List[Dict[str, Any]]:
    """Alias-canonicalize the K param dicts and verify they agree on
    every non-allowlisted key. Raises LightGBMError NAMING the first
    divergent key (sorted order, deterministic) instead of letting the
    divergence surface as an XLA shape error. Returns the canonical
    dicts."""
    if not params_list:
        raise log.LightGBMError("train_sweep needs at least one param dict")
    canon = [key_alias_transform(dict(p)) for p in params_list]
    if len(canon) == 1:
        return canon
    all_keys = sorted(set().union(*[set(p) for p in canon]))
    for key in all_keys:
        if key in SWEEP_VARIABLE_PARAMS:
            continue
        vals = [p.get(key, _MISSING) for p in canon]
        ref = vals[0]
        for ki, v in enumerate(vals[1:], start=1):
            if _agreement_key(v) != _agreement_key(ref):
                raise log.LightGBMError(
                    "Sweep configs disagree on %r (model 0: %s, model %d: "
                    "%s). A vmapped sweep shares one compiled program, so "
                    "every knob outside the per-model set %s must agree — "
                    "shape-affecting ones (max_bin, num_leaves, max_depth, "
                    "enable_bundle, num_class, bagging_freq, ...) "
                    "especially. Set it identically in every config, or "
                    "drop it everywhere."
                    % (key,
                       "<unset>" if ref is _MISSING else repr(ref), ki,
                       "<unset>" if v is _MISSING else repr(v),
                       sorted(SWEEP_VARIABLE_PARAMS)))
    return canon


class SweepTrainer:
    """Train K boosters in lockstep inside one XLA program per iteration.

    Built by engine.train_sweep; not a public API surface of its own.
    The LEAD config (index 0) decides everything shared: the dataset is
    bound/binned once under it, and its GBDT init derives the padded row
    layout, feature metadata, and grower schedule for the whole sweep.
    """

    def __init__(self, params_list: Sequence[Dict[str, Any]], train_set,
                 num_boost_round: int):
        import jax
        import jax.numpy as jnp

        canon = validate_sweep_params(params_list)
        self.params_list = [dict(p) for p in canon]
        # num_iterations is part of the lockstep contract (validated
        # shared above); pop it off like engine.train does
        rounds = [int(p.pop("num_iterations", num_boost_round))
                  for p in canon]
        self.num_boost_round = rounds[0]
        self.configs = [Config.from_params(dict(p)) for p in canon]
        lead_cfg = self.configs[0]
        K = len(self.configs)
        self.num_models = K

        if lead_cfg.tree_learner != "serial":
            raise log.LightGBMError(
                "train_sweep supports tree_learner=serial only (got %r); "
                "the model axis and the device mesh are separate batching "
                "dimensions" % lead_cfg.tree_learner)
        if lead_cfg.boosting_type not in ("gbdt", "goss"):
            raise log.LightGBMError(
                "train_sweep supports boosting_type gbdt or goss (got "
                "%r); dart/rf keep host-side per-iteration state that "
                "cannot run branch-free in lockstep"
                % lead_cfg.boosting_type)
        declared = int(lead_cfg.io.tpu_sweep_size)
        if declared > 0 and declared != K:
            raise log.LightGBMError(
                "tpu_sweep_size=%d but %d param dict(s) were given; the "
                "declared sweep width must match the sweep"
                % (declared, K))
        if jax.process_count() > 1:
            raise log.LightGBMError(
                "train_sweep is single-process (multi-host sweeps would "
                "need the model axis laid out over the mesh)")

        # ---- shared device state via the lead booster's init ----------
        train_set._update_params(dict(self.params_list[0]))
        inner = train_set._lazy_init()
        objective = create_objective(lead_cfg)
        if objective is None:
            raise log.LightGBMError(
                "train_sweep requires a built-in objective (custom fobj "
                "would need one gradient callback per model per step)")
        self.lead = create_boosting(lead_cfg.boosting_type, lead_cfg)
        self.lead.init(inner, objective, ())
        gb = self.lead
        self.kc = gb.num_tree_per_iteration
        self.n, self.n_pad = gb._n, gb._n_pad

        # ---- sweep grower schedule ------------------------------------
        # the sweep keeps the lead's auto-selected schedule VERBATIM:
        # subtraction and compaction reorder f32 partial sums, so
        # matching the serial counterpart's schedule exactly is what
        # makes model k's trees byte-identical to training it alone.
        # (Under the model-axis vmap the compaction cond batches — both
        # kernels run every pass and a select keeps each model's own
        # branch result: correct, merely slower. batch_k/table_mult are
        # bit-transparent by the grower's hard guarantee.) The one
        # override: K per-model subtraction caches multiply the memory
        # budget, so re-check it at K x and drop subtraction — with the
        # byte-identity caveat logged — only when it cannot fit.
        self.cfg = gb._grower_cfg
        if self.cfg.hist_subtract and not subtract_cache_fits(
                max(1, int(gb.train_data.num_groups)), gb._max_bins,
                lead_cfg.tree.num_leaves, self.cfg.table_mult,
                classes=self.kc, copies=K, rows_padded=self.n_pad,
                device_bytes=gb._device_bytes):
            log.warning(
                "Sweep: %d sibling-subtraction caches exceed the "
                "device budget; disabling subtraction for the sweep. "
                "Trees then match serial training only up to f32 "
                "summation order.", K)
            self.cfg = self.cfg._replace(hist_subtract=False)

        mode = MODE_PLAIN
        bag_freq = int(lead_cfg.boosting.bagging_freq)
        if lead_cfg.boosting_type == "goss":
            mode = MODE_GOSS
            for ki, c in enumerate(self.configs):
                if c.boosting.top_rate <= 0 or c.boosting.other_rate <= 0:
                    raise log.LightGBMError(
                        "GOSS sweep model %d requires top_rate > 0 and "
                        "other_rate > 0" % ki)
                # the serial GOSS ctor fatals on bagging (goss.py); a
                # non-lead model must be refused HERE, before the
                # lockstep run, not at finish() when its shell is built
                if bag_freq > 0 and c.boosting.bagging_fraction != 1.0:
                    raise log.LightGBMError(
                        "GOSS sweep model %d sets bagging_fraction=%g "
                        "with bagging_freq>0; cannot use bagging in "
                        "GOSS" % (ki, c.boosting.bagging_fraction))
        elif bag_freq > 0 and any(c.boosting.bagging_fraction < 1.0
                                  for c in self.configs):
            mode = MODE_BAGGING
        self.mode = mode

        # ---- per-model traced arrays ----------------------------------
        # every scalar below is computed with the serial path's exact
        # host expressions (gbdt._bagging_mask_impl / goss._goss_impl
        # derivations) so the traced values match the serial constants
        # bit-for-bit
        n = self.n
        f32 = np.float32
        self._lrs = [float(c.boosting.learning_rate) for c in self.configs]
        goss_top_k, goss_rest_p, goss_mult, goss_start = [], [], [], []
        for c in self.configs:
            b = c.boosting
            top_k = max(1, int(n * b.top_rate))
            other_k = max(1, int(n * b.other_rate))
            goss_top_k.append(top_k)
            goss_rest_p.append(f32(other_k / max(1, n - top_k)))
            goss_mult.append(f32((n - top_k) / other_k))
            goss_start.append(int(1.0 / max(b.learning_rate, 1e-12)))
        self._pm = SweepModelParams(
            grow=GrowParams(
                lambda_l1=jnp.asarray(
                    [c.tree.lambda_l1 for c in self.configs], f32),
                lambda_l2=jnp.asarray(
                    [c.tree.lambda_l2 for c in self.configs], f32),
                min_gain_to_split=jnp.asarray(
                    [c.tree.min_gain_to_split for c in self.configs], f32),
                min_data_in_leaf=jnp.asarray(
                    [c.tree.min_data_in_leaf for c in self.configs],
                    np.int32),
                min_sum_hessian_in_leaf=jnp.asarray(
                    [c.tree.min_sum_hessian_in_leaf for c in self.configs],
                    f32)),
            shrinkage=jnp.asarray(self._lrs, f32),
            bag_seed=jnp.asarray(
                [c.boosting.bagging_seed for c in self.configs], np.int32),
            bag_fraction=jnp.asarray(
                [c.boosting.bagging_fraction for c in self.configs], f32),
            goss_start=jnp.asarray(goss_start, np.int32),
            goss_top_k=jnp.asarray(goss_top_k, np.int32),
            goss_rest_p=jnp.asarray(goss_rest_p, f32),
            goss_multiply=jnp.asarray(goss_mult, f32),
        )

        # per-model feature_fraction host RNGs (exact serial draw order:
        # one RandomState per model, one draw per class tree). With no
        # fraction below 1.0 anywhere the masks are a constant all-ones
        # block — build it once and skip the per-iteration host stack +
        # upload entirely
        self._feature_rngs = [
            np.random.RandomState(c.tree.feature_fraction_seed)
            for c in self.configs]
        self._feature_fracs = [float(c.tree.feature_fraction)
                               for c in self.configs]
        self._static_masks = None
        if all(frac >= 1.0 for frac in self._feature_fracs):
            self._static_masks = jnp.ones(
                (K, self.kc, gb._num_features_padded), bool)

        from ..learner.grow import FMETA_KEYS
        self.grower = SweepGrower(
            self.cfg, objective, kc=self.kc, n=self.n, n_pad=self.n_pad,
            mode=mode, bag_freq=bag_freq,
            fmeta_args=tuple(gb._fmeta[k] for k in FMETA_KEYS),
            small_keys=_SMALL_STATE_KEYS,
            # quantized-gradient statics from the lead init (the gate
            # already ran inside lead.init; data_random_seed and the
            # hess_const-deciding params are sweep-SHARED by the
            # variable-params whitelist, so the lead's values hold for
            # every member)
            quant_seed=getattr(gb, "_quant_seed", 0),
            quant_hess_const=getattr(gb, "_quant_hess_const", False))

        # all K models start from the lead's initial score (same
        # objective + dataset => same init_score / boost-from-average)
        self._score = jnp.repeat(gb._score[None], K, axis=0)
        self._pending_bias = float(getattr(gb, "_pending_bias", 0.0))
        self._base_w = gb._base_weight
        self._smalls: List[Dict[str, Any]] = []
        self._it = 0
        log.info("Sweep: %d models x %d class tree(s), mode=%s, one "
                 "compiled program per iteration", K, self.kc, mode)

    # ------------------------------------------------------------------
    def _feature_mask(self, ki: int) -> np.ndarray:
        """Model ki's per-tree feature_fraction sample: the SHARED
        serial sampling code (gbdt.feature_fraction_mask), driven by
        the model's own RNG stream."""
        gb = self.lead
        return feature_fraction_mask(
            self._feature_rngs[ki], self._feature_fracs[ki],
            gb.train_data.num_features, gb._num_features_padded)

    def step(self) -> None:
        """One lockstep boosting iteration for all K models: ONE device
        dispatch, zero host syncs (tree states stay on device)."""
        import jax.numpy as jnp
        if self._static_masks is not None:
            masks = self._static_masks
        else:
            masks = jnp.asarray(np.stack([
                np.stack([self._feature_mask(ki) for _ in range(self.kc)])
                for ki in range(self.num_models)]))
        self._score, small = self.grower.step(
            self._score, self.lead._binned, self._it, self._pm,
            self._base_w, masks)
        self._smalls.append(small)
        self._it += 1
        telemetry.counter_add("sweep/iterations", 1)

    # ------------------------------------------------------------------
    def finish(self) -> List[Any]:
        """Materialize the K Boosters: fetch every iteration's small
        tree state in one go, build host trees, apply the serial
        per-model stop rule, and wrap each model through the (exact)
        model-text load path."""
        import jax

        from ..basic import Booster
        with telemetry.span("sweep/materialize"):
            hosts = jax.device_get(self._smalls)
        gb = self.lead
        kc = self.kc
        boosters = []
        num_passes = 0  # accumulated across ALL models for the counter
        for ki in range(self.num_models):
            trees: List[Tree] = []
            pending_bias = self._pending_bias
            for host in hosts:
                iter_trees = []
                any_split = False
                for ci in range(kc):
                    hs = _HostState({key: np.asarray(v[ki][ci])
                                     for key, v in host.items()})
                    tree = Tree.from_grower_state(hs, gb.train_data)
                    num_passes += int(hs.num_passes)
                    if tree.num_leaves > 1:
                        any_split = True
                        tree.apply_shrinkage(self._lrs[ki])
                    iter_trees.append(tree)
                if not any_split:
                    # the serial engine rolls this iteration back and
                    # stops training — every later lockstep iteration
                    # belongs to models that are still running
                    break
                if abs(pending_bias) > _K_EPSILON:
                    for tree in iter_trees:
                        if tree.num_leaves > 1:
                            tree.add_bias(pending_bias)
                            pending_bias = 0.0
                            break
                trees.extend(iter_trees)

            shell = create_boosting(self.configs[ki].boosting_type,
                                    self.configs[ki])
            shell.objective = create_objective(self.configs[ki])
            shell.num_class = gb.num_class
            shell.num_tree_per_iteration = kc
            shell.max_feature_idx = gb.max_feature_idx
            shell.feature_names = list(gb.feature_names)
            shell.feature_infos_ = list(gb.feature_infos_)
            shell.models = trees
            shell.iter_ = len(trees) // max(kc, 1)
            # an unfolded bias (model never split) rides the header the
            # way legacy models carry it; folded bias lives in tree 0
            shell.init_score_bias = pending_bias
            booster = Booster(params=dict(self.params_list[ki]),
                              model_str=shell.save_model_to_string())
            boosters.append(booster)
            telemetry.counter_add("sweep/trees", len(trees))
        telemetry.counter_add("sweep/models", self.num_models)
        telemetry.counter_add("sweep/passes", num_passes)
        return boosters
