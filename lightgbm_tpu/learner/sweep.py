"""Vmapped many-model tree growth: K boosters step in ONE XLA program.

Production GBDT shops train thousands of SMALL boosters — per-segment
fleets, hyperparameter sweeps — and each one pays its own trace, its own
per-iteration dispatch chain, and (on TPU) an MXU that a tiny dataset
cannot fill. The grower is already fixed-shape (padded rows, padded group
axis, pass functions over array state — learner/grow.py), which is
exactly what `jax.vmap` wants: this module maps a MODEL axis of size K
over the whole per-iteration pass — gradients, bagging/GOSS row weights,
tree growth, score update — so one compile and one dispatch per boosting
iteration serve the entire sweep.

What may differ per model (traced [K] arrays, mapped by vmap):
- regularization/constraint knobs (`GrowParams`: lambda_l1/l2,
  min_gain_to_split, min_data_in_leaf, min_sum_hessian_in_leaf);
- learning rate (shrinkage — and through it the GOSS sampling start);
- bagging/GOSS seeds, bagging_fraction, top_rate/other_rate;
- feature_fraction masks (host-sampled per model, stacked [K, C, F]).

What must be SHARED (static — it decides shapes and loop structure):
the dataset/binning, num_leaves, max_depth, max_bin, bundling, the
boosting mode, bagging_freq, objective, num_class. `boosting.sweep`
validates the agreement up front and raises a LightGBMError naming the
divergent key instead of leaving an XLA shape error.

Bit-identity contract: model k of a vmapped step is BYTE-IDENTICAL to
the serial path training that config alone (tests/test_sweep.py). Three
properties carry it: (1) XLA's batching of every op here is
element-wise exact, (2) per-model scalars are computed HOST-side with
the exact expressions the serial path uses (so e.g. the GOSS
`rest_p = other_k / (n - top_k)` sees the same double-rounding), and
(3) every RNG draw inside the vmapped region keeps the serial shape:
per-model keys drawing `(n,)` — NEVER a `(K, n)` batched draw, and
never the padded row count. The graftlint `padded-rng` invariant
extends to the model axis (a batched draw would make model k's sample
a function of K, the way a padded draw makes it a function of the
device count).
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import numpy as np

from .grow import GrowParams, GrowerConfig, grow_tree
from ..ops.lookup import row_lookup

MODE_PLAIN = "plain"
MODE_BAGGING = "bagging"
MODE_GOSS = "goss"
SWEEP_MODES = (MODE_PLAIN, MODE_BAGGING, MODE_GOSS)


class SweepModelParams(NamedTuple):
    """Per-model traced state, every leaf a [K] array (model-major).

    The GOSS fields are HOST-precomputed with the serial path's exact
    Python expressions (boosting/goss.py `_goss_impl`): `top_k/other_k`
    from the rates, `rest_p`/`multiply` as f64-then-f32 — the same
    double rounding the serial weak-typed comparison applies — and
    `start` = int(1/learning_rate). They ride as data even in
    plain/bagging mode (zeros) so the pytree structure is mode-stable.
    """
    grow: GrowParams              # five [K] leaves
    shrinkage: "np.ndarray"       # [K] f32
    bag_seed: "np.ndarray"        # [K] i32 (bagging_seed; GOSS keys too)
    bag_fraction: "np.ndarray"    # [K] f32
    goss_start: "np.ndarray"      # [K] i32 first sampling iteration
    goss_top_k: "np.ndarray"      # [K] i32
    goss_rest_p: "np.ndarray"     # [K] f32
    goss_multiply: "np.ndarray"   # [K] f32


class SweepGrower:
    """One-dispatch-per-iteration stepper for K lockstep boosters.

    Owns the jitted vmapped program; the host orchestration
    (boosting/sweep.SweepTrainer) owns configs, tree materialization,
    and stop semantics. `small_keys` names the TreeGrowerState fields
    fetched host-side per iteration (boosting.gbdt._SMALL_STATE_KEYS —
    passed in to keep this module import-cycle-free)."""

    def __init__(self, cfg: GrowerConfig, objective, *, kc: int, n: int,
                 n_pad: int, mode: str, bag_freq: int,
                 fmeta_args: Tuple, small_keys: Tuple[str, ...],
                 quant_seed: int = 0, quant_hess_const: bool = False):
        if mode not in SWEEP_MODES:
            raise ValueError(f"unknown sweep mode {mode!r}")
        self.cfg = cfg
        self.objective = objective
        self.kc = int(kc)
        self.n = int(n)
        self.n_pad = int(n_pad)
        self.mode = mode
        self.bag_freq = max(1, int(bag_freq))
        self.fmeta_args = tuple(fmeta_args)
        self.small_keys = tuple(small_keys)
        # quantized-gradient training (cfg.hist_quantize != "none"): the
        # rounding-key base seed and the constant-hessian flag are SHARED
        # statics — "data_random_seed" is not sweep-variable and the
        # boosting mode/objective decide hess_const, all of which every
        # sweep member must agree on. That sharing is what keeps model k
        # byte-identical to its solo quantized train: both derive keys
        # from fold_in(fold_in(fold_in(PRNGKey(seed), it), class), 0|1)
        self.quant_seed = int(quant_seed)
        self.quant_hess_const = bool(quant_hess_const)
        # objective row arrays ride as ARGUMENTS, not closure captures
        # (a captured [N] array inlines into the lowered module as a
        # giant literal and defeats the persistent compile cache) — the
        # same discovery rule as the serial gradient jit, via the
        # shared helper (lazy import: boosting imports this package)
        from ..boosting.gbdt import objective_array_keys
        self._arr_keys = objective_array_keys(objective)
        self._jit = None

    # ------------------------------------------------------------------
    def _row_weight(self, it, pm_k, g, h, base_w):
        """One model's [n_pad] row weights for iteration `it` — the
        vmapped analogue of GBDT._bagging_weights / GOSS._bagging_weights,
        branch-free over the model axis. Draws are (n,) then padded
        (never the padded or batched shape: the padded-rng invariant)."""
        import jax
        import jax.numpy as jnp
        n, n_pad = self.n, self.n_pad
        if self.mode == MODE_PLAIN:
            return base_w
        if self.mode == MODE_BAGGING:
            # refresh cadence iter//freq matches the serial cache key;
            # models with fraction 1.0 get all-ones masks — the same
            # VALUES the serial no-bagging path uses (u < 1.0 always)
            key = jax.random.fold_in(jax.random.PRNGKey(pm_k.bag_seed),
                                     it // self.bag_freq)
            u = jax.random.uniform(key, (n,))
            mask = (u < pm_k.bag_fraction).astype(jnp.float32)
            return jnp.pad(mask, (0, n_pad - n))
        # GOSS (boosting/goss.py _goss_impl, per-model scalars traced)
        mag = jnp.abs(g * h).sum(axis=0)
        real = jnp.arange(n_pad, dtype=jnp.int32) < n
        mag = jnp.where(real, mag, -jnp.inf)
        thresh = -jnp.sort(-mag)[pm_k.goss_top_k - 1]
        is_top = mag >= thresh
        key = jax.random.fold_in(jax.random.PRNGKey(pm_k.bag_seed), it)
        u = jax.random.uniform(key, (n,))
        u = jnp.pad(u, (0, n_pad - n), constant_values=1.0)
        w = jnp.where(is_top, 1.0,
                      jnp.where(u < pm_k.goss_rest_p,
                                pm_k.goss_multiply, 0.0))
        w = jnp.where(real, w, 0.0).astype(jnp.float32)
        # before each model's own 1/lr warmup the serial path skips
        # sampling entirely (goss.hpp:135-138) — heterogeneous learning
        # rates make the cutover per-model, so it is traced, not a
        # Python branch
        return jnp.where(it >= pm_k.goss_start, w, base_w)

    def _impl(self, score, binned, it, pm, arrs, base_w, fmasks):
        """score [K, C, n_pad]; fmasks [K, C, F]; pm leaves [K].
        Returns (new_score, small-state dict with [K, C, ...] leaves)."""
        import jax
        import jax.numpy as jnp
        obj = self.objective
        kc, n_pad = self.kc, self.n_pad
        cfg = self.cfg
        L = cfg.num_leaves

        def one_model(score_k, pm_k, fmask_k):
            g, h = obj.get_gradients(score_k.reshape(-1))
            g = g.reshape(kc, n_pad)
            h = h.reshape(kc, n_pad)
            w = self._row_weight(it, pm_k, g, h, base_w)

            if cfg.hist_quantize != "none":
                # quantized-gradient mode: per-class integer codes with
                # the solo path's exact key chain (gbdt.
                # _quantize_iter_device) — shared across models, so the
                # draw inside quantize_gradients stays the serial (n,)
                # shape under BOTH the class vmap and the model vmap
                from ..ops.histogram import quantize_gradients
                base = jax.random.fold_in(
                    jax.random.PRNGKey(self.quant_seed), it)

                def one_class_q(gc, hc, mc, ci):
                    kq = jax.random.fold_in(base, ci)
                    q_g, q_h, w01, qs = quantize_gradients(
                        gc, hc, w, n=self.n, qmax=cfg.hist_qmax,
                        key_g=jax.random.fold_in(kq, 0),
                        key_h=jax.random.fold_in(kq, 1),
                        hess_const=self.quant_hess_const)
                    return grow_tree(binned, q_g, q_h, w01, mc,
                                     *self.fmeta_args, cfg,
                                     n_valid=jnp.int32(self.n),
                                     gp=pm_k.grow, qscale=qs)

                state = jax.vmap(one_class_q)(
                    g, h, fmask_k, jnp.arange(kc, dtype=jnp.int32))
            else:
                def one_class(gc, hc, mc):
                    return grow_tree(binned, gc, hc, w, mc,
                                     *self.fmeta_args, cfg,
                                     n_valid=jnp.int32(self.n),
                                     gp=pm_k.grow)

                state = jax.vmap(one_class)(g, h, fmask_k)

            def upd(lv, lid, grew):
                vals = lv * pm_k.shrinkage
                return jnp.where(
                    grew, row_lookup(vals, jnp.clip(lid, 0, L - 1)), 0.0)

            delta = jax.vmap(upd)(state.leaf_value, state.leaf_id,
                                  state.num_leaves_used > 1)
            small = {k: getattr(state, k) for k in self.small_keys}
            return score_k + delta, small

        # the objective's row arrays are swapped to the traced arguments
        # for the duration of the trace (shared, unbatched under vmap)
        from ..boosting.gbdt import objective_arrays_swapped
        with objective_arrays_swapped(obj, self._arr_keys, arrs):
            return jax.vmap(one_model)(score, pm, fmasks)

    # ------------------------------------------------------------------
    def step(self, score, binned, it: int, pm: SweepModelParams, base_w,
             fmasks):
        """Dispatch one lockstep boosting iteration for all K models.
        Returns (new_score, small) UNFETCHED — the host loop stays
        sync-free and materializes trees after the last iteration."""
        import jax
        import jax.numpy as jnp
        if self._jit is None:
            self._jit = jax.jit(self._impl)
        arrs = {k: getattr(self.objective, k) for k in self._arr_keys}
        return self._jit(score, binned, jnp.int32(it), pm, arrs, base_w,
                         fmasks)
