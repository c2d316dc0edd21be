"""GOSS: Gradient-based One-Side Sampling.

Reference: `src/boosting/goss.hpp` — keep the top_rate fraction of rows by
|grad*hess|, sample other_rate of the rest, and amplify the sampled rows'
gradients and hessians by (1-top_rate)/other_rate (BaggingHelper,
goss.hpp:87-131). Sampling starts after 1/learning_rate iterations
(goss.hpp:135-138). In the leaf-id design the amplification folds into the
per-row weight channel fed to the histogram kernel.
"""
from __future__ import annotations

from .. import log
from .gbdt import GBDT


class GOSS(GBDT):
    def __init__(self, config):
        super().__init__(config)
        if config.boosting.top_rate <= 0 or config.boosting.other_rate <= 0:
            log.fatal("GOSS requires top_rate > 0 and other_rate > 0")
        if config.boosting.bagging_freq > 0 and config.boosting.bagging_fraction != 1.0:
            log.fatal("Cannot use bagging in GOSS")
        log.info("Using GOSS")

    def model_name(self) -> str:
        return "goss"

    def _checkpoint_extra(self) -> dict:
        """GOSS needs NO extra checkpoint state: its subsample RNG is
        stateless — the row weights are a pure function of
        (bagging_seed, iteration) via jax.random.fold_in, and the top-k
        threshold derives from the (restored) score's gradients. Resume
        at iteration k therefore reproduces the exact masks of the
        uninterrupted run (asserted in tests/test_checkpoint.py)."""
        return {}

    def _bagging_weights(self, iter_idx, grad=None, hess=None):
        """GOSS row weights built ON DEVICE (no per-iteration [N]
        argsort on host / H2D upload): the top_rate threshold comes from
        a device sort of |grad*hess| (the partial-selection analogue of
        the reference's ArgMaxAtK, array_args.h), and the "other" rows
        are Bernoulli-sampled at other_k/(n-top_k) with the jax PRNG —
        the reference's own per-block `rand < prob` scheme
        (goss.hpp:87-131) rather than exact without-replacement draws."""
        cfg = self.config.boosting
        n = self._n
        # no subsampling for the first 1/lr iterations (goss.hpp:137)
        if iter_idx < int(1.0 / max(cfg.learning_rate, 1e-12)) or grad is None:
            return None
        top_k = max(1, int(n * cfg.top_rate))
        other_k = max(1, int(n * cfg.other_rate))
        from .. import telemetry
        telemetry.counter_add("boosting/goss_sampled_iters", 1)
        return _goss_weights_device(
            grad, hess, cfg.bagging_seed, iter_idx,
            self.num_tree_per_iteration, n, self._n_pad, top_k, other_k)


def _goss_impl(g, h, it, *, seed, k, n, n_pad, top_k, other_k):
    import jax
    import jax.numpy as jnp

    # per-class |g*h| summed over classes (goss.hpp:91 accumulates
    # fabs(grad*hess) per class — abs BEFORE the class sum, so
    # opposite-signed class gradients don't cancel a row's magnitude)
    mag = jnp.abs(g.reshape(k, n_pad) * h.reshape(k, n_pad)).sum(axis=0)
    real = jnp.arange(n_pad, dtype=jnp.int32) < n
    mag = jnp.where(real, mag, -jnp.inf)
    # threshold = top_k-th largest magnitude
    thresh = -jnp.sort(-mag)[top_k - 1]
    is_top = mag >= thresh
    key = jax.random.fold_in(jax.random.PRNGKey(seed), it)
    # (n,) then pad, like the bagging mask in gbdt.py: a (n_pad,) draw
    # would tie the sample to the padded row count (a function of the
    # device count — threefry is not prefix-stable across shapes) and
    # break cross-world-size training bit-identity
    u = jnp.pad(jax.random.uniform(key, (n,)), (0, n_pad - n),
                constant_values=1.0)
    rest_p = other_k / max(1, n - top_k)
    multiply = (n - top_k) / other_k
    w = jnp.where(is_top, 1.0,
                  jnp.where(u < rest_p, multiply, 0.0))
    return jnp.where(real, w, 0.0).astype(jnp.float32)


_goss_jit = None


def _goss_weights_device(grad, hess, seed, iter_idx, k, n, n_pad,
                         top_k, other_k):
    import jax
    import jax.numpy as jnp
    global _goss_jit
    if _goss_jit is None:
        _goss_jit = jax.jit(_goss_impl, static_argnames=(
            "seed", "k", "n", "n_pad", "top_k", "other_k"))
    return _goss_jit(grad, hess, jnp.int32(iter_idx), seed=seed, k=k, n=n,
                     n_pad=n_pad, top_k=top_k, other_k=other_k)
