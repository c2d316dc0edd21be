"""Vectorized best-split finding over all features at once.

TPU-native replacement for the reference's per-feature sequential scans
(`FeatureHistogram::FindBestThresholdNumerical/Sequence/Categorical`,
src/treelearner/feature_histogram.hpp:81-369). The bidirectional
accumulate-and-scan becomes cumulative sums over the bin axis evaluated for
BOTH missing-value default directions simultaneously, with validity masks
replacing the `continue`/`break` guards — one `[F, B]` data-parallel pass
instead of `F` scalar loops.

Semantics preserved from the reference:
- gain  = (max(0,|G|-l1))^2 / (H+l2)  for each side   (hpp:206-212)
- leaf output = -sign(G)*max(0,|G|-l1) / (H+l2)       (hpp:220-225)
- missing handling (hpp:81-103): num_bin>2 and MissingType::Zero -> dual
  scans with the default(zero) bin's mass following the default direction;
  MissingType::NaN -> dual scans with the last (NaN) bin following the
  default direction; else single scan, default_left=true (false for 2-bin
  NaN).
- categorical = one-vs-rest over used bins (hpp:104-174), default_left=false.
- constraints: min_data_in_leaf / min_sum_hessian_in_leaf on both sides;
  reported gain is relative to min_gain_shift = parent_gain +
  min_gain_to_split (hpp:102).
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

from ..binning import MISSING_NAN, MISSING_NONE, MISSING_ZERO
from ..telemetry.layers import scope

K_EPSILON = 1e-15
K_MIN_SCORE = -jnp.inf


class SplitResult(NamedTuple):
    """Per-feature best split (device arrays, shape [F])."""
    gain: jnp.ndarray          # f32, already minus min_gain_shift
    threshold: jnp.ndarray     # i32 bin threshold (left: bin <= threshold)
    default_left: jnp.ndarray  # bool
    is_categorical: jnp.ndarray  # bool (threshold is the left-alone bin)
    left_sum_g: jnp.ndarray
    left_sum_h: jnp.ndarray
    left_count: jnp.ndarray    # f32 running count: the min_data_in_leaf
    right_sum_g: jnp.ndarray   # gate's view, exact under 2^24 rows only;
    right_sum_h: jnp.ndarray   # what is STORED is exact_left_count's
    right_count: jnp.ndarray


def dequantize_hist(hist: jnp.ndarray, qscale) -> jnp.ndarray:
    """Quantized-training seam (tpu_hist_quantize): map an int32 bin
    histogram back to real gradient units right before split scoring.

    qscale is the [3] per-channel scale (g_scale, h_scale, 1.0) from
    ops.histogram.quantize_gradients; it broadcasts over the trailing
    (g, h, cnt) channel axis of any [..., 3] histogram/total. None is the
    f32 path's no-op, so callers can thread an optional scale without
    branching on mode. Everything downstream of this point — gains, leaf
    outputs, min_sum_hessian constraints — sees ordinary f32 sums; the
    exact integer domain ends here (the parent-sum identity
    sum(left) + sum(right) == parent holds bitwise in int32, and both
    sides dequantize through the SAME scale)."""
    if qscale is None:
        return hist
    return hist.astype(jnp.float32) * qscale


def leaf_split_gain(sum_g, sum_h, l1: float, l2: float):
    """Reference: GetLeafSplitGain, feature_histogram.hpp:206-212."""
    reg = jnp.maximum(jnp.abs(sum_g) - l1, 0.0)
    return (reg * reg) / (sum_h + l2)


def leaf_output(sum_g, sum_h, l1: float, l2: float):
    """Reference: CalculateSplittedLeafOutput, feature_histogram.hpp:220-225."""
    reg = jnp.maximum(jnp.abs(sum_g) - l1, 0.0)
    return -jnp.sign(sum_g) * reg / (sum_h + l2)


@scope("lgbm/split/scan")
def find_best_splits(hist: jnp.ndarray,
                     parent_sum_g: jnp.ndarray,
                     parent_sum_h: jnp.ndarray,
                     parent_count: jnp.ndarray,
                     num_bin: jnp.ndarray,
                     missing_type: jnp.ndarray,
                     default_bin: jnp.ndarray,
                     is_categorical: jnp.ndarray,
                     *,
                     lambda_l1: float,
                     lambda_l2: float,
                     min_gain_to_split: float,
                     min_data_in_leaf: int,
                     min_sum_hessian_in_leaf: float) -> SplitResult:
    """Best split per feature from a complete leaf histogram.

    Args:
      hist: [F, B, 3] (sum_grad, sum_hess, count) per (feature, bin).
      parent_sum_g/h/count: scalars for the leaf being split (the count
        may be the grower's exact int32; the scan gates in float32).
      num_bin / missing_type / default_bin / is_categorical: [F] static
        per-feature metadata (Dataset.feature_meta_arrays).
    """
    f, b, _ = hist.shape
    g = hist[:, :, 0]
    h = hist[:, :, 1]
    c = hist[:, :, 2]
    bins = jnp.arange(b, dtype=jnp.int32)[None, :]          # [1,B]
    nb = num_bin[:, None]                                    # [F,1]
    parent_sum_h = parent_sum_h + 2 * K_EPSILON
    parent_count = jnp.asarray(parent_count).astype(jnp.float32)

    parent_gain = leaf_split_gain(parent_sum_g, parent_sum_h, lambda_l1, lambda_l2)
    min_gain_shift = parent_gain + min_gain_to_split

    dual = (nb > 2) & (missing_type[:, None] != MISSING_NONE)   # [F,1]
    is_zero = missing_type[:, None] == MISSING_ZERO
    is_nan = missing_type[:, None] == MISSING_NAN

    # --- numerical: cumulative left sums -------------------------------
    # zero out the default bin when its mass follows the default direction
    skip_default = dual & is_zero
    at_default = bins == default_bin[:, None]
    g_adj = jnp.where(skip_default & at_default, 0.0, g)
    h_adj = jnp.where(skip_default & at_default, 0.0, h)
    c_adj = jnp.where(skip_default & at_default, 0.0, c)
    # NaN bin (last bin) is excluded from the scan range; zero it so cumsums
    # through it are unaffected
    nan_bin = nb - 1
    at_nan = bins == nan_bin
    use_na = dual & is_nan
    g_adj = jnp.where(use_na & at_nan, 0.0, g_adj)
    h_adj = jnp.where(use_na & at_nan, 0.0, h_adj)
    c_adj = jnp.where(use_na & at_nan, 0.0, c_adj)

    cg = jnp.cumsum(g_adj, axis=1)     # inclusive: left sums for threshold t
    ch = jnp.cumsum(h_adj, axis=1)
    cc = jnp.cumsum(c_adj, axis=1)

    # mass that joins the left side when missing defaults left
    extra_g = jnp.where(use_na, (g * at_nan).sum(1, keepdims=True),
                        jnp.where(skip_default,
                                  (g * at_default).sum(1, keepdims=True), 0.0))
    extra_h = jnp.where(use_na, (h * at_nan).sum(1, keepdims=True),
                        jnp.where(skip_default,
                                  (h * at_default).sum(1, keepdims=True), 0.0))
    extra_c = jnp.where(use_na, (c * at_nan).sum(1, keepdims=True),
                        jnp.where(skip_default,
                                  (c * at_default).sum(1, keepdims=True), 0.0))

    def eval_variant(lg, lh, lc, t_valid):
        lh_eff = lh + K_EPSILON
        rg = parent_sum_g - lg
        rh = parent_sum_h - lh_eff
        rc = parent_count - lc
        ok = (t_valid
              & (lc >= min_data_in_leaf) & (rc >= min_data_in_leaf)
              & (lh_eff >= min_sum_hessian_in_leaf)
              & (rh >= min_sum_hessian_in_leaf))
        gains = (leaf_split_gain(lg, lh_eff, lambda_l1, lambda_l2)
                 + leaf_split_gain(rg, rh, lambda_l1, lambda_l2))
        gains = jnp.where(ok & (gains > min_gain_shift), gains, K_MIN_SCORE)
        return gains

    # default-right scan (reference dir=+1): valid for dual-scan features
    # and the 2-bin NaN case (hpp:96-99)
    right_mask = dual | (is_nan & (nb <= 2))
    t_valid_r = (bins <= nb - 2) & right_mask
    gains_right = eval_variant(cg, ch, cc, t_valid_r)

    # default-left scan (reference dir=-1): valid for dual-scan features and
    # all single-scan features (None missing); NaN dual scan stops one bin
    # earlier because the NaN bin is carved out of the range (hpp:241-242)
    left_tmax = jnp.where(use_na, nb - 3, nb - 2)
    left_mask = dual | ~(is_nan & (nb <= 2))
    t_valid_l = (bins <= left_tmax) & left_mask
    gains_left = eval_variant(cg + extra_g, ch + extra_h, cc + extra_c, t_valid_l)

    # --- categorical: one-vs-rest (hpp:104-174) ------------------------
    is_full_cat = missing_type[:, None] == MISSING_NONE
    used_bin = nb - 1 + is_full_cat.astype(jnp.int32)
    lh_cat = h + K_EPSILON
    rg_cat = parent_sum_g - g
    rh_cat = parent_sum_h - lh_cat
    rc_cat = parent_count - c
    cat_ok = ((bins < used_bin)
              & (c >= min_data_in_leaf) & (rc_cat >= min_data_in_leaf)
              & (lh_cat >= min_sum_hessian_in_leaf)
              & (rh_cat >= min_sum_hessian_in_leaf))
    gains_cat = (leaf_split_gain(g, lh_cat, lambda_l1, lambda_l2)
                 + leaf_split_gain(rg_cat, rh_cat, lambda_l1, lambda_l2))
    gains_cat = jnp.where(cat_ok & (gains_cat > min_gain_shift),
                          gains_cat, K_MIN_SCORE)

    cat_col = is_categorical[:, None]
    gains_right = jnp.where(cat_col, K_MIN_SCORE, gains_right)
    gains_left = jnp.where(cat_col, K_MIN_SCORE, gains_left)
    gains_cat = jnp.where(cat_col, gains_cat, K_MIN_SCORE)

    # --- pick best over {left-default, right-default, categorical} x bins
    # reference scan order dir=-1 then dir=+1 with strict '>' update means
    # on exact ties the default-left result wins (hpp:92-95 + :296)
    all_gains = jnp.stack([gains_left, gains_right, gains_cat], axis=1)  # [F,3,B]
    flat = all_gains.reshape(f, 3 * b)
    best_idx = jnp.argmax(flat, axis=1)
    best_gain = jnp.take_along_axis(flat, best_idx[:, None], axis=1)[:, 0]
    variant = (best_idx // b).astype(jnp.int32)       # 0=left,1=right,2=cat
    thr = (best_idx % b).astype(jnp.int32)

    at_thr = bins == thr[:, None]
    sel = lambda arr: (arr * at_thr).sum(axis=1)
    num_lg = sel(cg) + jnp.where(variant == 0, extra_g[:, 0], 0.0)
    num_lh = sel(ch) + jnp.where(variant == 0, extra_h[:, 0], 0.0) + K_EPSILON
    num_lc = sel(cc) + jnp.where(variant == 0, extra_c[:, 0], 0.0)
    cat_lg, cat_lh, cat_lc = sel(g), sel(h) + K_EPSILON, sel(c)

    is_cat_best = variant == 2
    lg_best = jnp.where(is_cat_best, cat_lg, num_lg)
    lh_best = jnp.where(is_cat_best, cat_lh, num_lh)
    lc_best = jnp.where(is_cat_best, cat_lc, num_lc)

    has_split = best_gain > K_MIN_SCORE
    final_gain = jnp.where(has_split, best_gain - min_gain_shift, K_MIN_SCORE)

    return SplitResult(
        gain=final_gain.astype(jnp.float32),
        threshold=thr,
        default_left=(variant == 0) & ~is_cat_best,
        is_categorical=is_cat_best,
        left_sum_g=lg_best,
        left_sum_h=lh_best - K_EPSILON,
        left_count=lc_best,
        right_sum_g=parent_sum_g - lg_best,
        right_sum_h=parent_sum_h - lh_best - K_EPSILON,
        right_count=parent_count - lc_best,
    )


def exact_count(cells) -> jnp.ndarray:
    """int32 sum of float32 count cells. A (group, bin) cell is an exact
    integer while it stays under 2^24; what float32 loses past 2^24 rows
    under one node is the SUM over cells, so every stored row count is
    summed here, in int32."""
    return jnp.round(cells).astype(jnp.int32).sum(axis=-1)


def exact_left_count(count_bins, parent_count, threshold, default_left,
                     is_categorical, num_bin, missing_type, default_bin,
                     is_bundled=False) -> jnp.ndarray:
    """Rows left of ONE chosen split, exact in int32: the int32 sum of
    that feature's own count bins [B] on the left of `threshold`, by
    find_best_splits' rules (a dual scan's default or NaN bin follows
    `default_left`; a categorical split sends one bin left). At most B
    terms a selected node and nothing per feature, where a second
    cumulative sum beside the scan's float32 one would cost the whole
    [F, B] pass again. `parent_count` is the node's int32 count: a
    bundled feature's default bin holds no rows of its own and is the
    parent less the rest (`_extract_feature_hist`)."""
    b = count_bins.shape[-1]
    bins = jnp.arange(b, dtype=jnp.int32)
    c = jnp.round(count_bins).astype(jnp.int32)
    at_default = bins == default_bin
    c = jnp.where(is_bundled & at_default,
                  parent_count - jnp.where(at_default, 0, c).sum(), c)
    dual = (num_bin > 2) & (missing_type != MISSING_NONE)
    at_moving = jnp.where(
        dual & (missing_type == MISSING_NAN), bins == num_bin - 1,
        dual & (missing_type == MISSING_ZERO) & at_default)
    moving = jnp.where(at_moving, c, 0).sum()
    scanned = jnp.where((bins <= threshold) & ~at_moving, c, 0).sum()
    numerical = scanned + jnp.where(default_left, moving, 0)
    return jnp.where(is_categorical,
                     jnp.where(bins == threshold, c, 0).sum(), numerical)
