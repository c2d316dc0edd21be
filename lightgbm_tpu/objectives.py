"""Objective functions (gradient/hessian producers), all device-side.

Re-implements the reference objective factory and semantics
(`src/objective/objective_function.cpp:10-36` and the per-objective
headers). Each objective exposes:

- `get_gradients(score) -> (grad, hess)` — a jitted elementwise (or
  per-query, for lambdarank) kernel over `[num_data * num_class]` scores,
  replacing the OMP loops;
- `convert_output(raw)` — sigmoid/softmax/exp transform for prediction;
- capability flags mirrored from the reference interface
  (`include/LightGBM/objective_function.h`): num_model_per_iteration,
  is_constant_hessian, boost_from_average.

Score layout for multiclass follows the reference: class-major
`[num_class, num_data]` flattened (multiclass_objective.hpp:60-64).
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import log, telemetry
from .config import Config
from .dataset import Metadata

K_MIN_SCORE = -1e30


class ObjectiveFunction:
    name = "base"
    num_class = 1

    def init(self, metadata: Metadata, num_data: int) -> None:
        """Capture label/weight statistics from the REAL (unpadded) data.
        The engine then calls pad_to() so the elementwise gradient kernels
        line up with the padded score arrays; all statistics (bias, class
        counts, query DCGs) must be computed here, before padding."""
        self.num_data = num_data
        self.label = jnp.asarray(metadata.label) if metadata.label is not None else None
        self.weights = jnp.asarray(metadata.weights) if metadata.weights is not None else None

    def pad_to(self, n_pad: int) -> None:
        """Zero-pad per-row arrays to the device row count (padded rows carry
        row_weight 0 in the grower, so their gradients are ignored)."""
        if n_pad == self.num_data:
            return
        extra = n_pad - self.num_data
        if self.label is not None:
            self.label = jnp.pad(self.label, (0, extra))
        if self.weights is not None:
            self.weights = jnp.pad(self.weights, (0, extra))
        self.num_data = n_pad

    def get_gradients(self, score: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
        raise NotImplementedError

    def convert_output(self, raw: jnp.ndarray) -> jnp.ndarray:
        return raw

    def num_model_per_iteration(self) -> int:
        return 1

    def is_constant_hessian(self) -> bool:
        return False

    def boost_from_average(self) -> bool:
        return False

    def bias(self) -> float:
        """Initial score when boost_from_average (gbdt.cpp:358-378)."""
        return 0.0

    def _apply_weights(self, grad, hess):
        if self.weights is not None:
            return grad * self.weights, hess * self.weights
        return grad, hess

    def to_string(self) -> str:
        return self.name

    def sync_distributed(self, allreduce_sum) -> None:
        """Fix label statistics computed on a row SHARD under multi-host
        training: `allreduce_sum(np_array) -> np_array` sums across
        processes (reference: the distributed boost-from-average
        Allreduce, gbdt.cpp:298-335, and the cross-machine label-count
        sync in binary_objective). Objectives whose statistics are purely
        per-row or per-query (held whole on one shard) need nothing."""
        return None


class RegressionL2(ObjectiveFunction):
    """reference: regression_objective.hpp:13-79 (grad = score - label)."""
    name = "regression"

    def get_gradients(self, score):
        grad = score - self.label
        hess = jnp.ones_like(score)
        return self._apply_weights(grad, hess)

    def is_constant_hessian(self):
        return self.weights is None

    def boost_from_average(self):
        return True

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        lab = np.asarray(metadata.label)
        if metadata.weights is not None:
            w = np.asarray(metadata.weights)
            self._sums = np.array([np.sum(lab * w), np.sum(w)])
        else:
            self._sums = np.array([lab.sum(), float(len(lab))])
        self._bias = float(self._sums[0] / self._sums[1])

    def sync_distributed(self, allreduce_sum):
        self._sums = allreduce_sum(self._sums)
        self._bias = float(self._sums[0] / self._sums[1])

    def bias(self):
        return self._bias


def _gaussian_hessian_approx(score, label, grad, eta, w=1.0):
    """reference: Common::ApproximateHessianWithGaussian, common.h:486-495."""
    diff = score - label
    x = jnp.abs(diff)
    a = 2.0 * jnp.abs(grad) * w
    c = jnp.maximum((jnp.abs(score) + jnp.abs(label)) * eta, 1e-10)
    return w * jnp.exp(-x * x / (2.0 * c * c)) * a / (c * jnp.sqrt(2 * jnp.pi))


class RegressionL1(ObjectiveFunction):
    """reference: regression_objective.hpp:80-150."""
    name = "regression_l1"

    def __init__(self, config: Config):
        self.eta = config.objective_config.gaussian_eta

    def get_gradients(self, score):
        diff = score - self.label
        w = self.weights if self.weights is not None else 1.0
        grad = jnp.where(diff >= 0, 1.0, -1.0) * w
        hess = _gaussian_hessian_approx(score, self.label, grad, self.eta,
                                        w if self.weights is not None else 1.0)
        return grad, hess


class RegressionHuber(ObjectiveFunction):
    """reference: regression_objective.hpp:151-230."""
    name = "huber"

    def __init__(self, config: Config):
        self.delta = config.objective_config.huber_delta
        self.eta = config.objective_config.gaussian_eta

    def get_gradients(self, score):
        diff = score - self.label
        w = self.weights if self.weights is not None else jnp.ones_like(score)
        inlier = jnp.abs(diff) <= self.delta
        grad_out = jnp.where(diff >= 0, self.delta, -self.delta)
        grad = jnp.where(inlier, diff, grad_out) * w
        hess_out = _gaussian_hessian_approx(score, self.label, grad_out * w,
                                            self.eta, w)
        hess = jnp.where(inlier, w, hess_out)
        return grad, hess


class RegressionFair(ObjectiveFunction):
    """reference: regression_objective.hpp:231-300."""
    name = "fair"

    def __init__(self, config: Config):
        self.c = config.objective_config.fair_c

    def get_gradients(self, score):
        x = score - self.label
        c = self.c
        grad = c * x / (jnp.abs(x) + c)
        hess = c * c / ((jnp.abs(x) + c) ** 2)
        return self._apply_weights(grad, hess)


class RegressionPoisson(ObjectiveFunction):
    """reference: regression_objective.hpp:301-407 (log-link)."""
    name = "poisson"

    def __init__(self, config: Config):
        self.max_delta_step = config.objective_config.poisson_max_delta_step

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if np.any(np.asarray(metadata.label) < 0):
            log.fatal("[poisson]: labels must be non-negative")

    def get_gradients(self, score):
        ef = jnp.exp(score)
        grad = ef - self.label
        hess = jnp.exp(score + self.max_delta_step)
        return self._apply_weights(grad, hess)

    def convert_output(self, raw):
        return jnp.exp(raw)


class BinaryLogloss(ObjectiveFunction):
    """reference: binary_objective.hpp:13-157."""
    name = "binary"

    def to_string(self):
        # the reference loader REQUIRES the sigmoid token
        # (binary_objective.hpp:32-42 fatals without it)
        return f"binary sigmoid:{self.sigmoid:g}"

    def __init__(self, config: Config):
        self.sigmoid = config.objective_config.sigmoid
        if self.sigmoid <= 0:
            log.fatal("Sigmoid parameter %f should be greater than zero" % self.sigmoid)
        self.is_unbalance = config.objective_config.is_unbalance
        self.scale_pos_weight = config.objective_config.scale_pos_weight
        if self.is_unbalance and abs(self.scale_pos_weight - 1.0) > 1e-6:
            log.fatal("Cannot set is_unbalance and scale_pos_weight at the same time")
        self.label_weights = (1.0, 1.0)

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        lab = np.asarray(metadata.label)
        cnt_pos = int((lab > 0).sum())
        cnt_neg = num_data - cnt_pos
        if cnt_pos == 0 or cnt_neg == 0:
            log.warning("Only one class present in label")
        log.info("Number of positive: %d, number of negative: %d", cnt_pos, cnt_neg)
        self._cnt_pos, self._cnt_neg = cnt_pos, cnt_neg
        self._set_label_weights()

    def _set_label_weights(self):
        cnt_pos, cnt_neg = self._cnt_pos, self._cnt_neg
        w_neg, w_pos = 1.0, 1.0
        if self.is_unbalance and cnt_pos > 0 and cnt_neg > 0:
            if cnt_pos > cnt_neg:
                w_neg = cnt_pos / cnt_neg
            else:
                w_pos = cnt_neg / cnt_pos
        w_pos *= self.scale_pos_weight
        self.label_weights = (w_neg, w_pos)

    def sync_distributed(self, allreduce_sum):
        s = allreduce_sum(np.array([self._cnt_pos, self._cnt_neg],
                                   np.float64))
        self._cnt_pos, self._cnt_neg = int(s[0]), int(s[1])
        self._set_label_weights()

    def get_gradients(self, score):
        is_pos = self.label > 0
        lv = jnp.where(is_pos, 1.0, -1.0)
        lw = jnp.where(is_pos, self.label_weights[1], self.label_weights[0])
        s = self.sigmoid
        response = -lv * s / (1.0 + jnp.exp(lv * s * score))
        abs_r = jnp.abs(response)
        grad = response * lw
        hess = abs_r * (s - abs_r) * lw
        return self._apply_weights(grad, hess)

    def convert_output(self, raw):
        return 1.0 / (1.0 + jnp.exp(-self.sigmoid * raw))


class MulticlassSoftmax(ObjectiveFunction):
    """reference: multiclass_objective.hpp:16-138."""
    name = "multiclass"

    def __init__(self, config: Config):
        self.num_class = config.objective_config.num_class
        if self.num_class < 2:
            log.fatal("num_class must be >= 2 for multiclass")

    def to_string(self):
        return f"multiclass num_class:{self.num_class}"

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        lab = np.asarray(metadata.label).astype(int)
        if lab.min() < 0 or lab.max() >= self.num_class:
            log.fatal("Label must be in [0, %d)" % self.num_class)
        self.label_int = jnp.asarray(lab)

    def pad_to(self, n_pad):
        extra = n_pad - self.num_data
        super().pad_to(n_pad)
        if extra > 0:
            self.label_int = jnp.pad(self.label_int, (0, extra))

    def get_gradients(self, score):
        # score layout: [num_class, num_data] flattened
        s = score.reshape(self.num_class, self.num_data)
        p = jax.nn.softmax(s, axis=0)
        onehot = (jnp.arange(self.num_class)[:, None] == self.label_int[None, :])
        grad = p - onehot.astype(p.dtype)
        hess = 2.0 * p * (1.0 - p)
        if self.weights is not None:
            grad = grad * self.weights[None, :]
            hess = hess * self.weights[None, :]
        return grad.reshape(-1), hess.reshape(-1)

    def convert_output(self, raw):
        return jax.nn.softmax(raw.reshape(self.num_class, -1), axis=0).reshape(-1)

    def num_model_per_iteration(self):
        return self.num_class


class MulticlassOVA(ObjectiveFunction):
    """reference: multiclass_objective.hpp:139-253 (one-vs-all binary)."""
    name = "multiclassova"

    def to_string(self):
        return (f"multiclassova num_class:{self.num_class} "
                f"sigmoid:{self.sigmoid:g}")

    def __init__(self, config: Config):
        self.num_class = config.objective_config.num_class
        self.sigmoid = config.objective_config.sigmoid

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        self.label_int = jnp.asarray(np.asarray(metadata.label).astype(int))

    def pad_to(self, n_pad):
        extra = n_pad - self.num_data
        super().pad_to(n_pad)
        if extra > 0:
            self.label_int = jnp.pad(self.label_int, (0, extra))

    def get_gradients(self, score):
        s = score.reshape(self.num_class, self.num_data)
        is_pos = (jnp.arange(self.num_class)[:, None] == self.label_int[None, :])
        lv = jnp.where(is_pos, 1.0, -1.0)
        sig = self.sigmoid
        response = -lv * sig / (1.0 + jnp.exp(lv * sig * s))
        abs_r = jnp.abs(response)
        grad = response
        hess = abs_r * (sig - abs_r)
        if self.weights is not None:
            grad = grad * self.weights[None, :]
            hess = hess * self.weights[None, :]
        return grad.reshape(-1), hess.reshape(-1)

    def convert_output(self, raw):
        return 1.0 / (1.0 + jnp.exp(-self.sigmoid * raw))

    def num_model_per_iteration(self):
        return self.num_class


class CrossEntropy(ObjectiveFunction):
    """reference: xentropy_objective.hpp:39-145 (labels in [0,1])."""
    name = "xentropy"

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        lab = np.asarray(metadata.label)
        if lab.min() < 0 or lab.max() > 1:
            log.fatal("[xentropy]: labels must be in [0, 1]")
        if metadata.weights is not None:
            w = np.asarray(metadata.weights)
            self._sums = np.array([np.sum(lab * w), np.sum(w)])
        else:
            self._sums = np.array([lab.sum(), float(len(lab))])
        self._set_bias()

    def _set_bias(self):
        pavg = float(self._sums[0] / self._sums[1])
        pavg = min(max(pavg, 1e-15), 1 - 1e-15)
        self._bias = float(np.log(pavg / (1 - pavg)))

    def sync_distributed(self, allreduce_sum):
        self._sums = allreduce_sum(self._sums)
        self._set_bias()

    def get_gradients(self, score):
        p = 1.0 / (1.0 + jnp.exp(-score))
        if self.weights is None:
            grad = p - self.label
            hess = p * (1.0 - p)
        else:
            w = self.weights
            grad = (p - self.label) * w
            hess = p * (1.0 - p) * w
        return grad, hess

    def convert_output(self, raw):
        return 1.0 / (1.0 + jnp.exp(-raw))

    def boost_from_average(self):
        return True

    def bias(self):
        return self._bias


class CrossEntropyLambda(ObjectiveFunction):
    """reference: xentropy_objective.hpp:146-268 (alternative
    parameterization; weighted labels via log1p/expm1 link)."""
    name = "xentlambda"

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        lab = np.asarray(metadata.label)
        if lab.min() < 0 or lab.max() > 1:
            log.fatal("[xentlambda]: labels must be in [0, 1]")

    def get_gradients(self, score):
        # hhat = exp(score) (w==1) or w*log1p(exp(score)); z = 1 - exp(-hhat)
        # gradients per reference hpp:186-230
        if self.weights is None:
            hhat = jnp.exp(score)
            dh_dscore = hhat  # d(hhat)/d(score)
        else:
            hhat = self.weights * jnp.log1p(jnp.exp(score))
            dh_dscore = self.weights / (1.0 + jnp.exp(-score))
        z = jnp.maximum(1.0 - jnp.exp(-hhat), 1e-15)
        grad = (z - self.label) * jnp.exp(-hhat) / z * dh_dscore
        hess = jnp.exp(-hhat) * dh_dscore * dh_dscore * (
            self.label * jnp.exp(-hhat) / (z * z) + 1.0 - self.label / z)
        # keep hessian positive for stable splits
        hess = jnp.maximum(hess, 1e-15)
        return grad, hess

    def convert_output(self, raw):
        return jnp.log1p(jnp.exp(raw))


def _lambdarank_pair_grads(score, gather, lab, mask, inv_max_dcg, gain_table,
                           sigmoid):
    """Pairwise lambda/hessian for ONE padded query batch [Qb, D].

    The reference's O(cnt^2) doc-pair loop (rank_objective.hpp:83-160) as a
    masked dense [Qb, D, D] computation. Returns per-doc (lam, hess)."""
    with telemetry.scope("lgbm/gradients/rank_sort"):
        s = score[gather]                        # [Qb, D]
        s = jnp.where(mask, s, K_MIN_SCORE)
        # sorted positions: position of each doc when sorted by score desc
        order = jnp.argsort(-s, axis=1, stable=True)
        pos = jnp.argsort(order, axis=1)         # pos[q, d] = rank of doc d
    with telemetry.scope("lgbm/gradients/rank_pairs"):
        discount = 1.0 / jnp.log2(pos.astype(jnp.float32) + 2.0)
        gain = gain_table[jnp.clip(lab, 0, gain_table.shape[0] - 1)]
        best = jnp.max(jnp.where(mask, s, -jnp.inf), axis=1, keepdims=True)
        worst = jnp.min(jnp.where(mask, s, jnp.inf), axis=1, keepdims=True)
        # pair tensors [Qb, D, D]: i = high, j = low
        ds = s[:, :, None] - s[:, None, :]
        valid = (mask[:, :, None] & mask[:, None, :]
                 & (lab[:, :, None] > lab[:, None, :]))
        dcg_gap = gain[:, :, None] - gain[:, None, :]
        paired_disc = jnp.abs(discount[:, :, None] - discount[:, None, :])
        delta_ndcg = dcg_gap * paired_disc * inv_max_dcg[:, None, None]
        norm = (best != worst)[:, :, None]
        delta_ndcg = jnp.where(norm, delta_ndcg / (0.01 + jnp.abs(ds)),
                               delta_ndcg)
        p_lambda = 2.0 / (1.0 + jnp.exp(2.0 * sigmoid * ds))
        p_hess = p_lambda * (2.0 - p_lambda)
        lam_pair = jnp.where(valid, -delta_ndcg * p_lambda, 0.0)
        hess_pair = jnp.where(valid, 2.0 * delta_ndcg * p_hess, 0.0)
        lam = lam_pair.sum(axis=2) - lam_pair.sum(axis=1)
        hess = hess_pair.sum(axis=2) + hess_pair.sum(axis=1)
        return jnp.where(mask, lam, 0.0), jnp.where(mask, hess, 0.0)


def _lambdarank_bucket_grads(score, gather, lab, mask, inv_max_dcg,
                             gain_table, sigmoid):
    """All batches of one length bucket: arrays are [nb, Qb, D] (stacked
    fixed-size batches); `lax.map` walks them SEQUENTIALLY so live pair
    memory stays O(Qb * D^2) regardless of bucket population. Scatter-adds
    each doc's lambda into flat gradient/hessian arrays of the score's
    length (a padded slot adds 0.0 to row 0). They start from ZEROS, one
    pair a bucket, and the caller adds them up: scattered into one
    running pair instead, the eight buckets of 19.8M slots took 0.942 s
    a call where this form takes 0.657 s (TPU v5e, PR 34)."""
    lam, hess = jax.lax.map(
        lambda args: _lambdarank_pair_grads(score, *args, gain_table,
                                            sigmoid),
        (gather, lab, mask, inv_max_dcg))
    with telemetry.scope("lgbm/gradients/rank_scatter"):
        idx = gather.reshape(-1)
        zeros = jnp.zeros(score.shape[0], jnp.float32)
        return (zeros.at[idx].add(lam.reshape(-1)),
                zeros.at[idx].add(hess.reshape(-1)))


class RankCounters(NamedTuple):
    """What one data set's pair layout holds, counted once in
    `LambdarankNDCG.init` (`schedule_info["rank"]`)."""
    queries: int
    docs: int
    max_docs: int
    buckets: Tuple[Tuple[int, int, int], ...]   # (D, queries, batches)
    slots: int          # padded documents: sum of nb x Qb x D
    pair_slots: int     # sum of nb x Qb x D x D, what the device evaluates
    valid_pairs: int    # pairs with label_i > label_j, what the sums need

    def as_dict(self) -> dict:
        return dict(self._asdict(), buckets={
            str(D): [queries, batches]
            for D, queries, batches in self.buckets})


class LambdarankNDCG(ObjectiveFunction):
    """reference: rank_objective.hpp:19-245. Per-query pairwise lambdas with
    deltaNDCG weighting.

    MSLR-scale redesign: queries are grouped into power-of-two LENGTH
    BUCKETS (16, 32, ..., next_pow2(max_docs)) and each bucket is processed
    in fixed-size query batches, so pair-tensor memory is bounded by
    O(batch * D_bucket^2) <= _PAIR_BUDGET elements — not O(Q * D_max^2) —
    while a query with 1,200 docs still gets its exact full pair set (the
    reference streams O(cnt^2) per query, hpp:83-160; it never samples).

    The pair layout is held as FLAT arrays over every bucket's slots
    (`_pair_gather`, `_pair_lab`, `_pair_mask` by document slot,
    `_pair_inv_max_dcg` by query slot) beside `_bucket_shapes`, the
    static (nb, Qb, D) of each bucket: the arrays reach the gradient
    program as arguments (`boosting/gbdt.objective_array_keys`), the
    shapes are part of its key, and nothing of the data set is a literal
    of the lowered program."""
    name = "lambdarank"
    _PAIR_BUDGET = 1 << 24  # max elements in one [Qb, D, D] pair tensor
    _MIN_BUCKET = 16

    def __init__(self, config: Config):
        self.sigmoid = config.objective_config.sigmoid
        self.optimize_pos_at = config.objective_config.max_position
        gains = config.objective_config.label_gain or \
            [float((1 << i) - 1) for i in range(31)]
        self.label_gain = np.asarray(gains, np.float64)

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if metadata.query_boundaries is None:
            log.fatal("Lambdarank tasks require query information")
        qb = np.asarray(metadata.query_boundaries)
        self.query_boundaries = qb
        sizes = np.diff(qb)
        self.max_docs = int(sizes.max())
        lab = np.asarray(metadata.label).astype(int)
        # inverse max DCG at k per query (dcg_calculator.cpp CalMaxDCGAtK),
        # vectorized: rows sorted by (query, -label) stay query-contiguous,
        # so per-query DCG is a segment sum over masked position discounts
        # (segment_sum tolerates zero-size queries, unlike reduceat)
        from .metrics import query_layout, segment_sum
        qid, pos_in_q = query_layout(qb)
        by_label = np.lexsort((-lab, qid))
        contrib = np.where(
            pos_in_q < self.optimize_pos_at,
            self.label_gain[np.clip(lab[by_label], 0, len(self.label_gain) - 1)]
            / np.log2(pos_in_q + 2.0), 0.0)
        dcg = segment_sum(contrib, qb)
        inv = np.where(dcg > 0, 1.0 / np.maximum(dcg, 1e-300), 0.0)

        # length buckets: D = next pow2 >= size (floored at _MIN_BUCKET)
        D_of = np.maximum(
            self._MIN_BUCKET,
            2 ** np.ceil(np.log2(np.maximum(sizes, 1))).astype(int))
        shapes, per_bucket, gathers, labs, masks, invs = [], [], [], [], [], []
        for D in sorted(set(D_of.tolist())):
            qs = np.nonzero(D_of == D)[0]
            Qb = max(1, self._PAIR_BUDGET // (D * D))
            nb = -(-len(qs) // Qb)               # ceil
            size = np.zeros(nb * Qb, np.int64)
            size[:len(qs)] = sizes[qs]
            start = np.zeros(nb * Qb, np.int64)
            start[:len(qs)] = qb[qs]
            mask = np.arange(D)[None, :] < size[:, None]
            gather = np.where(mask, start[:, None] + np.arange(D)[None, :], 0)
            binv = np.zeros(nb * Qb, np.float32)
            binv[:len(qs)] = inv[qs]
            shapes.append((nb, Qb, D))
            per_bucket.append((D, len(qs), nb))
            gathers.append(gather.astype(np.int32).reshape(-1))
            labs.append(np.where(mask, lab[gather], 0)
                        .astype(np.int32).reshape(-1))
            masks.append(mask.reshape(-1))
            invs.append(binv)
        self._bucket_shapes = tuple(shapes)
        self._pair_gather = jnp.asarray(np.concatenate(gathers))
        self._pair_lab = jnp.asarray(np.concatenate(labs))
        self._pair_mask = jnp.asarray(np.concatenate(masks))
        self._pair_inv_max_dcg = jnp.asarray(np.concatenate(invs))
        self._inv_max_dcg_np = inv
        self._gain_table = jnp.asarray(self.label_gain, jnp.float32)

        # pairs with label_i > label_j: per query (n^2 - sum_l c_l^2) / 2
        # over its label counts c_l
        lo = int(lab.min(initial=0))
        counts = np.bincount(qid * (int(lab.max(initial=0)) - lo + 1)
                             + (lab - lo)).astype(np.int64)
        self.rank_counters = RankCounters(
            queries=len(sizes), docs=int(qb[-1]), max_docs=self.max_docs,
            buckets=tuple(per_bucket),
            slots=sum(nb * Qb * D for nb, Qb, D in shapes),
            pair_slots=sum(nb * Qb * D * D for nb, Qb, D in shapes),
            valid_pairs=int((np.sum(sizes.astype(np.int64) ** 2)
                             - np.sum(counts ** 2)) // 2))

    def bucket_layout(self):
        """The pair layout by bucket: (gather, lab, mask [nb, Qb, D],
        inv_max_dcg [nb, Qb]) views of the flat arrays."""
        out, doc, query = [], 0, 0
        for nb, Qb, D in self._bucket_shapes:
            docs, queries = nb * Qb * D, nb * Qb
            gather, lab, mask = (
                a[doc:doc + docs].reshape(nb, Qb, D)
                for a in (self._pair_gather, self._pair_lab, self._pair_mask))
            inv = self._pair_inv_max_dcg[query:query + queries]
            out.append((gather, lab, mask, inv.reshape(nb, Qb)))
            doc, query = doc + docs, query + queries
        return out

    def get_gradients(self, score):
        grad = jnp.zeros(self.num_data, jnp.float32)
        hess = jnp.zeros(self.num_data, jnp.float32)
        for gather, lab, mask, inv in self.bucket_layout():
            g, h = _lambdarank_bucket_grads(
                score, gather, lab, mask, inv, self._gain_table, self.sigmoid)
            # XLA merges the buckets' scatters into zeros through these
            # sums and names the merged operation after the sum
            with telemetry.scope("lgbm/gradients/rank_scatter"):
                grad = grad + g
                hess = hess + h
        if self.weights is not None:
            grad = grad * self.weights
            hess = hess * self.weights
        return grad, hess


_OBJECTIVE_REGISTRY = {
    "regression": RegressionL2,
    "regression_l2": RegressionL2,
    "mean_squared_error": RegressionL2,
    "mse": RegressionL2,
    "l2": RegressionL2,
    "l2_root": RegressionL2,
    "rmse": RegressionL2,
    "regression_l1": RegressionL1,
    "l1": RegressionL1,
    "mean_absolute_error": RegressionL1,
    "mae": RegressionL1,
    "huber": RegressionHuber,
    "fair": RegressionFair,
    "poisson": RegressionPoisson,
    "binary": BinaryLogloss,
    "multiclass": MulticlassSoftmax,
    "softmax": MulticlassSoftmax,
    "multiclassova": MulticlassOVA,
    "multiclass_ova": MulticlassOVA,
    "ova": MulticlassOVA,
    "ovr": MulticlassOVA,
    "xentropy": CrossEntropy,
    "cross_entropy": CrossEntropy,
    "xentlambda": CrossEntropyLambda,
    "cross_entropy_lambda": CrossEntropyLambda,
    "lambdarank": LambdarankNDCG,
}


def create_objective(config: Config) -> Optional[ObjectiveFunction]:
    """Factory (reference: ObjectiveFunction::CreateObjectiveFunction,
    objective_function.cpp:10-36). Returns None for objective='none'
    (custom-objective training)."""
    name = config.objective
    if name in ("none", "null", "custom", ""):
        return None
    if name not in _OBJECTIVE_REGISTRY:
        log.fatal("Unknown objective type name: %s" % name)
    cls = _OBJECTIVE_REGISTRY[name]
    try:
        return cls(config)
    except TypeError:
        return cls()
