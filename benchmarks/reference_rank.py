"""The plain reference of the ranking cell, and its control.

`reference.Reference` (one histogram GBDT step from the raw rows, in
`jax.numpy` float32 with every contraction at `Precision.HIGHEST` and the
small arithmetic in float64 on the host) with the logistic gradients
replaced by lambdarank's, and the log-loss by NDCG@10. It imports nothing
of the program.

The equations (LightGBM v2.0 `rank_objective.hpp:83-160`,
`dcg_calculator.cpp`), per query, with s the reference's own score:

- position pos_i of document i: its place in a STABLE descending sort of
  the scores, ties by the document's place in the query. (A departure:
  the C++ sorts with an unstable sort; the program's rule is the stable
  one, and from a zero score every position is decided by it.)
- discount disc_i = 1 / log2(pos_i + 2); gain_i = 2^label_i - 1;
  inv = 1 / (the largest DCG the query's labels allow in its first
  `max_position` = 20 places), 0 where that is 0.
- for every pair with label_i > label_j:
  dNDCG = (gain_i - gain_j) |disc_i - disc_j| inv, divided by
  (0.01 + |s_i - s_j|) where the query's best and worst scores differ;
  p = 2 / (1 + exp(2 sigma (s_i - s_j))), sigma = 1;
  lambda_i -= dNDCG p, lambda_j += dNDCG p, and both hessians
  += 2 dNDCG p (2 - p).

Computed its own way, with no sort, no bucket and no scatter: queries are
runs of consecutive rows, so every partner of a row lies within
`max_docs - 1` rows of it. A tile of TILE consecutive rows is set against
the window of rows that holds all their partners; pos_i is the COUNT of
the query's documents that come before i (a higher score, or the same
score and an earlier row), which is the stable sort's position; then each
row's lambda and hessian are sums over its window of the pair terms
above, signed by which of the two has the higher label. Every valid pair
of every query, exact `exp`.

From those gradients a step is followed exactly as `reference.py` follows
one (rows routed by the real thresholds, per-leaf histograms, best split
and the chosen split's gain, leaf values, the score after the step); the
control is `reference.py`'s (gradients rounded once to bfloat16 before
the contraction). Numbers compared: `reference.COMPARED` with `loss_gap`
replaced by `ndcg_gap` (training NDCG@10 over all queries, a query with
no relevant document counting 1, the program's score against the
reference's, relative), plus `lambda_gap` and `hess_gap`
(`gradient_gaps`): the program's own gradients at a score against the
reference's at the same score, worst document over the largest |lambda|
(hessian) of its query.
"""
from __future__ import annotations

import numpy as np

import reference

TILE = 1024       # rows set against their window at a time
EVAL_AT = 10      # NDCG@10
COMPARED = tuple("ndcg_gap" if k == "loss_gap" else k
                 for k in reference.COMPARED)


class RankReference(reference.Reference):
    """`sizes[q]`: rows of query q, consecutive, every one at least 1."""

    def __init__(self, X, y, sizes, cuts, *, sigmoid: float = 1.0,
                 max_position: int = 20, label_gain=None, **kw):
        import jax
        import jax.numpy as jnp
        super().__init__(X, y, cuts, **kw)
        sizes = np.asarray(sizes, np.int64)
        if sizes.min() < 1 or sizes.sum() != self.n:
            raise ValueError("queries must be non-empty and cover the rows")
        self.sigmoid = float(sigmoid)
        self.sizes = sizes
        self.queries = len(sizes)
        self.max_docs = int(sizes.max())
        self.starts = np.cumsum(sizes) - sizes
        lab = np.asarray(y).astype(np.int64)
        table = (2.0 ** np.arange(31) - 1.0 if label_gain is None
                 else np.asarray(label_gain, np.float64))
        gain = table[lab]
        # the largest DCG in the first k places: each query's gains in
        # descending order against the discounts
        qid = np.repeat(np.arange(self.queries), sizes)
        place = np.arange(self.n) - self.starts[qid]
        ideal = gain[np.lexsort((-lab, qid))] / np.log2(place + 2.0)

        def inv_max_dcg(k):
            dcg = np.add.reduceat(np.where(place < k, ideal, 0.0),
                                  self.starts)
            return np.where(dcg > 0, 1.0 / np.maximum(dcg, 1e-300), 0.0)

        inv_eval = inv_max_dcg(EVAL_AT)
        self.no_gain_queries = int((inv_eval == 0).sum())
        # per row, so that a tile reads its query's facts by a slice
        self.y = {
            "label": jnp.asarray(lab.astype(np.float32)),
            "gain": jnp.asarray(gain.astype(np.float32)),
            "first": jnp.asarray(self.starts[qid].astype(np.int32)),
            "last": jnp.asarray((self.starts + sizes)[qid].astype(np.int32)),
            "inv": jnp.asarray(inv_max_dcg(int(max_position))[qid]
                               .astype(np.float32)),
            "inv_eval": jnp.asarray(inv_eval[qid].astype(np.float32)),
        }
        self._grads = jax.jit(self._rank_gradients)
        self._hist = jax.jit(self._histogram_pass)
        self._pass = self._rank_pass

    # -- the step's gradients are lambdarank's -------------------------
    def _rank_pass(self, X, y, score, *tables):
        """`Reference._histogram_pass` on precomputed gradients: it slices
        its `y` and `score` arguments by row block and asks `_gradients`
        for the block's (g, h); here they ARE g and h."""
        g, h = self._grads(score, y)
        return self._hist(X, g, h, *tables)

    @staticmethod
    def _gradients(h_block, g_block):
        return g_block, h_block

    # -- device side, plain jax.numpy -----------------------------------
    def _tiles(self, b):
        """Tile b and its window: (first row, rows), (first row, rows)."""
        import jax.numpy as jnp
        n, reach = self.n, self.max_docs - 1
        T = min(TILE, n)
        W = min(T + 2 * reach, n)
        a = jnp.minimum(b * T, n - T)            # the last tile overlaps
        return (a, T), (jnp.clip(a - reach, 0, n - W), W)

    def _pairs(self, b, y, score):
        """What both passes share for tile b: the slicer of per-row arrays
        (rows [T, 1], window [1, W]) and `same`, [T, W], true where the
        window's row is a document of the tile row's query."""
        import jax
        import jax.numpy as jnp
        (a, T), (w0, W) = self._tiles(b)

        def rows(v):
            return jax.lax.dynamic_slice(v, (a,), (T,))[:, None]

        def window(v):
            return jax.lax.dynamic_slice(v, (w0,), (W,))[None, :]

        gi = a + jnp.arange(T, dtype=jnp.int32)[:, None]
        gj = w0 + jnp.arange(W, dtype=jnp.int32)[None, :]
        same = (gj >= rows(y["first"])) & (gj < rows(y["last"]))
        return a, rows, window, gi, gj, same, rows(score), window(score)

    def _positions(self, score, y):
        """(pos, best, worst) of every row: its place in its query by
        descending score, ties by row; its query's best and worst score."""
        import jax
        import jax.numpy as jnp

        def tile(b, carry):
            a, _, _, gi, gj, same, si, sj = self._pairs(b, y, score)
            ahead = same & ((sj > si) | ((sj == si) & (gj < gi)))
            new = (jnp.sum(ahead, axis=1).astype(jnp.float32),
                   jnp.max(jnp.where(same, sj, -jnp.inf), axis=1),
                   jnp.min(jnp.where(same, sj, jnp.inf), axis=1))
            return tuple(jax.lax.dynamic_update_slice(old, v, (a,))
                         for old, v in zip(carry, new))

        zero = jnp.zeros((self.n,), jnp.float32)
        return jax.lax.fori_loop(0, -(-self.n // min(TILE, self.n)), tile,
                                 (zero, zero, zero))

    def _rank_gradients(self, score, y):
        import jax
        import jax.numpy as jnp
        pos, best, worst = self._positions(score, y)
        disc = 1.0 / jnp.log2(pos + 2.0)
        spread = (best != worst)
        sigma = jnp.float32(self.sigmoid)

        def tile(b, carry):
            a, rows, window, _, _, same, si, sj = self._pairs(b, y, score)
            sign = jnp.sign(rows(y["label"]) - window(y["label"]))
            ds = si - sj
            dndcg = (jnp.abs(rows(y["gain"]) - window(y["gain"]))
                     * jnp.abs(rows(disc) - window(disc)) * rows(y["inv"]))
            dndcg = jnp.where(rows(spread), dndcg / (0.01 + jnp.abs(ds)),
                              dndcg)
            # sign * ds: the higher-labelled document's score less the other's
            p = 2.0 / (1.0 + jnp.exp(2.0 * sigma * sign * ds))
            pair = same & (sign != 0)
            new = (jnp.sum(jnp.where(pair, -sign * dndcg * p, 0.0), axis=1),
                   jnp.sum(jnp.where(pair, 2.0 * dndcg * p * (2.0 - p), 0.0),
                           axis=1))
            return tuple(jax.lax.dynamic_update_slice(old, v, (a,))
                         for old, v in zip(carry, new))

        zero = jnp.zeros((self.n,), jnp.float32)
        return jax.lax.fori_loop(0, -(-self.n // min(TILE, self.n)), tile,
                                 (zero, zero))

    def _loss_blocks(self, score, y):
        """In the log-loss's place: sums whose total is the sum over
        queries of NDCG@10 (a query with no relevant document counts 1)."""
        import jax.numpy as jnp
        pos, _, _ = self._positions(score, y)
        per_row = jnp.where(pos < EVAL_AT,
                            y["gain"] * y["inv_eval"] / jnp.log2(pos + 2.0),
                            0.0)
        pad = (-per_row.shape[0]) % 4096
        return jnp.concatenate([
            jnp.pad(per_row, (0, pad)).reshape(-1, 4096).sum(axis=1),
            jnp.full((1,), self.no_gain_queries, jnp.float32)])

    # -- host side -------------------------------------------------------
    def follow(self, tree: dict, program_score: np.ndarray) -> dict:
        out = super().follow(tree, program_score)
        per_query = self.n / self.queries      # follow() divided by rows
        out["ndcg_ref"] = out.pop("loss_ref") * per_query
        out["ndcg_program"] = out.pop("loss_program") * per_query
        out["ndcg_gap"] = out.pop("loss_gap")
        if "ctl_loss_gap" in out:
            out["ctl_ndcg_gap"] = out.pop("ctl_loss_gap")
        return out

    def gradient_gaps(self, program_grad, program_hess, score) -> dict:
        """The program's (lambda, hessian) of every row at `score` against
        the reference's at the same score: the worst document's
        difference over the largest |lambda| (hessian) of its query; a
        query whose reference gradients are all 0 must read 0."""
        import jax.numpy as jnp
        mine = self._grads(jnp.asarray(np.asarray(score, np.float32)), self.y)
        out = {}
        for name, theirs, ref in zip(("lambda_gap", "hess_gap"),
                                     (program_grad, program_hess), mine):
            ref = np.asarray(ref, np.float64)
            diff = np.abs(np.asarray(theirs, np.float64)[:self.n] - ref)
            scale = np.repeat(np.maximum.reduceat(np.abs(ref), self.starts),
                              self.sizes)
            with np.errstate(divide="ignore", invalid="ignore"):
                gap = np.where(scale > 0, diff / scale,
                               np.where(diff > 0, np.inf, 0.0))
            out[name] = float(gap.max())
        return out


def worst_over_steps(per_step, prefix: str = "") -> dict:
    """The number compared is the worst of the followed steps."""
    return {k: max(step[prefix + k] for step in per_step)
            for k in COMPARED if prefix + k in per_step[0]}
