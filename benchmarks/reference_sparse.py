"""The plain reference of the sparse one-hot cell, and its control.

`reference.Reference` (one histogram GBDT step in `jax.numpy` float32
with every contraction at `Precision.HIGHEST`, the small arithmetic in
float64 on the host, nothing imported from the program) for a table that
cannot be held as raw rows: 25M x 700 float32 is 70 GB. It is given the
table the way it was before anybody one-hot coded it: the `[rows, 8]`
category CODES and, for every one-hot column, which categorical and
which of its values it stands for. It never sees a stored group, a
bundle or the CSR matrix.

- a row's raw value of one-hot feature j is `codes[:, c_j] == v_j`, and
  rows are routed through a tree by the program's real-valued thresholds
  on that value;
- one pass over the rows contracts, per leaf, the sums of (gradient,
  hessian, count) by CODE: a `[rows, 700]` one-hot of the eight code
  columns, in the categoricals' own order, against the leaves' channels;
- every one-hot feature's two-bin histogram is read off them: its value
  bin is its code's cell, its zero bin the leaf's total less it. Which
  of the dataset layer's bins holds a 0 and which a 1 is decided here,
  from the cut points, as `reference.py` bins raw floats;
- from there on a step is followed as `reference.py` follows one (best
  split by the published gain, the chosen split's gain, leaf values, the
  score after the step), and the control is `reference.py`'s (gradients
  and hessians rounded once to bfloat16 before the contraction).

Numbers: `reference.COMPARED`'s tree numbers and, for the dataset layer,
`bin_count_mismatch` (features whose number of bins is not 2) and
`bin_pop_mismatch` (features whose nonzero bin, by the cut points, does
not hold exactly the rows the column is nonzero in). `bundle_lost_values`
(below) is the third: the stored matrix decoded by the program's own
group layout against the CSR matrix's entries.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

import reference
from reference import BINS

COMPARED = ("bin_count_mismatch", "bin_pop_mismatch", "count_mismatch",
            "split_gap", "gain_gap", "leaf_gap", "score_gap", "loss_gap")
ROW_BLOCK = 32768


def bins_of(cuts, values) -> np.ndarray:
    """The bin of each value by increasing upper bounds `cuts` (the last
    +inf): x is in bin k iff cuts[k-1] < x <= cuts[k]."""
    cuts = np.asarray(cuts, np.float64)
    return np.minimum(np.searchsorted(cuts, values, side="left"),
                      len(cuts) - 1)


class SparseReference(reference.Reference):
    """Holds the codes on the device and its own running score."""

    def __init__(self, codes, column_map, y, cuts, *, num_leaves: int,
                 learning_rate: float, min_sum_hessian_in_leaf: float,
                 min_data_in_leaf: int, lambda_l2: float = 0.0,
                 control: bool = False):
        import jax
        import jax.numpy as jnp
        codes = np.asarray(codes)
        self.n = int(codes.shape[0])
        self.cards = tuple(int(k) for k in column_map["cards"])
        self.cat = np.asarray(column_map["categorical"], np.int32)
        self.val = np.asarray(column_map["value"], np.int32)
        self.f = int(self.cat.shape[0])
        self.L = int(num_leaves)
        self.lr = float(learning_rate)
        self.min_hess = float(min_sum_hessian_in_leaf)
        self.min_data = max(int(min_data_in_leaf), 1)
        self.l2 = float(lambda_l2)
        self.max_bin = 2          # what a one-hot column has
        self.control = bool(control)
        self.C = 5 if control else 3
        self.cuts = [np.asarray(c, np.float64) for c in cuts]
        self.row_block = min(ROW_BLOCK, self.n)
        # the cell of feature j in the categoricals' own order, and the
        # bins its two raw values fall in
        starts = np.concatenate([[0], np.cumsum(self.cards)[:-1]])
        self.cell = (starts[self.cat] + self.val).astype(np.int32)
        self.zero_bin = np.asarray([bins_of(c, 0.0) for c in self.cuts],
                                   np.int32)
        self.one_bin = np.asarray([bins_of(c, 1.0) for c in self.cuts],
                                  np.int32)
        # rows each column is nonzero in, from the codes
        nonzero = np.concatenate([
            np.bincount(codes[:, c].astype(np.int64), minlength=k)[:k]
            for c, k in enumerate(self.cards)])[self.cell]
        in_one_bin = np.where(self.one_bin != self.zero_bin, nonzero, self.n)
        self.bin_pop_mismatch = int((in_one_bin != nonzero).sum())
        self.X = jnp.asarray(codes.astype(np.int32))
        self.y = jnp.asarray(y)
        self.lower = self.upper = jnp.zeros((), jnp.float32)   # not read
        self.score = jnp.zeros((self.n,), jnp.float32)
        self._cells = jax.jit(self._cell_pass)
        self._after = jax.jit(self._apply_and_compare)
        self._add = jax.jit(self._add_tree)

    def _member(self, x, feat, thr, path, depth):
        """`reference.Reference._member` on the raw one-hot values of the
        tree's split features, formed from the codes `x` [B, 8]."""
        import jax.numpy as jnp
        cat, val = jnp.asarray(self.cat), jnp.asarray(self.val)
        raw = jnp.take(x, cat[feat], axis=1) == val[feat][None, :]
        return reference.Reference._member(
            raw.astype(jnp.float32), jnp.arange(feat.shape[0]), thr, path,
            depth)

    def _pass(self, *args):
        """What `reference.Reference.follow` asks of a pass: per leaf the
        [f, BINS] histograms, and every row's leaf. The sums by code come
        from the device; a feature's two bins are read off them here, in
        float64."""
        cells, leaf = self._cells(*args)
        cells = np.asarray(cells).astype(np.float64)
        # a leaf's total: every row has one month (any categorical would do)
        total = cells[:self.cards[0]].sum(axis=0)
        value = cells[self.cell]                               # [f, L*C]
        feats = np.arange(self.f)
        acc = np.zeros((self.f, BINS, cells.shape[1]), np.float64)
        acc[feats, self.zero_bin] += total[None, :] - value
        acc[feats, self.one_bin] += value
        return acc.reshape(self.f * BINS, -1), leaf

    def _cell_pass(self, codes, y, score, lower, upper, feat, thr, path,
                   depth):
        import jax
        import jax.numpy as jnp
        n, L, C, B = self.n, self.L, self.C, self.row_block
        hi = jax.lax.Precision.HIGHEST

        def row_block(b, carry):
            acc, leaf_all = carry
            start = jnp.minimum(b * B, n - B)
            idx = start + jnp.arange(B, dtype=jnp.int32)
            fresh = (idx >= b * B).astype(jnp.float32)  # last block overlaps
            x = jax.lax.dynamic_slice(codes, (start, 0), (B, len(self.cards)))
            yb = jax.lax.dynamic_slice(y, (start,), (B,))
            sb = jax.lax.dynamic_slice(score, (start,), (B,))
            g, h = self._gradients(sb, yb)
            member = self._member(x, feat, thr, path, depth)
            leaf = jnp.argmax(member, axis=1).astype(jnp.int32)
            chans = [g, h, jnp.ones_like(g)]
            if C == 5:
                chans += [jax.lax.reduce_precision(g, 8, 7),
                          jax.lax.reduce_precision(h, 8, 7)]
            ch = jnp.stack(chans, axis=1) * fresh[:, None]          # [B, C]
            W = (member[:, :, None] * ch[:, None, :]).reshape(B, L * C)
            by_code = jnp.concatenate(
                [(x[:, c:c + 1] == jnp.arange(k, dtype=x.dtype)[None, :])
                 for c, k in enumerate(self.cards)], axis=1)        # [B, 700]
            acc = acc + jnp.einsum("bm,bc->mc", by_code.astype(jnp.float32),
                                   W, precision=hi)
            leaf_all = jax.lax.dynamic_update_slice(leaf_all, leaf, (start,))
            return acc, leaf_all

        cells0 = jnp.zeros((sum(self.cards), L * C), jnp.float32)
        leaf0 = jnp.zeros((n,), jnp.int32)
        return jax.lax.fori_loop(0, -(-n // B), row_block, (cells0, leaf0))

    def _add_tree(self, codes, score, feat, thr, path, depth, values):
        import jax
        import jax.numpy as jnp
        n, B = self.n, self.row_block

        def row_block(b, score):
            start = jnp.minimum(b * B, n - B)
            idx = start + jnp.arange(B, dtype=jnp.int32)
            x = jax.lax.dynamic_slice(codes, (start, 0), (B, len(self.cards)))
            sb = jax.lax.dynamic_slice(score, (start,), (B,))
            member = self._member(x, feat, thr, path, depth)
            add = jnp.sum(jnp.where(member, values[None, :], 0.0), axis=1)
            sb = sb + jnp.where(idx >= b * B, add, 0.0)  # last block overlaps
            return jax.lax.dynamic_update_slice(score, sb, (start,))

        return jax.lax.fori_loop(0, -(-n // B), row_block, score)

    def follow(self, tree: dict, program_score: np.ndarray) -> dict:
        out = super().follow(tree, program_score)
        out.pop("bin_pop_gap")       # equal-count bins: not this table's
        out["bin_pop_mismatch"] = self.bin_pop_mismatch
        return out


def bundle_lost_values(csr, binned, layout: dict, cuts,
                       chunk_rows: int = 1 << 20) -> int:
    """Stored entries of `csr` (nonzero ones) that the program's stored
    matrix does not give back. `binned` [rows, groups] is decoded by the
    program's own layout (`layout`: for every used feature its column
    `used`, its `group`, its `offset` in the group, `bundled`, `num_bin`):
    a bundled feature's bin is the stored value less its offset where
    that lies in its `num_bin` bins, else its default (zero) bin; the bin
    the entry's value falls in is decided here, from `cuts`. Exact, over
    all rows."""
    n, f = csr.shape
    slot = np.full(f, -1, np.int64)
    slot[np.asarray(layout["used"], np.int64)] = np.arange(len(layout["used"]))
    group, offset, bundled, num_bin = (
        np.asarray(layout[k]) for k in ("group", "offset", "bundled",
                                        "num_bin"))

    def lost_in(lo):
        csc = csr[lo:lo + chunk_rows].tocsc()
        block = binned[lo:lo + chunk_rows]
        lost = 0
        for col in np.flatnonzero(np.diff(csc.indptr)):
            a, b = csc.indptr[col], csc.indptr[col + 1]
            rows, vals = csc.indices[a:b], csc.data[a:b]
            rows, vals = rows[vals != 0], vals[vals != 0]
            u = slot[col]
            if u < 0:                 # a column the program does not keep
                lost += len(rows)
                continue
            stored = block[rows, group[u]].astype(np.int64)
            if bundled[u]:
                own = stored - offset[u]
                stored = np.where((own >= 0) & (own < num_bin[u]), own,
                                  bins_of(cuts[col], 0.0))
            lost += int((stored != bins_of(cuts[col], vals)).sum())
        return lost

    with ThreadPoolExecutor(max_workers=8) as pool:
        return int(sum(pool.map(lost_in, range(0, n, chunk_rows))))


def worst_over_steps(per_step, prefix: str = "") -> dict:
    """The number compared is the worst of the followed steps."""
    return {k: max(step[prefix + k] for step in per_step)
            for k in COMPARED if prefix + k in per_step[0]}
