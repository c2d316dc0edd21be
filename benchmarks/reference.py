"""The plain reference of the training cells, and their control.

A straightforward histogram GBDT step in `jax.numpy` float32 with every
contraction at `Precision.HIGHEST` and the small arithmetic in float64 on
the host. It imports nothing of the program. It follows the program's
first steps the way a served model's reference follows served tokens:
the program's ANSWERS (which feature and threshold each node split on,
the leaf values, the row counts, the score after the step) are judged
against what the reference computes from the raw rows, the labels and
its own running score:

- rows are routed through the tree by the real-valued thresholds on the
  raw float features (not on anybody's bins), so every node's row count
  is the reference's own;
- gradients and hessians are the logistic ones of the reference's own
  score (which it updates with its own leaf values, never the program's);
- one pass over the rows contracts a per-leaf (feature, bin) histogram of
  (gradient, hessian, count); node histograms are sums of leaves;
- from them: each node's best split over every feature and threshold
  (the published gain, GL^2/HL + GR^2/HR - G^2/H, under
  min_sum_hessian_in_leaf and min_data_in_leaf), the gain of the split
  the program chose, the leaf values -G/H * learning_rate.

The only table it is given are the bin cut points that the dataset layer
chose in set-up: they define WHICH thresholds are candidates, as a
tokenizer defines which tokens exist. Rows are binned against them here,
from the raw floats, and the table itself is held to what the
configuration states (`bin_readings`): every feature has `max_bin` bins,
and their populations, counted here over all the raw rows, are the
equal-count ones of the documented rule (one bin for |x| <= 1e-35 where a
feature has values on both sides of zero, the rest of the rows shared
equally by the other bins). A bin finder that returns fewer or coarser
bins fails those two numbers.

The control (`control=True`) is this same reference put in the program's
place with the contraction one precision lower than the configuration
states (gradients and hessians rounded once to bfloat16, where the
program contracts bf16 hi+lo digits): at every node it reads the split a
single-bf16 histogram puts first, the gain it would report and the leaf
values it would write, and they are judged like the program's.
"""
from __future__ import annotations

import numpy as np

BINS = 64  # slots per feature; max_bin=63 gives at most 63 bins
ZERO = 1e-35  # the documented zero bin: |x| <= 1e-35 (kZeroThreshold)


def bin_readings(cuts, populations, max_bin: int) -> dict:
    """The dataset layer's table against what the configuration states.
    cuts[j]: feature j's increasing upper bounds; populations[j][k]: rows
    of the raw data that fall in bin k (counted by the reference).
    `bin_count_mismatch`: features whose number of bins is not `max_bin`.
    `bin_pop_gap`: the worst feature's root mean square, over its bins, of
    (population - the equal-count population) / (rows / max_bin); a zero
    bin expects the rows it holds, every other bin the share of the rest
    that `max_bin` bins would give it (so coarser bins read about 1 per
    halving, whatever their number)."""
    mismatch, worst = 0, 0.0
    for c, pop in zip(cuts, populations):
        c = np.asarray(c, np.float64)
        pop = np.asarray(pop, np.float64)[:len(c)]
        mismatch += int(len(c) != max_bin)
        lower = np.concatenate([[-np.inf], c[:-1]])
        zero = (c <= ZERO) & (lower >= -ZERO)
        n = pop.sum()
        expect = np.where(zero, pop, (n - pop[zero].sum())
                          / max(max_bin - int(zero.sum()), 1))
        gap = float(np.sqrt(np.mean(((pop - expect) / (n / max_bin)) ** 2)))
        worst = max(worst, gap)
    return {"bin_count_mismatch": mismatch, "bin_pop_gap": worst}


def floor_f32(a) -> np.ndarray:
    """Largest float32 <= a, so `x <= a` is decided exactly in float32."""
    a = np.asarray(a, np.float64)
    f = a.astype(np.float32)
    over = f.astype(np.float64) > a
    f[over] = np.nextafter(f[over], np.float32(-np.inf))
    return f


def cut_tables(cuts, features: int):
    """cuts[j]: increasing upper bounds of feature j's bins, the last one
    +inf. Returns (lower, upper) float32 [features, BINS]: a value x is in
    bin k of feature j iff lower[j, k] < x <= upper[j, k]."""
    upper = np.full((features, BINS), np.inf, np.float32)
    for j in range(features):
        c = np.asarray(cuts[j], np.float64)
        if len(c) > BINS:
            raise ValueError(f"feature {j} has {len(c)} bins > {BINS}")
        upper[j, :len(c)] = floor_f32(c)
    lower = np.concatenate(
        [np.full((features, 1), -np.inf, np.float32), upper[:, :-1]], axis=1)
    return lower, upper


def _block_sizes(rows: int, features: int):
    """Rows per block and features per inner block: the float32 one-hot
    of one (row block, feature block) stays near 512 MB."""
    budget = 512 * 1024 * 1024 // 4
    row_block = 65536 if features <= 64 else 8192
    row_block = min(row_block, rows)
    fb = max(1, min(features, budget // (row_block * BINS)))
    while features % fb:
        fb -= 1
    return row_block, fb


class Reference:
    """Holds the raw rows on the device and its own running score."""

    def __init__(self, X, y, cuts, *, num_leaves: int, learning_rate: float,
                 min_sum_hessian_in_leaf: float, min_data_in_leaf: int,
                 lambda_l2: float = 0.0, max_bin: int = BINS - 1,
                 control: bool = False):
        import jax
        import jax.numpy as jnp
        self.n, self.f = X.shape
        self.L = int(num_leaves)
        self.lr = float(learning_rate)
        self.min_hess = float(min_sum_hessian_in_leaf)
        self.min_data = max(int(min_data_in_leaf), 1)
        self.l2 = float(lambda_l2)
        self.max_bin = int(max_bin)
        self.control = bool(control)
        self.C = 5 if control else 3
        self.cuts = [np.asarray(c, np.float64) for c in cuts]
        lower, upper = cut_tables(self.cuts, self.f)
        self.row_block, self.feat_block = _block_sizes(self.n, self.f)
        self.X = jnp.asarray(X)
        self.y = jnp.asarray(y)
        self.lower = jnp.asarray(lower)
        self.upper = jnp.asarray(upper)
        self.score = jnp.zeros((self.n,), jnp.float32)  # binary: starts at 0
        self._pass = jax.jit(self._histogram_pass)
        self._after = jax.jit(self._apply_and_compare)
        self._add = jax.jit(self._add_tree)

    # -- device side, plain jax.numpy ---------------------------------
    @staticmethod
    def _gradients(score, y):
        import jax.numpy as jnp
        lv = jnp.where(y > 0, 1.0, -1.0).astype(jnp.float32)
        resp = -lv / (1.0 + jnp.exp(lv * score))
        a = jnp.abs(resp)
        return resp, a * (1.0 - a)

    @staticmethod
    def _member(x, feat, thr, path, depth):
        """[B, L] one-hot of every row's leaf, by the real-valued
        thresholds. Every node's decision for every row at once
        (x[:, feat[j]] <= thr[j], as +1 / -1), times `path` (+1 where a
        leaf lies under the node's left child, -1 under its right, else
        0): a row is in the leaf whose every ancestor agrees, where the
        sum equals the leaf's depth. Small whole numbers, exact in any
        float. (Following each row down the tree by gathers took 10 s a
        tree at 31.5M rows on the chip.)"""
        import jax
        import jax.numpy as jnp
        went = jnp.where(jnp.take(x, feat, axis=1) <= thr[None, :], 1.0, -1.0)
        agree = jnp.matmul(went.astype(jnp.float32), path,
                           precision=jax.lax.Precision.HIGHEST)
        return agree == depth[None, :]

    def _histogram_pass(self, X, y, score, lower, upper, feat, thr, path,
                        depth):
        import jax
        import jax.numpy as jnp
        n, f, L, C = self.n, self.f, self.L, self.C
        B, FB = self.row_block, self.feat_block
        n_blocks = -(-n // B)
        hi = jax.lax.Precision.HIGHEST

        def row_block(b, carry):
            acc, leaf_all = carry
            start = jnp.minimum(b * B, n - B)
            idx = start + jnp.arange(B, dtype=jnp.int32)
            fresh = (idx >= b * B).astype(jnp.float32)  # last block overlaps
            x = jax.lax.dynamic_slice(X, (start, 0), (B, f))
            yb = jax.lax.dynamic_slice(y, (start,), (B,))
            sb = jax.lax.dynamic_slice(score, (start,), (B,))
            g, h = self._gradients(sb, yb)
            member = self._member(x, feat, thr, path, depth)
            leaf = jnp.argmax(member, axis=1).astype(jnp.int32)
            chans = [g, h, jnp.ones_like(g)]
            if C == 5:
                # reduce_precision, not astype(bfloat16).astype(float32):
                # XLA:TPU drops that round trip as excess precision (the
                # control read 0 everywhere on the chip, PR 25)
                chans += [jax.lax.reduce_precision(g, 8, 7),
                          jax.lax.reduce_precision(h, 8, 7)]
            ch = jnp.stack(chans, axis=1) * fresh[:, None]          # [B, C]
            W = (member[:, :, None] * ch[:, None, :]).reshape(B, L * C)

            def feat_block(k, acc):
                xf = jax.lax.dynamic_slice(x, (0, k * FB), (B, FB))
                lo = jax.lax.dynamic_slice(lower, (k * FB, 0), (FB, BINS))
                up = jax.lax.dynamic_slice(upper, (k * FB, 0), (FB, BINS))
                onehot = ((xf[:, :, None] > lo[None]) &
                          (xf[:, :, None] <= up[None])).astype(jnp.float32)
                part = jnp.einsum("bm,bc->mc", onehot.reshape(B, FB * BINS),
                                  W, precision=hi)
                old = jax.lax.dynamic_slice(acc, (k * FB * BINS, 0),
                                            (FB * BINS, L * C))
                return jax.lax.dynamic_update_slice(
                    acc, old + part, (k * FB * BINS, 0))

            acc = jax.lax.fori_loop(0, f // FB, feat_block, acc)
            leaf_all = jax.lax.dynamic_update_slice(leaf_all, leaf, (start,))
            return acc, leaf_all

        acc0 = jnp.zeros((f * BINS, L * C), jnp.float32)
        leaf0 = jnp.zeros((n,), jnp.int32)
        return jax.lax.fori_loop(0, n_blocks, row_block, (acc0, leaf0))

    def _add_tree(self, X, score, feat, thr, path, depth, values):
        """score + values[leaf of every row], rows routed block by block."""
        import jax
        import jax.numpy as jnp
        n, f, B = self.n, self.f, self.row_block

        def row_block(b, score):
            start = jnp.minimum(b * B, n - B)
            idx = start + jnp.arange(B, dtype=jnp.int32)
            x = jax.lax.dynamic_slice(X, (start, 0), (B, f))
            sb = jax.lax.dynamic_slice(score, (start,), (B,))
            member = self._member(x, feat, thr, path, depth)
            add = jnp.sum(jnp.where(member, values[None, :], 0.0), axis=1)
            sb = sb + jnp.where(idx >= b * B, add, 0.0)  # last block overlaps
            return jax.lax.dynamic_update_slice(score, sb, (start,))

        return jax.lax.fori_loop(0, -(-n // B), row_block, score)

    @staticmethod
    def _loss_blocks(score, y):
        import jax.numpy as jnp
        lv = jnp.where(y > 0, 1.0, -1.0).astype(jnp.float32)
        per_row = jnp.logaddexp(0.0, -lv * score)
        pad = (-per_row.shape[0]) % 4096
        return jnp.pad(per_row, (0, pad)).reshape(-1, 4096).sum(axis=1)

    def _apply_and_compare(self, score, y, leaf, values, program_score,
                           other_values):
        import jax.numpy as jnp
        new = score + values[leaf]
        other = score + other_values[leaf]
        return (new, jnp.max(jnp.abs(program_score - new)),
                self._loss_blocks(new, y), self._loss_blocks(program_score, y),
                jnp.max(jnp.abs(other - new)), self._loss_blocks(other, y))

    # -- host side, float64 -------------------------------------------
    def _gains(self, hist, g_ch, h_ch, margin):
        """[F, BINS] gain of every (feature, threshold) of one node from
        channels (g_ch, h_ch) of its histogram; -inf where a side breaks
        min_sum_hessian (by `margin`, relative) or min_data_in_leaf."""
        cg = np.cumsum(hist[:, :, g_ch], axis=1)
        chh = np.cumsum(hist[:, :, h_ch], axis=1)
        cn = np.cumsum(hist[:, :, 2], axis=1)
        G, H, N = cg[:, -1:], chh[:, -1:], cn[:, -1:]
        rg, rh, rn = G - cg, H - chh, N - cn
        need = self.min_hess * (1.0 + margin)
        ok = ((chh >= need) & (rh >= need)
              & (cn >= self.min_data) & (rn >= self.min_data))
        with np.errstate(divide="ignore", invalid="ignore"):
            gains = cg * cg / (chh + self.l2) + rg * rg / (rh + self.l2)
        parent = float(G[0, 0] ** 2 / (H[0, 0] + self.l2))
        return np.where(ok, gains, -np.inf), parent

    def _tables(self, tree: dict):
        """The tree's routing tables, padded to the reference's size."""
        import jax.numpy as jnp
        L = self.L
        m = int(len(tree["leaf_value"])) - 1
        if m + 1 > L:
            raise ValueError(f"tree has {m + 1} leaves, reference holds {L}")
        feat = np.zeros(L - 1, np.int32)
        thr = np.full(L - 1, np.inf, np.float32)
        path = np.zeros((L - 1, L), np.float32)
        depth = np.full(L, -1.0, np.float32)     # -1: no such leaf
        if m > 0:
            feat[:m] = tree["split_feature"]
            thr[:m] = floor_f32(tree["threshold"])
        stack = [(0 if m > 0 else -1, [])]        # (child, [(node, side)])
        while stack:
            child, above = stack.pop()
            if child < 0:
                depth[~child] = len(above)
                for node, side in above:
                    path[node, ~child] = side
            else:
                stack.append((int(tree["left_child"][child]),
                              above + [(child, 1.0)]))
                stack.append((int(tree["right_child"][child]),
                              above + [(child, -1.0)]))
        return (jnp.asarray(feat), jnp.asarray(thr), jnp.asarray(path),
                jnp.asarray(depth))

    def seed_score(self, program_score: np.ndarray, trees) -> None:
        """Set the running score to a score the program held (a host copy)
        plus the program's leaf values of `trees`, every row routed here by
        the real-valued thresholds: the score the program must hold before
        the tree that follows them."""
        import jax.numpy as jnp
        score = jnp.asarray(np.asarray(program_score, np.float32))
        for tree in trees:
            values = np.zeros(self.L, np.float32)
            values[:len(tree["leaf_value"])] = tree["leaf_value"]
            score = self._add(self.X, score, *self._tables(tree),
                              jnp.asarray(values))
        self.score = score

    def follow(self, tree: dict, program_score: np.ndarray) -> dict:
        """One step. `tree` holds the program's answer as plain arrays:
        split_feature, threshold (float64), left_child, right_child
        (< 0: leaf ~c), split_gain, internal_count, leaf_value, leaf_count.
        `program_score` is the program's raw score of every row after this
        step. Returns the readings of this step."""
        import jax.numpy as jnp
        L, C, f = self.L, self.C, self.f
        nl = int(len(tree["leaf_value"]))
        m = nl - 1
        acc, leaf = self._pass(self.X, self.y, self.score, self.lower,
                               self.upper, *self._tables(tree))
        hist = np.asarray(acc).astype(np.float64).reshape(f, BINS, L, C)
        leaf_hist = np.ascontiguousarray(hist.transpose(2, 0, 1, 3))
        del hist, acc
        tot = leaf_hist[:, 0].sum(axis=1)                       # [L, C]
        with np.errstate(divide="ignore", invalid="ignore"):
            ref_val = np.where(tot[:, 2] > 0,
                               -tot[:, 0] / (tot[:, 1] + self.l2), 0.0)
            ctl_val = ref_val if C == 3 else np.where(
                tot[:, 2] > 0, -tot[:, 3] / (tot[:, 4] + self.l2), 0.0)
        ref_val = ref_val * self.lr
        ctl_val = ctl_val * self.lr

        out = {"leaves": nl}
        out.update(bin_readings(self.cuts, leaf_hist[:, :, :, 2].sum(axis=0),
                                self.max_bin))
        # counts: every leaf and every internal node, exact
        prog_leaf_n = np.asarray(tree["leaf_count"], np.int64)
        ref_leaf_n = np.rint(tot[:nl, 2]).astype(np.int64)
        mism = int((prog_leaf_n != ref_leaf_n).sum())
        mism += int(np.rint(tot[nl:, 2]).astype(np.int64).astype(bool).sum())

        # leaf values, by the worst leaf, against the larger of the
        # reference's own value and its median leaf's
        floor = float(np.median(np.abs(ref_val[:nl]))) if nl else 1.0
        denom = np.maximum(np.abs(ref_val[:nl]), floor)
        prog_val = np.asarray(tree["leaf_value"], np.float64)
        out["leaf_gap"] = float(np.max(np.abs(prog_val - ref_val[:nl]) / denom))
        if C == 5:
            out["ctl_leaf_gap"] = float(
                np.max(np.abs(ctl_val[:nl] - ref_val[:nl]) / denom))

        # nodes: the chosen split against the best, the reported gain
        # against the reference's gain of the same split
        split_gap = gain_gap = ctl_split_gap = ctl_gain_gap = 0.0
        if m > 0:
            node_n = np.asarray(tree["internal_count"], np.int64)
            imp_ref = np.zeros(m)
            imp_prog_choice = np.zeros(m)
            best_imp = np.zeros(m)
            ctl_choice_imp = np.zeros(m)
            ctl_reported = np.zeros(m)

            def node_hist(child):
                child = int(child)
                if child < 0:
                    return leaf_hist[~child]
                h = node_hist(tree["left_child"][child]) \
                    + node_hist(tree["right_child"][child])
                visit(child, h)
                return h

            def visit(i, h):
                nonlocal mism
                if int(np.rint(h[0, :, 2].sum())) != int(node_n[i]):
                    mism += 1
                strict, parent = self._gains(h, 0, 1, 1e-3)
                lenient, _ = self._gains(h, 0, 1, -1e-3)
                fj = int(tree["split_feature"][i])
                tj = int(np.searchsorted(self.cuts[fj],
                                         float(tree["threshold"][i])))
                if tj >= len(self.cuts[fj]) or \
                        self.cuts[fj][tj] != float(tree["threshold"][i]):
                    imp_prog_choice[i] = -np.inf   # not a candidate at all
                else:
                    imp_prog_choice[i] = lenient[fj, tj] - parent
                best_imp[i] = max(float(strict.max()) - parent,
                                  imp_prog_choice[i])
                imp_ref[i] = imp_prog_choice[i]
                if C == 5:
                    low, low_parent = self._gains(h, 3, 4, 0.0)
                    k = int(np.argmax(low))
                    cf, ct = divmod(k, BINS)
                    ctl_choice_imp[i] = lenient[cf, ct] - parent
                    ctl_reported[i] = low[cf, ct] - low_parent

            node_hist(0)
            scale = np.maximum(best_imp, np.median(best_imp))
            with np.errstate(invalid="ignore"):
                gaps = np.nan_to_num((best_imp - imp_prog_choice) / scale,
                                     nan=np.inf)
            split_gap = float(np.max(gaps))
            rep = np.asarray(tree["split_gain"], np.float64)
            with np.errstate(invalid="ignore"):
                ggaps = np.nan_to_num(np.abs(rep - imp_ref) / scale,
                                      nan=np.inf)
            gain_gap = float(np.max(ggaps))
            if C == 5:
                ok = np.isfinite(ctl_choice_imp)
                ctl_split_gap = float(np.max(np.where(
                    ok, (best_imp - ctl_choice_imp) / scale, np.inf)))
                ctl_gain_gap = float(np.max(np.where(
                    ok, np.abs(ctl_reported - ctl_choice_imp) / scale,
                    np.inf)))
        out["count_mismatch"] = mism
        out["split_gap"] = split_gap
        out["gain_gap"] = gain_gap
        if C == 5:
            out["ctl_split_gap"] = ctl_split_gap
            out["ctl_gain_gap"] = ctl_gain_gap

        # score and loss after the step, reference's own leaf values
        values = np.zeros(L, np.float32)
        values[:] = ref_val
        other = np.zeros(L, np.float32)
        other[:] = ctl_val
        new, dmax, loss_ref, loss_prog, cmax, loss_ctl = self._after(
            self.score, self.y, leaf, jnp.asarray(values),
            jnp.asarray(np.asarray(program_score, np.float32)),
            jnp.asarray(other))
        self.score = new
        step = float(np.max(np.abs(ref_val[:nl]))) or 1.0
        lr_ = float(np.asarray(loss_ref, np.float64).sum()) / self.n
        lp_ = float(np.asarray(loss_prog, np.float64).sum()) / self.n
        out["score_gap"] = float(dmax) / step
        out["loss_gap"] = abs(lp_ - lr_) / lr_
        out["loss_ref"] = lr_
        out["loss_program"] = lp_
        if C == 5:
            lc_ = float(np.asarray(loss_ctl, np.float64).sum()) / self.n
            out["ctl_score_gap"] = float(cmax) / step
            out["ctl_loss_gap"] = abs(lc_ - lr_) / lr_
        return out


COMPARED = ("bin_count_mismatch", "bin_pop_gap", "count_mismatch",
            "split_gap", "gain_gap", "leaf_gap", "score_gap", "loss_gap")


def worst_over_steps(per_step, prefix: str = "") -> dict:
    """The number compared is the worst of the followed steps."""
    return {k: max(step[prefix + k] for step in per_step)
            for k in COMPARED if prefix + k in per_step[0]}
