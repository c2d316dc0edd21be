"""Vectorized tree traversal (binned and raw feature spaces).

TPU-native replacement for the reference's per-row pointer-chasing
prediction walks (`Tree::Predict`/`NumericalDecision`, tree.h:416-450, and
`Tree::AddPredictionToScore`, tree.cpp:114-207): all rows advance one tree
level per step through gathers on fixed-capacity node arrays inside a
`lax.while_loop`; finished rows park on their (negative) leaf encoding.
Children use the reference encoding: internal node index >= 0, leaf `l`
stored as `~l` (tree.cpp:111).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..binning import MISSING_NAN, MISSING_NONE, MISSING_ZERO

K_ZERO_THRESHOLD = 1e-35


class DeviceTree(NamedTuple):
    """Fixed-capacity struct-of-arrays tree (reference: Tree, tree.h:20)."""
    num_leaves: jnp.ndarray        # scalar i32, actual leaves used
    split_feature: jnp.ndarray     # [M] i32 inner feature index
    threshold_bin: jnp.ndarray     # [M] i32
    threshold_real: jnp.ndarray    # [M] f32 (raw-space threshold / category)
    default_left: jnp.ndarray      # [M] bool
    is_categorical: jnp.ndarray    # [M] bool
    left_child: jnp.ndarray        # [M] i32 (negative = ~leaf)
    right_child: jnp.ndarray       # [M] i32
    node_missing: jnp.ndarray      # [M] i32 missing type of the node's feature
    node_nan_bin: jnp.ndarray      # [M] i32 (num_bin-1 of the feature)
    node_default_bin: jnp.ndarray  # [M] i32
    # EFB locators (efb.py): stored column + bin offset of the feature
    node_group: jnp.ndarray        # [M] i32
    node_offset: jnp.ndarray       # [M] i32
    node_bundled: jnp.ndarray      # [M] bool
    node_num_bin: jnp.ndarray      # [M] i32
    leaf_value: jnp.ndarray        # [L] f32
    split_gain: jnp.ndarray        # [M] f32
    internal_value: jnp.ndarray    # [M] f32
    internal_count: jnp.ndarray    # [M] f32
    leaf_count: jnp.ndarray        # [L] f32
    # categorical bitsets (tree.h:355-359): a cat node's threshold_real /
    # threshold_bin hold its cat_idx; membership is bit `value` of words
    # [cat_boundaries[idx], cat_boundaries[idx+1]) (raw space) and the
    # _inner variants (bin space)
    cat_boundaries: jnp.ndarray        # [C+1] i32
    cat_bitset: jnp.ndarray            # [W] u32 raw-value bitset words
    cat_boundaries_inner: jnp.ndarray  # [C+1] i32
    cat_bitset_inner: jnp.ndarray      # [W'] u32 bin-space bitset words
    # piecewise-linear leaves (linear/): zero-width (k = 0) for
    # constant-leaf trees. Feature indices follow split_feature's space
    # (inner for binned stacks, original columns after stack_trees_raw /
    # to_device_raw); the linear term needs RAW feature values, so only
    # the raw-space value paths can evaluate it.
    leaf_coeff: jnp.ndarray = None     # [L, k] f32 slopes
    leaf_feat: jnp.ndarray = None      # [L, k] i32 columns, -1-padded


def _in_bitset(boundaries, bitset, cat_idx, value):
    """Vectorized Common::FindInBitset over per-node bitset slices."""
    idx = jnp.maximum(cat_idx, 0)
    lo = boundaries[idx]
    nwords = boundaries[idx + 1] - lo
    word_i = value // 32
    valid = (value >= 0) & (word_i < nwords)
    word = bitset[jnp.clip(lo + word_i, 0, bitset.shape[0] - 1)]
    bit = (word >> (value % 32).astype(jnp.uint32)) & jnp.uint32(1)
    return valid & (bit == 1)


def _decide_binned(tree: DeviceTree, node: jnp.ndarray, bins: jnp.ndarray):
    """go-left decision in bin space (reference: Tree::DecisionInner paths)."""
    missing = tree.node_missing[node]
    is_missing = (((missing == MISSING_NAN) & (bins == tree.node_nan_bin[node]))
                  | ((missing == MISSING_ZERO) & (bins == tree.node_default_bin[node])))
    numeric_left = jnp.where(is_missing, tree.default_left[node],
                             bins <= tree.threshold_bin[node])
    cat_left = _in_bitset(tree.cat_boundaries_inner, tree.cat_bitset_inner,
                          tree.threshold_bin[node], bins)
    return jnp.where(tree.is_categorical[node], cat_left, numeric_left)


def predict_leaf_binned(tree: DeviceTree, binned: jnp.ndarray) -> jnp.ndarray:
    """leaf index per row for a binned matrix [N, F]."""
    n = binned.shape[0]
    node = jnp.where(tree.num_leaves > 1, jnp.zeros(n, jnp.int32),
                     jnp.full(n, -1, jnp.int32))

    def cond(state):
        return jnp.any(state >= 0)

    def body(node):
        active = node >= 0
        nd = jnp.maximum(node, 0)
        grp = tree.node_group[nd]
        gbins = jnp.take_along_axis(binned, grp[:, None], axis=1)[:, 0]
        gbins = gbins.astype(jnp.int32)
        # decode the feature-space bin out of the stored group column
        off = tree.node_offset[nd]
        nb = tree.node_num_bin[nd]
        in_slice = (gbins >= off) & (gbins < off + nb)
        bins = jnp.where(tree.node_bundled[nd],
                         jnp.where(in_slice, gbins - off,
                                   tree.node_default_bin[nd]),
                         gbins)
        go_left = _decide_binned(tree, nd, bins)
        nxt = jnp.where(go_left, tree.left_child[nd], tree.right_child[nd])
        return jnp.where(active, nxt, node)

    node = jax.lax.while_loop(cond, body, node)
    return ~node  # leaves encoded as ~leaf


def _decide_raw(tree: DeviceTree, node: jnp.ndarray, fval: jnp.ndarray):
    """go-left decision on raw values (reference: NumericalDecision, tree.h:416)."""
    missing = tree.node_missing[node]
    is_nan = jnp.isnan(fval)
    is_zero = jnp.abs(fval) <= K_ZERO_THRESHOLD
    is_missing = (((missing == MISSING_NAN) & is_nan)
                  | ((missing == MISSING_ZERO) & (is_zero | is_nan)))
    fval_safe = jnp.where(is_nan, 0.0, fval)
    numeric_left = jnp.where(is_missing, tree.default_left[node],
                             fval_safe <= tree.threshold_real[node])
    cat_left = (~is_nan) & _in_bitset(
        tree.cat_boundaries, tree.cat_bitset,
        tree.threshold_real[node].astype(jnp.int32),
        jnp.floor(fval_safe).astype(jnp.int32))
    return jnp.where(tree.is_categorical[node], cat_left, numeric_left)


def predict_leaf_raw(tree: DeviceTree, data: jnp.ndarray) -> jnp.ndarray:
    """leaf index per row for a raw feature matrix [N, F_total] (real feature
    indices must be pre-mapped into `split_feature`)."""
    n = data.shape[0]
    node = jnp.where(tree.num_leaves > 1, jnp.zeros(n, jnp.int32),
                     jnp.full(n, -1, jnp.int32))

    def cond(state):
        return jnp.any(state >= 0)

    def body(node):
        active = node >= 0
        nd = jnp.maximum(node, 0)
        feat = tree.split_feature[nd]
        fval = jnp.take_along_axis(data, feat[:, None], axis=1)[:, 0]
        go_left = _decide_raw(tree, nd, fval)
        nxt = jnp.where(go_left, tree.left_child[nd], tree.right_child[nd])
        return jnp.where(active, nxt, node)

    node = jax.lax.while_loop(cond, body, node)
    return ~node


def _is_linear_tree(tree: DeviceTree) -> bool:
    """Static (trace-time) check for a linear-leaf tree/stack."""
    return tree.leaf_coeff is not None and tree.leaf_coeff.shape[-1] > 0


def linear_leaf_addend(leaf_coeff, leaf_feat, leaf, data):
    """[N] linear-leaf contribution: sum_j coeff[l, j] * x[r, f_j] with
    l = leaf[r]. Padded slots (-1) contribute a structural zero; a row
    with a non-finite value in any live slot gets 0 (intercept only) —
    the solver excluded such rows from the fit the same way, so train
    and serve agree (linear/solver.py)."""
    feats = leaf_feat[leaf]                                   # [N, k]
    pad = feats < 0
    xv = jnp.take_along_axis(
        data, jnp.clip(feats, 0, data.shape[1] - 1), axis=1)
    finite = jnp.isfinite(xv) | pad
    row_ok = jnp.all(finite, axis=1)
    xv = jnp.where(pad | ~finite, 0.0, xv)
    lin = jnp.einsum("nk,nk->n", leaf_coeff[leaf].astype(jnp.float32),
                     xv.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    return jnp.where(row_ok, lin, 0.0)


def predict_value_binned(tree: DeviceTree, binned: jnp.ndarray) -> jnp.ndarray:
    if _is_linear_tree(tree):
        # the linear term contracts RAW feature values, which a binned
        # matrix cannot reconstruct — callers route linear models
        # through predict_leaf_binned + linear_leaf_addend on raw data
        raise ValueError(
            "binned value prediction cannot evaluate linear_tree leaves "
            "(raw feature values required); use the leaf + raw path")
    return tree.leaf_value[predict_leaf_binned(tree, binned)]


def predict_value_raw(tree: DeviceTree, data: jnp.ndarray) -> jnp.ndarray:
    leaf = predict_leaf_raw(tree, data)
    val = tree.leaf_value[leaf]
    if _is_linear_tree(tree):
        val = val.astype(jnp.float32) + linear_leaf_addend(
            tree.leaf_coeff, tree.leaf_feat, leaf, data)
    return val


def stack_trees(trees) -> DeviceTree:
    """Stack host Trees into one batched DeviceTree [T, ...] (node arrays
    padded to the max node count) for scan-based ensemble prediction —
    the TPU analogue of the reference's per-tree loop in
    GBDT::PredictRaw (gbdt_prediction.cpp)."""
    import numpy as np
    max_m = max(max(t.num_leaves - 1, 1) for t in trees)
    max_l = max(t.num_leaves for t in trees)
    max_cat = max(t.num_cat for t in trees)
    max_w = max(max(len(t.cat_threshold), 1) for t in trees)
    max_wi = max(max(len(t.cat_threshold_inner), 1) for t in trees)
    max_k = max(t.leaf_coeff.shape[1] for t in trees)
    fmax = np.finfo(np.float32).max

    def pad(get, size, dtype, fill=0):
        out = np.full((len(trees), size), fill, dtype)
        for i, t in enumerate(trees):
            arr = np.asarray(get(t))
            out[i, :len(arr)] = arr
        return jnp.asarray(out)

    def pad2(get, size, dtype, fill=0):
        out = np.full((len(trees), size, max_k), fill, dtype)
        for i, t in enumerate(trees):
            arr = np.asarray(get(t))
            out[i, :arr.shape[0], :arr.shape[1]] = arr
        return jnp.asarray(out)

    return DeviceTree(
        num_leaves=jnp.asarray([t.num_leaves for t in trees], jnp.int32),
        split_feature=pad(lambda t: t.split_feature_inner, max_m, np.int32),
        threshold_bin=pad(lambda t: t.threshold_in_bin, max_m, np.int32),
        threshold_real=pad(lambda t: np.clip(t.threshold, -fmax, fmax),
                           max_m, np.float32),
        default_left=pad(lambda t: [t.default_left_node(i) for i in
                                    range(max(t.num_leaves - 1, 0))], max_m, bool),
        is_categorical=pad(lambda t: [t.is_categorical_node(i) for i in
                                      range(max(t.num_leaves - 1, 0))], max_m, bool),
        left_child=pad(lambda t: t.left_child, max_m, np.int32, fill=-1),
        right_child=pad(lambda t: t.right_child, max_m, np.int32, fill=-1),
        node_missing=pad(lambda t: t.node_missing, max_m, np.int32),
        node_nan_bin=pad(lambda t: t.node_nan_bin, max_m, np.int32),
        node_default_bin=pad(lambda t: t.node_default_bin, max_m, np.int32),
        node_group=pad(lambda t: t.node_group, max_m, np.int32),
        node_offset=pad(lambda t: t.node_offset, max_m, np.int32),
        node_bundled=pad(lambda t: t.node_bundled, max_m, bool),
        node_num_bin=pad(lambda t: t.node_num_bin, max_m, np.int32),
        leaf_value=pad(lambda t: t.leaf_value, max_l, np.float32),
        split_gain=pad(lambda t: t.split_gain, max_m, np.float32),
        internal_value=pad(lambda t: t.internal_value, max_m, np.float32),
        internal_count=pad(lambda t: t.internal_count, max_m, np.float32),
        leaf_count=pad(lambda t: t.leaf_count, max_l, np.float32),
        # pad boundaries with the last offset so out-of-range cat_idx
        # slices are empty; bitset words pad with 0 (no membership)
        cat_boundaries=pad(
            lambda t: np.concatenate(
                [t.cat_boundaries,
                 np.full(max_cat + 2 - len(t.cat_boundaries),
                         t.cat_boundaries[-1], np.int32)]),
            max_cat + 2, np.int32),
        cat_bitset=pad(lambda t: t.cat_threshold, max_w, np.uint32),
        cat_boundaries_inner=pad(
            lambda t: np.concatenate(
                [t.cat_boundaries_inner,
                 np.full(max_cat + 2 - len(t.cat_boundaries_inner),
                         t.cat_boundaries_inner[-1], np.int32)]),
            max_cat + 2, np.int32),
        cat_bitset_inner=pad(lambda t: t.cat_threshold_inner, max_wi, np.uint32),
        # padding leaves get -1 features (structural zero contribution)
        leaf_coeff=pad2(lambda t: t.leaf_coeff, max_l, np.float32),
        leaf_feat=pad2(lambda t: t.leaf_features_inner, max_l, np.int32,
                       fill=-1),
    )


def stack_trees_raw(trees) -> DeviceTree:
    """Like stack_trees but with original-column feature indices for
    raw-feature traversal (split AND linear-leaf features)."""
    import numpy as np
    stacked = stack_trees(trees)
    max_m = stacked.split_feature.shape[1]
    out = np.zeros((len(trees), max_m), np.int32)
    for i, t in enumerate(trees):
        out[i, :len(t.split_feature)] = t.split_feature
    lf = np.array(stacked.leaf_feat)  # writable host copy
    for i, t in enumerate(trees):
        nl, k = t.leaf_features.shape
        lf[i, :nl, :k] = t.leaf_features
    return stacked._replace(split_feature=jnp.asarray(out),
                            leaf_feat=jnp.asarray(lf))


def predict_forest_binned(stacked: DeviceTree, binned: jnp.ndarray) -> jnp.ndarray:
    """Sum of all stacked trees' outputs per row, all trees descending in
    LOCKSTEP (vmap over the tree axis). A scan over trees looks natural
    but serializes T * depth tiny gather kernels — ~3000 sequential
    launches for a 100-tree forest. The vmapped walk runs max-depth
    steps of [T, N]-wide gathers instead."""
    vals = jax.vmap(lambda tr: predict_value_binned(tr, binned))(stacked)
    return vals.sum(axis=0)


def predict_forest_raw(stacked: DeviceTree, data: jnp.ndarray) -> jnp.ndarray:
    # f32 cast before the cross-tree sum: quantized layouts store leaf
    # values in f16 (see serving/forest.py) and a 500-term f16
    # accumulation would drift ~1% — storage precision is the quantized
    # contract, accumulation stays f32 (no-op for f32 forests)
    vals = jax.vmap(lambda tr: predict_value_raw(tr, data))(stacked)
    return vals.astype(jnp.float32).sum(axis=0)


class MatmulForest(NamedTuple):
    """Forest laid out for gather-free MXU evaluation (raw feature space).

    The reference predicts by per-row pointer chasing (tree.h:416-450);
    both a scan-over-trees and a lockstep vmap walk of that design are
    GATHER-bound on TPU (measured 94s / 207s for 100 trees x 500k rows —
    random [N]-indexed gathers per level are the one memory pattern the
    hardware hates). This layout turns prediction into three matmuls per
    tree:

      fsel[N, M] = data @ onehot(feat)       (exact: one-hot RHS, f32
                                              HIGHEST = 3x-bf16 split
                                              reconstructs f32 exactly)
      D[N, M]    = +-1 decisions              (thresholds/missing, VPU)
      S[N, L]    = D @ P                      (P[m,l] = +-1 if leaf l is
                                              in m's left/right subtree,
                                              0 if m is not an ancestor)
      leaf match: S[r, l] == depth[l]  — all ancestors agree exactly
                                         once; integers <= 254 are exact
                                         in the f32 accumulator
      value[r]   = match @ leaf_value

    Categorical splits (tree.h:355-359 bitsets) ride the MXU too: the
    categorical columns are one-hot expanded into a [N, V] block matrix
    (block = one feature's category range, the layout the reference's
    users build by hand for Expo) and each tree carries a [V, M] table
    with +-1 in (category, node) cells of the node's feature block
    (+1 = in the node's bitset). `expanded @ table` then lands exactly
    one +-1 per (row, categorical node); a 0 means NaN / out-of-range
    category, which resolves to "go right" — the same contract as
    _decide_raw. Forests whose category expansion exceeds _CAT_V_BUDGET
    keep the walk path.
    """
    feat: jnp.ndarray           # [T, M] i32 original-column index
    threshold: jnp.ndarray      # [T, M] f32
    default_left: jnp.ndarray   # [T, M] bool
    missing: jnp.ndarray        # [T, M] i32
    path: jnp.ndarray           # [T, M, L] f32 in {-1, 0, +1}
    leaf_depth: jnp.ndarray     # [T, L] f32 (-1 for padding leaves)
    leaf_value: jnp.ndarray     # [T, L] f32
    is_cat: jnp.ndarray         # [T, M] bool
    cat_table: jnp.ndarray      # [T, V, M] f32 in {-1, 0, +1}
    # piecewise-linear leaves: one leaf-gathered coeff . x contraction
    # on top of the one-hot reduction; k = 0 for constant forests (the
    # static gate) and the gathered coefficients of padding trees/leaves
    # are 0, so they contribute nothing
    leaf_feat: jnp.ndarray      # [T, L, k] i32 original columns, -1 pad
    leaf_coeff: jnp.ndarray     # [T, L, k] f32
    # forest-level expansion spec [Fc] (NOT per-tree; excluded from
    # _tree_batches' per-tree reshape and from the scan xs)
    cat_cols: jnp.ndarray       # [Fc] i32 original column
    cat_off: jnp.ndarray        # [Fc] i32 block offset into V
    cat_card: jnp.ndarray       # [Fc] i32 block width


# ceiling on the dense [T, M, L] path tensor (elements). Beyond this the
# MatmulForest layout stops paying for itself: at num_leaves=4095 a few
# hundred trees would materialize tens of GB on device, so callers fall
# back to the walk path instead.
_MATMUL_PATH_BUDGET = 1 << 28
# ceilings for the categorical extension: total one-hot expansion width
# and the [T, V, M] table
_CAT_V_BUDGET = 4096
_CAT_TABLE_BUDGET = 1 << 28


def stack_trees_matmul(trees):
    """Build the MatmulForest layout, or None if the [T, M, L] path
    tensor / categorical expansion would exceed the device-memory
    budgets (callers then use the walk path)."""
    import numpy as np
    max_m = max(max(t.num_leaves - 1, 1) for t in trees)
    max_l = max(t.num_leaves for t in trees)
    T = len(trees)
    if T * max_m * max_l > _MATMUL_PATH_BUDGET:
        return None

    # categorical expansion layout: per categorical FEATURE, a block wide
    # enough for every bitset that splits on it (words * 32 bits)
    cards = {}
    for t in trees:
        for i in range(max(t.num_leaves - 1, 0)):
            if not t.is_categorical_node(i):
                continue
            f = int(t.split_feature[i])
            ci = int(t.threshold[i])
            words = int(t.cat_boundaries[ci + 1] - t.cat_boundaries[ci])
            cards[f] = max(cards.get(f, 0), words * 32)
    cat_cols = sorted(cards)
    v_total = sum(cards[f] for f in cat_cols)
    if v_total > _CAT_V_BUDGET or T * v_total * max_m > _CAT_TABLE_BUDGET:
        return None
    offs = {}
    off = 0
    for f in cat_cols:
        offs[f] = off
        off += cards[f]

    fmax = np.finfo(np.float32).max
    feat = np.zeros((T, max_m), np.int32)
    thr = np.zeros((T, max_m), np.float32)
    dleft = np.zeros((T, max_m), bool)
    miss = np.zeros((T, max_m), np.int32)
    path = np.zeros((T, max_m, max_l), np.float32)
    depth = np.full((T, max_l), -1.0, np.float32)
    lval = np.zeros((T, max_l), np.float32)
    is_cat = np.zeros((T, max_m), bool)
    cat_table = np.zeros((T, v_total, max_m), np.float32)
    max_k = max(t.leaf_coeff.shape[1] for t in trees)
    lfeat = np.full((T, max_l, max_k), -1, np.int32)
    lcoef = np.zeros((T, max_l, max_k), np.float32)

    for t_i, t in enumerate(trees):
        m = max(t.num_leaves - 1, 0)
        feat[t_i, :m] = t.split_feature
        thr[t_i, :m] = np.clip(t.threshold, -fmax, fmax)
        dleft[t_i, :m] = [t.default_left_node(i) for i in range(m)]
        miss[t_i, :m] = t.node_missing[:m]
        lval[t_i, :t.num_leaves] = t.leaf_value
        nl_k = t.leaf_coeff.shape[1]
        if nl_k:
            lfeat[t_i, :t.num_leaves, :nl_k] = t.leaf_features
            lcoef[t_i, :t.num_leaves, :nl_k] = t.leaf_coeff
        for i in range(m):
            if not t.is_categorical_node(i):
                continue
            is_cat[t_i, i] = True
            f = int(t.split_feature[i])
            ci = int(t.threshold[i])
            lo, hi = int(t.cat_boundaries[ci]), int(t.cat_boundaries[ci + 1])
            words = np.asarray(t.cat_threshold[lo:hi], np.uint32)
            bits = np.unpackbits(
                words.view(np.uint8), bitorder="little")      # [words*32]
            col = np.where(bits > 0, 1.0, -1.0)
            blk = offs[f]
            cat_table[t_i, blk:blk + len(col), i] = col
            # block tail beyond this node's bitset: not in set -> right
            cat_table[t_i, blk + len(col):blk + cards[f], i] = -1.0

        # DFS from the root accumulating the ancestor signature
        if t.num_leaves == 1:
            depth[t_i, 0] = 0.0
            continue
        stack = [(0, [])]   # (node, [(ancestor, sign), ...])
        while stack:
            node, anc = stack.pop()
            for child, sign in ((t.left_child[node], 1.0),
                                (t.right_child[node], -1.0)):
                chain = anc + [(node, sign)]
                if child < 0:
                    leaf = ~child
                    depth[t_i, leaf] = len(chain)
                    for a, s in chain:
                        path[t_i, a, leaf] = s
                else:
                    stack.append((child, chain))
    return MatmulForest(
        feat=jnp.asarray(feat), threshold=jnp.asarray(thr),
        default_left=jnp.asarray(dleft), missing=jnp.asarray(miss),
        path=jnp.asarray(path), leaf_depth=jnp.asarray(depth),
        leaf_value=jnp.asarray(lval),
        is_cat=jnp.asarray(is_cat),
        cat_table=jnp.asarray(cat_table),
        cat_cols=jnp.asarray([f for f in cat_cols], jnp.int32)
        if cat_cols else jnp.zeros(0, jnp.int32),
        cat_off=jnp.asarray([offs[f] for f in cat_cols], jnp.int32)
        if cat_cols else jnp.zeros(0, jnp.int32),
        cat_card=jnp.asarray([cards[f] for f in cat_cols], jnp.int32)
        if cat_cols else jnp.zeros(0, jnp.int32),
        leaf_feat=jnp.asarray(lfeat), leaf_coeff=jnp.asarray(lcoef))


def _cat_expansion(mf: MatmulForest, nan_mask, clean):
    """[N, V] bf16 one-hot block expansion of the categorical columns
    (loop-invariant across trees — built once per dispatch). Out-of-range
    and NaN categories hit no block cell, so their table product is 0."""
    return _cat_expansion_spec(mf.cat_table.shape[1], mf.cat_cols,
                               mf.cat_off, mf.cat_card, nan_mask, clean)


def _cat_expansion_spec(v, cat_cols, cat_off, cat_card, nan_mask, clean):
    """_cat_expansion on a bare (V, cols, offsets, cards) spec — shared
    by the MatmulForest and QuantForest layouts."""
    if v == 0:
        return None
    n = clean.shape[0]
    fc = cat_cols.shape[0]
    vals = jnp.take(clean, cat_cols, axis=1)              # [N, Fc]
    nanv = jnp.take(nan_mask, cat_cols, axis=1)
    iv = jnp.floor(vals).astype(jnp.int32)
    ok = (~nanv) & (iv >= 0) & (iv < cat_card[None, :])
    # one scatter, O(N*Fc): invalid cells land in a per-feature parking
    # column beyond v (distinct per feature, so every (row, pos) index
    # is unique) and are sliced away
    pos = jnp.where(ok, iv + cat_off[None, :],
                    v + jnp.arange(fc, dtype=jnp.int32)[None, :])
    rows = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[:, None],
                            pos.shape)
    expanded = jnp.zeros((n, v + fc), jnp.bfloat16)
    expanded = expanded.at[rows, pos].set(1.0, unique_indices=True)
    return expanded[:, :v]


def _one_tree_match(tree, nan_mask, clean, expanded=None):
    """[N, L] exact one-hot leaf membership of one tree (tree = per-tree
    slice of a MatmulForest; expanded = the shared [N, V] categorical
    block expansion, None for category-free forests)."""
    feat, thr, dleft, miss, path, depth = (
        tree.feat, tree.threshold, tree.default_left, tree.missing,
        tree.path, tree.leaf_depth)
    f = clean.shape[1]
    onehot = (jnp.arange(f, dtype=jnp.int32)[:, None]
              == feat[None, :]).astype(jnp.float32)           # [F, M]
    # HIGHEST keeps the selection exact: each product is data * 1 and
    # each reduction has exactly one nonzero term
    fsel = jnp.einsum("nf,fm->nm", clean, onehot,
                      preferred_element_type=jnp.float32,
                      precision=jax.lax.Precision.HIGHEST)
    is_nan = jnp.einsum("nf,fm->nm", nan_mask.astype(jnp.float32),
                        onehot,
                        preferred_element_type=jnp.float32) > 0.5
    is_zero = jnp.abs(fsel) <= K_ZERO_THRESHOLD
    is_missing = (((miss[None, :] == MISSING_NAN) & is_nan)
                  | (((miss[None, :]) == MISSING_ZERO)
                     & (is_zero | is_nan)))
    go_left = jnp.where(is_missing, dleft[None, :],
                        fsel <= thr[None, :])
    D = jnp.where(go_left, 1.0, -1.0).astype(jnp.bfloat16)    # [N, M]
    if expanded is not None:
        # exactly one +-1 cell per (row, cat node); 0 = NaN/out-of-range
        # category -> right (the _decide_raw contract)
        dcat = jnp.einsum("nv,vm->nm", expanded,
                          tree.cat_table.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
        dcat = jnp.where(dcat > 0.5, 1.0, -1.0).astype(jnp.bfloat16)
        D = jnp.where(tree.is_cat[None, :], dcat, D)
    # +-1 x {-1,0,+1} products and integer partial sums <= 254 are exact
    # in bf16 inputs + f32 accumulation
    S = jnp.einsum("nm,ml->nl", D, path.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)        # [N, L]
    return S == depth[None, :]


_FOREST_LEVEL_FIELDS = ("cat_cols", "cat_off", "cat_card")


def _tree_batches(mf, batch: int, forest_fields=_FOREST_LEVEL_FIELDS):
    """Reshape the per-tree fields [T, ...] -> [ceil(T/b), b, ...]
    (padding with zero trees: path == 0 everywhere makes S == 0 !=
    leaf_depth(-1) so padding trees match no leaf and contribute
    nothing). Forest-level fields (the categorical expansion spec, and
    the code grids of the QuantForest layout) are nulled out — they are
    consumed outside the tree scan."""
    t = mf.feat.shape[0]
    nb = (t + batch - 1) // batch
    pad = nb * batch - t

    def prep(a):
        if pad:
            a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        return a.reshape((nb, batch) + a.shape[1:])

    per_tree = mf._replace(**{f: None for f in forest_fields})
    padded = jax.tree.map(prep, per_tree)
    # padding leaf_depth must stay -1 (unmatchable), not 0
    if pad:
        depth = padded.leaf_depth.at[-1, -pad:, :].set(-1.0)
        padded = padded._replace(leaf_depth=depth)
    return padded


def predict_forest_raw_matmul(mf: MatmulForest, data: jnp.ndarray,
                              tree_batch: int = 5) -> jnp.ndarray:
    """Sum of all trees' outputs per row, gather-free. A lax.scan over
    small TREE BATCHES (vmap inside each step) keeps per-step
    intermediates bounded while amortizing per-step scheduling — a
    1-tree scan spent ~18 ms/tree on step overhead alone."""
    nan_mask = jnp.isnan(data)
    clean = jnp.where(nan_mask, 0.0, data)
    expanded = _cat_expansion(mf, nan_mask, clean)
    batched = _tree_batches(mf, tree_batch)
    linear = mf.leaf_coeff.shape[-1] > 0
    lidx = jnp.arange(mf.leaf_value.shape[1], dtype=jnp.float32)

    def body(acc, trees):
        def one(tree):
            match = _one_tree_match(tree, nan_mask, clean, expanded)
            # HIGHEST: one-hot x f32 leaf values stay exact (default
            # bf16 inputs would truncate the leaf values); the f32 cast
            # upcasts f16-stored leaves of quantized layouts losslessly
            val = jnp.einsum("nl,l->n", match.astype(jnp.float32),
                             tree.leaf_value.astype(jnp.float32),
                             preferred_element_type=jnp.float32,
                             precision=jax.lax.Precision.HIGHEST)
            if linear:
                # leaf-gathered coeff . x contraction: recover the leaf
                # index from the one-hot match (HIGHEST — indices > 256
                # must stay exact), then gather that leaf's slope table.
                # Padding trees/leaves carry zero coefficients, so they
                # add exactly 0 here just as they do in the value einsum
                lid = jnp.einsum("nl,l->n", match.astype(jnp.float32),
                                 lidx, preferred_element_type=jnp.float32,
                                 precision=jax.lax.Precision.HIGHEST
                                 ).astype(jnp.int32)
                val = val + linear_leaf_addend(
                    tree.leaf_coeff, tree.leaf_feat, lid, data)
            return val

        return acc + jax.vmap(one)(trees).sum(axis=0), None

    init = jnp.zeros(data.shape[0], jnp.float32)
    out, _ = jax.lax.scan(body, init, batched)
    return out


def predict_forest_leaf_matmul(mf: MatmulForest, data: jnp.ndarray,
                               tree_batch: int = 5) -> jnp.ndarray:
    """[N, T] leaf index per (row, tree), gather-free."""
    nan_mask = jnp.isnan(data)
    clean = jnp.where(nan_mask, 0.0, data)
    t = mf.feat.shape[0]
    l = mf.leaf_value.shape[1]
    idx = jnp.arange(l, dtype=jnp.float32)
    expanded = _cat_expansion(mf, nan_mask, clean)
    batched = _tree_batches(mf, tree_batch)

    def body(_, trees):
        def one(tree):
            match = _one_tree_match(tree, nan_mask, clean, expanded)
            # HIGHEST: default TPU precision truncates operands to bf16,
            # which rounds leaf indices > 256 (num_leaves can be 4095)
            return jnp.einsum("nl,l->n", match.astype(jnp.float32),
                              idx, preferred_element_type=jnp.float32,
                              precision=jax.lax.Precision.HIGHEST)

        return None, jax.vmap(one)(trees)

    _, leaves = jax.lax.scan(body, None, batched)   # [nb, b, N]
    leaves = leaves.reshape(-1, data.shape[0])[:t]
    return leaves.T.astype(jnp.int32)


def predict_forest_leaf_raw(stacked: DeviceTree,
                            data: jnp.ndarray) -> jnp.ndarray:
    """Leaf index per (row, tree) as ONE scanned dispatch: [N, T] i32
    (reference: Predictor::PredictLeafIndex, predictor.hpp:84-101 — the
    TPU shape of it, consistent with the stacked value path instead of
    one dispatch per tree)."""
    leaves = jax.vmap(lambda tr: predict_leaf_raw(tr, data))(stacked)
    return leaves.T.astype(jnp.int32)               # [N, T]


class QuantForest(NamedTuple):
    """MatmulForest variant with fixed-point (bin-code) split thresholds
    and f16 leaf values (`tpu_predict_quantize=int8`).

    Booster accelerators (arXiv:2011.02022 §3) observe that GBDT split
    thresholds are bin boundaries frozen at dataset build, so a split
    decision needs only the value's POSITION among the per-feature
    bounds — an 8-bit code — not an f32 compare against an f32 value.
    Rows are coded once per dispatch (`1 + #{bounds < x}` against the
    per-feature grid, an elementwise pass amortized over every tree) and
    each node stores the code of its own bound, so the layout evaluates
    with ONE selection einsum per tree instead of MatmulForest's two
    HIGHEST-precision passes (feature values + NaN mask) plus the
    missing-logic chain:

      fsel[N, M] = codes @ onehot(feat)   (integer codes ≤ 256 are exact
                                           even in bf16 products — on
                                           MXU hardware this runs at
                                           default precision instead of
                                           the 3x-pass HIGHEST f32 the
                                           raw layout needs)
      go_left    = (fsel ≤ thr_code) & (fsel ≥ lo)
      S/match/value: unchanged from MatmulForest (bf16 path signature,
                     f32 accumulation, f16 leaf values upcast at use)

    Missing handling is folded into the codes: rows that are "missing"
    at a feature (NaN under MissingType::NaN, NaN/±0 under Zero) code
    to -1, and `lo` is -2 for default-left nodes / 0 for default-right
    — so -1 passes the left test exactly when the node defaults left,
    while real codes (≥ 1) never trip the lower bound. NaN under
    MissingType::None codes as 0.0, reproducing _decide_raw's
    fval_safe substitution. Split decisions are therefore BIT-EXACT vs
    the f32 layouts (codes compare the same frozen f32 bounds); the
    only lossy piece is the f16 leaf storage, which the build-time
    accuracy gate (`tpu_predict_quantize_tol`, boosting/gbdt.py)
    bounds. Categorical splits ride the same one-hot block expansion
    and ±1 tables as MatmulForest, bf16-stored."""
    # per-tree fields (names/shapes match MatmulForest so _tree_batches
    # and the cat expansion are shared)
    feat: jnp.ndarray           # [T, M] i32 original-column index
    thr_code: jnp.ndarray       # [T, M] f32 fixed-point threshold code
    lo: jnp.ndarray             # [T, M] f32 lower code bound (-2 dleft / 0)
    path: jnp.ndarray           # [T, M, L] bf16 in {-1, 0, +1}
    leaf_depth: jnp.ndarray     # [T, L] f32 (-1 for padding leaves)
    leaf_value: jnp.ndarray     # [T, L] f16
    is_cat: jnp.ndarray         # [T, M] bool
    cat_table: jnp.ndarray      # [T, V, M] bf16 in {-1, 0, +1}
    # forest-level fields (excluded from the per-tree batching)
    grid: jnp.ndarray           # [F, K] f32 sorted bounds (+inf padded)
    miss_nan: jnp.ndarray       # [F] bool feature MissingType == NaN
    miss_zero: jnp.ndarray      # [F] bool feature MissingType == Zero
    cat_cols: jnp.ndarray       # [Fc] i32 original column
    cat_off: jnp.ndarray        # [Fc] i32 block offset into V
    cat_card: jnp.ndarray       # [Fc] i32 block width


_QUANT_FOREST_LEVEL_FIELDS = ("grid", "miss_nan", "miss_zero",
                              "cat_cols", "cat_off", "cat_card")

# max distinct thresholds per feature: the 8-bit code space (codes
# 1..K+1 plus the -1 missing sentinel must stay distinguishable)
QUANT_MAX_CODES = 255


class QuantRefused(ValueError):
    """Raised when a forest cannot be laid out fixed-point (more
    distinct thresholds per feature than the 8-bit code space holds —
    models binned past max_bin=256)."""


def stack_trees_quant(trees):
    """Build the QuantForest layout for one class's trees, or None when
    the [T, M, L] path tensor / categorical expansion exceeds the
    shared device-memory budgets (callers then fall back to the walk
    layout with f16 leaves). Raises QuantRefused when any feature uses
    more than QUANT_MAX_CODES distinct thresholds, and for linear_tree
    forests (no quantized coefficient layout is designed yet)."""
    import numpy as np
    if any(t.is_linear for t in trees):
        raise QuantRefused(
            "linear_tree leaf coefficients have no int8 layout; "
            "predict linear forests with tpu_predict_quantize=none (f32)")
    base = stack_trees_matmul(trees)

    # per-feature threshold grids + missing types (missing type is a
    # property of the FEATURE's bin mapper, identical across nodes)
    fmax = np.finfo(np.float32).max
    grids: dict = {}
    miss: dict = {}
    n_feat = 1
    for t in trees:
        for i in range(max(t.num_leaves - 1, 0)):
            f = int(t.split_feature[i])
            n_feat = max(n_feat, f + 1)
            miss.setdefault(f, t.missing_type_node(i))
            if t.is_categorical_node(i):
                continue
            thr = np.float32(np.clip(t.threshold[i], -fmax, fmax))
            grids.setdefault(f, set()).add(float(thr))
    k_grid = max([len(v) for v in grids.values()] or [1])
    if k_grid > QUANT_MAX_CODES:
        raise QuantRefused(
            "int8 layout needs <= %d distinct split thresholds per "
            "feature; this forest uses %d (trained with max_bin > 256?)"
            % (QUANT_MAX_CODES, k_grid))
    if base is None:
        return None
    grid = np.full((n_feat, k_grid), np.inf, np.float32)
    sorted_grids = {}
    for f, vals in grids.items():
        sv = np.sort(np.asarray(list(vals), np.float32))
        sorted_grids[f] = sv
        grid[f, :len(sv)] = sv
    miss_nan = np.zeros(n_feat, bool)
    miss_zero = np.zeros(n_feat, bool)
    for f, mt in miss.items():
        miss_nan[f] = mt == MISSING_NAN
        miss_zero[f] = mt == MISSING_ZERO

    t_count, max_m = base.feat.shape
    thr_code = np.zeros((t_count, max_m), np.float32)
    lo = np.zeros((t_count, max_m), np.float32)
    for ti, t in enumerate(trees):
        for i in range(max(t.num_leaves - 1, 0)):
            if t.is_categorical_node(i):
                # decision comes from the cat table; park the code
                # compare on "never left" so the is_cat select is the
                # only voice (thr_code 0 < any real code)
                thr_code[ti, i] = 0.0
                lo[ti, i] = 0.0
                continue
            f = int(t.split_feature[i])
            thr = np.float32(np.clip(t.threshold[i], -fmax, fmax))
            thr_code[ti, i] = 1.0 + int(np.searchsorted(sorted_grids[f], thr))
            lo[ti, i] = -2.0 if t.default_left_node(i) else 0.0

    # numeric missing-typed splits are what the -1 sentinel exists for;
    # without any, the coding pass skips special detection entirely
    # (cat nodes resolve through the cat table, not the code compare)
    has_special = any(
        mt != MISSING_NONE for f, mt in miss.items()
        if f in grids) if grids else False
    return QuantForest(
        feat=base.feat, thr_code=jnp.asarray(thr_code), lo=jnp.asarray(lo),
        path=base.path.astype(jnp.bfloat16), leaf_depth=base.leaf_depth,
        leaf_value=base.leaf_value.astype(jnp.float16),
        is_cat=base.is_cat, cat_table=base.cat_table.astype(jnp.bfloat16),
        grid=jnp.asarray(grid),
        miss_nan=jnp.asarray(miss_nan) if has_special else None,
        miss_zero=jnp.asarray(miss_zero) if has_special else None,
        cat_cols=base.cat_cols,
        cat_off=base.cat_off, cat_card=base.cat_card)


def quant_codes(qf: QuantForest, data: jnp.ndarray):
    """(codes[N, F], nan_mask, clean): the fixed-point coding pass.
    Missing rows (per _decide_raw's per-feature missing type) code to
    -1; NaN under MissingType::None codes as 0.0 (the fval_safe
    substitution); everything else codes to 1 + #{bounds < x}, so
    `code ≤ thr_code` reproduces `value ≤ bound` bit-exactly."""
    nan_mask = jnp.isnan(data)
    clean = jnp.where(nan_mask, 0.0, data)
    n_feat = qf.grid.shape[0]
    x = clean[:, :n_feat]
    codes = 1.0 + (x[:, :, None] > qf.grid[None, :, :]).sum(
        -1, dtype=jnp.int32).astype(jnp.float32)
    if qf.miss_nan is not None:
        # only forests that actually carry missing-typed numeric splits
        # pay for the special-row detection (miss_nan is None otherwise)
        is_nan = nan_mask[:, :n_feat]
        special = ((qf.miss_nan[None, :] & is_nan)
                   | (qf.miss_zero[None, :]
                      & (is_nan | (jnp.abs(x) <= K_ZERO_THRESHOLD))))
        codes = jnp.where(special, -1.0, codes)
    if n_feat < data.shape[1]:
        pad = jnp.ones((data.shape[0], data.shape[1] - n_feat), jnp.float32)
        codes = jnp.concatenate([codes, pad], axis=1)
    return codes, nan_mask, clean


def _one_tree_match_quant(tree, codes, expanded=None):
    """[N, L] exact one-hot leaf membership through the code-space
    decision (tree = per-tree slice of a QuantForest)."""
    f = codes.shape[1]
    onehot = (jnp.arange(f, dtype=jnp.int32)[:, None]
              == tree.feat[None, :]).astype(jnp.float32)     # [F, M]
    # default precision: codes are integers ≤ 256 (exact in bf16
    # products) and each reduction has exactly one nonzero term — no
    # HIGHEST multi-pass needed, unlike the raw-value selection
    fsel = jnp.einsum("nf,fm->nm", codes, onehot,
                      preferred_element_type=jnp.float32)
    go_left = (fsel <= tree.thr_code[None, :]) \
        & (fsel >= tree.lo[None, :])
    D = jnp.where(go_left, 1.0, -1.0).astype(jnp.bfloat16)   # [N, M]
    if expanded is not None:
        dcat = jnp.einsum("nv,vm->nm", expanded, tree.cat_table,
                          preferred_element_type=jnp.float32)
        dcat = jnp.where(dcat > 0.5, 1.0, -1.0).astype(jnp.bfloat16)
        D = jnp.where(tree.is_cat[None, :], dcat, D)
    S = jnp.einsum("nm,ml->nl", D, tree.path,
                   preferred_element_type=jnp.float32)       # [N, L]
    return S == tree.leaf_depth[None, :]


def _leaf_value_reduce(match, leaf_value):
    """[N] leaf-value pick from a one-hot [N, L] match via select+sum.

    Numerically identical to the HIGHEST `match @ leaf_value` einsum
    (the sum has exactly one nonzero term, and f32 adds of zeros are
    exact) but measured 4x cheaper on the CPU backend, where the
    match-cast einsum lowered to a scalar loop. The quantized layouts
    use this form; the f32 layout keeps its frozen einsum kernel."""
    return jnp.where(match, leaf_value[None, :].astype(jnp.float32),
                     0.0).sum(-1)


def predict_forest_quant(qf: QuantForest, data: jnp.ndarray,
                         tree_batch: int = 10) -> jnp.ndarray:
    """Sum of all trees' outputs per row through the fixed-point layout
    (see QuantForest) — the same scanned tree-batch structure as
    predict_forest_raw_matmul."""
    codes, nan_mask, clean = quant_codes(qf, data)
    expanded = _cat_expansion_spec(qf.cat_table.shape[1], qf.cat_cols,
                                   qf.cat_off, qf.cat_card, nan_mask, clean)
    batched = _tree_batches(qf, tree_batch,
                            forest_fields=_QUANT_FOREST_LEVEL_FIELDS)

    def body(acc, trees):
        def one(tree):
            match = _one_tree_match_quant(tree, codes, expanded)
            return _leaf_value_reduce(match, tree.leaf_value)

        return acc + jax.vmap(one)(trees).sum(axis=0), None

    init = jnp.zeros(data.shape[0], jnp.float32)
    out, _ = jax.lax.scan(body, init, batched)
    return out


def _one_tree_match_f16(tree, nan_mask, clean, expanded=None):
    """_one_tree_match for the f16 layout: identical raw-space f32
    threshold compares, but when the forest has no missing-typed
    numeric splits (`tree.missing is None`, the common case for models
    trained on NaN-free data) the NaN-mask selection einsum and the
    missing-resolution chain are skipped — NaNs already behave as 0.0
    through the `clean` substitution, exactly _decide_raw's
    MissingType::None semantics."""
    if tree.missing is not None:
        return _one_tree_match(tree, nan_mask, clean, expanded)
    f = clean.shape[1]
    onehot = (jnp.arange(f, dtype=jnp.int32)[:, None]
              == tree.feat[None, :]).astype(jnp.float32)      # [F, M]
    fsel = jnp.einsum("nf,fm->nm", clean, onehot,
                      preferred_element_type=jnp.float32,
                      precision=jax.lax.Precision.HIGHEST)
    D = jnp.where(fsel <= tree.threshold[None, :], 1.0, -1.0) \
        .astype(jnp.bfloat16)                                 # [N, M]
    if expanded is not None:
        dcat = jnp.einsum("nv,vm->nm", expanded,
                          tree.cat_table.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
        dcat = jnp.where(dcat > 0.5, 1.0, -1.0).astype(jnp.bfloat16)
        D = jnp.where(tree.is_cat[None, :], dcat, D)
    S = jnp.einsum("nm,ml->nl", D, tree.path.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)        # [N, L]
    return S == tree.leaf_depth[None, :]


def predict_forest_f16(mf: MatmulForest, data: jnp.ndarray,
                       tree_batch: int = 10) -> jnp.ndarray:
    """predict_forest_raw_matmul for the f16 quantized layout (f16 leaf
    values, bf16 path/cat tables, `missing=None` when the forest has no
    missing-typed numeric splits). Split decisions stay bit-exact; the
    leaf-value reduction uses the select+sum form."""
    nan_mask = jnp.isnan(data)
    clean = jnp.where(nan_mask, 0.0, data)
    expanded = _cat_expansion(mf, nan_mask, clean)
    batched = _tree_batches(mf, tree_batch)

    def body(acc, trees):
        def one(tree):
            match = _one_tree_match_f16(tree, nan_mask, clean, expanded)
            return _leaf_value_reduce(match, tree.leaf_value)

        return acc + jax.vmap(one)(trees).sum(axis=0), None

    init = jnp.zeros(data.shape[0], jnp.float32)
    out, _ = jax.lax.scan(body, init, batched)
    return out


def predict_forest_raw_early_stop(stacked_kt: DeviceTree, data: jnp.ndarray,
                                  margin: float, freq: int) -> jnp.ndarray:
    """Per-row margin-based prediction early stop
    (reference: prediction_early_stop.cpp:22-68 + the round-period loop in
    GBDT::PredictRaw, gbdt_prediction.cpp:9-27).

    stacked_kt: DeviceTree whose leaves have leading dims [K, T] — K =
    num_tree_per_iteration (classes), T = iterations. A `lax.while_loop`
    walks iterations; rows whose margin exceeded the threshold at the last
    period check are frozen (their partial sum is the final answer, exactly
    the reference semantics), and the loop exits outright once EVERY row is
    frozen — the TPU-shaped version of the reference's per-row break.

    Margins: K == 1 -> 2*|pred| (binary); K >= 2 -> top1 - top2
    (multiclass). Returns [K, N] raw scores."""
    k, t_total = stacked_kt.split_feature.shape[:2]
    n = data.shape[0]

    def cond(st):
        t, _, active = st
        return (t < t_total) & jnp.any(active)

    def body(st):
        t, acc, active = st
        trees_t = jax.tree.map(lambda a: a[:, t], stacked_kt)
        preds = jax.vmap(lambda tr: predict_value_raw(tr, data))(trees_t)
        acc = acc + jnp.where(active[None, :], preds, 0.0)
        t = t + 1

        def check(a):
            if k == 1:
                m = 2.0 * jnp.abs(acc[0])
            else:
                top2 = jax.lax.top_k(acc.T, 2)[0]
                m = top2[:, 0] - top2[:, 1]
            return a & (m <= margin)

        active = jax.lax.cond(t % freq == 0, check, lambda a: a, active)
        return (t, acc, active)

    init = (jnp.int32(0), jnp.zeros((k, n), jnp.float32), jnp.ones(n, bool))
    _, acc, _ = jax.lax.while_loop(cond, body, init)
    return acc
