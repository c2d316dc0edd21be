"""The batched-prefetch grower must produce IDENTICAL trees for every
batch_k — batch_k=1 is the one-histogram-pass-per-split sequential
baseline, larger batch_k only prefetches the same computations earlier
(learner/grow.py). Mirrors the reference guarantee that histogram caching
strategy never changes the grown tree (HistogramPool is a pure cache,
feature_histogram.hpp:380-548)."""
import numpy as np
import pytest

import jax.numpy as jnp

import lightgbm_tpu as lgb
import lightgbm_tpu.basic  # noqa: F401  (`lgb.basic` resolves only once imported)
from lightgbm_tpu.learner.grow import GrowerConfig, grow_tree


def _grow(ds, g, h, batch_k, num_leaves=63):
    from lightgbm_tpu.learner.grow import FMETA_KEYS
    fm = {k: jnp.asarray(v) for k, v in ds.feature_meta_arrays().items()}
    cfg = GrowerConfig(
        num_leaves=num_leaves, max_bins=int(ds.max_num_bin()), chunk=2048,
        lambda_l1=0.0, lambda_l2=1.0, min_gain_to_split=0.0,
        min_data_in_leaf=20, min_sum_hessian_in_leaf=1e-3, max_depth=-1,
        batch_k=batch_k)
    return grow_tree(
        jnp.asarray(ds.binned), g, h, jnp.ones_like(g),
        jnp.ones(ds.num_features, bool), *[fm[k] for k in FMETA_KEYS], cfg)


@pytest.mark.parametrize("batch_k", [8, 32])
def test_batched_grower_identical_trees(batch_k):
    rng = np.random.RandomState(7)
    n = 4096
    X = np.asarray(rng.randn(n, 10), np.float32)
    X[rng.rand(n, 10) < 0.05] = np.nan   # exercise missing routing
    y = (np.nan_to_num(X[:, 0]) + np.nan_to_num(X[:, 1]) ** 2
         + 0.3 * rng.randn(n)).astype(np.float32)
    ds = lgb.basic.Dataset(X, y)._lazy_init()
    g = jnp.asarray(-y)
    h = jnp.ones_like(g)

    ref = _grow(ds, g, h, batch_k=1)
    out = _grow(ds, g, h, batch_k=batch_k)

    assert int(out.num_leaves_used) == int(ref.num_leaves_used) > 10
    np.testing.assert_array_equal(np.asarray(ref.node_feature),
                                  np.asarray(out.node_feature))
    np.testing.assert_array_equal(np.asarray(ref.node_threshold),
                                  np.asarray(out.node_threshold))
    np.testing.assert_array_equal(np.asarray(ref.leaf_id),
                                  np.asarray(out.leaf_id))
    np.testing.assert_array_equal(np.asarray(ref.leaf_value),
                                  np.asarray(out.leaf_value))
    # and it must actually batch: far fewer data passes than splits
    assert int(out.num_passes) < int(ref.num_passes) // 2

def _grow_cfg(ds, g, h, weight=None, num_leaves=63, **kw):
    from lightgbm_tpu.learner.grow import FMETA_KEYS
    fm = {k: jnp.asarray(v) for k, v in ds.feature_meta_arrays().items()}
    cfg = GrowerConfig(
        num_leaves=num_leaves, max_bins=int(ds.max_num_bin()), chunk=512,
        lambda_l1=0.0, lambda_l2=1.0, min_gain_to_split=0.0,
        min_data_in_leaf=20, min_sum_hessian_in_leaf=1e-3, max_depth=-1,
        **kw)
    w = jnp.ones_like(g) if weight is None else weight
    return grow_tree(
        jnp.asarray(ds.binned), g, h, w,
        jnp.ones(ds.num_features, bool), *[fm[k] for k in FMETA_KEYS], cfg)


def _int_friendly_case(n=4096, f=10, seed=7, bag=False):
    """Gradients on a coarse binary grid: every per-row product is
    bf16-exact (hi/lo residual 0) and every partial sum is an exact f32
    integer multiple, so histogram sums are identical for ANY summation
    order — subtraction and compaction must then give bit-identical
    trees, not merely close ones."""
    rng = np.random.RandomState(seed)
    X = np.asarray(rng.randn(n, f), np.float32)
    X[rng.rand(n, f) < 0.05] = np.nan
    y = (np.nan_to_num(X[:, 0]) + np.nan_to_num(X[:, 1]) ** 2
         + 0.3 * rng.randn(n)).astype(np.float32)
    ds = lgb.basic.Dataset(X, y)._lazy_init()
    g = jnp.asarray(np.clip(np.round(-y * 4) / 4, -8, 8))
    h = jnp.ones_like(g)
    w = jnp.asarray((rng.rand(n) < 0.8).astype(np.float32)) if bag else None
    return ds, g, h, w


def _assert_same_tree(a, b):
    assert int(a.num_leaves_used) == int(b.num_leaves_used)
    for field in ("node_feature", "node_threshold", "node_default_left",
                  "leaf_id", "leaf_value"):
        np.testing.assert_array_equal(np.asarray(getattr(a, field)),
                                      np.asarray(getattr(b, field)))


def test_sibling_subtraction_identical_trees():
    """hist_subtract builds only the smaller child per expansion and
    derives the larger as parent - smaller (the reference's
    FeatureHistogram::Subtract, feature_histogram.hpp:64-70); on
    order-invariant sums the grown tree must be bit-identical."""
    ds, g, h, _ = _int_friendly_case()
    base = _grow_cfg(ds, g, h, batch_k=8)
    sub = _grow_cfg(ds, g, h, batch_k=8, hist_subtract=True)
    _assert_same_tree(base, sub)
    assert int(base.num_leaves_used) > 10


def test_speculation_throttle_keeps_passes_bounded():
    """Late-boosting gain landscapes are flat/noisy; without the
    budget-aware speculation throttle (grow.py expand()) the node table
    fills with never-committed expansions and passes degrade to ~one
    commit each (measured 18 -> 145 passes/tree by iteration 100 at 2M
    rows). Noisy labels reproduce the flat-gain regime: the tree must
    still grow in far fewer passes than commits, bit-identically to the
    sequential grower."""
    rng = np.random.RandomState(11)
    n, f = 8192, 10
    X = np.asarray(rng.randn(n, f), np.float32)
    y = rng.randn(n).astype(np.float32)          # pure noise gains
    ds = lgb.basic.Dataset(X, y)._lazy_init()
    g = jnp.asarray(np.round(-y * 4) / 4)
    h = jnp.ones_like(g)
    out = _grow_cfg(ds, g, h, batch_k=8, num_leaves=255,
                    hist_subtract=True)
    ref = _grow_cfg(ds, g, h, batch_k=1, num_leaves=255)
    _assert_same_tree(ref, out)
    commits = int(out.num_leaves_used) - 1
    assert commits > 100
    assert int(out.num_passes) < commits // 2
    # and the table must not have been exhausted by mis-speculation
    m_cap = 6 * 255 + 2 * 8 + 2
    assert int(out.next_free) < m_cap - 2 * (255 - int(out.num_leaves_used))


def test_subtraction_with_bagging_weights():
    """Out-of-bag (weight 0) rows still route (their leaf ids feed the
    final score update); bagged runs must stay bit-identical with
    subtraction on."""
    ds, g, h, w = _int_friendly_case(bag=True)
    base = _grow_cfg(ds, g, h, weight=w, batch_k=8)
    both = _grow_cfg(ds, g, h, weight=w, batch_k=8, hist_subtract=True)
    _assert_same_tree(base, both)


@pytest.mark.parametrize("frac", [0.25, 1.0])
@pytest.mark.parametrize("subtract", [False, True])
def test_compaction_identical_trees(frac, subtract):
    """The gather-compacted path (hist_compact) contracts only the
    selected nodes' member rows; on order-invariant sums the grown tree
    must be bit-identical to the full-pass grower for ANY threshold —
    each case compares against the path fully OFF: 0.25 is the default
    switch (mixed full/compacted passes), 1.0 forces EVERY pass through
    the gather — and composed with sibling subtraction."""
    ds, g, h, _ = _int_friendly_case()
    base = _grow_cfg(ds, g, h, batch_k=8, hist_subtract=subtract)
    comp = _grow_cfg(ds, g, h, batch_k=8, hist_subtract=subtract,
                     hist_compact=True, compact_fraction=frac)
    _assert_same_tree(base, comp)
    assert int(base.num_leaves_used) > 10
    if frac >= 1.0:
        # forced: every expansion pass gathered, so the total contracted
        # rows must undercut the full-pass economics
        assert float(comp.rows_contracted) < float(base.rows_contracted)


def test_compaction_with_bagging_weights():
    """Zero-weight (out-of-bag) rows are EXCLUDED from the compaction
    buffer (they contribute zero to every channel either way), so bagged
    nodes compact earlier; trees must stay bit-identical."""
    ds, g, h, w = _int_friendly_case(bag=True)
    base = _grow_cfg(ds, g, h, weight=w, batch_k=8)
    comp = _grow_cfg(ds, g, h, weight=w, batch_k=8,
                     hist_compact=True, compact_fraction=1.0)
    _assert_same_tree(base, comp)
    both = _grow_cfg(ds, g, h, weight=w, batch_k=8, hist_subtract=True,
                     hist_compact=True)
    _assert_same_tree(base, both)


def test_compaction_efb_group_widths():
    """The gathered kernel must honor the same static group-width block
    plan as the full-pass kernels: one-hot exclusive feature blocks
    bundle under EFB, giving a stored-group matrix with heterogeneous
    widths."""
    rng = np.random.RandomState(13)
    n, blocks = 2048, 6
    X = np.zeros((n, blocks * 8 + 4), np.float32)
    for b in range(blocks):  # one-hot blocks: EFB bundles each to 1 group
        pick = rng.randint(0, 8, size=n)
        X[np.arange(n), b * 8 + pick] = rng.rand(n).astype(np.float32) + 0.1
    X[:, blocks * 8:] = rng.randn(n, 4).astype(np.float32)
    y = (X[:, 0] - X[:, 9] + X[:, blocks * 8] * 2
         + 0.1 * rng.randn(n)).astype(np.float32)
    ds = lgb.basic.Dataset(X, y)._lazy_init()
    assert ds.num_groups < ds.num_features  # bundling actually happened
    gw = tuple(int(b) for b in ds.groups.group_num_bin)
    g = jnp.asarray(np.round(-y * 4) / 4)
    h = jnp.ones_like(g)
    base = _grow_cfg(ds, g, h, num_leaves=31, batch_k=8, group_widths=gw)
    comp = _grow_cfg(ds, g, h, num_leaves=31, batch_k=8, group_widths=gw,
                     hist_compact=True, compact_fraction=1.0)
    _assert_same_tree(base, comp)
    assert int(base.num_leaves_used) > 5


def test_rows_contracted_economics_on_deep_tree():
    """On a deep 255-leaf tree the compacted path's late passes contract
    an ever-shrinking row count: the `rows_contracted`/`pass_rows`
    counters must show the full-pass grower at exactly passes * N while
    the compacted grower undercuts it, with a strictly decreasing tail
    of small passes summing to less than N/2 (the late-tree regime the
    optimization exists for)."""
    rng = np.random.RandomState(11)
    n, f = 8192, 10
    X = np.asarray(rng.randn(n, f), np.float32)
    y = rng.randn(n).astype(np.float32)
    ds = lgb.basic.Dataset(X, y)._lazy_init()
    g = jnp.asarray(np.round(-y * 4) / 4)
    h = jnp.ones_like(g)
    base = _grow_cfg(ds, g, h, batch_k=8, num_leaves=255,
                     hist_subtract=True)
    comp = _grow_cfg(ds, g, h, batch_k=8, num_leaves=255,
                     hist_subtract=True, hist_compact=True)
    _assert_same_tree(base, comp)
    assert int(comp.num_leaves_used) == 255
    passes = int(comp.num_passes)
    # old economics: every pass contracts all N rows
    assert int(base.rows_contracted) == int(base.num_passes) * n
    # new economics: a real discount, recorded per pass
    assert float(comp.rows_contracted) < 0.75 * float(base.rows_contracted)
    pr = np.asarray(comp.pass_rows)[:passes]
    assert pr[0] == n                       # root pass is always full
    compacted = pr[pr <= n // 4]
    assert len(compacted) >= 10             # late tree mostly compacts
    # the end-of-tree tail contracts a strictly decreasing row count,
    # totalling under N/2 where the old path would report ~7 full N
    tail = pr[-5:]
    assert np.all(np.diff(tail) < 0)
    assert pr[-7:].sum() < n // 2
    assert pr[-1] < n // 16


def test_subtraction_respects_padding_suffix():
    """Padding rows (beyond n_valid) contribute nothing; real-row trees
    must be unchanged under subtraction + padding."""
    from lightgbm_tpu.learner.grow import FMETA_KEYS
    ds, g, h, _ = _int_friendly_case(n=3072)
    n, pad = 3072, 1024
    fm = {k: jnp.asarray(v) for k, v in ds.feature_meta_arrays().items()}
    binned_p = np.pad(np.asarray(ds.binned), ((0, pad), (0, 0)))
    gp = jnp.asarray(np.pad(np.asarray(g), (0, pad)))
    hp = jnp.asarray(np.pad(np.asarray(h), (0, pad)))
    wp = jnp.asarray(np.pad(np.ones(n, np.float32), (0, pad)))
    cfg = GrowerConfig(
        num_leaves=63, max_bins=int(ds.max_num_bin()), chunk=512,
        lambda_l1=0.0, lambda_l2=1.0, min_gain_to_split=0.0,
        min_data_in_leaf=20, min_sum_hessian_in_leaf=1e-3, max_depth=-1,
        batch_k=8, hist_subtract=True)
    out = grow_tree(jnp.asarray(binned_p), gp, hp, wp,
                    jnp.ones(ds.num_features, bool),
                    *[fm[k] for k in FMETA_KEYS], cfg,
                    n_valid=jnp.int32(n))
    base = _grow_cfg(ds, g, h, batch_k=8)
    assert int(out.num_leaves_used) == int(base.num_leaves_used)
    np.testing.assert_array_equal(np.asarray(out.node_feature),
                                  np.asarray(base.node_feature))
    np.testing.assert_array_equal(np.asarray(out.leaf_id)[:n],
                                  np.asarray(base.leaf_id))
