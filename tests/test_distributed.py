"""Distributed learner tests on the 8-virtual-device CPU mesh — the
in-process N-rank harness the reference lacks (SURVEY.md §4 item 4:
'Distributed testing: none automated' — we fix that)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lightgbm_tpu.dataset import Dataset
from lightgbm_tpu.learner.grow import GrowerConfig, grow_tree
from lightgbm_tpu.parallel import (DataParallelGrower, FeatureParallelGrower,
                                   VotingParallelGrower, make_mesh)


@pytest.fixture(scope="module")
def problem():
    rng = np.random.RandomState(0)
    n, f = 2048, 8
    X = rng.randn(n, f)
    y = (X[:, 0] + 0.5 * X[:, 1] ** 2 + 0.1 * rng.randn(n)).astype(np.float32)
    ds = Dataset.from_numpy(X, y, max_bin=63, min_data_in_bin=1)
    grad = -y
    hess = np.ones(n, np.float32)
    return ds, grad, hess


def _cfg(ds, chunk=256, **kw):
    base = dict(num_leaves=31, max_bins=int(ds.max_num_bin()), chunk=chunk,
                lambda_l1=0.0, lambda_l2=0.0, min_gain_to_split=0.0,
                min_data_in_leaf=20, min_sum_hessian_in_leaf=1e-3, max_depth=-1)
    base.update(kw)
    return GrowerConfig(**base)


def _serial_state(ds, grad, hess):
    from lightgbm_tpu.learner.grow import FMETA_KEYS
    fm = {k: jnp.asarray(v) for k, v in ds.feature_meta_arrays().items()}
    cfg = _cfg(ds)
    return grow_tree(jnp.asarray(ds.binned), jnp.asarray(grad),
                     jnp.asarray(hess), jnp.ones(ds.num_data, jnp.float32),
                     jnp.ones(ds.num_features, bool),
                     *[fm[k] for k in FMETA_KEYS], cfg)


def test_data_parallel_matches_serial(problem):
    ds, grad, hess = problem
    serial = _serial_state(ds, grad, hess)

    mesh = make_mesh(axis_name="data")
    grower = DataParallelGrower(mesh, _cfg(ds), axis="data")
    fm = ds.feature_meta_arrays()
    state = grower(jnp.asarray(ds.binned), jnp.asarray(grad), jnp.asarray(hess),
                   jnp.ones(ds.num_data, jnp.float32),
                   jnp.ones(ds.num_features, bool), fm)

    assert int(state.num_leaves_used) == int(serial.num_leaves_used)
    np.testing.assert_array_equal(np.asarray(state.node_feature),
                                  np.asarray(serial.node_feature))
    np.testing.assert_array_equal(np.asarray(state.node_threshold),
                                  np.asarray(serial.node_threshold))
    np.testing.assert_allclose(np.asarray(state.leaf_value),
                               np.asarray(serial.leaf_value), rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(state.leaf_id),
                                  np.asarray(serial.leaf_id))


def test_feature_parallel_matches_serial(problem):
    ds, grad, hess = problem
    serial = _serial_state(ds, grad, hess)

    mesh = make_mesh(axis_name="feature")
    grower = FeatureParallelGrower(mesh, _cfg(ds), axis="feature")
    fm = ds.feature_meta_arrays()
    binned, fm = grower.pad_features(ds.binned, fm)
    fmask = np.ones(binned.shape[1], bool)
    state = grower(jnp.asarray(binned), jnp.asarray(grad), jnp.asarray(hess),
                   jnp.ones(ds.num_data, jnp.float32), jnp.asarray(fmask), fm)

    assert int(state.num_leaves_used) == int(serial.num_leaves_used)
    np.testing.assert_array_equal(np.asarray(state.node_feature),
                                  np.asarray(serial.node_feature))
    np.testing.assert_array_equal(np.asarray(state.node_threshold),
                                  np.asarray(serial.node_threshold))
    np.testing.assert_allclose(np.asarray(state.leaf_value),
                               np.asarray(serial.leaf_value), rtol=1e-4, atol=1e-5)


def test_voting_parallel_runs(problem):
    ds, grad, hess = problem
    mesh = make_mesh(axis_name="data")
    grower = VotingParallelGrower(mesh, _cfg(ds), axis="data")
    fm = ds.feature_meta_arrays()
    state = grower(jnp.asarray(ds.binned), jnp.asarray(grad), jnp.asarray(hess),
                   jnp.ones(ds.num_data, jnp.float32),
                   jnp.ones(ds.num_features, bool), fm)
    assert int(state.num_leaves_used) > 1


def test_distributed_training_end_to_end():
    """Full GBDT training with tree_learner=data on the mesh."""
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(1)
    n = 1024
    X = rng.randn(n, 6)
    y = (X[:, 0] > 0.3).astype(float)
    params = {"objective": "binary", "tree_learner": "data",
              "num_machines": 8, "verbose": -1}
    gbm = lgb.train(params, lgb.Dataset(X, y), num_boost_round=10,
                    verbose_eval=False)
    pred = gbm.predict(X)
    assert np.mean((pred > 0.5) == (y > 0)) > 0.95


def test_feature_parallel_sparse_data_pins_unbundled_behavior(caplog):
    """Feature-parallel + sparse data: EFB is auto-disabled (shards map
    1:1 onto stored columns) with a user-facing warning, the stored
    matrix keeps its full column width, and training still works
    end-to-end. Pins the trade VERDICT r2 weak #5 called out as silent."""
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(11)
    n, f = 1024, 64
    # one-hot-ish sparse block: EFB would bundle these aggressively
    X = np.zeros((n, f), np.float32)
    hot = rng.randint(0, f // 2, n)
    X[np.arange(n), hot] = 1.0
    X[:, f // 2:] = rng.randn(n, f - f // 2)
    y = (X[:, f // 2] + (hot % 3 == 0) > 0.5).astype(np.float32)

    params = {"objective": "binary", "tree_learner": "feature",
              "num_machines": 8, "verbose": -1, "min_data_in_leaf": 5}
    ds = lgb.Dataset(X, y)
    booster = lgb.train(dict(params), ds, num_boost_round=5,
                        verbose_eval=False)
    assert booster.current_iteration() == 5
    # stored width == logical features (no bundling)
    inner = ds._inner
    assert inner.num_groups == inner.num_features == f
    # the SAME data under the serial learner does bundle (the sparse
    # block collapses), proving feature-parallel is what forfeits EFB
    ds2 = lgb.Dataset(X, y, params={"verbose": -1})
    ds2.construct()
    assert ds2._inner.num_groups < f


def test_multiclass_serial_batched_matches_data_parallel():
    """The vmap'd one-program multiclass iteration (serial learner) must
    produce the SAME model as the data-parallel learner's per-class loop
    on the 8-device mesh — cross-validating the two multiclass paths."""
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(5)
    n, f, k = 600, 8, 3
    X = rng.randn(n, f).astype(np.float32)
    y = np.argmax(X[:, :k] + 0.3 * rng.randn(n, k), axis=1).astype(np.float32)

    base = {"objective": "multiclass", "num_class": k, "verbose": -1,
            "num_leaves": 15, "min_data_in_leaf": 5, "tpu_hist_chunk": 128}
    m_serial = lgb.train(dict(base), lgb.Dataset(X, y),
                         num_boost_round=4, verbose_eval=False)
    m_dist = lgb.train(dict(base, tree_learner="data", num_machines=8),
                       lgb.Dataset(X, y), num_boost_round=4,
                       verbose_eval=False)
    # identical tree STRUCTURE (split features/thresholds/children);
    # float reduction order differs between the one-shard program and
    # the 8-shard psum, so gains/values only match to ~1e-6 relative
    s_struct = [l for l in m_serial.model_to_string().splitlines()
                if l.split("=")[0] in ("split_feature", "threshold",
                                       "decision_type", "left_child",
                                       "right_child", "num_leaves")]
    d_struct = [l for l in m_dist.model_to_string().splitlines()
                if l.split("=")[0] in ("split_feature", "threshold",
                                       "decision_type", "left_child",
                                       "right_child", "num_leaves")]
    assert s_struct == d_struct and len(s_struct) > 0
    np.testing.assert_allclose(m_serial.predict(X), m_dist.predict(X),
                               rtol=1e-5, atol=1e-6)


def test_feature_parallel_keeps_narrow_width_plan():
    """The bin-width discount must survive feature sharding: the grower
    plans group blocks at the per-position max width across shards
    (grow.py shard_group_widths), so 15-bin data sharded over features
    still contracts 16-wide blocks, not max_bins-wide ones — and the
    feature-parallel trees stay identical to serial."""
    import numpy as np

    import lightgbm_tpu as lgb
    from lightgbm_tpu.learner.grow import shard_group_widths

    # unit: per-position max across shards
    assert shard_group_widths((16, 16, 16, 16, 16, 16, 16, 16), 2) == \
        (16, 16, 16, 16)
    assert shard_group_widths((4, 8, 16, 2), 2) == (16, 8)

    rng = np.random.RandomState(3)
    # f NOT divisible by the 8-device shard count: pad_features extends
    # the width plan, and the plan the GROWER reads (the dist grower's
    # cfg, captured at construction) must be the padded one
    n, f = 4096, 10
    X = np.round(rng.rand(n, f) * 12).astype(np.float32)  # ~13 bins
    y = (X[:, 0] + X[:, 1] > 12).astype(np.float32)

    def run(learner):
        params = {"objective": "binary", "verbose": -1, "max_bin": 15,
                  "num_leaves": 31, "min_data_in_leaf": 5,
                  "tree_learner": learner, "enable_bundle": False}
        ds = lgb.Dataset(X, y, params=dict(params))
        ds.construct()
        bst = lgb.train(dict(params), ds, num_boost_round=5,
                        verbose_eval=False)
        # the width plan the grower actually consumes must exist, cover
        # the (padded) feature axis, and stay narrow
        grower = bst._inner._dist_grower
        cfg = grower.cfg if grower is not None else bst._inner._grower_cfg
        widths = cfg.group_widths
        assert widths and max(widths) <= 16
        if grower is not None:
            binned_cols = bst._inner._binned.shape[1]
            assert len(widths) == binned_cols
        return bst.predict(X[:400])

    ps = run("serial")
    pf = run("feature")
    np.testing.assert_allclose(ps, pf, atol=1e-5)
