"""Piecewise-linear leaves (`linear_tree`, lightgbm_tpu/linear/).

Pins the subsystem's contracts end to end:

- off-mode is byte-identical: `linear_tree=false` produces exactly the
  model text the default path produces, with no linear sections;
- the post-growth fit is schedule-invariant: the data-parallel scatter
  grower's state feeds the SAME fit program and yields bitwise-identical
  coefficients to the serial grower (child process, 2 forced host
  devices, same harness as test_scatter_reduce);
- text round trip is exact and exported artifacts (format 2) replay
  bit-identically, while constant forests keep format 1;
- every refusal is named: SHAP, plotting, quantized serving layouts,
  dart, multiclass, and continued training without raw features.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import log

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BASE = {"objective": "regression", "num_leaves": 15, "learning_rate": 0.5,
        "min_data_in_leaf": 5, "max_bin": 63, "verbose": -1}
ROUNDS = 10


def _linear_problem(n=800, f=6, seed=3):
    """A steep slope on one feature plus a step on another: the split
    features ARE the regression features (leaf regressions see only
    path features), so one linear leaf expresses exactly what constant
    leaves must staircase."""
    rng = np.random.RandomState(seed)
    X = rng.uniform(-1.0, 1.0, (n, f))
    y = 4.0 * X[:, 1] + 2.0 * (X[:, 0] > 0) + 0.05 * rng.randn(n)
    return X.astype(np.float32), y.astype(np.float32)


@pytest.fixture(scope="module")
def trained():
    """(X, y, constant-leaf booster, linear booster) on one shared
    shape so every test rides the same compiled programs."""
    X, y = _linear_problem()
    const = lgb.train(dict(BASE), lgb.Dataset(X, y, params=dict(BASE)),
                      num_boost_round=ROUNDS, verbose_eval=False)
    lin_params = dict(BASE, linear_tree=True, linear_lambda=0.01)
    linear = lgb.train(lin_params,
                       lgb.Dataset(X, y, params=dict(lin_params)),
                       num_boost_round=ROUNDS, verbose_eval=False)
    return X, y, const, linear


# ---------------------------------------------------------------------------
# off-mode identity + fit quality
# ---------------------------------------------------------------------------
def test_off_mode_byte_identical_and_sectionless(trained):
    """linear_tree=false must be the SAME code path as not mentioning
    linear_tree at all: identical model text, no linear sections."""
    X, y, const, _ = trained
    p = dict(BASE, linear_tree=False)
    off = lgb.train(p, lgb.Dataset(X, y, params=dict(p)),
                    num_boost_round=ROUNDS, verbose_eval=False)
    assert off.model_to_string() == const.model_to_string()
    assert "tpu_leaf_coeff" not in const.model_to_string()


def test_linear_beats_constant_on_linear_data(trained):
    X, y, const, linear = trained
    mse_c = float(np.mean((const.predict(X) - y) ** 2))
    mse_l = float(np.mean((linear.predict(X) - y) ** 2))
    assert mse_l < 0.5 * mse_c, (mse_l, mse_c)
    assert any(getattr(m, "is_linear", False) for m in linear._inner.models)


# ---------------------------------------------------------------------------
# serialization: text round trip + exported artifacts
# ---------------------------------------------------------------------------
def test_text_round_trip_bit_exact(trained):
    X, _, _, linear = trained
    s = linear.model_to_string()
    assert "tpu_leaf_coeff" in s and "tpu_leaf_features" in s
    clone = lgb.Booster(model_str=s)
    assert clone.model_to_string() == s
    np.testing.assert_array_equal(linear.predict(X), clone.predict(X))


def test_export_format2_round_trip_and_const_stays_format1(trained,
                                                          tmp_path):
    from lightgbm_tpu.export import (FORMAT_VERSION, FORMAT_VERSION_LINEAR,
                                     load_artifact, read_manifest)
    X, _, const, linear = trained
    lpath = str(tmp_path / "linear.artifact")
    linear.export_forest(lpath, layouts=["none"])
    manifest = read_manifest(lpath)
    assert manifest["format"] == FORMAT_VERSION_LINEAR
    assert manifest["forest"]["linear_tree"] is True
    model = load_artifact(lpath)
    np.testing.assert_array_equal(linear.predict(X[:64]),
                                  model.predict(X[:64]))
    # constant forests must NOT pay the version bump: their artifacts
    # stay byte-compatible with format-1 readers
    cpath = str(tmp_path / "const.artifact")
    const.export_forest(cpath, layouts=["none"])
    cm = read_manifest(cpath)
    assert cm["format"] == FORMAT_VERSION
    assert cm["forest"]["linear_tree"] is False


def test_export_future_format_refused_by_name(trained, tmp_path):
    """A reader must refuse formats newer than it knows, naming the
    manifest section — the same contract that makes format-1-only
    readers refuse today's linear (format 2) artifacts."""
    from lightgbm_tpu.export import (ArtifactError, FORMAT_VERSION_LINEAR,
                                     load_artifact)
    X, _, _, linear = trained
    path = str(tmp_path / "lin.artifact")
    linear.export_forest(path, layouts=["none"])
    blob = open(path, "rb").read()
    patched = blob.replace(
        b'"format": %d,' % FORMAT_VERSION_LINEAR, b'"format": 99,', 1)
    assert patched != blob
    skew = str(tmp_path / "skew.artifact")
    with open(skew, "wb") as fh:
        fh.write(patched)
    with pytest.raises(ArtifactError, match="format"):
        load_artifact(skew)


# ---------------------------------------------------------------------------
# schedule invariance: serial vs data-parallel scatter (child process)
# ---------------------------------------------------------------------------
DIST_CHILD = r"""
import sys
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, {repo!r})
import jax.numpy as jnp
from lightgbm_tpu.learner.grow import (GrowerConfig, grow_tree,
                                       FMETA_KEYS, leaf_path_features)
from lightgbm_tpu.linear.solver import fit_leaves
from lightgbm_tpu.parallel import DataParallelGrower, make_mesh

assert len(jax.devices()) >= 2, len(jax.devices())
N, F, B, L, K = 768, 6, 31, 15, 3
rng = np.random.RandomState(0)
x = rng.uniform(-1.0, 1.0, (N, F)).astype(np.float32)
binned = np.clip((x + 1.0) * 0.5 * B, 0, B - 1).astype(np.uint8)
grad = (x[:, 0] + 0.5 * x[:, 1] + 0.3 * rng.randn(N)).astype(np.float32)
hess = np.ones(N, np.float32)
rw = (rng.rand(N) < 0.8).astype(np.float32)
fmeta = {{
    "num_bin": np.full(F, B, np.int32),
    "missing_type": np.zeros(F, np.int32),
    "default_bin": np.zeros(F, np.int32),
    "is_categorical": np.zeros(F, bool),
    "group": np.arange(F, dtype=np.int32),
    "offset": np.zeros(F, np.int32),
    "is_bundled": np.zeros(F, bool),
}}
fmj = {{k: jnp.asarray(v) for k, v in fmeta.items()}}
cfg = GrowerConfig(num_leaves=L, max_bins=B, chunk=64, lambda_l1=0.0,
                   lambda_l2=0.0, min_gain_to_split=0.0,
                   min_data_in_leaf=2, min_sum_hessian_in_leaf=1e-3,
                   max_depth=-1, hist_subtract=True)
serial = grow_tree(jnp.asarray(binned), jnp.asarray(grad),
                   jnp.asarray(hess), jnp.asarray(rw),
                   jnp.ones(F, bool), *[fmj[k] for k in FMETA_KEYS], cfg)
mesh = make_mesh(num_devices=2, axis_name="data")
scatter = DataParallelGrower(mesh, cfg, axis="data",
                             hist_reduce="scatter")(
    jnp.asarray(binned), jnp.asarray(grad), jnp.asarray(hess),
    jnp.asarray(rw), jnp.ones(F, bool), fmeta)
# the scatter schedule grows the SAME tree structure
for k in ("node_feature", "node_threshold", "node_left", "node_right",
          "leaf_parent", "leaf_id"):
    np.testing.assert_array_equal(np.asarray(getattr(serial, k)),
                                  np.asarray(getattr(scatter, k)),
                                  err_msg=k)
assert int(serial.num_leaves_used) == int(scatter.num_leaves_used) > 2

def fit(state):
    feats = leaf_path_features(state.leaf_parent, state.node_feature,
                               state.node_left, state.node_right,
                               state.num_leaves_used, K)
    lv, lc, fitted = fit_leaves(
        jnp.asarray(x), jnp.asarray(grad), jnp.asarray(hess),
        jnp.asarray(rw), jnp.clip(state.leaf_id, 0, L - 1), feats,
        serial.leaf_value, jnp.float32(0.01), L)
    return (np.asarray(feats), np.asarray(lv), np.asarray(lc),
            np.asarray(fitted))

fs, vs, cs, ds = fit(serial)
fd, vd, cd, dd = fit(scatter)
# ... and feeds the shared fit program to BITWISE-identical output
np.testing.assert_array_equal(fs, fd)
np.testing.assert_array_equal(vs, vd)
np.testing.assert_array_equal(cs, cd)
np.testing.assert_array_equal(ds, dd)
assert np.abs(cs).sum() > 0 and ds.any()
print("LINEAR_DIST_OK")
"""


def test_serial_vs_scatter_bitidentical_fit():
    """2 forced host devices in a child: the scatter grower's state
    yields bitwise-identical leaf regressions to the serial grower."""
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=2"
                        ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run(
        [sys.executable, "-c", DIST_CHILD.format(repo=REPO)],
        env=env, capture_output=True, text=True, timeout=420)
    assert res.returncode == 0, \
        f"linear dist child failed:\n{res.stdout}\n{res.stderr}"
    assert "LINEAR_DIST_OK" in res.stdout


# ---------------------------------------------------------------------------
# named refusals
# ---------------------------------------------------------------------------
def test_shap_refuses_linear_by_name(trained):
    X, _, _, linear = trained
    with pytest.raises(log.LightGBMError, match="linear_tree"):
        linear.predict(X[:16], pred_contrib=True)


def test_plotting_refuses_linear_by_name(trained):
    pytest.importorskip("graphviz")
    _, _, _, linear = trained
    from lightgbm_tpu.plotting import create_tree_digraph
    with pytest.raises(log.LightGBMError, match="linear_tree"):
        create_tree_digraph(linear)


@pytest.mark.parametrize("mode", ["f16", "int8"])
def test_quantized_serving_refuses_linear_by_name(trained, mode):
    X, _, _, linear = trained
    clone = lgb.Booster(model_str=linear.model_to_string(),
                        params={"tpu_predict_quantize": mode,
                                "verbose": -1})
    with pytest.raises(log.LightGBMError, match="linear_tree"):
        clone.predict(X[:16])


def test_dart_and_multiclass_refused_by_name():
    X, y = _linear_problem(n=200)
    p = dict(BASE, linear_tree=True, boosting="dart")
    with pytest.raises(log.LightGBMError, match="linear_tree"):
        lgb.train(p, lgb.Dataset(X, y, params=dict(p)),
                  num_boost_round=2, verbose_eval=False)
    yk = (np.arange(len(y)) % 3).astype(np.float32)
    p = dict(BASE, linear_tree=True, objective="multiclass", num_class=3)
    with pytest.raises(log.LightGBMError, match="linear_tree"):
        lgb.train(p, lgb.Dataset(X, yk, params=dict(p)),
                  num_boost_round=2, verbose_eval=False)


# ---------------------------------------------------------------------------
# continued training + sklearn surface
# ---------------------------------------------------------------------------
def test_continued_training_requires_linear_params(trained):
    X, y, _, linear = trained
    p = dict(BASE)  # no linear_tree: the replay has no raw matrix
    with pytest.raises(log.LightGBMError, match="linear_tree"):
        lgb.train(p, lgb.Dataset(X, y, params=dict(p)),
                  num_boost_round=2, init_model=linear,
                  verbose_eval=False)
    p = dict(BASE, linear_tree=True, linear_lambda=0.01)
    cont = lgb.train(p, lgb.Dataset(X, y, params=dict(p)),
                     num_boost_round=2, init_model=linear,
                     verbose_eval=False)
    assert cont.current_iteration() == ROUNDS + 2
    assert np.isfinite(cont.predict(X[:32])).all()


def test_sklearn_exposes_linear_tree(trained):
    from lightgbm_tpu.sklearn import LGBMRegressor
    X, y, const, _ = trained
    reg = LGBMRegressor(linear_tree=True, linear_lambda=0.01,
                        n_estimators=ROUNDS, num_leaves=15,
                        learning_rate=0.5, min_child_samples=5,
                        max_bin=63, verbose=-1)
    assert reg.get_params()["linear_tree"] is True
    reg.fit(X, y)
    mse_l = float(np.mean((reg.predict(X) - y) ** 2))
    assert mse_l < 0.1, mse_l
    assert "tpu_leaf_coeff" in reg.booster_.model_to_string()
