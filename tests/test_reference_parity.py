"""Accuracy + model-format parity against the REAL reference binary.

Builds /root/reference out-of-tree (cached in /tmp/lgbm_ref_build, same
recipe as scripts/measure_baseline.py), trains both frameworks on the same
synthetic datasets with equal hyperparameters, and asserts:

- metric parity (AUC / L2) within tolerance on binary + regression;
- cross-loading: a reference-written model file predicts identically when
  loaded by this framework;
- cross-loading the other way: a model written here is read by the
  reference CLI and its file predictions match ours.

Skipped when the reference tree or a toolchain is unavailable.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import lightgbm_tpu as lgb

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REFERENCE = "/root/reference"


@pytest.fixture(scope="module")
def ref_exe():
    if not os.path.isdir(REFERENCE):
        pytest.skip("reference tree not present")
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    from measure_baseline import build_reference
    try:
        return build_reference()
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"cannot build reference: {e}")


def _run_ref(ref_exe, workdir, **conf):
    args = [ref_exe] + [f"{k}={v}" for k, v in conf.items()]
    res = subprocess.run(args, cwd=workdir, capture_output=True, text=True,
                         timeout=600)
    assert res.returncode == 0, res.stdout + res.stderr
    return res.stdout


def _auc(y, s):
    order = np.argsort(s)
    ranks = np.empty(len(s))
    ranks[order] = np.arange(1, len(s) + 1)
    pos = y > 0
    return ((ranks[pos].sum() - pos.sum() * (pos.sum() + 1) / 2)
            / (pos.sum() * (~pos).sum()))


def _binary_data(tmp, n=20000, f=12, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f).astype(np.float32)
    score = X[:, 0] * 1.2 - X[:, 1] + 0.8 * X[:, 2] * X[:, 3] \
        + 0.5 * np.abs(X[:, 4])
    y = (score + rng.logistic(size=n) > 0.3).astype(np.float32)
    path = os.path.join(tmp, "bin.train")
    np.savetxt(path, np.column_stack([y, X]), fmt="%.6g", delimiter="\t")
    return X, y, path


PARAMS = dict(num_leaves=31, max_bin=63, learning_rate=0.1,
              min_data_in_leaf=20, min_sum_hessian_in_leaf=1e-3)


def test_binary_auc_parity(ref_exe, tmp_path):
    tmp = str(tmp_path)
    X, y, data_path = _binary_data(tmp)
    iters = 30

    ref_model = os.path.join(tmp, "ref_model.txt")
    _run_ref(ref_exe, tmp, task="train", objective="binary", data=data_path,
             num_trees=iters, output_model=ref_model, verbosity=-1,
             **PARAMS)
    ref_pred_file = os.path.join(tmp, "ref_preds.txt")
    _run_ref(ref_exe, tmp, task="predict", data=data_path,
             input_model=ref_model, output_result=ref_pred_file,
             verbosity=-1)
    ref_preds = np.loadtxt(ref_pred_file)

    # both frameworks must see the exact same values: what the reference
    # CLI trained/predicted on is the PARSED text file, not the raw array
    from lightgbm_tpu.io.parser import load_data_file
    Xp, yp = load_data_file(data_path)
    ours = lgb.train(dict(objective="binary", verbose=-1, **PARAMS),
                     lgb.Dataset(Xp, yp, params=dict(PARAMS)),
                     num_boost_round=iters, verbose_eval=False)
    our_preds = ours.predict(Xp)

    auc_ref = _auc(y, ref_preds)
    auc_ours = _auc(y, our_preds)
    # same-data training AUC within 0.5% of the reference binary
    assert abs(auc_ref - auc_ours) < 5e-3, (auc_ref, auc_ours)

    # cross-load: reference-written model through OUR loader
    loaded = lgb.Booster(model_file=ref_model)
    cross = loaded.predict(Xp)
    np.testing.assert_allclose(cross, ref_preds, rtol=1e-4, atol=1e-5)

    # cross-load the other way: OUR model through the reference CLI
    our_model = os.path.join(tmp, "our_model.txt")
    ours.save_model(our_model)
    out_pred_file = os.path.join(tmp, "ours_via_ref.txt")
    _run_ref(ref_exe, tmp, task="predict", data=data_path,
             input_model=our_model, output_result=out_pred_file,
             verbosity=-1)
    via_ref = np.loadtxt(out_pred_file)
    np.testing.assert_allclose(via_ref, our_preds, rtol=1e-4, atol=1e-5)


def test_regression_l2_parity(ref_exe, tmp_path):
    tmp = str(tmp_path)
    rng = np.random.RandomState(1)
    n, f = 20000, 10
    X = rng.randn(n, f).astype(np.float32)
    y = (X[:, 0] * 2 + X[:, 1] ** 2 - X[:, 2] * X[:, 3]
         + 0.2 * rng.randn(n)).astype(np.float32)
    data_path = os.path.join(tmp, "reg.train")
    np.savetxt(data_path, np.column_stack([y, X]), fmt="%.6g",
               delimiter="\t")
    iters = 30

    ref_model = os.path.join(tmp, "ref_model.txt")
    _run_ref(ref_exe, tmp, task="train", objective="regression",
             data=data_path, num_trees=iters, output_model=ref_model,
             verbosity=-1, **PARAMS)
    ref_pred_file = os.path.join(tmp, "ref_preds.txt")
    _run_ref(ref_exe, tmp, task="predict", data=data_path,
             input_model=ref_model, output_result=ref_pred_file,
             verbosity=-1)
    ref_preds = np.loadtxt(ref_pred_file)

    from lightgbm_tpu.io.parser import load_data_file
    Xp, yp = load_data_file(data_path)
    ours = lgb.train(dict(objective="regression", verbose=-1, **PARAMS),
                     lgb.Dataset(Xp, yp, params=dict(PARAMS)),
                     num_boost_round=iters, verbose_eval=False)
    our_preds = ours.predict(Xp)

    mse_ref = float(np.mean((ref_preds - y) ** 2))
    mse_ours = float(np.mean((our_preds - y) ** 2))
    var = float(np.var(y))
    # train L2 within 2% of label variance of each other
    assert abs(mse_ref - mse_ours) < 0.02 * var, (mse_ref, mse_ours)

    # round-trip our regression model through the reference binary
    our_model = os.path.join(tmp, "our_model.txt")
    ours.save_model(our_model)
    out_pred_file = os.path.join(tmp, "ours_via_ref.txt")
    _run_ref(ref_exe, tmp, task="predict", data=data_path,
             input_model=our_model, output_result=out_pred_file,
             verbosity=-1)
    via_ref = np.loadtxt(out_pred_file)
    np.testing.assert_allclose(via_ref, our_preds, rtol=1e-4, atol=1e-4)


def _ndcg_at(y, scores, qsizes, k=10):
    """Mean NDCG@k with 2^rel-1 gains (the reference's definition,
    src/metric/dcg_calculator.cpp) applied identically to both
    frameworks' predictions."""
    out, start = [], 0
    for qs in qsizes:
        rel = y[start:start + qs]
        sc = scores[start:start + qs]
        start += qs
        top = np.argsort(-sc, kind="stable")[:k]
        dcg = float(np.sum((2.0 ** rel[top] - 1) / np.log2(np.arange(len(top)) + 2)))
        ideal = np.sort(rel)[::-1][:k]
        idcg = float(np.sum((2.0 ** ideal - 1) / np.log2(np.arange(len(ideal)) + 2)))
        if idcg > 0:
            out.append(dcg / idcg)
    return float(np.mean(out))


def test_lambdarank_ndcg_parity(ref_exe, tmp_path):
    """MSLR-shaped synthetic ranking: NDCG@10 of both frameworks within
    tolerance at equal params + model cross-load both directions
    (reference floors: docs/GPU-Performance.md:136-144)."""
    tmp = str(tmp_path)
    rng = np.random.RandomState(5)
    nq, qlen, f = 400, 50, 16
    n = nq * qlen
    X = rng.randn(n, f).astype(np.float32)
    true_score = X[:, 0] * 1.5 + X[:, 1] - 0.5 * X[:, 2] * X[:, 3]
    # graded relevance 0..4 per query by true-score quantile
    y = np.zeros(n, np.float32)
    for q in range(nq):
        s = slice(q * qlen, (q + 1) * qlen)
        ranks = np.argsort(np.argsort(-(true_score[s] + rng.randn(qlen))))
        y[s] = np.clip(4 - ranks // 10, 0, 4)
    data_path = os.path.join(tmp, "rank.train")
    np.savetxt(data_path, np.column_stack([y, X]), fmt="%.6g", delimiter="\t")
    with open(data_path + ".query", "w") as fh:
        fh.write("\n".join([str(qlen)] * nq))
    iters = 30
    qsizes = [qlen] * nq

    ref_model = os.path.join(tmp, "ref_model.txt")
    _run_ref(ref_exe, tmp, task="train", objective="lambdarank",
             data=data_path, num_trees=iters, output_model=ref_model,
             verbosity=-1, **PARAMS)
    ref_pred_file = os.path.join(tmp, "ref_preds.txt")
    _run_ref(ref_exe, tmp, task="predict", data=data_path,
             input_model=ref_model, output_result=ref_pred_file,
             verbosity=-1)
    ref_preds = np.loadtxt(ref_pred_file)

    from lightgbm_tpu.io.parser import load_data_file
    Xp, yp = load_data_file(data_path)
    ds = lgb.Dataset(Xp, yp, params=dict(PARAMS))
    ds.set_group(np.asarray(qsizes, np.int32))
    ours = lgb.train(dict(objective="lambdarank", verbose=-1, **PARAMS),
                     ds, num_boost_round=iters, verbose_eval=False)
    our_preds = ours.predict(Xp)

    ndcg_ref = _ndcg_at(y, ref_preds, qsizes)
    ndcg_ours = _ndcg_at(y, our_preds, qsizes)
    # train NDCG@10 within 1% of the reference binary
    assert abs(ndcg_ref - ndcg_ours) < 0.01, (ndcg_ref, ndcg_ours)

    # cross-load both directions
    loaded = lgb.Booster(model_file=ref_model)
    np.testing.assert_allclose(loaded.predict(Xp), ref_preds,
                               rtol=1e-4, atol=1e-5)
    our_model = os.path.join(tmp, "our_model.txt")
    ours.save_model(our_model)
    out_pred_file = os.path.join(tmp, "ours_via_ref.txt")
    _run_ref(ref_exe, tmp, task="predict", data=data_path,
             input_model=our_model, output_result=out_pred_file,
             verbosity=-1)
    np.testing.assert_allclose(np.loadtxt(out_pred_file), our_preds,
                               rtol=1e-4, atol=1e-5)


def test_multiclass_logloss_parity(ref_exe, tmp_path):
    tmp = str(tmp_path)
    rng = np.random.RandomState(7)
    n, f, k = 20000, 10, 5
    X = rng.randn(n, f).astype(np.float32)
    centers = rng.randn(k, f) * 1.5
    logits = X @ centers.T + rng.gumbel(size=(n, k))
    y = np.argmax(logits, axis=1).astype(np.float32)
    data_path = os.path.join(tmp, "mc.train")
    np.savetxt(data_path, np.column_stack([y, X]), fmt="%.6g", delimiter="\t")
    iters = 30

    ref_model = os.path.join(tmp, "ref_model.txt")
    _run_ref(ref_exe, tmp, task="train", objective="multiclass", num_class=k,
             data=data_path, num_trees=iters, output_model=ref_model,
             verbosity=-1, **PARAMS)
    ref_pred_file = os.path.join(tmp, "ref_preds.txt")
    _run_ref(ref_exe, tmp, task="predict", data=data_path,
             input_model=ref_model, output_result=ref_pred_file,
             verbosity=-1)
    ref_preds = np.loadtxt(ref_pred_file)          # [n, k] probabilities

    from lightgbm_tpu.io.parser import load_data_file
    Xp, yp = load_data_file(data_path)
    ours = lgb.train(dict(objective="multiclass", num_class=k, verbose=-1,
                          **PARAMS),
                     lgb.Dataset(Xp, yp, params=dict(PARAMS)),
                     num_boost_round=iters, verbose_eval=False)
    our_preds = ours.predict(Xp)                   # [n, k]

    yi = y.astype(int)
    ll_ref = float(-np.mean(np.log(np.clip(ref_preds[np.arange(n), yi],
                                           1e-15, 1))))
    ll_ours = float(-np.mean(np.log(np.clip(our_preds[np.arange(n), yi],
                                            1e-15, 1))))
    # train softmax logloss within 0.02 of the reference binary
    assert abs(ll_ref - ll_ours) < 0.02, (ll_ref, ll_ours)

    # cross-load both directions
    loaded = lgb.Booster(model_file=ref_model)
    np.testing.assert_allclose(loaded.predict(Xp), ref_preds,
                               rtol=1e-4, atol=1e-5)
    our_model = os.path.join(tmp, "our_model.txt")
    ours.save_model(our_model)
    out_pred_file = os.path.join(tmp, "ours_via_ref.txt")
    _run_ref(ref_exe, tmp, task="predict", data=data_path,
             input_model=our_model, output_result=out_pred_file,
             verbosity=-1)
    np.testing.assert_allclose(np.loadtxt(out_pred_file), our_preds,
                               rtol=1e-4, atol=1e-5)


def test_categorical_feature_parity(ref_exe, tmp_path):
    """Expo-shaped: two integer categorical features drive the label
    (reference benchmark row: docs/GPU-Performance.md:140)."""
    tmp = str(tmp_path)
    rng = np.random.RandomState(9)
    n, ncat = 20000, 24
    # skewed category draw with 0 present but NOT most frequent: the
    # reference's categorical mapper asserts ValueToBin(0) > 0
    # (bin.cpp:367-370) — value 0 must be a seen, non-top category
    probs = np.arange(ncat, 0, -1, dtype=np.float64) ** 1.5
    probs[0] = probs[-1]  # make category 0 rare
    probs /= probs.sum()
    c0 = rng.choice(ncat, n, p=probs)
    c1 = rng.choice(ncat, n, p=probs)
    xnum = rng.randn(n, 4).astype(np.float32)
    eff0 = rng.randn(ncat) * 1.2
    eff1 = rng.randn(ncat)
    score = eff0[c0] + eff1[c1] + 0.5 * xnum[:, 0]
    y = (score + rng.logistic(size=n) > 0.0).astype(np.float32)
    X = np.column_stack([c0, c1, xnum]).astype(np.float32)
    data_path = os.path.join(tmp, "cat.train")
    np.savetxt(data_path, np.column_stack([y, X]), fmt="%.6g", delimiter="\t")
    iters = 30
    cat_cols = "0,1"  # feature indices, label column excluded
                      # (dataset_loader.cpp:506 indexes features)

    ref_model = os.path.join(tmp, "ref_model.txt")
    _run_ref(ref_exe, tmp, task="train", objective="binary", data=data_path,
             num_trees=iters, output_model=ref_model, verbosity=-1,
             categorical_column=cat_cols, **PARAMS)
    ref_pred_file = os.path.join(tmp, "ref_preds.txt")
    _run_ref(ref_exe, tmp, task="predict", data=data_path,
             input_model=ref_model, output_result=ref_pred_file,
             verbosity=-1)
    ref_preds = np.loadtxt(ref_pred_file)

    from lightgbm_tpu.io.parser import load_data_file
    Xp, yp = load_data_file(data_path)
    ours = lgb.train(dict(objective="binary", verbose=-1,
                          categorical_feature="0,1", **PARAMS),
                     lgb.Dataset(Xp, yp, params=dict(
                         PARAMS, categorical_feature="0,1")),
                     num_boost_round=iters, verbose_eval=False)
    our_preds = ours.predict(Xp)

    auc_ref = _auc(y, ref_preds)
    auc_ours = _auc(y, our_preds)
    assert abs(auc_ref - auc_ours) < 5e-3, (auc_ref, auc_ours)

    # categorical bitset thresholds survive the text format both ways
    loaded = lgb.Booster(model_file=ref_model)
    np.testing.assert_allclose(loaded.predict(Xp), ref_preds,
                               rtol=1e-4, atol=1e-5)
    our_model = os.path.join(tmp, "our_model.txt")
    ours.save_model(our_model)
    out_pred_file = os.path.join(tmp, "ours_via_ref.txt")
    _run_ref(ref_exe, tmp, task="predict", data=data_path,
             input_model=our_model, output_result=out_pred_file,
             verbosity=-1)
    np.testing.assert_allclose(np.loadtxt(out_pred_file), our_preds,
                               rtol=1e-4, atol=1e-5)


@pytest.mark.skipif(os.environ.get("LGBM_TPU_SLOW_TESTS") != "1",
                    reason="accuracy floor: set LGBM_TPU_SLOW_TESTS=1 "
                           "(500k rows x 100 iters, run on the TPU)")
def test_binary_accuracy_floor_higgs_scale(ref_exe, tmp_path):
    """BASELINE.md-class floor (VERDICT r2 item 10): 500k rows, 63 bins,
    255 leaves, 100 iterations — train AUC within 5e-4 of the reference
    binary (round-2 measured delta was 3.7e-4; codified so binning/split
    semantics cannot silently regress)."""
    tmp = str(tmp_path)
    X, y, data_path = _binary_data(tmp, n=500_000, f=28, seed=2)
    iters = 100
    params = dict(num_leaves=255, max_bin=63, learning_rate=0.1,
                  min_data_in_leaf=1, min_sum_hessian_in_leaf=100)

    # OUR phase runs first, in a child of its own
    code = subprocess.run(
        [sys.executable, "-c", f'''
import sys
sys.path.insert(0, {REPO!r})
import numpy as np
import lightgbm_tpu as lgb
from lightgbm_tpu.io.parser import load_data_file
Xp, yp = load_data_file({data_path!r})
params = dict(num_leaves=255, max_bin=63, learning_rate=0.1,
              min_data_in_leaf=1, min_sum_hessian_in_leaf=100)
ours = lgb.train(dict(objective="binary", verbose=-1, **params),
                 lgb.Dataset(Xp, yp, params=dict(params)),
                 num_boost_round={iters}, verbose_eval=False)
np.save({tmp!r} + "/our_preds.npy", ours.predict(Xp))
'''], capture_output=True, text=True, timeout=1500)
    assert code.returncode == 0, code.stdout + code.stderr
    our_preds = np.load(os.path.join(tmp, "our_preds.npy"))

    ref_model = os.path.join(tmp, "ref_model.txt")
    _run_ref(ref_exe, tmp, task="train", objective="binary", data=data_path,
             num_trees=iters, output_model=ref_model, verbosity=-1, **params)
    ref_pred_file = os.path.join(tmp, "ref_preds.txt")
    _run_ref(ref_exe, tmp, task="predict", data=data_path,
             input_model=ref_model, output_result=ref_pred_file,
             verbosity=-1)
    ref_preds = np.loadtxt(ref_pred_file)

    auc_ref = _auc(y, ref_preds)
    auc_ours = _auc(y, our_preds)
    assert auc_ours > auc_ref - 5e-4, (auc_ref, auc_ours)
