"""One way to run a boosting iteration: a call that cannot pipeline (a
validation set, explicit gradients, `_supports_pipeline` off) runs the
pipelined call's dispatch and flush drained at once. Over the one-class
and multiclass objectives and the serial, data-parallel and
feature-parallel learners on the 8 host devices, the drained model is the
pipelined one where the learner pipelines, and the serial one within the
distributed tests' tolerances where it does not; each iteration writes
one record. Nothing here is a device measurement."""
import functools

import numpy as np
import pytest

import lightgbm_tpu as lgb

ROUNDS = 5
N, F, K = 600, 8, 3
LEARNERS = {"serial": {},
            "data": {"tree_learner": "data", "num_machines": 8},
            "feature": {"tree_learner": "feature", "num_machines": 8}}
STRUCTURE = ("split_feature", "threshold", "decision_type", "left_child",
             "right_child", "num_leaves")


def _table():
    rng = np.random.RandomState(5)
    X = rng.randn(N, F).astype(np.float32)
    y = np.argmax(X[:, :K] + 0.3 * rng.randn(N, K), axis=1).astype(np.float32)
    return X, y


def _params(objective, learner, boost_from_average=True):
    params = {"objective": objective, "verbose": -1, "num_leaves": 15,
              "min_data_in_leaf": 5, "tpu_hist_chunk": 128,
              "boost_from_average": boost_from_average, **LEARNERS[learner]}
    if objective == "multiclass":
        params["num_class"] = K
    return params


def _labels(objective, y):
    return (y == 0).astype(np.float32) if objective == "binary" else y


def _booster(objective, learner, boost_from_average=True):
    X, y = _table()
    params = _params(objective, learner, boost_from_average)
    train = lgb.Dataset(X, _labels(objective, y), params=dict(params))
    return lgb.Booster(dict(params), train), train


@functools.lru_cache(maxsize=None)
def _pipelined(objective, learner, boost_from_average):
    """The model text of ROUNDS pipelined calls: the serial learner's, or
    the data-parallel one-class iteration's (its rows on this process's
    devices)."""
    booster, _ = _booster(objective, learner, boost_from_average)
    inner = booster._inner
    for i in range(ROUNDS):
        assert booster.update() is False
        assert inner._pending_small is not None, "the call pipelines"
    return booster.model_to_string()


def _own_gradients(booster):
    """An fobj that hands back the built-in objective's own gradients."""
    inner = booster._inner
    k, n = inner.num_tree_per_iteration, inner._n

    def fobj(preds, train_set):
        grad, hess = inner._compute_gradients(inner._score)
        return (np.asarray(grad).reshape(k, -1)[:, :n].reshape(-1),
                np.asarray(hess).reshape(k, -1)[:, :n].reshape(-1))
    return fobj


def _structure(text):
    return [line for line in text.splitlines()
            if line.split("=")[0] in STRUCTURE]


@pytest.mark.parametrize("forced_by", ["valid_set", "fobj", "no_pipeline"])
@pytest.mark.parametrize("learner", ["serial", "data", "feature"])
@pytest.mark.parametrize("objective", ["binary", "multiclass"])
def test_a_drained_call_is_the_pipelined_one(objective, learner, forced_by):
    # a custom objective has no bias: the fobj runs without the
    # boost-from-average its pipelined twin would fold
    bfa = forced_by != "fobj"
    booster, train = _booster(objective, learner, bfa)
    inner = booster._inner
    fobj = None
    if forced_by == "valid_set":
        X, y = _table()
        booster.add_valid(lgb.Dataset(X[:200], _labels(objective, y[:200]),
                                      reference=train), "valid")
    elif forced_by == "fobj":
        fobj = _own_gradients(booster)
    else:
        inner._supports_pipeline = False
    for i in range(ROUNDS):
        assert booster.update(fobj=fobj) is False
        # drained: the iteration's trees are in the model on return
        assert inner._pending_small is None
        assert len(inner.models) == inner.num_tree_per_iteration * (i + 1)
        assert len(inner.pass_log) == i + 1
    k = inner.num_tree_per_iteration
    assert all(rec.classes == k for rec in inner.pass_log)
    text = booster.model_to_string()

    if learner == "serial" or (learner == "data" and objective == "binary"):
        assert text == _pipelined(objective, learner, bfa)
        return
    # the learners that do not pipeline here against the serial model:
    # the same tree structure, values to the float reduction order of
    # the 8-shard merge (tests/test_distributed.py's tolerances)
    serial = _pipelined(objective, "serial", bfa)
    assert _structure(text) == _structure(serial) and _structure(text)
    X, _ = _table()
    serial_booster = lgb.Booster(model_str=serial)
    np.testing.assert_allclose(booster.predict(X),
                               serial_booster.predict(X),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("learner", ["serial", "data", "feature"])
def test_the_bias_is_folded_after_the_validation_update(learner):
    """Boost-from-average (regression: the label mean) starts the
    validation score at the bias and folds the bias into the first tree
    that grows: after ROUNDS drained iterations the validation score is
    the model's own raw prediction, which holds the bias once."""
    booster, train = _booster("regression", learner)
    inner = booster._inner
    assert inner.init_score_bias != 0.0
    X, y = _table()
    Xv = X[:200]
    booster.add_valid(lgb.Dataset(Xv, _labels("regression", y[:200]),
                                  reference=train), "valid")
    for _ in range(ROUNDS):
        assert booster.update() is False
    assert inner._pending_bias == 0.0
    np.testing.assert_allclose(np.asarray(inner._valid_score[0])[0],
                               booster.predict(Xv, raw_score=True),
                               rtol=1e-6, atol=1e-6)
