"""Serving prediction engine: device-resident CompiledForest cache,
shape-bucketed dispatch, pipelined chunk loop, Predictor front end.

The contract under test (ISSUE 5): predictions are BIT-IDENTICAL to the
per-call-restack seed behavior across the predict matrix, repeated
predict on an unchanged booster restacks exactly once per model
version, and every ensemble mutation (more training, rollback,
checkpoint restore, model load) invalidates the cache.

Read-only tests share one module-scoped booster (tier-1 runs under a
fixed wall-clock budget); tests that mutate the ensemble or assert
absolute restack counts train their own.
"""
from __future__ import annotations

import threading

import numpy as np
import pytest

import lightgbm_tpu as lgb


def _make(n=240, f=6, seed=0, classes=2):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f).astype(np.float32)
    if classes == 2:
        y = (X[:, 0] + X[:, 1] * X[:, 2] > 0).astype(np.float32)
    else:
        y = (np.argmax(X[:, :classes], axis=1)).astype(np.float32)
    return X, y


def _train(X, y, iters=8, **params):
    p = {"objective": "binary", "verbose": -1, "num_leaves": 7,
         "min_data_in_leaf": 5}
    p.update(params)
    ds = lgb.Dataset(X, y, params=dict(p))
    return lgb.train(dict(p), ds, num_boost_round=iters, verbose_eval=False)


def _seed_clone(booster, **extra):
    """The pre-cache behavior: restack per call, no buckets, no
    pipelining — the bit-identity reference."""
    params = {"tpu_predict_cache": "false", "tpu_predict_bucket_min": 0,
              "tpu_predict_pipeline": "false"}
    params.update(extra)
    return lgb.Booster(model_str=booster.model_to_string(), params=params)


@pytest.fixture(scope="module")
def base():
    """(X, booster, seed_clone) shared by the read-only tests."""
    X, y = _make()
    b = _train(X, y, iters=10)
    return X, b, _seed_clone(b)


# ---------------------------------------------------------------------------
# bit-identity across the predict matrix
def test_predict_bit_identical_to_uncached_across_batch_sizes(base):
    X, b, ref = base
    for n in (1, 2, 3, 17, 100, 240):
        for kw in ({}, {"raw_score": True}, {"num_iteration": 3}):
            a = b.predict(X[:n], **kw)
            r = ref.predict(X[:n], **kw)
            assert np.array_equal(a, r), (n, kw)


def test_predict_bit_identical_multiclass():
    X, y = _make(classes=3)
    b = _train(X, y, objective="multiclass", num_class=3)
    ref = _seed_clone(b)
    for n in (1, 5, 240):
        assert np.array_equal(b.predict(X[:n]), ref.predict(X[:n]))
        assert np.array_equal(b.predict(X[:n], raw_score=True),
                              ref.predict(X[:n], raw_score=True))


def test_predict_bit_identical_categorical():
    rng = np.random.RandomState(3)
    n = 300
    cat = rng.randint(0, 12, size=n).astype(np.float32)
    Xn = rng.randn(n, 4).astype(np.float32)
    X = np.column_stack([cat, Xn])
    y = ((cat % 3 == 0) ^ (Xn[:, 0] > 0)).astype(np.float32)
    b = _train(X, y, categorical_feature=[0], min_data_in_leaf=2)
    ref = _seed_clone(b)
    for nn in (1, 7, 300):
        assert np.array_equal(b.predict(X[:nn]), ref.predict(X[:nn]))


def test_pred_leaf_bit_identical_and_shared_route(base):
    X, b, ref = base
    for nn in (1, 3, 240):
        assert np.array_equal(b.predict(X[:nn], pred_leaf=True),
                              ref.predict(X[:nn], pred_leaf=True))
    # num_iteration cap flows through the shared _capped_total
    assert np.array_equal(b.predict(X, pred_leaf=True, num_iteration=4),
                          ref.predict(X, pred_leaf=True, num_iteration=4))
    assert b.predict(X, pred_leaf=True, num_iteration=4).shape == (240, 4)


def test_pred_early_stop_bit_identical(base):
    X, b, ref = base
    for kw in ({"pred_early_stop": True, "pred_early_stop_freq": 2,
                "pred_early_stop_margin": 1e9},
               {"pred_early_stop": True, "pred_early_stop_freq": 2,
                "pred_early_stop_margin": 0.0}):
        a = b.predict(X[:37], raw_score=True, **kw)
        r = ref.predict(X[:37], raw_score=True, **kw)
        assert np.array_equal(a, r), kw


# ---------------------------------------------------------------------------
# restack economics: exactly one restack per model version
def test_single_restack_per_model_version():
    X, y = _make()
    b = _train(X, y)
    stats = b._inner._compiled_forest.stats
    for _ in range(3):
        b.predict(X)
    assert stats["restacks"] == 1, stats
    assert stats["hits"] == 2, stats
    # different batch sizes inside the same bucket: still no restack
    b.predict(X[:5])
    b.predict(X[:9])
    assert stats["restacks"] == 1, stats
    # pred_leaf is a different layout -> one more stack, then cached
    b.predict(X[:10], pred_leaf=True)
    b.predict(X[:10], pred_leaf=True)
    assert stats["restacks"] == 2, stats
    # more training -> new model version -> exactly one more restack
    p0 = b.predict(X)
    v0 = b._inner.model_version()
    b.update()
    assert b._inner.model_version() > v0
    p1 = b.predict(X)
    assert not np.array_equal(p0, p1)
    assert np.array_equal(p1, _seed_clone(b).predict(X))
    assert stats["restacks"] == 3, stats


def test_cache_invalidation_on_rollback_and_restore():
    X, y = _make()
    b = _train(X, y)
    p_before = b.predict(X)
    b.update()
    b.predict(X)
    b.rollback_one_iter()
    assert np.array_equal(b.predict(X), p_before)
    # checkpoint restore: predictions must reflect the restored forest
    payload = b.checkpoint_state()
    b.update()
    p_more = b.predict(X)
    assert not np.array_equal(p_before, p_more)
    b.restore_state(payload)
    assert np.array_equal(b.predict(X), p_before)


def test_cache_invalidation_on_model_from_string():
    X, y = _make()
    b = _train(X, y, iters=10)
    short = lgb.Booster(model_str=b.model_to_string(num_iteration=3))
    p_short = short.predict(X)
    b.predict(X)                       # populate the cache
    b._inner.load_model_from_string(b.model_to_string(num_iteration=3))
    assert np.array_equal(b.predict(X), p_short)


def test_cache_invalidation_on_continued_training():
    X, y = _make()
    b = _train(X, y, iters=5)
    p5 = b.predict(X, raw_score=True)
    ds = lgb.Dataset(X, y, params={"objective": "binary", "verbose": -1,
                                   "num_leaves": 7, "min_data_in_leaf": 5})
    cont = lgb.train({"objective": "binary", "verbose": -1, "num_leaves": 7,
                      "min_data_in_leaf": 5}, ds, num_boost_round=3,
                     init_model=b, verbose_eval=False)
    p8 = cont.predict(X, raw_score=True)
    assert cont.num_trees() == 8
    assert not np.array_equal(p5, p8)
    assert np.array_equal(p8, _seed_clone(cont).predict(X, raw_score=True))


def test_dart_renormalization_invalidates():
    X, y = _make(n=300)
    p = {"objective": "binary", "verbose": -1, "num_leaves": 7,
         "min_data_in_leaf": 5, "boosting_type": "dart", "drop_rate": 0.5,
         "skip_drop": 0.0, "drop_seed": 7}
    ds = lgb.Dataset(X, y, params=dict(p))
    b = lgb.train(dict(p), ds, num_boost_round=6, verbose_eval=False)
    # DART mutates EXISTING trees' leaf values each iteration; the
    # cached stacks must always match a fresh uncached clone
    assert np.array_equal(b.predict(X, raw_score=True),
                          _seed_clone(b).predict(X, raw_score=True))
    b.predict(X)
    b.update()
    assert np.array_equal(b.predict(X, raw_score=True),
                          _seed_clone(b).predict(X, raw_score=True))


# ---------------------------------------------------------------------------
# Predictor front end
def test_predictor_warmup_then_no_restack_or_retrace(base):
    import jax.monitoring
    X, b, _ = base
    pred = b.serving_predictor()
    warm = pred.warmup(max_rows=64)
    assert warm["buckets"] == [16, 32, 64]
    pred.predict_one(X[0])             # settle
    compiles = []

    def listener(name, **kw):
        if "compil" in name:
            compiles.append(name)

    jax.monitoring.register_event_listener(listener)
    try:
        restacks0 = pred.stats()["stack_restacks"]
        for i in range(10):
            pred.predict_one(X[i])
            pred.predict(X[:3])
        stats = pred.stats()
        assert stats["stack_restacks"] == restacks0
        assert not compiles, compiles
        assert stats["requests"] >= 20
        assert stats["p50_latency_ms"] is not None
    finally:
        # this listener alone: clearing them all would also silence the
        # compile observer (telemetry/observer.py) for every later test
        # that shares this worker
        jax.monitoring.unregister_event_listener(listener)


def test_predictor_values_match_booster(base):
    X, b, _ = base
    direct = b.predict(X[:20])
    pred = b.serving_predictor()
    assert np.array_equal(pred.predict(X[:20]), direct)
    assert np.allclose(pred.predict_one(X[0]), direct[0])


def test_micro_batching_matches_direct(base):
    X, b, _ = base
    direct = b.predict(X[:32])
    pred = b.serving_predictor()
    try:
        futs = []
        threads = []

        def fire(lo, hi):
            for i in range(lo, hi):
                futs.append((i, pred.submit(X[i])))

        for t0 in range(0, 32, 8):
            th = threading.Thread(target=fire, args=(t0, t0 + 8))
            threads.append(th)
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        for i, fut in futs:
            assert np.allclose(fut.result(timeout=30), direct[i])
        assert pred.stats()["micro_rows"] == 32
    finally:
        pred.close()


def test_cancelled_submit_does_not_kill_the_batcher(base):
    X, b, _ = base
    pred = b.serving_predictor()
    try:
        fut = pred.submit(X[0])
        fut.cancel()                   # may or may not win the race
        # the batcher must survive and serve later requests either way
        later = pred.submit(X[1])
        assert np.allclose(later.result(timeout=30), b.predict(X[1:2])[0])
    finally:
        pred.close()


def test_predictor_disabled_micro_batch_is_synchronous(base):
    X, b, _ = base
    pred = b.serving_predictor()
    pred._micro_batch = 0              # tpu_predict_micro_batch=0 path
    fut = pred.submit(X[0])
    assert fut.done()
    assert np.allclose(fut.result(), b.predict(X[:1])[0])


def test_sklearn_route_and_accessor():
    X, y = _make()
    clf = lgb.LGBMClassifier(n_estimators=5, num_leaves=7,
                             min_child_samples=5, verbose=-1)
    clf.fit(X, y)
    clf.predict(X[:10])
    clf.predict_proba(X[:10])
    pred = clf.serving_predictor()
    stats = pred.stats()
    assert stats["stack_restacks"] >= 1
    # the sklearn predicts rode the booster's shared predictor
    assert clf.booster_._serving().stats()["requests"] >= 2


def test_predict_header_reshape_warning_once(base):
    from lightgbm_tpu import basic, log
    X, b, _ = base
    basic._PREDICT_COMPAT_WARNED = False
    seen = []
    log.register_callback(lambda line: seen.append(line))
    try:
        b.predict(X[:2], data_has_header=True)
        b.predict(X[:2], is_reshape=False)
    finally:
        log.register_callback(None)
        basic._PREDICT_COMPAT_WARNED = False
    warned = [s for s in seen if "data_has_header" in s]
    assert len(warned) == 1, seen


def test_pred_contrib_keeps_float64_through_serving_route(base):
    """TreeSHAP walks f64 thresholds: the serving route must not
    truncate contrib inputs to f32 (a value just above a split
    threshold in f64 can round below it in f32 and flip the path)."""
    X, b, _ = base
    # craft rows straddling the f32 rounding of every first-split
    # threshold in the model
    thresholds = [t.threshold[0] for t in b._inner.models
                  if t.num_leaves > 1]
    feats = [t.split_feature[0] for t in b._inner.models
             if t.num_leaves > 1]
    rows = np.repeat(np.asarray(X[:1], np.float64), len(thresholds), axis=0)
    for i, (f, t) in enumerate(zip(feats, thresholds)):
        rows[i, f] = np.float64(t) + 1e-9
    direct = b._inner.predict(rows, pred_contrib=True)
    routed = b.predict(rows, pred_contrib=True)
    assert np.array_equal(routed, direct)


def test_zero_tree_and_empty_input(base):
    X, b, _ = base
    assert b.predict(X[:0]).shape == (0,)
    assert b.predict(X[:0], pred_leaf=True).shape == (0, b.num_trees())


def test_tracing_counters_surfaced():
    from lightgbm_tpu import telemetry
    X, y = _make()
    b = _train(X, y)
    telemetry.enable(True)
    telemetry.reset()
    try:
        b.predict(X)
        b.predict(X)
        counters = {c.name: c.value
                    for c in telemetry.registry().counters.values()
                    if not c.labels}
        assert counters.get("predict/restack") == 1
        assert counters.get("predict/stack_cache_hit") == 1
        assert counters.get("predict/chunks") == 2
    finally:
        telemetry.enable(False)
        telemetry.reset()


# ---------------------------------------------------------------------------
# multi-model registry (serving/registry.py)
def test_registry_publish_predict_and_stats(base):
    from lightgbm_tpu.serving import ModelRegistry
    X, b, _ = base
    reg = ModelRegistry(warmup_rows=32)
    try:
        rec = reg.publish("main", b)
        assert rec["publish_version"] == 1
        assert rec["warmed_buckets"] == [16, 32]
        assert np.array_equal(reg.predict("main", X[:7]), b.predict(X[:7]))
        assert np.allclose(reg.predict_one("main", X[0]),
                           b.predict(X[:1])[0])
        fut = reg.submit("main", X[1])
        assert np.allclose(fut.result(timeout=30), b.predict(X[1:2])[0])
        stats = reg.stats()
        assert stats["resident_models"] == 1
        assert stats["stack_bytes"] > 0
        assert stats["models"]["main"]["registry_requests"] == 3
        assert stats["models"]["main"]["publish_version"] == 1
    finally:
        reg.close()


def test_registry_hot_swap_serves_new_model_immediately():
    from lightgbm_tpu.serving import ModelRegistry
    X, y = _make()
    b1 = _train(X, y, iters=4)
    b2 = _train(X, y, iters=12)
    assert not np.array_equal(b1.predict(X[:5]), b2.predict(X[:5]))
    reg = ModelRegistry(warmup_rows=16)
    try:
        reg.publish("m", b1)
        assert np.array_equal(reg.predict("m", X[:5]), b1.predict(X[:5]))
        rec = reg.publish("m", b2)
        assert rec["publish_version"] == 2
        # the swap point: every request AFTER publish() returns must
        # serve the new model
        assert np.array_equal(reg.predict("m", X[:5]), b2.predict(X[:5]))
        assert reg.models() == ["m"]
        assert reg.stats()["swaps"] == 1
    finally:
        reg.close()


def test_registry_swap_in_flight_submits_complete():
    """Futures accepted before a hot swap resolve (on the model that
    accepted them); submits racing the swap retry onto the new entry —
    zero dropped either way."""
    from lightgbm_tpu.serving import ModelRegistry
    X, y = _make()
    b1 = _train(X, y, iters=4)
    b2 = _train(X, y, iters=12)
    p1 = b1.predict(X)
    p2 = b2.predict(X)
    reg = ModelRegistry(warmup_rows=16)
    try:
        reg.publish("m", b1)
        futs = []
        stop = threading.Event()

        def fire():
            i = 0
            while not stop.is_set() and i < 400:
                futs.append((i % 50, reg.submit("m", X[i % 50])))
                i += 1

        th = threading.Thread(target=fire)
        th.start()
        reg.publish("m", b2)
        stop.set()
        th.join()
        assert len(futs) > 0
        for i, fut in futs:
            val = fut.result(timeout=30)    # no dropped/failed futures
            ok = np.allclose(val, p1[i]) or np.allclose(val, p2[i])
            assert ok, (i, val, p1[i], p2[i])
        # post-swap requests serve b2 only
        assert np.allclose(reg.submit("m", X[3]).result(timeout=30), p2[3])
    finally:
        reg.close()


def test_registry_budget_evicts_lru_stacks():
    from lightgbm_tpu.serving import ModelRegistry
    X, y = _make()
    b1 = _train(X, y, iters=4)
    b2 = _train(X, y, iters=4, seed=7)
    reg = ModelRegistry(budget_mb=1e-3, warmup_rows=0)  # ~1 KiB: too small
    try:
        reg.publish("a", b1)
        reg.publish("b", b2)
        reg.predict("a", X[:4])
        reg.predict("b", X[:4])
        stats = reg.stats()
        assert stats["evictions"] >= 1
        assert b1._inner._compiled_forest.stats["evictions"] >= 1
        # eviction drops stacks, not models: both still serve correctly
        assert np.array_equal(reg.predict("a", X[:4]), b1.predict(X[:4]))
        assert np.array_equal(reg.predict("b", X[:4]), b2.predict(X[:4]))
        # eviction never bumps the model version (stale-stack safety is
        # version-keyed, eviction is memory-only)
        assert stats["models"]["a"]["model_version"] \
            == b1._inner.model_version()
    finally:
        reg.close()


def test_registry_unknown_model_and_close():
    from lightgbm_tpu import log
    from lightgbm_tpu.serving import ModelRegistry
    X, y = _make()
    b = _train(X, y, iters=3)
    reg = ModelRegistry(warmup_rows=0)
    reg.publish("only", b)
    try:
        reg.predict("nope", X[:2])
        assert False, "unknown model must raise"
    except log.LightGBMError as exc:
        assert "not published" in str(exc)
    assert reg.unpublish("only")
    assert not reg.unpublish("only")
    reg.close()
    try:
        reg.publish("late", b)
        assert False, "closed registry must refuse publish"
    except log.LightGBMError:
        pass


def test_predictor_rejects_wrong_width_rows(base):
    from lightgbm_tpu import log
    X, b, _ = base
    pred = b.serving_predictor()
    with pytest.raises(log.LightGBMError, match="expects"):
        pred.predict(X[:3, :4])
    with pytest.raises(log.LightGBMError, match="expects"):
        pred.predict_one(X[0][:3])
    with pytest.raises(log.LightGBMError, match="expects"):
        pred.submit(np.zeros(2, np.float32))
    # a wrong-width row must not have burned a retrace or poisoned the
    # predictor: correct requests still serve
    assert np.array_equal(pred.predict(X[:3]), b.predict(X[:3]))


def test_registry_telemetry_gauges_without_stats_caller():
    """The hot paths themselves keep the serving/registry_* gauges
    fresh — no stats() call in this test before the assertion."""
    from lightgbm_tpu import telemetry
    from lightgbm_tpu.serving import ModelRegistry
    X, y = _make()
    b = _train(X, y, iters=3)
    telemetry.enable(True)
    telemetry.reset()
    reg = ModelRegistry(warmup_rows=0)
    try:
        reg.publish("g", b)
        reg.predict("g", X[:4])
        snap = telemetry.registry().snapshot()
        gauges = {g["name"] for g in snap["gauges"]}
        counters = {c["name"] for c in snap["counters"]}
        assert "serving/registry_models" in gauges
        assert "serving/registry_stack_bytes" in gauges
        assert "serving/registry_requests" in counters
    finally:
        reg.close()
        telemetry.enable(False)
        telemetry.reset()


# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_small_batch_speedup_vs_percall_restack_500_trees():
    """Acceptance: repeated small-batch predict on a >=500-tree model is
    >=5x faster than the per-call-restack seed behavior (CPU backend)."""
    import time
    rng = np.random.RandomState(0)
    X = rng.randn(500, 8).astype(np.float32)
    # noisy labels: residuals never vanish, so all 500 rounds split
    y = (X[:, 0] + X[:, 1] * X[:, 2] + rng.logistic(size=500) > 0) \
        .astype(np.float32)
    b = _train(X, y, iters=500, min_data_in_leaf=2)
    assert b.num_trees() >= 500
    pred = b.serving_predictor(raw_score=True)
    pred.warmup(max_rows=16)
    t0 = time.perf_counter()
    for i in range(20):
        pred.predict(X[i * 8:(i + 1) * 8])
    cached = (time.perf_counter() - t0) / 20
    seed = _seed_clone(b)
    t0 = time.perf_counter()
    for i in range(3):
        seed.predict(X[i * 8:(i + 1) * 8], raw_score=True)
    uncached = (time.perf_counter() - t0) / 3
    assert uncached / cached >= 5.0, (uncached, cached)
