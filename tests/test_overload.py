"""Overload-resilient serving (ISSUE 12): admission control, request
deadlines, EWMA load shedding, per-model QPS isolation + circuit
breakers, shutdown drain guarantees, per-row batch-failure isolation,
and cold-start-storm protection.

The contract under test: a refused request ALWAYS gets a structured,
retriable `ServingOverload`/`DeadlineExceeded` (never a silent drop or
an unbounded queue wait), admitted requests stay bit-identical to an
unloaded serve, and the defaults (every cap 0) reproduce the
pre-admission behavior exactly. The full 2x-saturation storm runs in
scripts/overload_smoke.py; the tier-1 tests
here exercise each mechanism in isolation at millisecond scale.
"""
from __future__ import annotations

import threading
import time

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.serving import (DeadlineExceeded, ModelRegistry,
                                  Predictor, PredictorShutdown,
                                  ServingOverload)
from lightgbm_tpu.testing import faults
from lightgbm_tpu.testing.faults import InjectedFault


def _make(n=240, f=6, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f).astype(np.float32)
    y = (X[:, 0] + X[:, 1] * X[:, 2] > 0).astype(np.float32)
    return X, y


def _train(X, y, iters=6, **params):
    p = {"objective": "binary", "verbose": -1, "num_leaves": 7,
         "min_data_in_leaf": 5}
    p.update(params)
    ds = lgb.Dataset(X, y, params=dict(p))
    return lgb.train(dict(p), ds, num_boost_round=iters, verbose_eval=False)


@pytest.fixture(scope="module")
def base():
    X, y = _make()
    return X, _train(X, y)


def _serving_clone(booster, **params):
    return lgb.Booster(model_str=booster.model_to_string(), params=params)


@pytest.fixture(autouse=True)
def _reset_faults():
    yield
    faults.reset()


# ---------------------------------------------------------------------------
# admission: queue caps, deadlines, shedding
def test_queue_cap_rejects_structured(base):
    X, b = base
    p = Predictor(_serving_clone(
        b, tpu_serving_max_queue=2, tpu_predict_micro_batch=4,
        tpu_predict_micro_batch_window_ms=5))
    p.warmup(max_rows=16)
    faults.slow_predict(0.2)
    futs, errs = [], []
    for i in range(8):
        try:
            futs.append(p.submit(X[i]))
        except ServingOverload as exc:
            errs.append(exc)
    faults.reset()
    assert errs, "queue cap never engaged"
    for exc in errs:
        assert exc.reason == "queue_full"
        assert exc.retriable is True
        assert exc.retry_after_s is not None
    # accepted futures all resolve (no silent drops)
    for f in futs:
        f.result(timeout=10)
    assert p.admission.counts["queue_full"] == len(errs)
    p.close()


def test_deadline_expires_in_queue_before_device_time(base):
    X, b = base
    p = Predictor(_serving_clone(
        b, tpu_serving_deadline_ms=40, tpu_predict_micro_batch=4,
        tpu_predict_micro_batch_window_ms=1))
    p.warmup(max_rows=16)
    faults.slow_predict(0.15)      # each dispatch outlives the deadline
    futs = [p.submit(X[i]) for i in range(12)]
    outcomes = {"ok": 0, "deadline": 0}
    for f in futs:
        try:
            f.result(timeout=10)
            outcomes["ok"] += 1
        except DeadlineExceeded as exc:
            assert exc.retriable is True
            assert exc.waited_ms is not None and exc.waited_ms >= 40
            outcomes["deadline"] += 1
    faults.reset()
    # the first batch dispatches in time; later batches sat past 40ms
    assert outcomes["deadline"] > 0
    assert outcomes["ok"] > 0
    assert p.admission.counts["deadline_expired"] == outcomes["deadline"]
    p.close()


def test_per_call_deadline_override(base):
    X, b = base
    p = Predictor(_serving_clone(
        b, tpu_predict_micro_batch=4, tpu_predict_micro_batch_window_ms=1))
    p.warmup(max_rows=16)
    # no config deadline: the override alone must arm expiry
    faults.slow_predict(0.15)
    futs = [p.submit(X[i], deadline_ms=30) for i in range(12)]
    expired = 0
    for f in futs:
        try:
            f.result(timeout=10)
        except DeadlineExceeded:
            expired += 1
    faults.reset()
    assert expired > 0
    p.close()


def test_sync_predict_shed_before_device(base):
    """predict(deadline_ms=) refuses BEFORE dispatch once the EWMA
    service estimate exceeds the budget — the rejection is immediate,
    not a late answer. The estimate only gates while work is IN
    FLIGHT: an idle predictor admits and re-measures, so a stale
    overload-era estimate can never shed an idle tier forever."""
    X, b = base
    p = Predictor(_serving_clone(b))
    p.warmup(max_rows=16)
    faults.slow_predict(0.1)
    p.predict(X[:4])               # prime the service EWMA at ~100ms
    shed = []

    def occupant():
        p.predict(X[:4])           # holds inflight > 0 for ~100ms

    def sheddee():
        t0 = time.perf_counter()
        try:
            p.predict(X[:4], deadline_ms=5)
        except ServingOverload as exc:
            shed.append((exc.reason, time.perf_counter() - t0))

    t1 = threading.Thread(target=occupant)
    t2 = threading.Thread(target=sheddee)
    t1.start()
    time.sleep(0.03)               # occupant is mid-dispatch
    t2.start()
    t2.join()
    t1.join()
    faults.reset()
    assert shed and shed[0][0] == "shed"
    assert shed[0][1] < 0.05       # refused without dispatch
    assert p.admission.counts["shed"] == 1
    # idle predictor + stale 100ms estimate: ADMITS and re-measures
    # (the EWMA decays toward the true ~ms service time instead of
    # freezing at the overload-era value)
    stale = p.admission.ewma_service_s
    for _ in range(3):
        p.predict(X[:4], deadline_ms=5)
    assert p.admission.ewma_service_s < stale
    assert p.admission.counts["shed"] == 1     # no further sheds


def test_ewma_shed_on_saturated_queue(base):
    X, b = base
    p = Predictor(_serving_clone(
        b, tpu_serving_deadline_ms=30, tpu_serving_max_queue=64,
        tpu_predict_micro_batch=2, tpu_predict_micro_batch_window_ms=1))
    p.warmup(max_rows=16)
    faults.slow_predict(0.08)
    reasons = []
    futs = []
    for i in range(40):
        try:
            futs.append(p.submit(X[i % len(X)]))
        except ServingOverload as exc:
            reasons.append(exc.reason)
        time.sleep(0.005)
    faults.reset()
    for f in futs:
        try:
            f.result(timeout=10)
        except ServingOverload:
            pass
    # once the EWMA wait passed 30ms the controller refused at
    # admission (shed), well before the 64-deep queue cap could
    assert "shed" in reasons
    assert p.admission.ewma_wait_s > 0.03
    p.close()


def test_inflight_cap(base):
    X, b = base
    p = Predictor(_serving_clone(b, tpu_serving_max_inflight=1))
    p.warmup(max_rows=16)
    faults.slow_predict(0.2)
    errs = []

    def call():
        try:
            p.predict(X[:4])
        except ServingOverload as exc:
            errs.append(exc.reason)

    threads = [threading.Thread(target=call) for _ in range(3)]
    for t in threads:
        t.start()
        time.sleep(0.02)           # let the first call occupy the slot
    for t in threads:
        t.join()
    faults.reset()
    assert errs and all(r == "inflight_full" for r in errs)


def test_defaults_reproduce_unbounded_behavior(base):
    """All caps default 0: no request is ever refused, the pre-ISSUE-12
    contract."""
    X, b = base
    p = Predictor(_serving_clone(b, tpu_predict_micro_batch=4))
    p.warmup(max_rows=16)
    futs = [p.submit(X[i]) for i in range(32)]
    for f in futs:
        f.result(timeout=10)
    assert p.admission.counts["rejected"] == 0
    p.close()


def test_admitted_predictions_bit_identical_under_load(base):
    """Shedding changes WHETHER a request is answered, never WHAT is
    answered."""
    X, b = base
    ref = b.predict(X[:32])
    p = Predictor(_serving_clone(
        b, tpu_serving_deadline_ms=50, tpu_serving_max_queue=8,
        tpu_predict_micro_batch=4, tpu_predict_micro_batch_window_ms=1))
    p.warmup(max_rows=16)
    faults.slow_predict(0.02)
    got = {}
    for i in range(32):
        try:
            got[i] = p.submit(X[i])
        except ServingOverload:
            pass
    answered = 0
    for i, f in got.items():
        try:
            val = f.result(timeout=10)
        except ServingOverload:
            continue
        assert float(val) == float(ref[i]), i
        answered += 1
    faults.reset()
    assert answered > 0
    p.close()


# ---------------------------------------------------------------------------
# shutdown drain: no future may leak unresolved
def test_close_drains_queued_requests(base):
    X, b = base
    p = Predictor(_serving_clone(b, tpu_predict_micro_batch=4))
    p.warmup(max_rows=16)
    futs = [p.submit(X[i]) for i in range(8)]
    p.close()
    for f in futs:
        f.result(timeout=1)        # graceful drain still answers them


def test_close_fails_stuck_futures_with_structured_error(base):
    """A wedged batcher (device hang) must not leak pending futures:
    past the drain timeout they fail with PredictorShutdown."""
    X, b = base
    p = Predictor(_serving_clone(
        b, tpu_predict_micro_batch=2, tpu_predict_micro_batch_window_ms=1))
    p.warmup(max_rows=16)
    faults.slow_predict(1.0)       # every dispatch wedges 1s
    futs = [p.submit(X[i]) for i in range(10)]
    t0 = time.perf_counter()
    p.close(timeout=0.2)
    assert time.perf_counter() - t0 < 3.0
    faults.reset()
    resolved = {"ok": 0, "shutdown": 0}
    for f in futs:
        try:
            f.result(timeout=5)    # in-flight batch may still land
            resolved["ok"] += 1
        except PredictorShutdown as exc:
            assert exc.retriable is True
            assert "closed" in str(exc)
            resolved["shutdown"] += 1
    assert resolved["shutdown"] > 0, "stuck futures leaked unresolved"


def test_submit_after_close_raises_shutdown(base):
    X, b = base
    p = Predictor(_serving_clone(b, tpu_predict_micro_batch=4))
    p.close()
    with pytest.raises(PredictorShutdown):
        p.submit(X[0])


def test_unpublish_resolves_all_inflight(base):
    X, b = base
    reg = ModelRegistry(warmup_rows=16)
    reg.publish("m", _serving_clone(
        b, tpu_predict_micro_batch=2, tpu_predict_micro_batch_window_ms=1))
    faults.slow_predict(0.3)
    futs = [reg.submit("m", X[i]) for i in range(6)]
    assert reg.unpublish("m") is True
    faults.reset()
    for f in futs:
        try:
            f.result(timeout=10)
        except ServingOverload:
            pass                   # structured — the contract
    reg.close()


# ---------------------------------------------------------------------------
# per-row isolation of batch predict failures
def test_batch_failure_retried_per_row(base):
    """One transient dispatch failure must not fail every co-riding
    future: the batch is re-run row-by-row."""
    X, b = base
    p = Predictor(_serving_clone(
        b, tpu_predict_micro_batch=4,
        tpu_predict_micro_batch_window_ms=50))
    p.warmup(max_rows=16)
    ref = b.predict(X[:4])
    faults.fail_predict(1)         # fails the coalesced dispatch once
    futs = [p.submit(X[i]) for i in range(4)]
    vals = [f.result(timeout=10) for f in futs]
    assert [float(v) for v in vals] == [float(r) for r in ref]
    assert p.stats()["batch_isolated_rows"] >= 4
    p.close()


def test_poisoned_row_fails_only_its_future(base):
    """Two injected failures: the batch dispatch, then the FIRST
    per-row retry — exactly one future fails, the rest resolve."""
    X, b = base
    p = Predictor(_serving_clone(
        b, tpu_predict_micro_batch=4,
        tpu_predict_micro_batch_window_ms=50))
    p.warmup(max_rows=16)
    faults.fail_predict(2)
    futs = [p.submit(X[i]) for i in range(4)]
    outcomes = []
    for f in futs:
        try:
            f.result(timeout=10)
            outcomes.append("ok")
        except InjectedFault:
            outcomes.append("fault")
    assert outcomes.count("fault") == 1
    assert outcomes.count("ok") == 3
    p.close()


# ---------------------------------------------------------------------------
# registry: circuit breaker + per-model QPS isolation
def test_breaker_trips_and_half_open_recovers(base):
    X, b = base
    reg = ModelRegistry(warmup_rows=16, breaker_failures=2,
                        breaker_reset_s=0.2)
    reg.publish("m", _serving_clone(b))
    reg.predict("m", X[:4])
    faults.fail_predict(2)
    for _ in range(2):
        with pytest.raises(InjectedFault):
            reg.predict("m", X[:4])
    # breaker now open: refused WITHOUT consuming device time
    with pytest.raises(ServingOverload) as ei:
        reg.predict("m", X[:4])
    assert ei.value.reason == "breaker_open"
    assert ei.value.retry_after_s is not None
    time.sleep(0.25)               # past reset: half-open probe allowed
    reg.predict("m", X[:4])
    st = reg.stats()["models"]["m"]["breaker"]
    assert st["state"] == "closed"
    assert st["trips"] == 1 and st["recoveries"] == 1
    reg.close()


def test_failed_probe_reopens_with_backoff(base):
    X, b = base
    reg = ModelRegistry(warmup_rows=16, breaker_failures=1,
                        breaker_reset_s=0.15)
    reg.publish("m", _serving_clone(b))
    reg.predict("m", X[:4])
    faults.fail_predict(2)         # trip + fail the probe
    with pytest.raises(InjectedFault):
        reg.predict("m", X[:4])
    time.sleep(0.2)
    with pytest.raises(InjectedFault):
        reg.predict("m", X[:4])    # half-open probe fails
    st = reg.stats()["models"]["m"]["breaker"]
    assert st["state"] == "open"
    assert st["trips"] == 2
    assert st["backoff_s"] == pytest.approx(0.3)   # doubled
    reg.close()


def test_rejected_probe_releases_half_open_slot(base):
    """A half-open probe that gets shed (or fails client-side) is NO
    evidence about the model: it must release the probe slot so the
    next request can probe — not wedge the breaker half-open forever."""
    X, b = base
    reg = ModelRegistry(warmup_rows=16, breaker_failures=1,
                        breaker_reset_s=0.15)
    reg.publish("m", _serving_clone(b))
    reg.predict("m", X[:4])
    faults.fail_predict(1)
    with pytest.raises(InjectedFault):
        reg.predict("m", X[:4])    # trips (failures=1)
    time.sleep(0.2)                # half-open
    # the probe request dies CLIENT-side (wrong width): no evidence
    with pytest.raises(lgb.log.LightGBMError):
        reg.predict("m", X[:4, :3])
    # the slot was released: a viable request still probes and closes
    reg.predict("m", X[:4])
    st = reg.stats()["models"]["m"]["breaker"]
    assert st["state"] == "closed" and st["recoveries"] == 1
    reg.close()


def test_stale_success_does_not_close_open_breaker():
    """A pre-trip request resolving successfully AFTER the trip (a
    queued micro-batch future) is stale evidence: only the half-open
    probe may close an open breaker, or old successes would defeat the
    reset window."""
    from lightgbm_tpu.serving import CircuitBreaker
    brk = CircuitBreaker(failures=1, reset_s=0.1)
    assert brk.allow()
    brk.record_failure()           # trips open
    assert brk.state() == "open"
    brk.record_success()           # stale: must NOT close
    assert brk.state() == "open"
    assert not brk.allow()
    time.sleep(0.12)               # reset window -> half-open probe
    assert brk.allow()
    brk.record_success()           # the probe closes it
    assert brk.state() == "closed"
    assert brk.counts["recoveries"] == 1


def test_single_flight_key_capped_at_dispatch_chunk(base):
    """Over-chunk requests of different sizes compile the same
    chunk-bucket program and must share ONE single-flight key."""
    X, b = base
    p = Predictor(_serving_clone(b, tpu_predict_chunk=64))
    assert p._request_bucket(1) == 16
    assert p._request_bucket(40) == 64
    # 100 and 1000 rows both dispatch 64-row chunk programs
    assert p._request_bucket(100) == p._request_bucket(1000) == 64


def test_overload_rejections_do_not_trip_breaker(base):
    """Shed/deadline rejections say nothing about model health: a
    breaker with failures=1 must stay closed through arbitrarily many
    of them."""
    X, b = base
    reg = ModelRegistry(warmup_rows=16, breaker_failures=1,
                        breaker_reset_s=60)
    reg.publish("m", _serving_clone(
        b, tpu_serving_max_queue=1, tpu_predict_micro_batch=2,
        tpu_predict_micro_batch_window_ms=5))
    faults.slow_predict(0.2)
    sheds = 0
    futs = []
    for i in range(8):
        try:
            futs.append(reg.submit("m", X[i]))
        except ServingOverload:
            sheds += 1
    faults.reset()
    for f in futs:
        try:
            f.result(timeout=10)
        except ServingOverload:
            pass
    assert sheds > 0
    assert reg.stats()["models"]["m"]["breaker"]["state"] == "closed"
    reg.close()


def test_token_bucket_qps_isolation(base):
    X, b = base
    reg = ModelRegistry(warmup_rows=16, model_qps=2.0)
    reg.publish("hot", _serving_clone(b))
    reg.publish("cold", _serving_clone(b))
    # burst = one second's budget = 2 tokens
    reg.predict("hot", X[:2])
    reg.predict("hot", X[:2])
    with pytest.raises(ServingOverload) as ei:
        reg.predict("hot", X[:2])
    assert ei.value.reason == "rate_limited"
    assert ei.value.retry_after_s > 0
    assert ei.value.model == "hot"
    # the hot model's exhaustion never touches the other resident
    reg.predict("cold", X[:2])
    time.sleep(0.6)                # ~1.2 tokens refilled
    reg.predict("hot", X[:2])
    assert reg.stats()["rate_limited"] == 1
    reg.close()


def test_hot_swap_while_shedding(base):
    """Satellite: publish() during active shedding — post-swap requests
    route to the NEW version, shed decisions never count against the
    incoming model's breaker, and the outgoing drain respects
    deadlines (every old future resolves, late ones with structured
    errors)."""
    X, y = _make(seed=5)
    b_old = _train(X, y, iters=4)
    b_new = _train(X, y, iters=12)
    ref_new = b_new.predict(X[:4])
    reg = ModelRegistry(warmup_rows=16, breaker_failures=1,
                        breaker_reset_s=60)
    reg.publish("m", _serving_clone(
        b_old, tpu_serving_deadline_ms=60, tpu_serving_max_queue=4,
        tpu_predict_micro_batch=2, tpu_predict_micro_batch_window_ms=5))
    faults.slow_predict(0.15)
    old_futs, sheds = [], 0
    for i in range(10):            # overflow the queue: shedding active
        try:
            old_futs.append(reg.submit("m", X[i % len(X)]))
        except ServingOverload:
            sheds += 1
    assert sheds > 0, "not shedding — the scenario needs overload"
    reg.publish("m", _serving_clone(
        b_new, tpu_serving_deadline_ms=60, tpu_serving_max_queue=4,
        tpu_predict_micro_batch=2, tpu_predict_micro_batch_window_ms=5))
    faults.reset()
    # post-swap traffic serves the NEW version
    assert float(reg.predict("m", X[:4])[0]) == float(ref_new[0])
    # outgoing drain: every accepted future resolved — completed on the
    # old model, expired (deadline respected during drain), or shutdown
    outcomes = {"ok": 0, "structured": 0}
    for f in old_futs:
        try:
            f.result(timeout=10)
            outcomes["ok"] += 1
        except ServingOverload:
            outcomes["structured"] += 1
    assert outcomes["ok"] + outcomes["structured"] == len(old_futs)
    # shed decisions did not poison the incoming model's breaker
    assert reg.stats()["models"]["m"]["breaker"]["state"] == "closed"
    reg.close()


# ---------------------------------------------------------------------------
# cold-start-storm protection
def test_single_flight_one_compile_per_cold_bucket(base):
    X, b = base
    p = Predictor(_serving_clone(b), raw_score=True)   # cold ladder
    faults.compile_storm(0.15)
    results, errs = [], []

    def worker(i):
        try:
            results.append(p.predict_one(X[i]))
        except Exception as exc:   # pragma: no cover — gate fails below
            errs.append(exc)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(6)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    faults.reset()
    assert not errs
    assert len(results) == 6
    assert p._single_flight.counts["leads"] == 1
    assert p._single_flight.counts["waits"] >= 5
    assert wall < 6 * 0.15 / 2     # collapsed, not serialized storms


def test_single_flight_follower_sheds_on_deadline(base):
    X, b = base
    p = Predictor(_serving_clone(b), raw_score=True)
    faults.compile_storm(0.4)
    errs = []

    def leader():
        p.predict(X[:20])          # cold bucket 32: pays the storm

    def follower():
        try:
            p.predict(X[:20], deadline_ms=50)
        except ServingOverload as exc:
            errs.append(exc.reason)

    t1 = threading.Thread(target=leader)
    t2 = threading.Thread(target=follower)
    t1.start()
    time.sleep(0.05)
    t2.start()
    t1.join()
    t2.join()
    faults.reset()
    assert errs == ["compile_wait"]
    assert p.admission.counts["compile_wait"] == 1


def test_warmup_marks_ladder_no_single_flight(base):
    X, b = base
    p = Predictor(_serving_clone(b))
    p.warmup(max_rows=64)
    leads_after_warmup = p._single_flight.counts["leads"]
    p.predict_one(X[0])
    p.predict(X[:30])
    assert p._single_flight.counts["leads"] == leads_after_warmup
    assert p._single_flight.counts["waits"] == 0


def test_compile_cache_param_arms_jax_config(base, tmp_path, monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR unset, `tpu_compile_cache_dir`
    re-points the cache (tests/test_chip_smoke.py covers the case where
    the environment wins)."""
    import os

    import jax
    from lightgbm_tpu.serving.forest import enable_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    X, b = base
    cache_dir = str(tmp_path / "cc")
    prev = jax.config.jax_compilation_cache_dir
    try:
        p = Predictor(_serving_clone(b, tpu_compile_cache_dir=cache_dir))
        assert jax.config.jax_compilation_cache_dir == cache_dir
        p.warmup(max_rows=16)
        assert os.path.isdir(cache_dir) and os.listdir(cache_dir), \
            "warmup wrote no programs to the persistent cache"
    finally:
        if prev is not None:
            enable_compile_cache(prev)


# ---------------------------------------------------------------------------
# telemetry: counters, gauges, run-log evidence
def test_overload_counters_in_prometheus_export(base):
    from lightgbm_tpu import telemetry
    from lightgbm_tpu.telemetry import export as telemetry_export
    X, b = base
    telemetry.reset()
    telemetry.enable(True)
    try:
        p = Predictor(_serving_clone(
            b, tpu_serving_max_queue=1, tpu_predict_micro_batch=2,
            tpu_predict_micro_batch_window_ms=5))
        p.warmup(max_rows=16)
        faults.slow_predict(0.1)
        futs = []
        for i in range(6):
            try:
                futs.append(p.submit(X[i]))
            except ServingOverload:
                pass
        faults.reset()
        for f in futs:
            f.result(timeout=10)
        p.close()
        text = telemetry_export.prometheus_text(
            telemetry.registry().snapshot())
        assert "serving/queue_full" in text
        assert "serving/rejected" in text
        assert "serving/admitted" in text
        assert "serving/queue_wait_ewma_ms" in text
    finally:
        telemetry.reset()
        telemetry.enable(False)


def test_serving_overload_runlog_event(base):
    """The first rejection lands a structured `serving_overload` event
    through the active-recorder registry — PR 11's rank_failure
    evidence idiom on the serving side."""
    from lightgbm_tpu import telemetry
    X, b = base
    events = []

    class _Rec:
        def event(self, kind, **fields):
            events.append((kind, fields))

    telemetry.set_active_recorder(_Rec())
    try:
        p = Predictor(_serving_clone(
            b, tpu_serving_max_queue=1, tpu_predict_micro_batch=2,
            tpu_predict_micro_batch_window_ms=5))
        p.warmup(max_rows=16)
        faults.slow_predict(0.1)
        futs = []
        for i in range(6):
            try:
                futs.append(p.submit(X[i]))
            except ServingOverload:
                pass
        faults.reset()
        for f in futs:
            f.result(timeout=10)
        p.close()
    finally:
        telemetry.set_active_recorder(None)
    kinds = [k for k, _ in events]
    assert "serving_overload" in kinds
    _, fields = events[kinds.index("serving_overload")]
    assert fields["reason"] == "queue_full"
    assert fields["max_queue"] == 1
    assert "counts" in fields and fields["counts"]["queue_full"] >= 1


# ---------------------------------------------------------------------------
# the full storm (slow tier): abbreviated in-process 2x-saturation run
@pytest.mark.slow
def test_overload_storm_bounded_p99(base):
    X, b = base
    p = Predictor(_serving_clone(
        b, tpu_serving_deadline_ms=80, tpu_serving_max_queue=32,
        tpu_predict_micro_batch=8, tpu_predict_micro_batch_window_ms=2))
    p.warmup(max_rows=32)
    faults.slow_predict(0.02)      # capacity = 8 / 0.02 = 400 rows/s
    rng = np.random.RandomState(11)
    lats, rejected, lock = [], [0], threading.Lock()
    pending = [0]

    def on_done(f, t_arr):
        with lock:
            pending[0] -= 1
            if f.exception() is None:
                lats.append(time.perf_counter() - t_arr)
            else:
                assert isinstance(f.exception(), ServingOverload)
                rejected[0] += 1

    n = 1600                       # 2x capacity for 2 seconds
    gaps = rng.exponential(1.0 / 800.0, size=n)
    start = time.perf_counter()
    arrivals = np.cumsum(gaps)
    for i in range(n):
        target = start + arrivals[i]
        now = time.perf_counter()
        if target > now:
            time.sleep(target - now)
        t_arr = time.perf_counter()
        try:
            fut = p.submit(X[i % len(X)])
        except ServingOverload:
            with lock:
                rejected[0] += 1
            continue
        with lock:
            pending[0] += 1
        fut.add_done_callback(lambda f, t=t_arr: on_done(f, t))
    deadline = time.time() + 20
    while time.time() < deadline:
        with lock:
            if pending[0] == 0:
                break
        time.sleep(0.01)
    faults.reset()
    with lock:
        assert pending[0] == 0, "futures leaked past the grace window"
        done = sorted(lats)
        n_rej = rejected[0]
    assert done and n_rej > 0
    assert len(done) + n_rej == n
    p99 = done[int(len(done) * 0.99)]
    # bounded by the deadline envelope, NOT by the backlog (an
    # unbounded queue at 2x for 2s would show seconds of p99)
    assert p99 < 0.45, p99
    p.close()
