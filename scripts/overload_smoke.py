"""Overload-resilience gates for the serving tier (ISSUE 12).

Four phases, one machine-readable artifact (`--out`):

1. **overload** — open-loop bench at ~2x saturation. Capacity is made
   deterministic with `faults.slow_predict` (every coalesced dispatch
   pays a fixed service time, so saturation = micro_batch / service_s
   rows/s regardless of host speed). Gates: every offered request is
   RESOLVED (completed or promptly rejected with a structured
   retriable ServingOverload/DeadlineExceeded — zero silently dropped
   futures), admitted-request p99 stays bounded (within the deadline
   envelope, and a bounded multiple of the at-capacity p99) instead of
   growing with the backlog, and admitted predictions are bit-identical
   to an unloaded reference predict.
2. **breaker** — `faults.fail_predict(n)` trips the per-model circuit
   breaker after n consecutive failures; requests are then refused
   with "breaker_open" WITHOUT touching the model, and after the reset
   window a half-open probe recovers it.
3. **single_flight** — `faults.compile_storm` wedges the cold-bucket
   first compile; N concurrent cold requests must pay exactly ONE
   simulated trace (leads == 1) and all complete.
4. **cold_start** — two child processes share a
   `tpu_compile_cache_dir`: the second (a "restarted replica") must
   warm its whole bucket ladder + first request with ZERO compile-cache
   misses (every program loads from disk) and produce bit-identical
   predictions.

Usage: python scripts/overload_smoke.py [--out OVERLOAD_r01.json]
Exits nonzero on any gate failure; prints one machine-readable JSON
line per phase plus a final summary line.

CPU gate: this script and every child it starts run on the CPU platform
(JAX_PLATFORMS=cpu); it checks behaviour, not device speed, and needs no
chip — no parent here holds a chip that a child then needs.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import threading
import time

os.environ["JAX_PLATFORMS"] = "cpu"

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402

N_FEATURES = 10
SERVICE_S = 0.02          # injected per-dispatch service time
MICRO_BATCH = 8           # rows per coalesced dispatch
# deadline below the full-queue wait (48/8 dispatches x ~25ms ≈ 150ms),
# so the overload run exercises ALL THREE rejection paths: early
# entries expire in the queue (deadline_expired) until the EWMA
# converges, after which the shed policy refuses at admission, and
# bursts past the cap are queue_full
DEADLINE_MS = 80.0
MAX_QUEUE = 48


def _train(params_extra=None):
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(0)
    X = rng.randn(3000, N_FEATURES).astype(np.float32)
    y = (X[:, 0] + X[:, 1] * X[:, 2] > 0).astype(np.float32)
    params = {"objective": "binary", "verbose": -1, "num_leaves": 15,
              "min_data_in_leaf": 5}
    params.update(params_extra or {})
    ds = lgb.Dataset(X, y, params=dict(params))
    booster = lgb.train(dict(params), ds, num_boost_round=20,
                        verbose_eval=False)
    return X, booster, params


def _open_loop(reg, X, qps: float, seconds: float, seed: int):
    """Offer Poisson arrivals at `qps` via submit(); resolve everything.
    Returns (admitted_latencies_s, rejections, failures, results)."""
    from lightgbm_tpu.serving import ServingOverload
    rng = np.random.RandomState(seed)
    n_req = max(1, int(qps * seconds))
    gaps = rng.exponential(1.0 / qps, size=n_req)
    arrivals = np.cumsum(gaps)
    lock = threading.Lock()
    lats, results = [], {}
    rejections = []      # (reason, latency_s, retriable)
    failures = []        # future-side structured failures
    pending = [0]

    def on_done(fut, arrival_abs, idx):
        dt = time.perf_counter() - arrival_abs
        exc = fut.exception()
        with lock:
            pending[0] -= 1
            if exc is None:
                lats.append(dt)
                if idx not in results:
                    results[idx] = fut.result()
            else:
                failures.append((type(exc).__name__,
                                 getattr(exc, "reason", None), dt,
                                 bool(getattr(exc, "retriable", False))))

    start = time.perf_counter()
    for i in range(n_req):
        target = start + arrivals[i]
        now = time.perf_counter()
        if target > now:
            time.sleep(target - now)
        idx = i % 256
        arrival_abs = time.perf_counter()
        try:
            fut = reg.submit("main", X[idx])
        except ServingOverload as exc:
            with lock:
                rejections.append(
                    (exc.reason, time.perf_counter() - arrival_abs,
                     bool(exc.retriable)))
            continue
        with lock:
            pending[0] += 1
        fut.add_done_callback(
            lambda f, a=arrival_abs, j=idx: on_done(f, a, j))
    deadline = time.time() + 30
    while time.time() < deadline:
        with lock:
            if pending[0] == 0:
                break
        time.sleep(0.01)
    with lock:
        return (sorted(lats), list(rejections), list(failures),
                dict(results), n_req, pending[0])


def phase_overload() -> dict:
    import lightgbm_tpu as lgb
    from lightgbm_tpu.serving import ModelRegistry
    from lightgbm_tpu.testing import faults

    X, booster, _ = _train()
    serve = lgb.Booster(model_str=booster.model_to_string(), params={
        "tpu_serving_deadline_ms": DEADLINE_MS,
        "tpu_serving_max_queue": MAX_QUEUE,
        "tpu_predict_micro_batch": MICRO_BATCH,
        "tpu_predict_micro_batch_window_ms": 2.0,
    })
    ref = booster.predict(X[:256])   # unloaded bit-identity reference

    reg = ModelRegistry(warmup_rows=64)
    reg.publish("main", serve)
    reg.submit("main", X[0]).result(timeout=30)   # settle the batcher

    capacity = MICRO_BATCH / SERVICE_S            # rows/s at saturation
    seconds = float(os.environ.get("OVERLOAD_SECONDS", 2.5))
    faults.slow_predict(SERVICE_S)
    try:
        (cap_lats, cap_rej, cap_fail, _cap_res, cap_n,
         cap_pending) = _open_loop(reg, X, 0.4 * capacity, seconds, seed=3)
        (ov_lats, ov_rej, ov_fail, ov_res, ov_n,
         ov_pending) = _open_loop(reg, X, 2.0 * capacity, seconds, seed=7)
    finally:
        faults.reset()
    pred_stats = reg.stats()["models"]["main"]
    reg.close()

    def p99(lats):
        return lats[int(len(lats) * 0.99)] if lats else None

    cap_p99, ov_p99 = p99(cap_lats), p99(ov_lats)
    n_rejected = len(ov_rej) + len(ov_fail)
    n_resolved = len(ov_lats) + n_rejected
    rejected_structured = (
        all(retriable for _, _, retriable in ov_rej)
        and all(retriable for _, _, _, retriable in ov_fail))
    max_rej_latency = max(
        [lat for _, lat, _ in ov_rej]
        + [lat for _, _, lat, _ in ov_fail] + [0.0])
    # bit-identity on admitted requests: shedding changes WHETHER a
    # request is answered, never WHAT is answered
    bit_identical = all(
        float(v) == float(ref[idx]) for idx, v in ov_res.items())
    deadline_s = DEADLINE_MS / 1e3
    bound_s = deadline_s + 0.35    # queue-expiry envelope + dispatch slack
    gates = {
        "zero_dropped": ov_pending == 0 and n_resolved == ov_n
        and cap_pending == 0,
        "rejections_structured_retriable": rejected_structured
        and n_rejected > 0,
        "rejections_prompt": max_rej_latency <= deadline_s + 0.5,
        "admitted_p99_bounded": ov_p99 is not None
        and ov_p99 <= bound_s
        and (cap_p99 is None or ov_p99 <= max(20 * cap_p99, bound_s)),
        "some_traffic_admitted": len(ov_lats) >= MICRO_BATCH,
        "bit_identical_admitted": bit_identical and len(ov_res) > 0,
    }
    reasons = {}
    for reason, _, _ in ov_rej:
        reasons[reason] = reasons.get(reason, 0) + 1
    for _, reason, _, _ in ov_fail:
        reasons[reason] = reasons.get(reason, 0) + 1
    return {
        "phase": "overload", "ok": all(gates.values()), "gates": gates,
        "capacity_rows_per_s": capacity,
        "offered_qps": {"at_capacity": 0.4 * capacity,
                        "overload": 2.0 * capacity},
        "seconds_per_run": seconds,
        "at_capacity": {"offered": cap_n, "completed": len(cap_lats),
                        "rejected": len(cap_rej) + len(cap_fail),
                        "p50_ms": round(cap_lats[len(cap_lats) // 2] * 1e3,
                                        2) if cap_lats else None,
                        "p99_ms": round(cap_p99 * 1e3, 2)
                        if cap_p99 else None},
        "overload": {"offered": ov_n, "completed": len(ov_lats),
                     "rejected_at_submit": len(ov_rej),
                     "rejected_in_queue": len(ov_fail),
                     "pending_after_grace": ov_pending,
                     "p50_ms": round(ov_lats[len(ov_lats) // 2] * 1e3, 2)
                     if ov_lats else None,
                     "p99_ms": round(ov_p99 * 1e3, 2) if ov_p99 else None,
                     "p99_multiple_of_capacity":
                     round(ov_p99 / cap_p99, 2)
                     if (ov_p99 and cap_p99) else None,
                     "max_rejection_latency_ms":
                     round(max_rej_latency * 1e3, 2),
                     "rejection_reasons": reasons},
        "deadline_ms": DEADLINE_MS, "max_queue": MAX_QUEUE,
        "admission": pred_stats.get("admission"),
    }


def phase_breaker() -> dict:
    import lightgbm_tpu as lgb
    from lightgbm_tpu.serving import ModelRegistry, ServingOverload
    from lightgbm_tpu.testing import faults

    X, booster, _ = _train()
    reg = ModelRegistry(warmup_rows=16, breaker_failures=3,
                        breaker_reset_s=0.4)
    reg.publish("m", lgb.Booster(model_str=booster.model_to_string()))
    reg.predict("m", X[:4])

    faults.fail_predict(3)
    injected = 0
    for _ in range(3):
        try:
            reg.predict("m", X[:4])
        except ServingOverload:
            break
        except Exception:
            injected += 1
    tripped_reason = None
    t_reject0 = time.perf_counter()
    try:
        reg.predict("m", X[:4])
    except ServingOverload as exc:
        tripped_reason = exc.reason
    reject_latency = time.perf_counter() - t_reject0
    faults.reset()

    time.sleep(0.5)               # past the reset window: half-open
    probe_ok = True
    try:
        reg.predict("m", X[:4])   # the single probe; success closes it
        reg.predict("m", X[:4])
    except Exception:
        probe_ok = False
    st = reg.stats()["models"]["m"]["breaker"]
    reg.close()
    gates = {
        "tripped_after_failures": injected == 3
        and tripped_reason == "breaker_open",
        "rejection_without_device_time": reject_latency < 0.05,
        "recovered_via_half_open": probe_ok and st["state"] == "closed"
        and st["recoveries"] >= 1,
    }
    return {"phase": "breaker", "ok": all(gates.values()), "gates": gates,
            "breaker": st, "injected_failures": injected,
            "reject_latency_ms": round(reject_latency * 1e3, 3)}


def phase_single_flight() -> dict:
    from lightgbm_tpu.serving import Predictor
    from lightgbm_tpu.testing import faults

    X, booster, _ = _train()
    predictor = Predictor(booster, raw_score=True)   # cold: no warmup
    storm_s = 0.3
    n_threads = 12
    faults.compile_storm(storm_s)
    results, errs = [], []

    def worker(i):
        try:
            results.append(float(predictor.predict_one(X[i])))
        except Exception as exc:
            errs.append(repr(exc))

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(n_threads)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    faults.reset()
    sf = dict(predictor._single_flight.counts)
    gates = {
        "exactly_one_compile": sf["leads"] == 1,
        "followers_waited": sf["waits"] >= n_threads - 1,
        "all_completed": len(results) == n_threads and not errs,
        # one shared trace, not one per request (would be ~3.6s)
        "storm_collapsed": wall < n_threads * storm_s / 2,
    }
    return {"phase": "single_flight", "ok": all(gates.values()),
            "gates": gates, "single_flight": sf, "threads": n_threads,
            "storm_seconds": storm_s, "wall_seconds": round(wall, 3),
            "errors": errs[:3]}


def _cold_child(cache_dir: str) -> None:
    """One 'replica': train deterministically, then warm the serving
    ladder + first request counting compile-cache traffic."""
    import jax.monitoring
    events = []
    jax.monitoring.register_event_listener(
        lambda name, **kw: events.append(name))
    X, booster, _ = _train({"tpu_compile_cache_dir": cache_dir})
    predictor = booster.serving_predictor(raw_score=True)
    events.clear()                 # count serving warmup only
    t0 = time.perf_counter()
    predictor.warmup(max_rows=64)
    first = predictor.predict_one(X[0])
    wall = time.perf_counter() - t0
    print(json.dumps({
        "hits": sum(1 for e in events if "cache_hit" in e),
        "misses": sum(1 for e in events if "cache_miss" in e),
        "warmup_seconds": round(wall, 3), "first_pred": float(first),
    }), flush=True)


def phase_cold_start() -> dict:
    import tempfile
    # a scratch cache for the cold-vs-warm replica pair, placed the way
    # a deployment places one — from outside, by the standard variable,
    # which wins over any default (lightgbm_tpu/__init__.py). The
    # replicas still name tpu_compile_cache_dir: that drops the
    # persistence floor so the small bucket programs persist.
    cache_dir = tempfile.mkdtemp(prefix="lgbm_tpu_overload_cc_")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    runs = []
    for i in range(2):
        res = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--cold-child", cache_dir],
            env=env, capture_output=True, text=True, timeout=600)
        line = next((ln for ln in reversed(res.stdout.splitlines())
                     if ln.startswith("{")), None)
        if res.returncode != 0 or line is None:
            return {"phase": "cold_start", "ok": False,
                    "error": (res.stdout + res.stderr)[-400:]}
        runs.append(json.loads(line))
    first, second = runs
    gates = {
        # replica 1 really compiled (the cache was genuinely cold)
        "first_replica_compiled": first["misses"] > 0,
        # replica 2 = the restarted replica: its whole ladder + first
        # bucketed request load from disk — no fresh trace anywhere
        "warm_replica_zero_misses": second["misses"] == 0
        and second["hits"] > 0,
        "bit_identical": first["first_pred"] == second["first_pred"],
    }
    return {"phase": "cold_start", "ok": all(gates.values()),
            "gates": gates, "cold_replica": first,
            "warm_replica": second}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(REPO,
                                                  "OVERLOAD_r01.json"))
    ap.add_argument("--cold-child", default=None)
    args = ap.parse_args()
    if args.cold_child:
        _cold_child(args.cold_child)
        return 0

    t0 = time.time()
    phases = {}
    for fn in (phase_overload, phase_breaker, phase_single_flight):
        rec = fn()
        phases[rec["phase"]] = rec
        print(json.dumps(rec), flush=True)
    rec = phase_cold_start()
    phases[rec["phase"]] = rec
    print(json.dumps(rec), flush=True)

    ok = all(p.get("ok") for p in phases.values())
    summary = {"shape": "overload", "ok": ok,
               "wall_seconds": round(time.time() - t0, 1),
               "phases": phases}
    with open(args.out, "w") as fh:
        json.dump(summary, fh, indent=1)
    print(json.dumps({"shape": "overload", "ok": ok,
                      "out": args.out}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
