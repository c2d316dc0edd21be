"""Serving fast-path regression gates.

Phase 1 — steady state: repeated single-row predict must trigger ZERO
recompilations and ZERO forest restacks after warmup. Trains a tiny
model, warms the serving Predictor over its bucket ladder, then fires
repeated single-row predicts while counting jax backend compilations
(via jax.monitoring compile events) and CompiledForest restacks. Any
nonzero count means the low-latency path silently regressed to
retracing/restacking — the exact failure mode the shape-bucketed
dispatch and the model-version cache exist to prevent.

Phase 2 — hot swap under load: a ModelRegistry serves continuous
submit() traffic while a new model version is published mid-stream.
Gates: ZERO dropped/failed futures across the swap, no stale-version
results after publish() returns (every post-swap future resolves to
the NEW model's prediction), and ZERO compilations on already-seen
buckets after the swap (the incoming predictor warms its ladder
BEFORE the swap, so swap-time traffic never retraces).

Usage: python scripts/predict_latency_smoke.py
Exits nonzero on regression; prints one machine-readable JSON line.

CPU gate: this script and every child it starts run on the CPU platform
(JAX_PLATFORMS=cpu); it checks behaviour, not device speed, and needs no
chip — no parent here holds a chip that a child then needs.
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time

os.environ["JAX_PLATFORMS"] = "cpu"

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402


def main() -> int:
    import jax.monitoring
    import lightgbm_tpu as lgb
    from lightgbm_tpu.serving import ModelRegistry

    compile_events = []
    jax.monitoring.register_event_listener(
        lambda name, **kw: compile_events.append(name)
        if "compil" in name else None)

    rng = np.random.RandomState(0)
    X = rng.randn(2000, 10).astype(np.float32)
    y = (X[:, 0] + X[:, 1] * X[:, 2] > 0).astype(np.float32)
    params = {"objective": "binary", "verbose": -1, "num_leaves": 15,
              "min_data_in_leaf": 5}
    ds = lgb.Dataset(X, y, params=dict(params))
    booster = lgb.train(dict(params), ds, num_boost_round=20,
                        verbose_eval=False)

    predictor = booster.serving_predictor()
    warm = predictor.warmup(max_rows=64)
    # one settling request per path the loop exercises
    predictor.predict_one(X[0])
    predictor.predict(X[:3])

    stats0 = predictor.stats()
    compile_events.clear()
    reps = int(os.environ.get("SMOKE_REPS", 50))
    t0 = time.perf_counter()
    for i in range(reps):
        predictor.predict_one(X[i % len(X)])
        predictor.predict(X[i % 100:i % 100 + 3])
    wall = time.perf_counter() - t0
    stats1 = predictor.stats()

    compiles = len(compile_events)
    restacks = stats1["stack_restacks"] - stats0["stack_restacks"]
    steady_ok = compiles == 0 and restacks == 0

    # ---- phase 2: hot swap under load ----------------------------------
    booster_b = lgb.train(dict(params), ds, num_boost_round=35,
                          verbose_eval=False)
    pa = booster.predict(X[:64])
    pb = booster_b.predict(X[:64])
    assert not np.array_equal(pa, pb), "swap models must differ"

    reg = ModelRegistry(warmup_rows=64)
    reg.publish("m", booster)
    # settle the registry's submit/micro-batch route on model A
    reg.submit("m", X[0]).result(timeout=30)

    pre_futs, post_futs = [], []
    swapped = threading.Event()
    stop = threading.Event()

    def fire():
        i = 0
        while not stop.is_set() and i < 20000:
            # classify BEFORE submitting: a future counts as post-swap
            # only if publish() had returned before submit() started —
            # a submit racing the swap may legitimately resolve on the
            # old model (in-flight futures complete on the accepting
            # model), which must not flake the stale gate
            was_swapped = swapped.is_set()
            fut = reg.submit("m", X[i % 64])
            (post_futs if was_swapped else pre_futs).append((i % 64, fut))
            i += 1
            time.sleep(0.0005)            # paced open-loop-ish stream

    th = threading.Thread(target=fire)
    th.start()
    time.sleep(0.05)                      # load running against A
    reg.publish("m", booster_b)           # warms BEFORE the atomic swap
    swapped.set()
    compile_events.clear()                # post-swap compiles gate
    time.sleep(0.05)                      # load running against B
    stop.set()
    th.join()

    dropped = 0
    stale_after_swap = 0
    for i, fut in pre_futs + post_futs:
        try:
            val = fut.result(timeout=30)
        except Exception:
            dropped += 1
            continue
        if not (np.allclose(val, pa[i]) or np.allclose(val, pb[i])):
            dropped += 1                  # misrouted = dropped contract
    # futures submitted after publish() returned must be NEW-model only
    for i, fut in post_futs:
        try:
            if not np.allclose(fut.result(timeout=30), pb[i]):
                stale_after_swap += 1
        except Exception:
            pass                          # already counted as dropped
    # steady post-swap traffic on already-seen buckets: zero compiles
    for i in range(20):
        reg.submit("m", X[i % 64]).result(timeout=30)
    swap_compiles = len(compile_events)
    reg.close()

    swap_ok = (dropped == 0 and stale_after_swap == 0
               and swap_compiles == 0 and len(post_futs) > 0)
    ok = steady_ok and swap_ok
    print(json.dumps({
        "metric": "predict_latency_smoke",
        "value": 1 if ok else 0,
        "unit": "pass",
        "detail": {
            "reps": reps,
            "compiles_after_warmup": compiles,
            "restacks_after_warmup": int(restacks),
            "warmup_buckets": warm["buckets"],
            "warmup_seconds": round(warm["seconds"], 3),
            "p50_latency_ms": stats1.get("p50_latency_ms"),
            "steady_wall_seconds": round(wall, 3),
            "hot_swap": {
                "in_flight_futures": len(pre_futs),
                "post_swap_futures": len(post_futs),
                "dropped_or_misrouted": dropped,
                "stale_after_swap": stale_after_swap,
                "compiles_after_swap_on_seen_buckets": swap_compiles,
            },
        },
    }), flush=True)
    if not steady_ok:
        print("FAIL: fast path retraced (%d compiles) or restacked (%d) "
              "after warmup" % (compiles, restacks), file=sys.stderr)
    if not swap_ok:
        print("FAIL: hot swap dropped/misrouted %d future(s), %d stale "
              "post-swap result(s), %d post-swap compile(s), %d post-swap "
              "future(s)" % (dropped, stale_after_swap, swap_compiles,
                             len(post_futs)), file=sys.stderr)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
