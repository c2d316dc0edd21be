"""Serving front end: warmup, low-latency small-batch path, optional
micro-batching, admission control, and throughput/latency counters.

The reference serves predictions through a per-model `Predictor`
(predictor.hpp:24-205) whose closures are built once and reused per
request; this is its TPU-shaped counterpart for the ROADMAP's
"heavy traffic from millions of users" north star. The heavy lifting —
device-resident stacked forests, shape-bucketed dispatch, the pipelined
chunk loop — lives in `GBDT` + `serving.forest.CompiledForest`; this
layer adds what a serving process needs around it:

- `warmup()` compiles the whole bucket ladder up front so the first
  real request never pays a trace (and the stacking happens exactly
  once, before traffic arrives); with `tpu_compile_cache_dir` set the
  ladder's programs persist to disk, so a RESTARTED replica's warmup
  loads them back instead of re-tracing;
- `predict()` / `predict_one()` time every request into a latency ring
  and telemetry counters (`serving/requests`, `serving/rows`), the same
  surface as the training-side counters;
- `submit()` optionally coalesces concurrent single-row requests into
  one device dispatch (micro-batching): rows arriving within
  `tpu_predict_micro_batch_window_ms` of each other ride one bucketed
  program instead of one dispatch each;
- admission control (serving/admission.py): queue-depth / in-flight
  caps (`tpu_serving_max_queue` / `tpu_serving_max_inflight`),
  per-request deadlines (`tpu_serving_deadline_ms` + per-call
  `deadline_ms=` overrides), and the EWMA shed policy — past
  saturation, requests that would expire in the queue are refused
  IMMEDIATELY with a structured retriable `ServingOverload` /
  `DeadlineExceeded` instead of being answered late. Shedding changes
  *whether* a request is answered, never *what* is answered: admitted
  requests stay bit-identical to an unloaded serve;
- cold-start-storm protection: concurrent first requests on an unseen
  shape bucket run exactly one compile (`serving.forest.SingleFlight`);
  the others wait under their deadlines or shed.
"""
from __future__ import annotations

import threading
import time
from concurrent.futures import Future, InvalidStateError
from typing import Any, Dict, List, Optional

import numpy as np

from .. import log, telemetry
from ..testing import faults
from .admission import (AdmissionController, DeadlineExceeded,
                        PredictorShutdown, ServingOverload)
from .forest import (SingleFlight, SingleFlightExpired, bucket_ladder,
                     bucket_rows, enable_compile_cache)

# latency histogram bounds: 10us..~20s exponential — a fixed-memory
# distribution replacing the old bounded ring, so p50/p95/p99 cover the
# predictor's WHOLE service life, not the last window
_LATENCY_BOUNDS = tuple(1e-5 * (2.0 ** i) for i in range(22))
# micro-batch size distribution (rows per coalesced dispatch)
_BATCH_BOUNDS = (1, 2, 4, 8, 16, 32, 64, 128, 256)


class _QueueItem:
    """One queued submit(): the row, its future, and the admission
    evidence the batch loop needs to expire/time it."""
    __slots__ = ("arr", "fut", "enqueued", "deadline_abs")

    def __init__(self, arr, fut, enqueued, deadline_abs):
        self.arr = arr
        self.fut = fut
        self.enqueued = enqueued
        self.deadline_abs = deadline_abs


def _resolve(fut: Future, value) -> None:
    try:
        fut.set_result(value)
    except InvalidStateError:  # raced close()'s shutdown sweep
        pass


def _fail(fut: Future, exc: BaseException) -> None:
    try:
        fut.set_exception(exc)
    except InvalidStateError:
        pass


class Predictor:
    """Reference: class Predictor, predictor.hpp:24-205 — built once per
    booster, reused per request. Accepts a `basic.Booster` or a bare
    `boosting.GBDT`; per-request overrides ride on `predict(**kw)`."""

    def __init__(self, booster, num_iteration: int = -1,
                 raw_score: bool = False, pred_leaf: bool = False,
                 pred_contrib: bool = False, pred_early_stop: bool = False,
                 pred_early_stop_freq: int = 10,
                 pred_early_stop_margin: float = 10.0):
        self._gbdt = getattr(booster, "_inner", booster)
        self._kwargs = dict(
            num_iteration=num_iteration, raw_score=raw_score,
            pred_leaf=pred_leaf, pred_contrib=pred_contrib,
            pred_early_stop=pred_early_stop,
            pred_early_stop_freq=pred_early_stop_freq,
            pred_early_stop_margin=pred_early_stop_margin)
        io = self._gbdt.config.io
        self._micro_batch = max(0, int(io.tpu_predict_micro_batch))
        self._window_s = max(0.0, float(
            io.tpu_predict_micro_batch_window_ms)) / 1e3
        self._bucket_min = int(io.tpu_predict_bucket_min)
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._queue: List[_QueueItem] = []
        self._batcher: Optional[threading.Thread] = None
        self._closed = False
        # admission control: all caps default to 0 (= off), reproducing
        # the pre-admission unbounded behavior exactly
        self.admission = AdmissionController(
            max_queue=int(io.tpu_serving_max_queue),
            max_inflight=int(io.tpu_serving_max_inflight),
            deadline_s=max(0.0, float(io.tpu_serving_deadline_ms)) / 1e3)
        # cold-start-storm protection: one compile per unseen bucket
        self._single_flight = SingleFlight()
        if getattr(io, "tpu_compile_cache_dir", ""):
            enable_compile_cache(io.tpu_compile_cache_dir)
        # always-on local instruments (stats() must work with global
        # telemetry off), registered as SHARED registry instruments so
        # the Prometheus export reads the same series — one observe per
        # request, not a local copy plus a registry twin (a later
        # telemetry.reset() only drops them from export, never from
        # stats())
        self._latency_hist = telemetry.registry().register_histogram(
            telemetry.Histogram("serving/latency_seconds",
                                bounds=_LATENCY_BOUNDS))
        self._batch_hist = telemetry.registry().register_histogram(
            telemetry.Histogram("serving/micro_batch_rows",
                                bounds=_BATCH_BOUNDS))
        self._counts = {"requests": 0, "rows": 0,
                        "micro_batches": 0, "micro_rows": 0,
                        "batch_isolated_rows": 0}
        self._warmup_seconds: Optional[float] = None
        self._warmup_buckets: List[int] = []

    # ------------------------------------------------------------------
    def num_features(self) -> int:
        return self._gbdt.max_feature_idx + 1

    def _check_width(self, arr: np.ndarray) -> None:
        """Reject wrong-width rows up front with a clear error — before
        this check a mis-shaped row surfaced as an XLA shape failure at
        the dispatch site AND burned a spurious retrace for a program
        no valid request can ever reuse."""
        want = self.num_features()
        if arr.ndim != 2 or arr.shape[1] != want:
            raise log.LightGBMError(
                "Prediction input has %s feature column(s); this model "
                "expects %d (shape %s)"
                % (arr.shape[1] if arr.ndim == 2 else "a bad number of",
                   want, tuple(arr.shape)))

    def warmup(self, max_rows: Optional[int] = None) -> Dict[str, Any]:
        """Compile every bucket program up to `max_rows` (default
        `tpu_predict_warmup_rows`) and stack the forest once, so the
        first real request is pure device compute. Warmup traffic is
        NOT counted in the request/latency stats. With
        `tpu_compile_cache_dir` set the compiled programs also persist
        to disk, so the next replica's warmup is a cache read."""
        io = self._gbdt.config.io
        cap = int(max_rows if max_rows is not None
                  else io.tpu_predict_warmup_rows)
        ladder = bucket_ladder(int(io.tpu_predict_bucket_min), max(1, cap))
        f = self.num_features()
        t0 = time.perf_counter()
        # synthetic all-zeros rows compile/stack fine but are useless —
        # and dangerous — as quantize-gate calibration (16 identical
        # rows traverse one leaf per tree, freezing a near-zero delta
        # per model version): flag them so the gate defers to the first
        # REAL batch
        self._gbdt._quant_gate_defer = True
        try:
            for rows in ladder:
                self._predict_timed(np.zeros((rows, f), np.float32),
                                    count=False)
                self._single_flight.mark(rows)
        finally:
            self._gbdt._quant_gate_defer = False
        self._warmup_seconds = time.perf_counter() - t0
        self._warmup_buckets = ladder
        telemetry.counter_add("serving/warmup_buckets", len(ladder))
        log.debug("Predictor warmup: %d bucket programs in %.3fs",
                  len(ladder), self._warmup_seconds)
        return {"buckets": ladder, "seconds": self._warmup_seconds}

    # ------------------------------------------------------------------
    def _request_bucket(self, nrows: int) -> Optional[int]:
        """The shape bucket a request of `nrows` rows dispatches
        through (the single-flight key). None when bucketing is off —
        every size then traces its own program and there is no shared
        bucket for a storm to pile onto. The row count is capped at the
        dispatch chunk EXACTLY like GBDT._pipelined_chunks caps it:
        two over-chunk requests of different sizes compile the same
        chunk-bucket program and must share one flight key (the walk
        default is used — for matmul layouts whose chunk is larger,
        over-chunk requests merely share a key early, which only
        widens the guard, never splits it)."""
        if self._bucket_min <= 0 or nrows <= 0:
            return None
        cap = self._gbdt._predict_chunk_rows(
            self._gbdt._PREDICT_ROW_CHUNK)
        return bucket_rows(min(nrows, cap), self._bucket_min, cap=cap)

    def _predict_timed(self, arr: np.ndarray, count: bool = True,
                       deadline_abs: Optional[float] = None, **overrides):
        """The timed dispatch body shared by predict(), the micro-batch
        loop, and warmup(). Admission decisions happen in the PUBLIC
        entry points — this layer only guards the cold-bucket compile
        (single flight) and feeds the latency instruments."""
        kw = dict(self._kwargs)
        kw.update(overrides)
        t0 = time.perf_counter()
        bucket = self._request_bucket(arr.shape[0])
        lead = False
        cold = bucket is not None and not self._single_flight.seen(bucket)
        if cold:
            timeout = None if deadline_abs is None \
                else deadline_abs - time.perf_counter()
            try:
                lead = self._single_flight.begin(bucket, timeout=timeout)
            except SingleFlightExpired:
                raise self.admission._reject("compile_wait", ServingOverload(
                    "Deadline expired while waiting for bucket %d's "
                    "first compile (single-flight); retriable" % bucket,
                    reason="compile_wait"))
        ok = False
        try:
            if lead:
                # test seam: compile_storm() wedges the leader here,
                # simulating the 29-81s trace the followers must NOT
                # replicate
                faults.inject("serving.compile")
            faults.inject("serving.predict")
            out = self._gbdt.predict(arr, **kw)
            ok = True
        finally:
            if lead:
                self._single_flight.finish(bucket, ok)
        dt = time.perf_counter() - t0
        if count and not cold:
            # compile time is NOT service-time evidence: a cold-bucket
            # request (the single-flight leader pays the trace, its
            # followers pay the wait) or a slow warmup would otherwise
            # prime the EWMA at compile scale — ~30s on wide shapes —
            # and the shed policy would then refuse every deadline-
            # bearing request forever (shed requests never dispatch, so
            # nothing would ever correct the estimate)
            self.admission.observe_service(dt)
        if count:
            with self._lock:
                self._counts["requests"] += 1
                self._counts["rows"] += int(arr.shape[0])
            self._latency_hist.observe(dt)
            telemetry.counter_add("serving/requests", 1)
            telemetry.counter_add("serving/rows", int(arr.shape[0]))
        return out

    def predict(self, data, deadline_ms: Optional[float] = None,
                **overrides):
        """Timed predict over a [N, F] batch (rows also accepted as a
        single 1-D row, returned as a 1-row result — use predict_one()
        for the squeezed scalar path). `deadline_ms` overrides
        `tpu_serving_deadline_ms` for this call: a request whose
        estimated service time already exceeds it is refused with a
        structured retriable error BEFORE any device work."""
        # TreeSHAP walks raw f64 thresholds (shap._decision_vec): an f32
        # cast here can flip a hot/cold path for values straddling an
        # f32-rounded threshold, so contrib keeps the caller's dtype
        # (_predict_timed does the full kwargs merge for the dispatch)
        contrib = overrides.get("pred_contrib",
                                self._kwargs["pred_contrib"])
        arr = np.asarray(data) if contrib \
            else np.asarray(data, np.float32)
        if arr.ndim == 1:
            arr = arr.reshape(1, -1)
        self._check_width(arr)
        deadline_abs = self.admission.deadline_for(deadline_ms)
        self.admission.admit_sync(deadline_abs)
        try:
            return self._predict_timed(arr, deadline_abs=deadline_abs,
                                       **overrides)
        finally:
            self.admission.release_sync()

    def predict_one(self, row, deadline_ms: Optional[float] = None,
                    **overrides):
        """Single-row fast path: pads to the smallest bucket on one
        resident compiled program; returns the row's prediction with
        the batch axis squeezed."""
        return self.predict(np.asarray(row, np.float32).reshape(1, -1),
                            deadline_ms=deadline_ms, **overrides)[0]

    # ------------------------------------------------------------------
    # micro-batching: coalesce concurrent single-row requests
    def submit(self, row, deadline_ms: Optional[float] = None) -> Future:
        """Enqueue one row; resolves to its prediction. With
        `tpu_predict_micro_batch` 0 this degenerates to a synchronous
        predict_one; otherwise rows arriving within the window share
        one device dispatch. Refusals (queue full, shed, closed) raise
        `ServingOverload` HERE — an accepted Future either resolves to
        a prediction or fails with a structured error (deadline expiry,
        shutdown, a predict failure); it is never silently dropped."""
        arr = np.asarray(row, np.float32).reshape(-1)
        # validate BEFORE enqueueing: a wrong-width row must fail its
        # caller, not poison the whole coalesced batch it would ride in
        self._check_width(arr.reshape(1, -1))
        deadline_abs = self.admission.deadline_for(deadline_ms)
        fut: Future = Future()
        if self._micro_batch <= 0:
            self.admission.admit_sync(deadline_abs)
            try:
                _resolve(fut, self._predict_timed(
                    arr.reshape(1, -1), deadline_abs=deadline_abs)[0])
            except Exception as exc:  # surface through the future
                _fail(fut, exc)
            finally:
                self.admission.release_sync()
            return fut
        with self._cv:
            if self._closed:
                raise PredictorShutdown()
            # queue cap + EWMA shed under the lock: the depth the
            # decision reads is the depth the enqueue appends to
            self.admission.admit_queued(len(self._queue), deadline_abs)
            if self._batcher is None:
                self._batcher = threading.Thread(
                    target=self._batch_loop, name="lgbm-tpu-microbatch",
                    daemon=True)
                self._batcher.start()
            self._queue.append(_QueueItem(arr, fut, time.perf_counter(),
                                          deadline_abs))
            telemetry.gauge_set("serving/queue_depth", len(self._queue))
            self._cv.notify()
        return fut

    def _batch_loop(self) -> None:
        while True:
            with self._cv:
                while not self._queue and not self._closed:
                    self._cv.wait()
                if self._closed and not self._queue:
                    return
                # collect up to micro_batch rows arriving within the window
                deadline = time.perf_counter() + self._window_s
                while len(self._queue) < self._micro_batch:
                    remaining = deadline - time.perf_counter()
                    if remaining <= 0 or self._closed:
                        break
                    self._cv.wait(timeout=remaining)
                batch = self._queue[:self._micro_batch]
                del self._queue[:len(batch)]
                telemetry.gauge_set("serving/queue_depth", len(self._queue))
            now = time.perf_counter()
            live = []
            for item in batch:
                self.admission.observe_wait(now - item.enqueued)
                # claim each future; a client may have cancel()ed while
                # its row sat in the window (request-timeout pattern) —
                # resolving a cancelled future raises and would kill
                # this thread
                if not item.fut.set_running_or_notify_cancel():
                    continue
                if item.deadline_abs is not None and now > item.deadline_abs:
                    # expired in the queue: prompt structured rejection
                    # BEFORE burning device time on a row whose answer
                    # nobody is waiting for anymore
                    _fail(item.fut, self.admission.expire(
                        now - item.enqueued, item.deadline_abs))
                    continue
                live.append(item)
            if not live:
                continue
            rows = np.stack([item.arr for item in live])
            # the batch inherits its TIGHTEST member deadline so a
            # cold-bucket compile (single-flight wait) cannot answer
            # deadline-bearing futures tens of seconds late; if the
            # dispatch sheds on it, the per-row isolation pass below
            # re-runs each row under its OWN deadline (a no-deadline
            # row then waits the compile out instead of failing)
            deadlines = [item.deadline_abs for item in live
                         if item.deadline_abs is not None]
            try:
                res = self._predict_timed(
                    rows, deadline_abs=min(deadlines) if deadlines
                    else None)
            except Exception as exc:
                self._isolate_batch_failure(live, exc)
                continue
            with self._lock:
                self._counts["micro_batches"] += 1
                self._counts["micro_rows"] += len(live)
            self._batch_hist.observe(len(live))
            telemetry.counter_add("serving/micro_batches", 1)
            for i, item in enumerate(live):
                _resolve(item.fut, res[i])

    def _isolate_batch_failure(self, live: List[_QueueItem],
                               exc: BaseException) -> None:
        """A predict failure inside a coalesced batch must fail only
        the rows that actually fail: re-run each row alone so one
        poisoned row (or one transient fault) cannot take down every
        co-riding future. Single-row batches skip the retry — the
        failure IS that row's answer. Each re-run honors its row's
        deadline: under overload the serialized per-row dispatches can
        outlive deadlines that were met at pop time, and an expired
        row must not burn device time nobody is waiting for."""
        if len(live) == 1:
            _fail(live[0].fut, exc)
            return
        telemetry.counter_add("serving/batch_isolated", 1)
        with self._lock:
            self._counts["batch_isolated_rows"] += len(live)
        for item in live:
            now = time.perf_counter()
            if item.deadline_abs is not None and now > item.deadline_abs:
                _fail(item.fut, self.admission.expire(
                    now - item.enqueued, item.deadline_abs))
                continue
            try:
                out = self._predict_timed(item.arr.reshape(1, -1),
                                          count=False,
                                          deadline_abs=item.deadline_abs)
            except Exception as row_exc:
                _fail(item.fut, row_exc)
            else:
                _resolve(item.fut, out[0])

    def close(self, timeout: float = 5.0) -> None:
        """Stop the micro-batcher. Queued requests are drained (they
        complete on this model — the registry's hot-swap contract);
        anything the batcher fails to drain within `timeout` (a wedged
        device, a dead thread) is failed with a structured
        `PredictorShutdown` instead of leaking an unresolved Future."""
        with self._cv:
            self._closed = True
            self._cv.notify_all()
            # read (don't clear) the batcher under the lock: EVERY
            # racing close() must wait out the same drain window —
            # Thread.join is multi-caller-safe, whereas clearing here
            # would let a second closer skip straight to the sweep and
            # fail futures the batcher was actively draining. Join
            # OUTSIDE the lock — the batcher takes it to drain
            batcher = self._batcher
        if batcher is not None:
            batcher.join(timeout=timeout)
            with self._cv:
                if self._batcher is batcher:
                    self._batcher = None
        # shutdown sweep: after the drain window nothing may stay
        # pending forever — a leaked Future is an indefinitely blocked
        # caller, the one outcome the overload contract forbids
        with self._cv:
            leftovers = self._queue[:]
            del self._queue[:]
            telemetry.gauge_set("serving/queue_depth", 0)
        for item in leftovers:
            if item.fut.set_running_or_notify_cancel():
                _fail(item.fut, PredictorShutdown())
                telemetry.counter_add("serving/shutdown_failed_futures", 1)

    # ------------------------------------------------------------------
    def stats(self) -> Dict[str, Any]:
        """Counters in the same spirit as the training-side counters:
        request/row totals, service-lifetime latency percentiles (from
        the bucketed telemetry histogram — bucket-resolution estimates,
        not a bounded recent-window sort), throughput, admission /
        shed / single-flight counters, and the forest cache's restack
        economics. The aggregates are also mirrored into `serving/*`
        registry gauges so the Prometheus export carries them without a
        stats() caller in the loop."""
        with self._lock:
            counts = dict(self._counts)
        hist = self._latency_hist.snapshot()
        out: Dict[str, Any] = dict(counts)
        out["model_version"] = int(self._gbdt._compiled_forest.version)
        stack = self._gbdt._compiled_forest.stats
        out.update({f"stack_{k}": int(v) for k, v in stack.items()})
        out["quantize"] = str(self._gbdt.config.io.tpu_predict_quantize)
        out["warmup_seconds"] = self._warmup_seconds
        out["warmup_buckets"] = list(self._warmup_buckets)
        out["admission"] = self.admission.stats()
        out["single_flight"] = dict(self._single_flight.counts)
        if hist["count"]:
            out["p50_latency_ms"] = round(
                self._latency_hist.quantile(0.50) * 1e3, 4)
            out["p95_latency_ms"] = round(
                self._latency_hist.quantile(0.95) * 1e3, 4)
            out["p99_latency_ms"] = round(
                self._latency_hist.quantile(0.99) * 1e3, 4)
            out["mean_latency_ms"] = round(
                hist["sum"] / hist["count"] * 1e3, 4)
            out["max_latency_ms"] = round(hist["max"] * 1e3, 4)
            if hist["sum"] > 0:
                out["rows_per_second"] = round(counts["rows"] / hist["sum"],
                                               2)
        if self._micro_batch > 0:
            with self._cv:
                out["queue_depth"] = len(self._queue)
            batch = self._batch_hist.snapshot()
            if batch["count"]:
                out["mean_micro_batch_rows"] = round(
                    batch["sum"] / batch["count"], 2)
        # cache hit/miss + latency mirrors for the file exporter
        telemetry.gauge_set("serving/stack_restacks", stack["restacks"])
        telemetry.gauge_set("serving/stack_hits", stack["hits"])
        telemetry.gauge_set("serving/stack_bytes", stack["bytes"])
        telemetry.gauge_set("serving/stack_evictions", stack["evictions"])
        telemetry.gauge_set("serving/model_version", out["model_version"])
        if hist["count"]:
            telemetry.gauge_set("serving/p99_latency_ms",
                                out["p99_latency_ms"])
        return out
