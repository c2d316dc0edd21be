"""Admission control for the serving tier: bounded queues, deadlines,
EWMA-based load shedding, per-model rate limits, and circuit breakers.

PR 10's open-loop bench shows the failure mode this module exists to
prevent: past saturation an unbounded `submit()` queue accepts every
request and answers all of them LATE — p99 grows without bound, callers
retry, and the retry storm compounds the overload. A resilient tier
degrades instead of collapsing: it answers the requests it can answer
on time and refuses the rest IMMEDIATELY with a structured, retriable
error, so callers back off against a clear signal instead of timing out
against a silent queue.

Four cooperating pieces (reference points: the shed/deadline discipline
of production RPC stacks, ported onto PR 11's robustness idiom of
structured failure evidence):

- `ServingOverload` / `DeadlineExceeded` — the rejection contract.
  Every refused request gets one of these, with a machine-readable
  `reason`, `retriable=True`, and a `retry_after_s` hint. Shedding
  changes *whether* a request is answered, never *what* is answered —
  admitted requests stay bit-identical to an unloaded serve.
- `AdmissionController` — per-predictor queue-depth / in-flight caps
  plus the EWMA shed policy: it tracks the exponentially-weighted
  queue wait and starts refusing new work when the estimated wait
  already exceeds the request's deadline (the request would expire in
  the queue; rejecting it now costs nothing and tells the caller the
  truth `deadline_ms` earlier).
- `TokenBucket` — per-model QPS isolation for the registry: one hot
  model exhausts its OWN budget and sheds, instead of queueing into
  the shared device and starving every other resident model.
- `CircuitBreaker` — per-model failure isolation: repeated predict
  failures trip the breaker open (requests are refused without
  touching the model), and after a backoff window it half-opens for a
  single probe — success closes it, failure re-opens with exponential
  backoff. Overload rejections are NOT failures and never trip it.

All counters live on the objects themselves (stats() must work with
global telemetry off) and are mirrored into `serving/*` registry
counters so the Prometheus export carries them with cross-rank
aggregation, PR 7 style. The first shed also lands a structured
`serving_overload` run-log event through `telemetry.active_recorder()`
— the serving-side mirror of PR 11's `rank_failure` evidence idiom.
"""
from __future__ import annotations

import threading
import time
from typing import Any, Dict, Optional

from .. import log, telemetry


class ServingOverload(log.LightGBMError):
    """A request refused by admission control. Always retriable: the
    refusal is about the server's CURRENT load, not about the request.

    `reason` is machine-readable: "queue_full", "inflight_full",
    "shed" (EWMA queue wait already exceeds the deadline),
    "rate_limited" (per-model token bucket), "breaker_open",
    "shutdown" (predictor closing; retry against the current entry /
    another replica), "compile_wait" (cold-bucket single-flight wait
    exceeded the deadline)."""

    retriable = True

    def __init__(self, message: str, reason: str = "overload",
                 retry_after_s: Optional[float] = None,
                 model: Optional[str] = None):
        super().__init__(message)
        self.reason = str(reason)
        self.retry_after_s = retry_after_s
        self.model = model


class DeadlineExceeded(ServingOverload):
    """The request's deadline expired before device dispatch (it would
    have been answered late; failing it in the queue burns no device
    time and unblocks the caller's retry immediately)."""

    def __init__(self, message: str, deadline_ms: Optional[float] = None,
                 waited_ms: Optional[float] = None):
        super().__init__(message, reason="deadline")
        self.deadline_ms = deadline_ms
        self.waited_ms = waited_ms


class PredictorShutdown(ServingOverload):
    """The predictor is closed (hot swap drained it, or the process is
    shutting down). The message contains "closed" by contract: the
    registry's swap-retry path keys on it to re-route the request to
    the entry that replaced this predictor."""

    def __init__(self, message: str = "Predictor is closed "
                 "(shutting down; retry against the current model)"):
        super().__init__(message, reason="shutdown")


class AdmissionController:
    """Per-predictor admission decisions: caps, deadlines, EWMA shed.

    `max_queue` bounds the micro-batch queue depth, `max_inflight`
    bounds concurrent synchronous predicts, `deadline_s` is the default
    request deadline (0 = none; per-call overrides ride on the request).
    All three are 0-disabled so the pre-existing unbounded behavior is
    exactly reproduced by the defaults."""

    # EWMA weight for queue-wait observations: 0.2 ~ the last ~10
    # dispatches dominate, fast enough to track a saturation edge and
    # smooth enough not to shed on one slow dispatch
    EWMA_ALPHA = 0.2
    # serving_overload run-log events: first rejection + every Nth
    EVENT_EVERY = 1000

    def __init__(self, max_queue: int = 0, max_inflight: int = 0,
                 deadline_s: float = 0.0):
        self.max_queue = max(0, int(max_queue))
        self.max_inflight = max(0, int(max_inflight))
        self.deadline_s = max(0.0, float(deadline_s))
        self._lock = threading.Lock()
        self._ewma_wait_s: Optional[float] = None
        self._ewma_service_s: Optional[float] = None
        self.inflight = 0
        self.counts: Dict[str, int] = {
            "admitted": 0, "shed": 0, "deadline_expired": 0,
            "queue_full": 0, "inflight_full": 0, "compile_wait": 0,
            "rejected": 0}

    # ------------------------------------------------------------------
    def deadline_for(self, deadline_ms: Optional[float]) -> Optional[float]:
        """Absolute deadline (perf_counter clock) for a request arriving
        now, honoring a per-call override (ms; <=0 = no deadline)."""
        d = self.deadline_s if deadline_ms is None \
            else max(0.0, float(deadline_ms)) / 1e3
        return (time.perf_counter() + d) if d > 0 else None

    def observe_wait(self, wait_s: float) -> None:
        """Fold one queue-wait observation (enqueue -> dispatch) into
        the EWMA the shed policy reads."""
        with self._lock:
            prev = self._ewma_wait_s
            self._ewma_wait_s = wait_s if prev is None else \
                (1 - self.EWMA_ALPHA) * prev + self.EWMA_ALPHA * wait_s
        telemetry.gauge_set("serving/queue_wait_ewma_ms",
                            round(self._ewma_wait_s * 1e3, 4))

    def observe_service(self, service_s: float) -> None:
        with self._lock:
            prev = self._ewma_service_s
            self._ewma_service_s = service_s if prev is None else \
                (1 - self.EWMA_ALPHA) * prev + self.EWMA_ALPHA * service_s

    @property
    def ewma_wait_s(self) -> float:
        with self._lock:
            return self._ewma_wait_s or 0.0

    @property
    def ewma_service_s(self) -> float:
        with self._lock:
            return self._ewma_service_s or 0.0

    # ------------------------------------------------------------------
    def _reject(self, kind: str, exc: ServingOverload) -> ServingOverload:
        with self._lock:
            self.counts[kind] += 1
            self.counts["rejected"] += 1
            total = self.counts["rejected"]
        telemetry.counter_add("serving/" + kind, 1)
        telemetry.counter_add("serving/rejected", 1)
        if total == 1 or total % self.EVENT_EVERY == 0:
            self._overload_event(kind, total)
        return exc

    def _overload_event(self, kind: str, total: int) -> None:
        """Structured overload evidence in the run log (PR 11's
        `rank_failure` idiom): an operator reading the trail of a
        degraded replica sees WHEN shedding started and what the
        controller believed about its queue at that moment."""
        rec = telemetry.active_recorder()
        if rec is None:
            return
        with self._lock:
            counts = dict(self.counts)
            ewma = self._ewma_wait_s
        rec.event("serving_overload", reason=kind,
                  rejected_total=int(total),
                  queue_wait_ewma_ms=None if ewma is None
                  else round(ewma * 1e3, 3),
                  deadline_ms=round(self.deadline_s * 1e3, 3),
                  max_queue=self.max_queue,
                  max_inflight=self.max_inflight, counts=counts)

    # ------------------------------------------------------------------
    def admit_queued(self, queue_depth: int,
                     deadline_abs: Optional[float]) -> None:
        """Admission decision for one submit(): queue cap, then the
        EWMA shed policy. Raises ServingOverload on refusal."""
        if self.max_queue > 0 and queue_depth >= self.max_queue:
            raise self._reject("queue_full", ServingOverload(
                "Serving queue is full (%d queued >= tpu_serving_max_queue"
                "=%d); retriable" % (queue_depth, self.max_queue),
                reason="queue_full",
                retry_after_s=max(self.ewma_wait_s, 0.001)))
        if deadline_abs is not None:
            remaining = deadline_abs - time.perf_counter()
            # the EWMA only updates when queued items are POPPED, so it
            # can hold a stale overload-era value after the burst ends;
            # shedding into an EMPTY queue on that stale estimate would
            # refuse traffic forever (nothing enqueued -> nothing
            # popped -> estimate never corrects). An empty queue admits
            # on the wait estimate — the pop-time deadline check still
            # expires anything that genuinely waits too long, and its
            # observe_wait drags the EWMA back down
            est = self.ewma_wait_s if queue_depth > 0 else 0.0
            if remaining <= 0 or est > remaining:
                raise self._reject("shed", ServingOverload(
                    "Shedding: estimated queue wait %.1fms exceeds the "
                    "request deadline (%.1fms remaining); retriable"
                    % (est * 1e3, max(remaining, 0.0) * 1e3),
                    reason="shed", retry_after_s=max(est, 0.001)))
        with self._lock:
            self.counts["admitted"] += 1
        telemetry.counter_add("serving/admitted", 1)

    def admit_sync(self, deadline_abs: Optional[float]) -> None:
        """Admission for one synchronous predict(): in-flight cap plus
        the deadline pre-check (estimated service time vs remaining
        budget — refuse BEFORE burning device time). Check and
        increment happen under ONE lock hold: a check-then-increment
        race would let K concurrent callers exceed the cap by K-1."""
        refusal = None
        with self._lock:
            if self.max_inflight > 0 and self.inflight >= self.max_inflight:
                refusal = ("inflight_full", ServingOverload(
                    "Too many in-flight predicts (%d >= tpu_serving_max_"
                    "inflight=%d); retriable"
                    % (self.inflight, self.max_inflight),
                    reason="inflight_full",
                    retry_after_s=max(self._ewma_service_s or 0.0, 0.001)))
            elif deadline_abs is not None:
                remaining = deadline_abs - time.perf_counter()
                # same staleness guard as the queue path: the service
                # EWMA only corrects when something DISPATCHES, so
                # shedding an idle predictor on a stale estimate (a
                # past slow-device period) would refuse deadline-
                # bearing traffic forever. With work in flight the
                # estimate is live evidence; idle, the request runs
                # immediately and its measurement re-anchors the EWMA
                est = (self._ewma_service_s or 0.0) \
                    if self.inflight > 0 else 0.0
                if remaining <= 0 or est > remaining:
                    refusal = ("shed", ServingOverload(
                        "Shedding: estimated service time %.1fms exceeds "
                        "the request deadline (%.1fms remaining); "
                        "retriable" % (est * 1e3, max(remaining, 0.0) * 1e3),
                        reason="shed", retry_after_s=max(est, 0.001)))
            if refusal is None:
                self.counts["admitted"] += 1
                self.inflight += 1
        if refusal is not None:
            # _reject re-takes the lock, so it must run OUTSIDE it
            raise self._reject(*refusal)
        telemetry.counter_add("serving/admitted", 1)

    def release_sync(self) -> None:
        with self._lock:
            self.inflight -= 1

    def expire(self, waited_s: float,
               deadline_abs: float) -> DeadlineExceeded:
        """Build + count the rejection for a queued request whose
        deadline passed before dispatch."""
        with self._lock:
            self.counts["deadline_expired"] += 1
            self.counts["rejected"] += 1
            total = self.counts["rejected"]
        telemetry.counter_add("serving/deadline_expired", 1)
        telemetry.counter_add("serving/rejected", 1)
        if total == 1 or total % self.EVENT_EVERY == 0:
            self._overload_event("deadline_expired", total)
        over_ms = (time.perf_counter() - deadline_abs) * 1e3
        return DeadlineExceeded(
            "Request deadline expired in the serving queue (waited "
            "%.1fms, %.1fms past deadline); retriable"
            % (waited_s * 1e3, over_ms),
            waited_ms=round(waited_s * 1e3, 3))

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            out: Dict[str, Any] = dict(self.counts)
            out["inflight"] = self.inflight
            if self._ewma_wait_s is not None:
                out["queue_wait_ewma_ms"] = round(self._ewma_wait_s * 1e3, 4)
            if self._ewma_service_s is not None:
                out["service_ewma_ms"] = round(self._ewma_service_s * 1e3, 4)
        out["max_queue"] = self.max_queue
        out["max_inflight"] = self.max_inflight
        out["deadline_ms"] = round(self.deadline_s * 1e3, 3)
        return out


class TokenBucket:
    """Per-model QPS isolation (registry): `rate` tokens/s refill, burst
    of `burst` tokens (default: one second's worth). `take()` is a
    non-blocking admission decision — a drained bucket REFUSES (the
    caller sheds with "rate_limited") instead of queueing, so a hot
    model's backlog can never occupy the shared device at another
    model's expense."""

    def __init__(self, rate: float, burst: Optional[float] = None):
        self.rate = float(rate)
        self.burst = float(burst) if burst is not None \
            else max(1.0, self.rate)
        self._tokens = self.burst
        self._t = time.monotonic()
        self._lock = threading.Lock()

    def take(self, n: float = 1.0) -> bool:
        with self._lock:
            now = time.monotonic()
            self._tokens = min(self.burst,
                               self._tokens + (now - self._t) * self.rate)
            self._t = now
            if self._tokens >= n:
                self._tokens -= n
                return True
            return False

    def retry_after_s(self, n: float = 1.0) -> float:
        with self._lock:
            missing = max(0.0, n - self._tokens)
        return missing / self.rate if self.rate > 0 else 1.0


class CircuitBreaker:
    """Per-model failure isolation: `failures` CONSECUTIVE predict
    failures trip the breaker open for `reset_s`; it then half-opens
    for a single probe. Probe success closes it (and resets the
    backoff); probe failure re-opens with exponential backoff capped at
    `backoff_cap_s`. Overload rejections never count: shedding a
    request says nothing about the model's health."""

    CLOSED, OPEN, HALF_OPEN = "closed", "open", "half_open"

    def __init__(self, failures: int = 5, reset_s: float = 5.0,
                 backoff_cap_s: float = 60.0):
        self.failures = max(1, int(failures))
        self.reset_s = max(0.001, float(reset_s))
        self.backoff_cap_s = float(backoff_cap_s)
        self._lock = threading.Lock()
        self._state = self.CLOSED
        self._consecutive = 0
        self._opened_at = 0.0
        self._backoff = self.reset_s
        self._probing = False
        self.counts: Dict[str, int] = {"trips": 0, "rejected": 0,
                                       "recoveries": 0}

    def state(self) -> str:
        with self._lock:
            self._maybe_half_open()
            return self._state

    def _maybe_half_open(self) -> None:
        if self._state == self.OPEN and \
                time.monotonic() - self._opened_at >= self._backoff:
            self._state = self.HALF_OPEN
            self._probing = False

    def allow(self) -> bool:
        """True = the request may proceed. In half-open exactly ONE
        caller gets through as the probe; everyone else is refused
        until the probe reports."""
        with self._lock:
            self._maybe_half_open()
            if self._state == self.CLOSED:
                return True
            if self._state == self.HALF_OPEN and not self._probing:
                self._probing = True
                return True
            self.counts["rejected"] += 1
            return False

    def retry_after_s(self) -> float:
        with self._lock:
            if self._state != self.OPEN:
                return 0.0
            return max(0.0, self._backoff
                       - (time.monotonic() - self._opened_at))

    def release_probe(self) -> None:
        """The half-open probe produced NO evidence about the model —
        it was shed upstream, failed client-side, or was cancelled.
        Free the slot so the NEXT request can probe; without this, a
        rejected probe would leave the breaker half-open-and-probing
        forever (no success to close it, no failure to re-open it)."""
        with self._lock:
            if self._state == self.HALF_OPEN:
                self._probing = False

    def record_success(self) -> None:
        with self._lock:
            if self._state == self.OPEN:
                # stale evidence: a pre-trip request (e.g. a queued
                # micro-batch future) that resolved after the trip.
                # Only the half-open PROBE may close an open breaker —
                # otherwise a trickle of old successes would defeat the
                # reset window and keep hammering a failing model
                return
            recovered = self._state == self.HALF_OPEN
            if recovered:
                self.counts["recoveries"] += 1
            self._state = self.CLOSED
            self._consecutive = 0
            self._probing = False
            self._backoff = self.reset_s
        if recovered:
            telemetry.counter_add("serving/breaker_recoveries", 1)

    def record_failure(self) -> None:
        tripped = False
        with self._lock:
            if self._state == self.HALF_OPEN:
                # failed probe: back off harder before the next one
                self._state = self.OPEN
                self._opened_at = time.monotonic()
                self._backoff = min(self._backoff * 2, self.backoff_cap_s)
                self._probing = False
                self.counts["trips"] += 1
                tripped = True
            else:
                self._consecutive += 1
                if self._state == self.CLOSED \
                        and self._consecutive >= self.failures:
                    self._state = self.OPEN
                    self._opened_at = time.monotonic()
                    self._backoff = self.reset_s
                    self.counts["trips"] += 1
                    tripped = True
        if tripped:
            telemetry.counter_add("serving/breaker_trips", 1)

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            self._maybe_half_open()
            return {"state": self._state, **self.counts,
                    "consecutive_failures": self._consecutive,
                    "backoff_s": round(self._backoff, 3)}
