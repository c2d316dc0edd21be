"""Labeled metrics registry + span-scoped timers.

The reference's only instrumentation is the compile-time TIMETAG wall
accumulators (`gbdt.cpp:53-62`); this registry is the production-shaped
replacement the ROADMAP items need: labeled counters/gauges and BUCKETED
histograms (serving latency as a real p50/p95/p99 distribution, not a
running mean, following the per-phase accounting of the GBDT accelerator
literature — XGBoost-GPU 1806.11248 §5, booster accelerators
2011.02022 §4), plus `span()` timers of HOST time that also land in the
profiler's trace, on the device's clock, whenever a profiler session is
open. A span never waits for the device.

Cost discipline: with telemetry disabled and no profiler session open
every entry point is a flag test returning a module-level singleton — no
allocation, no locks (tests/test_telemetry.py probes the disabled path
with tracemalloc).
Enabled-path instruments append to plain dict/float slots under the GIL;
the only lock taken per event is the histogram's (shared with the
serving threads).
"""
from __future__ import annotations

import bisect
import os
import threading
import time
from typing import Any, Dict, Iterable, List, Optional, Tuple

from jax.profiler import TraceAnnotation

LabelItems = Tuple[Tuple[str, str], ...]

# default histogram bounds: exponential 100us .. ~100s — wide enough for
# single-row serving latency AND wide-shape grower compile tails
DEFAULT_TIME_BUCKETS = tuple(1e-4 * (2.0 ** i) for i in range(21))


def _label_key(labels: Optional[Dict[str, Any]]) -> LabelItems:
    if not labels:
        return ()
    return tuple(sorted((str(k), str(v)) for k, v in labels.items()))


class Counter:
    """Monotonic accumulator. `value` is the accumulated total, `events`
    the number of inc() calls (the (value, count) pair the exit dump
    reports)."""

    __slots__ = ("name", "labels", "value", "events")

    def __init__(self, name: str, labels: LabelItems = ()):
        self.name = name
        self.labels = labels
        self.value = 0.0
        self.events = 0

    def inc(self, v: float = 1.0) -> None:
        self.value += float(v)
        self.events += 1


class Gauge:
    """Last-write-wins scalar (heartbeats, queue depths)."""

    __slots__ = ("name", "labels", "value", "updated_at")

    def __init__(self, name: str, labels: LabelItems = ()):
        self.name = name
        self.labels = labels
        self.value = 0.0
        self.updated_at = 0.0

    def set(self, v: float) -> None:
        self.value = float(v)
        self.updated_at = time.time()


class Histogram:
    """Fixed-bound bucketed histogram (Prometheus semantics: `buckets[i]`
    counts observations <= bounds[i], with a +Inf overflow bucket).

    Quantiles interpolate linearly inside the winning bucket — the
    standard exposition-format estimation, good to a bucket width. The
    instrument is safe for concurrent observers (serving threads)."""

    __slots__ = ("name", "labels", "bounds", "buckets", "count", "sum",
                 "_min", "_max", "_lock")

    def __init__(self, name: str, labels: LabelItems = (),
                 bounds: Iterable[float] = DEFAULT_TIME_BUCKETS):
        self.name = name
        self.labels = labels
        self.bounds = tuple(sorted(float(b) for b in bounds))
        self.buckets = [0] * (len(self.bounds) + 1)  # +Inf tail
        self.count = 0
        self.sum = 0.0
        self._min = None
        self._max = None
        self._lock = threading.Lock()

    def observe(self, v: float) -> None:
        v = float(v)
        i = bisect.bisect_left(self.bounds, v)
        with self._lock:
            self.buckets[i] += 1
            self.count += 1
            self.sum += v
            if self._min is None or v < self._min:
                self._min = v
            if self._max is None or v > self._max:
                self._max = v

    def quantile(self, q: float) -> Optional[float]:
        """Estimated q-quantile (0..1); None with no observations."""
        with self._lock:
            if self.count == 0:
                return None
            rank = q * self.count
            cum = 0
            for i, c in enumerate(self.buckets):
                if c == 0:
                    continue
                prev_cum = cum
                cum += c
                if cum >= rank:
                    lo = self.bounds[i - 1] if i > 0 else \
                        (self._min if self._min is not None else 0.0)
                    hi = self.bounds[i] if i < len(self.bounds) else \
                        (self._max if self._max is not None else lo)
                    frac = (rank - prev_cum) / c
                    est = lo + (hi - lo) * frac
                    # clamp to the observed range: interpolation inside
                    # the min/max bucket must not invent values outside it
                    if self._max is not None:
                        est = min(est, self._max)
                    if self._min is not None:
                        est = max(est, self._min)
                    return est
            return self._max

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {"bounds": list(self.bounds), "buckets": list(self.buckets),
                    "count": self.count, "sum": self.sum,
                    "min": self._min, "max": self._max}


class _PhaseAccum:
    """Span-timer accumulator: total seconds + span count per name (what
    the exit dump and the run log report)."""

    __slots__ = ("total", "count")

    def __init__(self):
        self.total = 0.0
        self.count = 0


class Registry:
    """One process-wide instrument store. Instruments are created on
    first use and keyed by (name, sorted label items); `snapshot()`
    returns a JSON-safe dict the exporters and the run log consume."""

    def __init__(self):
        self._lock = threading.Lock()
        self.counters: Dict[Tuple[str, LabelItems], Counter] = {}
        self.gauges: Dict[Tuple[str, LabelItems], Gauge] = {}
        self.histograms: Dict[Tuple[str, LabelItems], Histogram] = {}
        self.phases: Dict[str, _PhaseAccum] = {}

    # -- instrument lookup (create on first use) ------------------------
    def counter(self, name: str, labels: Optional[Dict] = None) -> Counter:
        key = (name, _label_key(labels))
        c = self.counters.get(key)
        if c is None:
            with self._lock:
                c = self.counters.setdefault(key, Counter(*key))
        return c

    def gauge(self, name: str, labels: Optional[Dict] = None) -> Gauge:
        key = (name, _label_key(labels))
        g = self.gauges.get(key)
        if g is None:
            with self._lock:
                g = self.gauges.setdefault(key, Gauge(*key))
        return g

    def histogram(self, name: str, labels: Optional[Dict] = None,
                  bounds: Iterable[float] = DEFAULT_TIME_BUCKETS) -> Histogram:
        key = (name, _label_key(labels))
        h = self.histograms.get(key)
        if h is None:
            with self._lock:
                h = self.histograms.get(key)
                if h is None:
                    h = Histogram(key[0], key[1], bounds)
                    self.histograms[key] = h
        return h

    def register_histogram(self, hist: Histogram) -> Histogram:
        """Adopt an externally-owned Histogram as a shared instrument:
        the owner keeps observing/reading it directly (always-on local
        stats) and the exporters see the SAME object — one series, one
        lock, instead of a local copy plus a registry twin."""
        with self._lock:
            self.histograms[(hist.name, hist.labels)] = hist
        return hist

    def phase(self, name: str) -> _PhaseAccum:
        p = self.phases.get(name)
        if p is None:
            with self._lock:
                p = self.phases.setdefault(name, _PhaseAccum())
        return p

    # -- views ----------------------------------------------------------
    def reset(self) -> None:
        with self._lock:
            self.counters.clear()
            self.gauges.clear()
            self.histograms.clear()
            self.phases.clear()

    def snapshot(self) -> Dict[str, Any]:
        """JSON-safe dump of every instrument (label items as lists)."""
        with self._lock:
            return {
                "counters": [
                    {"name": c.name, "labels": [list(kv) for kv in c.labels],
                     "value": c.value, "events": c.events}
                    for c in self.counters.values()],
                "gauges": [
                    {"name": g.name, "labels": [list(kv) for kv in g.labels],
                     "value": g.value, "updated_at": g.updated_at}
                    for g in self.gauges.values()],
                "histograms": [
                    dict({"name": h.name,
                          "labels": [list(kv) for kv in h.labels]},
                         **h.snapshot())
                    for h in self.histograms.values()],
                "phases": [
                    {"name": name, "seconds": p.total, "count": p.count}
                    for name, p in self.phases.items()],
            }


# ---------------------------------------------------------------------------
# module-global state: ONE registry, one enabled flag, one span stack
# ---------------------------------------------------------------------------
_registry = Registry()
_enabled = os.environ.get("LGBM_TPU_TIMETAG",
                          os.environ.get("LGBM_TPU_TELEMETRY", "")) \
    not in ("", "0", "false")

# innermost open span per thread — the compile observer charges jax
# compile events to it (observer.py)
_local = threading.local()


def registry() -> Registry:
    return _registry


def enable(on: bool = True) -> None:
    global _enabled
    _enabled = bool(on)


def enabled() -> bool:
    return _enabled


def reset() -> None:
    _registry.reset()


def current_site() -> Optional[str]:
    """Name of this thread's innermost open span (compile attribution)."""
    stack = getattr(_local, "spans", None)
    return stack[-1] if stack else None


# ---------------------------------------------------------------------------
# fast-path helpers (the only functions hot loops should call)
# ---------------------------------------------------------------------------
def counter_add(name: str, value: float = 1.0,
                labels: Optional[Dict] = None) -> None:
    """Accumulate into a counter; free when telemetry is disabled."""
    if _enabled:
        _registry.counter(name, labels).inc(value)


def gauge_set(name: str, value: float, labels: Optional[Dict] = None) -> None:
    if _enabled:
        _registry.gauge(name, labels).set(value)


def observe(name: str, value: float, labels: Optional[Dict] = None,
            bounds: Iterable[float] = DEFAULT_TIME_BUCKETS) -> None:
    if _enabled:
        _registry.histogram(name, labels, bounds).observe(value)


class _NullSpan:
    """Disabled-path span: ONE module-level instance, allocation-free."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    """Enabled-path span: host wall-clock charged to a phase
    accumulator, and the same interval written into the profiler's trace
    (a no-op while no profiler session is open). It never waits for the
    device: device time is the device trace's to tell."""

    __slots__ = ("name", "ann", "t0")

    def __init__(self, name: str, ids):
        self.name = name
        self.ann = TraceAnnotation(name, **ids)
        self.t0 = 0.0

    def __enter__(self):
        stack = getattr(_local, "spans", None)
        if stack is None:
            stack = _local.spans = []
        stack.append(self.name)
        self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        acc = _registry.phase(self.name)
        acc.total += time.perf_counter() - self.t0
        acc.count += 1
        self.ann.__exit__(*exc)
        _local.spans.pop()
        return False


def span(name: str, **ids):
    """Context manager around one named piece of host work. It always
    lands in the profiler's trace as a `TraceAnnotation` carrying `ids`
    (e.g. `iteration=i`: spans of one tree share it) when a profiler
    session is open; it accumulates host seconds into the registry
    (`registry().phases`) only when telemetry is enabled. With both
    off it is the shared no-op singleton."""
    if _enabled:
        return _Span(name, ids)
    if TraceAnnotation.is_enabled():
        return TraceAnnotation(name, **ids)
    return _NULL_SPAN


# ---------------------------------------------------------------------------
# heartbeat: last-seen-iteration evidence for watchdogs
# ---------------------------------------------------------------------------
# cached at import: heartbeats must stay one env-dict lookup away from
# free in the common (unset) case
_HEARTBEAT_FILE = os.environ.get("LGBM_TPU_HEARTBEAT_FILE", "")


def set_heartbeat_file(path: str) -> None:
    global _HEARTBEAT_FILE
    _HEARTBEAT_FILE = path or ""


def heartbeat_file() -> str:
    return _HEARTBEAT_FILE


def heartbeat(iteration: int, phase: str = "train",
              rank: Optional[int] = None) -> None:
    """Record liveness: a gauge (when telemetry is on) and — when a
    heartbeat file is armed (LGBM_TPU_HEARTBEAT_FILE, set per rank by
    watchdog harnesses like scripts/dryrun_multichip.py, or derived
    from tpu_heartbeat_dir) — an atomically replaced one-line JSON file
    carrying (rank, iteration, phase, time, pid, lease_s), the artifact
    a timed-out run's parent reads to say WHERE each rank was. The
    lease stamp lets any reader (`parallel.watchdog.read_cohort`)
    classify the rank alive/expired without knowing the run's config.
    File writes go through the durable layer with fsync OFF and zero
    retries (evidence, not durability — a heartbeat sleeping in retry
    backoff reads as an expired lease): failures drop into the
    `watchdog/heartbeat_write_errors` counter, never into training."""
    if _enabled:
        _registry.gauge("heartbeat/iteration",
                        {"phase": phase}).set(float(iteration))
    if _HEARTBEAT_FILE:
        import json
        lease = 0.0
        try:
            from ..parallel import watchdog as _wd
            if rank is None:
                # watchdog.current_rank, NOT the raw env var: under
                # machine-list / explicit-param launches the rank is
                # resolved inside init_distributed and configured by
                # GBDT.init — the env default of 0 would stamp every
                # rank's heartbeat as rank 0 and collapse the
                # supervisor's cohort view into one entry
                rank = _wd.current_rank()
            lease = _wd.lease_s()
        except Exception:  # pragma: no cover — import-order edge
            if rank is None:
                rank = int(os.environ.get("LGBM_TPU_RANK", "0") or 0)
        rec = {"rank": int(rank), "iteration": int(iteration),
               "phase": str(phase), "time": time.time(),
               "pid": os.getpid()}
        if lease > 0:
            rec["lease_s"] = lease
        from .. import durable
        durable.best_effort_write_text(
            _HEARTBEAT_FILE, json.dumps(rec) + "\n",
            stream="watchdog.heartbeat",
            counter="watchdog/heartbeat_write_errors")
