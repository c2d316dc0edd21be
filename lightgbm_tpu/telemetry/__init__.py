"""Unified telemetry subsystem (subsumes the old flat `tracing.py`).

Six pieces, one import surface:

- `metrics` — labeled counters/gauges/bucketed histograms + span-scoped
  timers of host time (`span(name, **ids)`), which also land in the
  profiler's trace while a profiler session is open and never wait for
  the device. Zero-allocation when disabled.
- `layers` — the names of this program's layers: `jax.named_scope`
  names inside the jitted programs (`scope()`), the host spans of one
  boosting iteration and of one dataset construction, `TreeRecord`, the
  per-tree `pass_log` entry, and `ConstructRecord`, the phases of one
  dataset's build (its `construct_record`; `last_construct()` for the
  one this process built last).
- `devtrace` — from a profiler trace (`.xplane.pb`) to device seconds by
  scope, host seconds by span and idle gaps by span.
- `runlog` — the structured JSONL run log: header + one record per
  boosting iteration + events + summary, written alongside PR 3's
  checkpoints so a preempted run leaves a readable trail.
- `observer` — compile/retrace accounting hooked into `jax.monitoring`,
  attributed to the innermost open span; warns on retrace storms.
- `export` — Prometheus text-exposition file dump with multihost rank
  labels and end-of-run cross-rank aggregation.

Enablement: metric collection turns on via `LGBM_TPU_TIMETAG=1` /
`LGBM_TPU_TELEMETRY=1` (the historical tracing switch), the
`tpu_telemetry` config param, or automatically for the duration of a
run when `tpu_telemetry_dir` is set. `lightgbm_tpu.tracing` remains as
a thin back-compat shim over this package.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

from .layers import (DATASET_SPANS, INIT_SPANS, ITER_SPANS, SCOPES,
                     ConstructRecord, TreeRecord, scope)
from .metrics import (DEFAULT_TIME_BUCKETS, Counter, Gauge, Histogram,
                      Registry, block, counter_add, current_site, enable,
                      enabled, gauge_set, heartbeat, observe, registry,
                      reset, set_heartbeat_file, span)
from .observer import CompileObserver, install as install_observer, observer
from .runlog import (SCHEMA_VERSION, RunLog, TrainRecorder, read_records,
                     validate_record)

__all__ = [
    "DEFAULT_TIME_BUCKETS", "Counter", "Gauge", "Histogram", "Registry",
    "RunLog", "TrainRecorder", "CompileObserver", "SCHEMA_VERSION",
    "DATASET_SPANS", "INIT_SPANS", "ITER_SPANS", "SCOPES", "ConstructRecord",
    "TreeRecord", "scope", "last_construct", "record_construct",
    "active_recorder", "block", "counter_add", "current_site", "enable",
    "enabled", "gauge_set", "heartbeat", "observe", "observer",
    "install_observer", "registry", "reset", "read_records",
    "set_active_recorder", "set_heartbeat_file", "span",
    "start_run", "validate_record", "dump",
]

# the recorder of the training run currently in flight (engine.train
# installs/clears it): lets out-of-band reporters — the collective
# watchdog's expiry path above all — append structured events to the
# run log without plumbing a recorder reference through every layer
_ACTIVE_RECORDER: Optional["TrainRecorder"] = None


def set_active_recorder(rec: Optional["TrainRecorder"]) -> None:
    global _ACTIVE_RECORDER
    _ACTIVE_RECORDER = rec


def active_recorder() -> Optional["TrainRecorder"]:
    return _ACTIVE_RECORDER


# the `construct_record` of the dataset this process built last
# (ingest/build.py writes it): for a caller that no longer holds the
# dataset, as a benchmark reader after the run. Any later build replaces
# it, a validation set's too: who holds the dataset reads the dataset's
_LAST_CONSTRUCT: Optional[ConstructRecord] = None


def record_construct(rec: ConstructRecord) -> None:
    global _LAST_CONSTRUCT
    _LAST_CONSTRUCT = rec


def last_construct() -> Optional[ConstructRecord]:
    return _LAST_CONSTRUCT


def start_run(gbdt, params: Dict[str, Any]) -> Optional[TrainRecorder]:
    """Engine entry point: arm telemetry for one training run.

    Returns a TrainRecorder when telemetry is active (tpu_telemetry_dir
    set, tpu_telemetry=true, or the registry already enabled via env),
    None otherwise — the engine treats None as "stay silent". With a
    telemetry dir the recorder also owns the JSONL run log; without one
    it still keeps span/counter/compile accounting for the exit dump."""
    cfg = gbdt.config
    directory = getattr(cfg.io, "tpu_telemetry_dir", "") or ""
    want = bool(directory) or bool(getattr(cfg.io, "tpu_telemetry", False))
    if not (want or enabled()):
        return None
    was_enabled = enabled()
    enable(True)
    install_observer()

    rank, world = 0, 1
    try:
        import jax
        rank, world = jax.process_index(), jax.process_count()
    except Exception:  # pragma: no cover — backend-free unit tests
        pass

    run_log = None
    if directory:
        run_log = RunLog(directory, rank=rank)

    from .. import checkpoint as ckpt
    # global rows, matching engine._setup_checkpointing: the run-log
    # header's fingerprint must stay stable across world sizes so an
    # elastically-resumed run's trail chains to the original's
    n_fp = int(getattr(getattr(gbdt, "train_data", None),
                       "num_global_rows", 0) or getattr(gbdt, "_n", 0))
    fingerprint = ckpt.config_fingerprint(
        cfg.raw_params, n_fp,
        int(getattr(gbdt, "max_feature_idx", -1)) + 1, cfg.boosting_type)
    rec = TrainRecorder(gbdt, run_log, rank=rank, world=world,
                        fingerprint=fingerprint, params=params,
                        prometheus=bool(
                            getattr(cfg.io, "tpu_telemetry_prometheus",
                                    True)))
    # dir-based runs restore the disabled default at close (their output
    # is the run log + prom files); tpu_telemetry=true asked for the
    # TIMETAG-style accumulate-and-dump-at-exit behavior, so it stays on
    rec.disable_on_close = not was_enabled and run_log is not None \
        and not getattr(cfg.io, "tpu_telemetry", False)
    return rec


def dump() -> None:
    """Log the accumulated phase timers + counters (the TIMETAG exit
    printout shape; kept for tracing back-compat)."""
    from .. import log
    reg = registry()
    if reg.phases:
        log.info("=== phase timers ===")
        for name in sorted(reg.phases, key=lambda n: reg.phases[n].total,
                           reverse=True):
            acc = reg.phases[name]
            log.info("%-28s %8.3f s  x%d", name, acc.total, acc.count)
    counters = {}
    for c in reg.counters.values():
        if not c.labels:
            counters[c.name] = (c.value, c.events)
    if counters:
        log.info("=== counters ===")
        for name in sorted(counters, key=lambda n: counters[n][0],
                           reverse=True):
            v, e = counters[name]
            log.info("%-28s %12.0f  x%d", name, v, e)
    obs = observer()
    if obs.total_compiles:
        snap = obs.snapshot()
        log.info("=== compilation ===")
        for site, rec in sorted(snap["sites"].items(),
                                key=lambda kv: kv[1]["seconds"],
                                reverse=True):
            log.info("%-28s %8.3f s  x%d", site, rec["seconds"],
                     rec["compiles"])
