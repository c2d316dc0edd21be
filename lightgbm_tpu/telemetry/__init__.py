"""Unified telemetry subsystem.

Six pieces, one import surface:

- `metrics` — labeled counters/gauges/bucketed histograms + span-scoped
  timers of host time (`span(name, **ids)`), which also land in the
  profiler's trace while a profiler session is open and never wait for
  the device. Zero-allocation when disabled.
- `layers` — the names of this program's layers: `jax.named_scope`
  names inside the jitted programs (`scope()`), the host spans of one
  boosting iteration, of one dataset construction and of one
  `GBDT.init`, and the records that are always taken, telemetry on or
  off: `TreeRecord`, the per-tree `pass_log` entry; `ConstructRecord`,
  the phases of one dataset's build (its `construct_record`;
  `last_construct()` for the one this process built last); `InitRecord`,
  the phases of one `GBDT.init` (the booster's `init_record`).
  `last_run()` hands all three of the booster this process initialised
  last to a caller that no longer holds it.
- `devtrace` — from a profiler trace (`.xplane.pb`) to device seconds by
  scope, host seconds by span and idle gaps by span.
- `runlog` — the structured JSONL run log: header + one record per
  boosting iteration + events + summary, written alongside PR 3's
  checkpoints so a preempted run leaves a readable trail.
- `observer` — jax's compile path from `jax.monitoring`: trace, lower,
  cache load against compile, in running totals and by program; with
  telemetry enabled also by innermost open span; warns on retrace storms.
- `export` — Prometheus text-exposition file dump with multihost rank
  labels and end-of-run cross-rank aggregation.

Enablement: metric collection turns on via `LGBM_TPU_TIMETAG=1` /
`LGBM_TPU_TELEMETRY=1`, the
`tpu_telemetry` config param, or automatically for the duration of a
run when `tpu_telemetry_dir` is set. A process that ends with it enabled
logs the accumulated timers and counters (`dump()`) at exit.
"""
from __future__ import annotations

import atexit
from typing import Any, Dict, List, Optional, Tuple

from .layers import (DATASET_SPANS, INIT_SPANS, ITER_SPANS, SCOPES,
                     ConstructRecord, EfbCounters, InitRecord, Phases,
                     TreeRecord, scope)
from .metrics import (DEFAULT_TIME_BUCKETS, Counter, Gauge, Histogram,
                      Registry, counter_add, current_site, enable,
                      enabled, gauge_set, heartbeat, observe, registry,
                      reset, set_heartbeat_file, span)
from .observer import (CompileObserver, CompileTotals, compile_path_since,
                       install as install_observer, observer)
from .runlog import (SCHEMA_VERSION, RunLog, TrainRecorder, read_records,
                     validate_record)

__all__ = [
    "DEFAULT_TIME_BUCKETS", "Counter", "Gauge", "Histogram", "Registry",
    "RunLog", "TrainRecorder", "CompileObserver", "SCHEMA_VERSION",
    "CompileTotals", "DATASET_SPANS", "INIT_SPANS", "ITER_SPANS", "SCOPES",
    "ConstructRecord", "EfbCounters", "InitRecord", "Phases", "TreeRecord", "scope",
    "last_construct", "record_construct", "last_run", "record_run",
    "active_recorder", "compile_path_since", "counter_add", "current_site",
    "enable", "enabled", "gauge_set", "heartbeat", "observe", "observer",
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


# the records of the booster this process initialised last (`GBDT.init`
# writes them), for a caller that no longer holds it, as a benchmark
# reader after the mode has freed the booster: its dataset's
# `construct_record` (None where the dataset was not built by
# ingest/build), its `init_record`, and its own `pass_log` list, which
# goes on growing a `TreeRecord` a tree. Tuples of numbers: holding them
# pins no device memory.
_LAST_RUN: Optional[Tuple[Optional[ConstructRecord], InitRecord,
                          List[TreeRecord]]] = None


def record_run(construct: Optional[ConstructRecord], init: InitRecord,
               trees: List[TreeRecord]) -> None:
    global _LAST_RUN
    _LAST_RUN = (construct, init, trees)


def last_run():
    """(construct, init, trees) of the booster initialised last, or None."""
    return _LAST_RUN


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
    printout shape)."""
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


@atexit.register
def _dump_at_exit() -> None:
    """`LGBM_TPU_TIMETAG=1` (or telemetry left enabled any other way):
    the accumulated timers and counters, once, as the process ends."""
    if enabled():
        dump()
