"""Structured JSONL run log: one readable trail per training run.

The rc-124 multichip timeout left NO artifact saying where it died;
this sink makes every run leave one. A `RunLog` appends self-contained
JSON records to `<tpu_telemetry_dir>/runlog_r<rank>.jsonl`:

- one `header` record per run start (config fingerprint, device
  topology, schedule, `init_record`, library versions) — a resumed run
  appends a new header, so the file reads as the full preemption history;
- one `iteration` record per boosting iteration: eval metric values,
  per-phase wall deltas, counter deltas (pass economics
  `rows_contracted`/`pass_rows`, bagging/DART activity), compile-event
  deltas from the observer;
- `event` records for discrete occurrences (resume, checkpoint saves,
  early stop, non-finite guard trips);
- a `summary` record on close with run totals.

Writes are append + flush per line (a preempted run's trail is readable
up to its last completed iteration; each line is independently
parseable). The heavyweight sibling — full-state snapshots — is
PR 3's checkpoint store; the run log is the cheap always-readable
narration alongside it.

`validate_record` is the schema contract tests and
scripts/telemetry_report.py both consume.
"""
from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, List, Optional

from .. import durable, log
from ..testing import faults
from . import metrics as metrics_mod
from .observer import observer as _observer

SCHEMA_VERSION = 1

RECORD_TYPES = ("header", "iteration", "event", "summary")

# required fields per record type (the round-trip contract)
_REQUIRED = {
    "header": ("type", "schema", "time", "rank", "world", "run_id",
               "fingerprint", "devices", "versions"),
    "iteration": ("type", "time", "iteration", "metrics", "phases",
                  "counters", "compile"),
    "event": ("type", "time", "kind"),
    "summary": ("type", "time", "iterations", "phases", "compile"),
}


def validate_record(rec: Dict[str, Any]) -> None:
    """Raise ValueError when `rec` violates the run-log schema."""
    if not isinstance(rec, dict):
        raise ValueError("run-log record must be a JSON object")
    rtype = rec.get("type")
    if rtype not in RECORD_TYPES:
        raise ValueError(f"unknown run-log record type: {rtype!r}")
    missing = [f for f in _REQUIRED[rtype] if f not in rec]
    if missing:
        raise ValueError(f"{rtype} record missing fields: {missing}")
    if rtype == "header" and int(rec["schema"]) > SCHEMA_VERSION:
        raise ValueError(
            f"run-log schema {rec['schema']} is newer than this build "
            f"supports ({SCHEMA_VERSION})")
    if rtype == "iteration":
        if not isinstance(rec["iteration"], int):
            raise ValueError("iteration record: 'iteration' must be int")
        for fld in ("metrics", "phases", "counters", "compile"):
            if not isinstance(rec[fld], dict):
                raise ValueError(f"iteration record: '{fld}' must be a dict")


def read_records(path: str) -> List[Dict[str, Any]]:
    """Parse a run-log file; truncated trailing lines (a run killed
    mid-write) are dropped, everything before them is returned."""
    out: List[Dict[str, Any]] = []
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            try:
                out.append(json.loads(line))
            except json.JSONDecodeError:
                break  # torn tail — the preemption case; keep the prefix
    return out


class RunLog:
    """Append-only JSONL sink for one rank.

    Best-effort stream: every OS-level failure (directory cannot be
    created, append/flush hits EIO or ENOSPC) is swallowed into the
    `telemetry/runlog_write_errors` counter with a rate-limited warning,
    and `write` reports it by returning False — narration must never
    raise into the training loop it narrates. Schema violations
    (ValueError) still raise: those are caller bugs, not disk weather.
    A failed handle is dropped and lazily reopened on the next write, so
    a transient full disk costs only the records written while full."""

    def __init__(self, directory: str, rank: int = 0):
        self.directory = directory
        self.rank = int(rank)
        self.path = os.path.join(directory, f"runlog_r{self.rank}.jsonl")
        self._fh = None
        try:
            os.makedirs(directory, exist_ok=True)
            self._fh = open(self.path, "a")
        except OSError as exc:
            durable.note_dropped("telemetry.runlog", self.path, exc,
                                 counter="telemetry/runlog_write_errors")

    def _open(self):
        if self._fh is None:
            self._fh = open(self.path, "a")
        return self._fh

    def write(self, rec: Dict[str, Any]) -> bool:
        """Append one record; returns False when the write was dropped."""
        rec.setdefault("time", time.time())
        validate_record(rec)
        try:
            faults.inject("runlog.write")
            fh = self._open()
            fh.write(json.dumps(rec, sort_keys=True,
                                separators=(",", ":")) + "\n")
            fh.flush()
            return True
        except OSError as exc:
            self.close()
            durable.note_dropped("telemetry.runlog", self.path, exc,
                                 counter="telemetry/runlog_write_errors")
            return False

    def close(self) -> None:
        if self._fh is None:
            return
        try:
            self._fh.close()
        except OSError:  # pragma: no cover
            pass
        self._fh = None


def _versions() -> Dict[str, str]:
    import numpy as np
    out = {"numpy": np.__version__}
    try:
        import jax
        out["jax"] = jax.__version__
    except Exception:  # pragma: no cover
        pass
    try:
        from .. import __version__ as own
        out["lightgbm_tpu"] = own
    except Exception:
        pass
    return out


def _device_topology() -> Dict[str, Any]:
    """Backend topology for the header (the backend is already up by the
    time training telemetry starts — booster init touched devices). A
    backend error propagates: a run log must never record a run whose
    device it could not name."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "num_devices": len(devs),
            "num_processes": jax.process_count(),
            "local_devices": len(jax.local_devices())}


class TrainRecorder:
    """Engine-facing glue: snapshots the registry + compile observer at
    iteration boundaries and writes per-iteration deltas, so a record
    says what THIS iteration cost — without ever touching device arrays
    or draining the async tree pipeline (the recorder must not tax the
    pipelined training path it measures)."""

    def __init__(self, gbdt, run_log: Optional[RunLog], rank: int,
                 world: int, fingerprint: str, params: Dict[str, Any],
                 prometheus: bool = True):
        self.gbdt = gbdt
        self.run_log = run_log
        self.rank = rank
        self.world = world
        self.prometheus = bool(prometheus)
        # when start_run enabled collection just for this run, close()
        # restores the disabled default so later runs in the same
        # process don't silently keep accumulating
        self.disable_on_close = False
        self.run_id = f"{int(time.time() * 1e3):x}-r{rank}"
        # remembered for the end-of-run collective: the run log itself
        # may be dropped mid-run (disk full), but the cross-rank
        # aggregation must still run on EVERY rank or the others hang
        self._directory = run_log.directory if run_log is not None else ""
        self._t_start = time.time()
        self._iterations = 0
        self._pass_log_seen = len(getattr(gbdt, "pass_log", []) or [])
        # baseline deltas at the CURRENT accumulator values: anything
        # collected before this run (a previous train() in the same
        # process under LGBM_TPU_TIMETAG, booster-construction spans)
        # must not be billed to iteration 0
        reg = metrics_mod.registry()
        self._phase_prev: Dict[str, tuple] = {
            name: (acc.total, acc.count) for name, acc in reg.phases.items()}
        self._counter_prev: Dict[str, tuple] = {
            key: (c.value, c.events) for key, c in reg.counters.items()}
        self._compile_prev = _observer().snapshot()
        if run_log is not None:
            init_record = getattr(gbdt, "init_record", None)
            run_log.write({
                "type": "header", "schema": SCHEMA_VERSION,
                "rank": rank, "world": world, "run_id": self.run_id,
                "fingerprint": fingerprint,
                "devices": _device_topology(),
                "versions": _versions(),
                "params": {str(k): str(v) for k, v in params.items()},
                "schedule": dict(getattr(gbdt, "_schedule_info", {}) or {}),
                # GBDT.init by phase, the rows a shard holds, what was
                # traced, loaded and compiled meanwhile (InitRecord)
                "init_record": init_record._asdict() if init_record else {},
                "boosting": gbdt.model_name(),
                "num_data": int(getattr(gbdt, "_n", 0)),
                "start_iteration": int(getattr(gbdt, "iter_", 0)),
            })

    # -- delta plumbing ---------------------------------------------------
    def _phase_delta(self) -> Dict[str, Dict[str, float]]:
        reg = metrics_mod.registry()
        out = {}
        for name, acc in list(reg.phases.items()):
            prev = self._phase_prev.get(name, (0.0, 0))
            d_total, d_count = acc.total - prev[0], acc.count - prev[1]
            self._phase_prev[name] = (acc.total, acc.count)
            if d_count or d_total:
                out[name] = {"seconds": round(d_total, 6), "count": d_count}
        return out

    def _counter_delta(self) -> Dict[str, float]:
        reg = metrics_mod.registry()
        out = {}
        for key, c in list(reg.counters.items()):
            prev = self._counter_prev.get(key, (0.0, 0))
            dv = c.value - prev[0]
            self._counter_prev[key] = (c.value, c.events)
            if dv:
                name = c.name if not c.labels else \
                    c.name + "{" + ",".join(f"{k}={v}"
                                            for k, v in c.labels) + "}"
                out[name] = dv
        return out

    def _compile_delta(self) -> Dict[str, Any]:
        snap = _observer().snapshot()
        prev = self._compile_prev
        self._compile_prev = snap
        return {
            "compiles": snap["total_compiles"] - prev["total_compiles"],
            "seconds": round(snap["total_seconds"] - prev["total_seconds"], 6),
            "retraces": snap["retraces"] - prev["retraces"],
        }

    def _pass_economics(self) -> Dict[str, float]:
        plog = getattr(self.gbdt, "pass_log", None) or []
        new = plog[self._pass_log_seen:]
        self._pass_log_seen = len(plog)
        if not new:
            return {}
        return {
            "trees": len(new),
            "num_passes": sum(p[0] for p in new),
            "table_high_water": max(int(p[1]) for p in new),
            "rows_contracted": sum(float(p[2]) for p in new if len(p) > 2),
            "comm_elems": sum(float(p[3]) for p in new if len(p) > 3),
            "comm_bytes": sum(float(p[4]) for p in new if len(p) > 4),
        }

    # -- record emission --------------------------------------------------
    def iteration(self, i: int, eval_results) -> None:
        """One record per boosting iteration; `eval_results` is the
        engine's (data_name, metric_name, value, bigger_better) list."""
        self._iterations += 1
        metrics_mod.heartbeat(i, phase="train", rank=self.rank)
        if self.run_log is None:
            return
        rec = {
            "type": "iteration", "iteration": int(i),
            "metrics": {f"{d}/{m}": float(v)
                        for d, m, v, _ in (eval_results or [])},
            "phases": self._phase_delta(),
            "counters": self._counter_delta(),
            "compile": self._compile_delta(),
        }
        passes = self._pass_economics()
        if passes:
            rec["pass"] = passes
        try:
            # OS-level failures are absorbed inside RunLog.write (counted
            # + rate-limited warning); only schema bugs surface here, and
            # those disable the sink — narration must never kill training
            self.run_log.write(rec)
        except ValueError as exc:
            log.warning("Run log write failed (%s); disabling run log", exc)
            self.run_log = None

    def event(self, kind: str, **fields) -> None:
        if self.run_log is None:
            return
        rec = {"type": "event", "kind": str(kind)}
        rec.update({k: v for k, v in fields.items()})
        try:
            self.run_log.write(rec)
        except ValueError as exc:
            log.warning("Run log write failed (%s); disabling run log", exc)
            self.run_log = None

    def close(self, status: str = "finished") -> None:
        """Prometheus dump + cross-rank aggregation + summary record.

        The aggregate collective runs BEFORE the summary is written and
        the log closed: it can wedge on a peer that died late, and the
        collective watchdog's rank_failure event must still have an
        OPEN run log to land in (the log of a run that died there
        correctly ends with the rank_failure event, no summary)."""
        if self.disable_on_close:
            metrics_mod.enable(False)
        if self._directory and self.prometheus:
            from . import export
            # per-rank file write and the cross-rank collective are
            # isolated from each other: a local write failure on one
            # rank must NOT skip its allgather participation, or every
            # other rank blocks in write_cross_rank_aggregate at end of
            # training
            try:
                export.write_prometheus(
                    os.path.join(self._directory,
                                 f"metrics_r{self.rank}.prom"),
                    extra_labels={"rank": str(self.rank)})
            except Exception as exc:  # export is best-effort narration
                log.warning("Telemetry export failed: %s", exc)
            # the aggregate is a COLLECTIVE: only run it on clean
            # finishes, when every rank reaches close() together. On an
            # error close the other ranks are still inside training
            # collectives — joining an allgather here would mismatch
            # them and wedge the job that was about to exit with a
            # diagnosable error.
            if self.world > 1 and status == "finished":
                try:
                    export.write_cross_rank_aggregate(self._directory,
                                                      self.rank,
                                                      self.world)
                except Exception as exc:
                    log.warning("Cross-rank telemetry aggregation "
                                "failed: %s", exc)
        reg = metrics_mod.registry()
        summary = {
            "type": "summary", "status": status,
            "iterations": self._iterations,
            "wall_seconds": round(time.time() - self._t_start, 3),
            "phases": {name: {"seconds": round(acc.total, 6),
                              "count": acc.count}
                       for name, acc in reg.phases.items()},
            "compile": _observer().snapshot(),
        }
        if self.run_log is not None:
            try:
                self.run_log.write(summary)
            except ValueError:  # pragma: no cover
                pass
            self.run_log.close()
