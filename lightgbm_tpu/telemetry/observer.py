"""Compile/retrace observer over `jax.monitoring` events.

The measured 29-81s wide-shape compile tails and the pervasive retrace
risk on new shapes (ROADMAP "kill cold-start") are invisible today
outside manual profiling. jax emits monitoring events for every
compilation — `/jax/core/compile/backend_compile_duration` fires once
per backend compile with its wall time — but carries no clue WHICH
jitted entry point compiled. This observer supplies the attribution:
compile events are charged to the innermost open telemetry span
(`metrics.current_site()` — `lgbm/iter/dispatch`, `predict/dispatch`,
...), so the run log can say "iteration 0 spent 31s compiling under
lgbm/iter/dispatch".

Retrace counting: the first compile at a site is the expected trace;
every further one is a RETRACE (a new input signature reached the same
entry point). Sites crossing `retrace_warn` compiles log a warning once
— the retrace-storm tripwire the AOT-cache work needs a baseline for.

jax.monitoring has no per-listener deregistration, so `install()` is
once-per-process and `uninstall()` just deactivates the hooks (cheap
flag test per event).
"""
from __future__ import annotations

import os
import threading
from typing import Dict, Optional

from . import metrics

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_UNATTRIBUTED = "(no-span)"


class CompileObserver:
    """Per-site compile/retrace accounting fed by jax.monitoring."""

    def __init__(self, retrace_warn: int = 10):
        self.retrace_warn = int(
            os.environ.get("LGBM_TPU_RETRACE_WARN", retrace_warn))
        self._lock = threading.Lock()
        self._registered = False
        self.active = False
        # site -> {"compiles": int, "seconds": float, "warned": bool}
        self.sites: Dict[str, Dict] = {}
        self.total_compiles = 0
        self.total_seconds = 0.0

    # -- listener plumbing ----------------------------------------------
    def install(self) -> None:
        """Register with jax.monitoring (idempotent) and activate."""
        self.active = True
        if self._registered:
            return
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._on_duration)
        self._registered = True

    def uninstall(self) -> None:
        self.active = False

    def reset(self) -> None:
        with self._lock:
            self.sites.clear()
            self.total_compiles = 0
            self.total_seconds = 0.0

    # -- event handling --------------------------------------------------
    def _on_duration(self, event: str, duration: float, **kwargs) -> None:
        if not self.active or event != _COMPILE_EVENT:
            return
        site = metrics.current_site() or _UNATTRIBUTED
        with self._lock:
            rec = self.sites.get(site)
            if rec is None:
                rec = self.sites[site] = {
                    "compiles": 0, "seconds": 0.0, "warned": False}
            rec["compiles"] += 1
            rec["seconds"] += float(duration)
            self.total_compiles += 1
            self.total_seconds += float(duration)
            # the unattributed bucket aggregates every compile outside a
            # span — many distinct entry points, not one retracing — so
            # it can't meaningfully "storm"
            storm = (site != _UNATTRIBUTED
                     and not rec["warned"]
                     and rec["compiles"] > max(1, self.retrace_warn))
            if storm:
                rec["warned"] = True
        if metrics.enabled():
            metrics.counter_add("compile/count", 1, {"site": site})
            metrics.counter_add("compile/seconds", float(duration),
                                {"site": site})
        if storm:
            from .. import log
            log.warning(
                "Retrace storm at '%s': %d compilations (%.1fs total) — "
                "the same entry point keeps seeing new input signatures; "
                "check shape bucketing / static-arg churn "
                "(LGBM_TPU_RETRACE_WARN tunes this threshold)",
                site, rec["compiles"], rec["seconds"])

    # -- views ------------------------------------------------------------
    def retraces(self, site: Optional[str] = None) -> int:
        """Compiles beyond the first per site (summed when site=None).
        The unattributed bucket is excluded from the sum: it aggregates
        many distinct entry points, so its count says nothing about any
        one of them retracing."""
        with self._lock:
            if site is not None:
                rec = self.sites.get(site)
                return max(0, rec["compiles"] - 1) if rec else 0
            return sum(max(0, r["compiles"] - 1)
                       for s, r in self.sites.items()
                       if s != _UNATTRIBUTED)

    def snapshot(self) -> Dict:
        with self._lock:
            return {
                "total_compiles": self.total_compiles,
                "total_seconds": self.total_seconds,
                "retraces": sum(max(0, r["compiles"] - 1)
                                for s, r in self.sites.items()
                                if s != _UNATTRIBUTED),
                "sites": {s: {"compiles": r["compiles"],
                              "seconds": r["seconds"]}
                          for s, r in self.sites.items()},
            }


_observer: Optional[CompileObserver] = None


def observer() -> CompileObserver:
    """The process-wide observer (created lazily, NOT auto-installed)."""
    global _observer
    if _observer is None:
        _observer = CompileObserver()
    return _observer


def install() -> CompileObserver:
    obs = observer()
    obs.install()
    return obs
