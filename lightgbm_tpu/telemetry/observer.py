"""Compile-path observer over `jax.monitoring` events.

jax tells of every step between a call of a jitted function and a loaded
executable, and names the program (`fun_name=`) with each of the three
that take time:

- `/jax/core/compile/jaxpr_trace_duration` — the Python trace to a jaxpr.
  It fires for every `jit` inside the traced function too (each `jnp`
  call is one) and for the small functions a lowering traces, so only a
  trace that leaves its thread at top level as it closes is counted (jax
  fires the event after it has put back the enclosing trace): the nested
  ones are inside its seconds or the lowering's. A program traced under
  an eager `grad` or `vmap` is nested by that test and not counted.
- `/jax/core/compile/jaxpr_to_mlir_module_duration` — lowering to MLIR.
- `/jax/core/compile/backend_compile_duration` — the XLA compile, or the
  load of the executable from the persistent cache where it was there.
  Which of the two it was say `/jax/compilation_cache/cache_hits` /
  `cache_misses` and `cache_retrieval_time_sec`, which jax fires INSIDE
  that interval and without a name; they are
  charged to the program whose `backend_compile_duration` closes next on
  the same thread. A miss is a program compiled and written to the cache.

The observer keeps running totals of each (`totals()`, one cheap read:
`GBDT.init` and `train_one_iter` difference it over their own intervals
for `InitRecord` and `TreeRecord`) and a table by program
(`snapshot()["programs"]`), with telemetry enabled or not.

Attribution to SPANS is kept for runs with telemetry enabled, the only
ones that keep a span stack (`metrics.current_site()`): a backend compile
is charged to the innermost open span (`lgbm/iter/dispatch`,
`predict/dispatch`, ...), so the run log can say "iteration 0 spent 31s
compiling under lgbm/iter/dispatch". With telemetry off every compile
lands in `(no-span)`, and `programs` is what says which one it was.

Retrace counting: the first compile at a site is the expected trace;
every further one is a RETRACE (a new input signature reached the same
entry point). Sites crossing `retrace_warn` compiles log a warning once.
`(no-span)` and the phases of `GBDT.init` (`INIT_SPANS`) hold many
programs compiled once each, and are left out of both.

jax.monitoring has no per-listener deregistration, so `install()` is
once-per-process and `uninstall()` just deactivates the hooks (cheap
flag test per event).
"""
from __future__ import annotations

import os
import threading
from typing import Dict, NamedTuple, Optional

from . import metrics
from .layers import INIT_SPANS

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# the persistent cache's events -> the field each adds to
_CACHE_FIELDS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
    "/jax/compilation_cache/cache_retrieval_time_sec": "retrieval_s",
}
_UNATTRIBUTED = "(no-span)"
_UNNAMED = "(unnamed)"
# sites that hold many distinct entry points, each compiled once: every
# compile outside a span, and the phases of `GBDT.init` (a dozen small
# eager programs). Their counts say nothing about any one program
# retracing, so they neither count as retraces nor "storm".
_MANY_PROGRAMS = frozenset((_UNATTRIBUTED,) + INIT_SPANS)


class CompileTotals(NamedTuple):
    """Running totals of the compile path since install (or `reset()`)."""
    trace_s: float = 0.0        # outermost Python traces
    lower_s: float = 0.0        # jaxpr -> MLIR
    backend_s: float = 0.0      # XLA compiles and cache loads
    cache_hits: int = 0
    cache_misses: int = 0
    compiles: int = 0           # backend_compile_duration events

    @property
    def trace_lower_s(self) -> float:
        return self.trace_s + self.lower_s


def program_name(fun_name) -> str:
    """`jit(f)` as lowering and compile call it -> `f` as the trace does."""
    name = str(fun_name) if fun_name else _UNNAMED
    if name.endswith(")") and "(" in name:
        name = name[name.index("(") + 1:-1] or name
    return name


def _new_program() -> Dict:
    return {"traces": 0, "trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0,
            "cache_hits": 0, "cache_misses": 0, "retrieval_s": 0.0}


class CompileObserver:
    """Compile-path accounting fed by jax.monitoring: totals, by program,
    and (backend compiles, telemetry enabled) by span site."""

    def __init__(self, retrace_warn: int = 10):
        self.retrace_warn = int(
            os.environ.get("LGBM_TPU_RETRACE_WARN", retrace_warn))
        self._lock = threading.Lock()
        # per thread: what the nameless cache events said since the last
        # backend compile closed there
        self._local = threading.local()
        # jax's own test of a thread being outside every trace, bound at
        # `install()` (jax is not imported before): None = not registered
        self._at_top_level = None
        self.active = False
        # site -> {"compiles": int, "seconds": float, "warned": bool}
        self.sites: Dict[str, Dict] = {}
        self.programs: Dict[str, Dict] = {}
        self._totals = CompileTotals()

    # the two numbers the modes and the run log have always read
    @property
    def total_compiles(self) -> int:
        return self._totals.compiles

    @property
    def total_seconds(self) -> float:
        return self._totals.backend_s

    # -- listener plumbing ----------------------------------------------
    def install(self) -> None:
        """Register with jax.monitoring (idempotent) and activate."""
        self.active = True
        if self._at_top_level is not None:
            return
        from jax import monitoring
        from jax._src.core import trace_state_clean
        self._at_top_level = trace_state_clean
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def uninstall(self) -> None:
        self.active = False

    def reset(self) -> None:
        with self._lock:
            self.sites.clear()
            self.programs.clear()
            self._totals = CompileTotals()

    # -- event handling --------------------------------------------------
    def _add(self, program: Optional[str], **fields) -> None:
        """Under the lock: add to the totals and, where the event said
        which, to the program's row (same field names in both)."""
        t = self._totals
        self._totals = t._replace(
            **{k: getattr(t, k) + v for k, v in fields.items()
               if k in t._fields})
        if program is not None:
            row = self.programs.get(program)
            if row is None:
                row = self.programs[program] = _new_program()
            for k, v in fields.items():
                if k in row:
                    row[k] += v

    def _pending(self) -> Dict:
        pend = getattr(self._local, "pending", None)
        if pend is None:
            pend = self._local.pending = {}
        return pend

    def _on_cache(self, field: str, value, named) -> None:
        if named:
            with self._lock:
                self._add(program_name(named), **{field: value})
        else:
            pend = self._pending()
            pend[field] = pend.get(field, 0) + value

    def _on_event(self, event: str, **kwargs) -> None:
        if self.active and event in _CACHE_FIELDS:
            self._on_cache(_CACHE_FIELDS[event], 1, kwargs.get("fun_name"))

    def _on_duration(self, event: str, duration: float, **kwargs) -> None:
        if not self.active:
            return
        duration = float(duration)
        named = kwargs.get("fun_name")
        if event == _TRACE_EVENT:
            if self._at_top_level():
                with self._lock:
                    self._add(program_name(named), trace_s=duration,
                              traces=1)
        elif event == _LOWER_EVENT:
            with self._lock:
                self._add(program_name(named), lower_s=duration)
        elif event == _COMPILE_EVENT:
            self._on_backend_compile(duration, program_name(named))
        elif event in _CACHE_FIELDS:
            self._on_cache(_CACHE_FIELDS[event], duration, named)

    def _on_backend_compile(self, duration: float, program: str) -> None:
        site = metrics.current_site() or _UNATTRIBUTED
        pend = self._pending()
        with self._lock:
            self._add(program, backend_s=duration, compiles=1, **pend)
            pend.clear()
            rec = self.sites.get(site)
            if rec is None:
                rec = self.sites[site] = {
                    "compiles": 0, "seconds": 0.0, "warned": False}
            rec["compiles"] += 1
            rec["seconds"] += duration
            storm = (site not in _MANY_PROGRAMS
                     and not rec["warned"]
                     and rec["compiles"] > max(1, self.retrace_warn))
            if storm:
                rec["warned"] = True
        if metrics.enabled():
            metrics.counter_add("compile/count", 1, {"site": site})
            metrics.counter_add("compile/seconds", duration, {"site": site})
        if storm:
            from .. import log
            log.warning(
                "Retrace storm at '%s': %d compilations (%.1fs total) — "
                "the same entry point keeps seeing new input signatures; "
                "check shape bucketing / static-arg churn "
                "(LGBM_TPU_RETRACE_WARN tunes this threshold)",
                site, rec["compiles"], rec["seconds"])

    # -- views ------------------------------------------------------------
    def totals(self) -> CompileTotals:
        """The running totals: one attribute read of an immutable tuple,
        so a caller may difference two of them around any interval."""
        return self._totals

    def retraces(self, site: Optional[str] = None) -> int:
        """Compiles beyond the first per site (summed when site=None).
        The sites of many programs (`(no-span)`, the phases of
        `GBDT.init`) are left out of the sum."""
        with self._lock:
            if site is not None:
                rec = self.sites.get(site)
                return max(0, rec["compiles"] - 1) if rec else 0
            return self._retraces()

    def _retraces(self) -> int:
        return sum(max(0, r["compiles"] - 1)
                   for s, r in self.sites.items()
                   if s not in _MANY_PROGRAMS)

    def snapshot(self) -> Dict:
        with self._lock:
            return {
                "total_compiles": self._totals.compiles,
                "total_seconds": self._totals.backend_s,
                "retraces": self._retraces(),
                "sites": {s: {"compiles": r["compiles"],
                              "seconds": r["seconds"]}
                          for s, r in self.sites.items()},
                "programs": {p: dict(r) for p, r in self.programs.items()},
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


def compile_path_since(before: CompileTotals):
    """(trace_lower_s, backend_s, cache_hits, cache_misses) of the compile
    path since `before`, a `totals()` read earlier: what `InitRecord` and
    `TreeRecord` keep of it. All 0 where no observer is installed."""
    now = observer().totals()
    return (now.trace_lower_s - before.trace_lower_s,
            now.backend_s - before.backend_s,
            now.cache_hits - before.cache_hits,
            now.cache_misses - before.cache_misses)
