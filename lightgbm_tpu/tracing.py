"""Back-compat shim over `lightgbm_tpu.telemetry` (the old flat timers).

This module used to hold the TIMETAG-style global accumulators
(reference `gbdt.cpp:53-62`); the real implementation now lives in
`lightgbm_tpu/telemetry/` (labeled registry, run log, compile observer,
Prometheus export). The historical entry points that still have a
caller keep their names:

- `phase(name)` — span-scoped timer of host time (it never waits for
  the device)
- `counter(name, value)` / `counters()` — accumulate / read
  `{name: (total, events)}`
- `totals()` — `{phase: (seconds, count)}`
- `enable/enabled/reset/dump/block` — as before; `LGBM_TPU_TIMETAG=1`
  still enables at import and dumps at exit

New code should import `lightgbm_tpu.telemetry` directly.
"""
from __future__ import annotations

import atexit
from typing import Dict, Tuple

from . import telemetry as _t

enable = _t.enable
enabled = _t.enabled
reset = _t.reset
block = _t.block
dump = _t.dump


def totals() -> Dict[str, Tuple[float, int]]:
    return {name: (acc.total, acc.count)
            for name, acc in _t.registry().phases.items()}


def counter(name: str, value: float) -> None:
    """Accumulate a numeric event counter; zero-cost when disabled."""
    _t.counter_add(name, value)


def counters() -> Dict[str, Tuple[float, int]]:
    out: Dict[str, Tuple[float, int]] = {}
    for c in _t.registry().counters.values():
        if not c.labels:
            out[c.name] = (c.value, c.events)
    return out


def phase(name: str):
    """Accumulate host time under `name` (telemetry.span)."""
    return _t.span(name)


@atexit.register
def _dump_at_exit() -> None:
    if _t.enabled():
        _t.dump()
