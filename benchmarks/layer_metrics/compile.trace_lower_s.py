"""Seconds jax spent tracing this program's Python to jaxprs (outermost
traces only) and lowering them to MLIR during set-up: `trace_lower_s` of
the `InitRecord` plus the warm-up trees' `TreeRecord`s, differences of
`telemetry.observer().totals()` over `GBDT.init` and over each tree's
dispatch. Paid on every run, warm cache or cold: the persistent cache is
keyed by the lowered module. Layer: compile. Moves: setup_s."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import run_record  # noqa: E402


def read(ctx):
    return run_record.setup_sum(ctx, "trace_lower_s")
