"""What the trees before the window cost beyond a tree of the window:
the sum over the warm-up trees of `dispatch_s + fetch_wait_s +
build_tree_s` (`GBDT.pass_log`, `telemetry.last_run()`), less their number
times the window's mean of the same sum. It holds the Python traces, the
lowerings, the cache loads or compiles, and each program's first run.
Layer: boosting loop. Moves: setup_s."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import run_record  # noqa: E402


def read(ctx):
    return run_record.warmup_overhead_s(ctx)
