"""Seconds the host waited for the device per tree: the `device_get` of
the tree's small state (`fetch_wait_s` of `GBDT.pass_log`), mean over
the window's trees. In the pipelined loop this is where an iteration's
device time shows on the host. Layer: boosting loop. Moves:
train_mrow_iters_per_s."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import tree_record  # noqa: E402


def read(ctx):
    wait = tree_record.column(ctx, "fetch_wait_s")
    return None if wait is None else tree_record.mean(wait)
