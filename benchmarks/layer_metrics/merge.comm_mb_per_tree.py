"""Megabytes one device keeps from the histogram merges of one tree (the
reduce-scatter of every pass's children over the stored groups, and the
root's): the mean over the window's trees of `comm_bytes` of
`GBDT.pass_log`, which is 4 bytes an element the grower counted through
its data-axis collectives (`comm_elems`), over 1e6. Nothing to read where
the rows are on one device (`schedule.num_shards` 1: no collective runs)
or the program keeps no such record. Layer: data-parallel. Moves:
train_mrow_iters_per_s."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import tree_record  # noqa: E402


def read(ctx):
    if int((ctx.get("schedule") or {}).get("num_shards", 1)) <= 1:
        return None
    comm = tree_record.column(ctx, "comm_bytes")
    if comm is None:
        return None
    return tree_record.mean(comm) / 1e6
