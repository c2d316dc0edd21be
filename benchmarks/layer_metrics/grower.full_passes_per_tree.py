"""Mean histogram passes per tree whose contraction ran over ALL valid
rows (the root's pass and every pass whose selected nodes did not fit
the compaction buffer), over the window's trees; `full_passes` of
`GBDT.pass_log`, told on the host from the per-pass row counts the tree
fetch already carries. With `grower.passes_per_tree` it says how many
passes were compacted. Layer: grower. Moves: train_mrow_iters_per_s."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import tree_record  # noqa: E402


def read(ctx):
    full = tree_record.column(ctx, "full_passes")
    return None if full is None else tree_record.mean(full)
