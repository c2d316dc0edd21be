"""The host's own seconds per tree: `dispatch_s` (entry of
`train_one_iter` to the grow program's enqueue returning: gradients,
bagging, dispatch) plus `build_tree_s` (end of the tree's fetch to the
tree being appended), mean over the window's trees, from
`GBDT.pass_log`. None of it waits for the device, so it is the floor of
an iteration once the device is fast. Layer: boosting loop. Moves:
train_mrow_iters_per_s."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import tree_record  # noqa: E402


def read(ctx):
    dispatch = tree_record.column(ctx, "dispatch_s")
    build = tree_record.column(ctx, "build_tree_s")
    if dispatch is None or build is None:
        return None
    return tree_record.mean(dispatch) + tree_record.mean(build)
