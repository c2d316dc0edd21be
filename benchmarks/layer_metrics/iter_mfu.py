"""The whole step's share of the chip's peak: the least time the chip
could take for the traced trees (benchmarks/work.py: root rows plus the
smaller child of every split, bytes and operations per row, the larger
of the two bounds) over the host's seconds from the drain before them to
the drain after them, in percent. The profiler's start and stop and the
window's other iterations are outside both. Layer: boosting
loop. Moves: train_mrow_iters_per_s. Counted from the finished trees, so
it does not depend on how the program builds its histograms."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import work  # noqa: E402


def read(ctx):
    span = ctx.get("traced_trees")
    if not span or not ctx.get("traced_host_s") or not ctx.get("peaks"):
        return None
    trees = ctx["trees_window"][span[0]:span[1]]
    rows = sum(work.rows_to_histogram(t["left_child"], t["right_child"],
                                      t["internal_count"], t["leaf_count"])
               for t in trees)
    least = work.least_seconds(rows, ctx["features"], ctx["peaks"])
    return 100.0 * least["seconds"] / ctx["traced_host_s"]
