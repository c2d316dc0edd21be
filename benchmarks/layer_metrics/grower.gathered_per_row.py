"""Rows gathered through the compaction index per tree (the member rows
of every compacted pass: bins, channels and leaf ids each gathered once a
row), over the rows of the training set; `rows_gathered` of
`GBDT.pass_log`. The gathers are the dearest term of a compacted pass
(43-47 ns a gathered row at every width, PERF.md section 6); 0 where the
schedule compacts nothing. Layer: grower. Moves: train_mrow_iters_per_s."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import tree_record  # noqa: E402


def read(ctx):
    gathered = tree_record.column(ctx, "rows_gathered")
    if gathered is None or not ctx.get("rows"):
        return None
    return tree_record.mean(gathered) / ctx["rows"]
