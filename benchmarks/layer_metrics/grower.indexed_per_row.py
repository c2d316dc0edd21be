"""Padded rows scanned by compaction index builds per tree (compacted
passes x padded rows: each build is a member mask, a cumsum and a scatter
over every padded row), over the rows of the training set;
`rows_indexed` of `GBDT.pass_log`. Layer: grower. Moves:
train_mrow_iters_per_s."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import tree_record  # noqa: E402


def read(ctx):
    indexed = tree_record.column(ctx, "rows_indexed")
    if indexed is None or not ctx.get("rows"):
        return None
    return tree_record.mean(indexed) / ctx["rows"]
