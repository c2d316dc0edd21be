"""The histogram contraction's share of its roofline on a table kept
sparse: the least time the chip could take for the rows the traced trees
handed the contraction (`rows_contracted` of `GBDT.pass_log`) at the
table's stored entries a row (`work_sparse.py`: 3 operations and 1 byte
an entry, 8 bytes a row once; `peaks.json`) over the measured self
seconds of `lgbm/hist/contract` and `lgbm/hist/operand` in the trace, in
percent. Nothing to read on a table that is not handed over sparse (the
mode says so by `nonzeros`), nor without the trace's scopes, the per-tree
records or the chip's peaks. Layer: grower. Moves:
train_mrow_iters_per_s."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import trace_scopes  # noqa: E402
import tree_record  # noqa: E402
import work_sparse  # noqa: E402


def read(ctx):
    span = ctx.get("traced_trees")
    contracted = tree_record.column(ctx, "rows_contracted")
    parts = [trace_scopes.layer_seconds(ctx, "lgbm/hist/" + name)
             for name in ("contract", "operand")]
    if (not span or contracted is None or not ctx.get("peaks")
            or not ctx.get("nonzeros") or not all(parts)):
        return None
    seconds = sum(p[0] for p in parts)
    if not seconds:
        return None
    least = work_sparse.least_seconds(
        sum(contracted[span[0]:span[1]]), ctx["nonzeros"] // ctx["rows"],
        ctx["peaks"])
    return 100.0 * least["seconds"] / seconds
