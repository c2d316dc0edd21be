"""The gradient layer's share of its roofline: the least time the chip
could take for the traced iterations' lambdarank gradients
(`work_rank.py`: the data set's valid pairs x the operations one pair's
equations need, 16 bytes a document; the larger of operations over the
float32 elementwise peak and bytes over the memory's, `peaks_rank.json`)
over the layer's measured self seconds in the trace
(`gradients.device_share`'s numerator), in percent. Nothing to read
without the trace's scopes, the objective's counters or the chip's
peaks. Layer: gradients. Moves: train_mrow_iters_per_s."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import trace_scopes  # noqa: E402
import work_rank  # noqa: E402


def read(ctx):
    rank = (ctx.get("schedule") or {}).get("rank")
    span = ctx.get("traced_trees")
    seconds = trace_scopes.layer_seconds(ctx, "lgbm/gradients")
    peaks = work_rank.load_peaks(ctx.get("device_kind", ""))
    if not rank or not span or not seconds or not seconds[0] or not peaks:
        return None
    least = work_rank.least_seconds(rank["valid_pairs"], rank["docs"], peaks)
    return 100.0 * (span[1] - span[0]) * least["seconds"] / seconds[0]
