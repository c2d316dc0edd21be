"""Share of the device's busy time that split finding takes: self seconds
under `lgbm/split/scan` and `lgbm/split/extract` (per-feature histograms
gathered out of the stored groups', a bundled feature's default bin
repaired) over the self seconds of every scope (which sum to busy), the
mean over device planes, in percent, from `trace_scopes` of the traced
trees (`trace_scopes.py`). Read where the features are gathered out of
bundles, on a table handed over sparse (the mode says so by `nonzeros`);
nothing to read elsewhere, or where the mode hands no scopes.
Layer: grower. Moves: train_mrow_iters_per_s."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import trace_scopes  # noqa: E402


def read(ctx):
    seconds = trace_scopes.layer_seconds(ctx, "lgbm/split")
    if not ctx.get("nonzeros") or not seconds or not seconds[1]:
        return None
    return 100.0 * seconds[0] / seconds[1]
