"""Share of the device's busy time that the gradient layer takes: self
seconds under `lgbm/gradients` and its sub-scopes over the self seconds
of every scope (which sum to busy), the mean over device planes, in
percent, from `trace_scopes` of the traced trees (`trace_scopes.py`).
Nothing to read where the mode hands none. Layer: gradients. Moves:
train_mrow_iters_per_s."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import trace_scopes  # noqa: E402


def read(ctx):
    seconds = trace_scopes.layer_seconds(ctx, "lgbm/gradients")
    if not seconds or not seconds[1]:
        return None
    return 100.0 * seconds[0] / seconds[1]
