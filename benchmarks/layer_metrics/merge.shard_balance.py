"""The fewest real rows a device's shard holds over the most:
`shard_rows` of the program's `InitRecord`, the layout `GBDT.init` landed.
1.0 is even; under it the devices with more rows set the pace and the
others wait inside the merge collective, busy by the device's clock.
Nothing to read where the rows are on one device. Layer: data-parallel.
Moves: train_mrow_iters_per_s."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import run_record  # noqa: E402


def read(ctx):
    shard_rows = run_record.init_field(ctx, "shard_rows")
    if not shard_rows or len(shard_rows) < 2 or max(shard_rows) <= 0:
        return None
    return min(shard_rows) / max(shard_rows)
