"""Host seconds of the dataset layer's pass 2: every value to its bin,
bundling, and the landing; `bin_s` of the program's
`ConstructRecord`, a `perf_counter` pair in `ingest/build.build_inner`,
span `lgbm/dataset/bin`. Layer: dataset. Moves: setup_s."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import construct_record  # noqa: E402


def read(ctx):
    return construct_record.field(ctx, "bin_s")
