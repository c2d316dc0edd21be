"""Host seconds of the dataset layer's first phase: pass 1 over the rows
(the bin finder's and the bundler's row samples) and bin finding for
every column; `sketch_s` of the program's `ConstructRecord`, a
`perf_counter` pair in `ingest/build.build_inner`, span
`lgbm/dataset/sketch`. Layer: dataset. Moves: setup_s."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import construct_record  # noqa: E402


def read(ctx):
    return construct_record.field(ctx, "sketch_s")
