"""Host seconds between the dataset layer's two passes: the bundler's row
sample binned and `efb.find_groups_sampled` over the used columns;
`groups_s` of the program's `ConstructRecord`, a `perf_counter` pair in
`ingest/build.build_inner`, span `lgbm/dataset/groups`. Layer: dataset.
Moves: setup_s."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import construct_record  # noqa: E402


def read(ctx):
    return construct_record.field(ctx, "groups_s")
