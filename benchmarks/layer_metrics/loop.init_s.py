"""Host seconds of `GBDT.init`, entry to return: `total_s` of the
program's `InitRecord` (`perf_counter`, always taken; its phases are the
spans `lgbm/init/*`). With `dataset.sketch_s` and `dataset.bin_s` it is
what `dataset.construct_s` times from outside. Layer: boosting loop.
Moves: setup_s."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import run_record  # noqa: E402


def read(ctx):
    return run_record.init_field(ctx, "total_s")
