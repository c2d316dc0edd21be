"""Host seconds `GBDT.init` spent handing the binned matrix to the
device(s): `land_s` of the program's `InitRecord`, span `lgbm/init/land`.
Where the rows are sharded over the chips the upload is waited for and
this is its whole time; on one chip it is the enqueue alone. Layer:
boosting loop. Moves: setup_s."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import run_record  # noqa: E402


def read(ctx):
    return run_record.init_field(ctx, "land_s")
