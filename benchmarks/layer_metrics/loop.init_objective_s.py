"""Host seconds `GBDT.init` spent in the objective's and the metrics'
`init` (label statistics; under lambdarank the pair layout of every
query, built on the host): `objective_s` of the program's `InitRecord`,
span `lgbm/init/objective`. Layer: boosting loop. Moves: setup_s."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import run_record  # noqa: E402


def read(ctx):
    return run_record.init_field(ctx, "objective_s")
