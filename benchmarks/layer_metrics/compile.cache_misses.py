"""Programs that were compiled and written to the persistent cache
during set-up, where a warm run loads them: `cache_misses` of the
`InitRecord` plus the warm-up trees' `TreeRecord`s
(`/jax/compilation_cache/cache_misses`). 0 says the run was warm. Layer:
compile. Moves: setup_s."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import run_record  # noqa: E402


def read(ctx):
    return run_record.setup_sum(ctx, "cache_misses")
