"""Host nanoseconds pass 2 of the dataset layer takes for one stored
entry it visits: `bin_s` over `nonzeros` of the program's
`ConstructRecord` (the entries of a sparse source that lie in used
columns; every value of a dense source). Nothing to read where the
program counts no such entries. Layer: dataset. Moves: setup_s."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import construct_record  # noqa: E402


def read(ctx):
    seconds = construct_record.field(ctx, "bin_s")
    visited = construct_record.field(ctx, "nonzeros")
    if not ctx.get("nonzeros") or seconds is None or not visited:
        return None
    return 1e9 * seconds / visited
