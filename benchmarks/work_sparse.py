"""`work.py` for a table kept sparse: the least work of an algorithm that
knows a row holds `nonzeros_per_row` stored entries and that every other
value is its column's default, whose histogram cell follows by
subtraction from the node's totals. A row that must be histogrammed then
costs, per stored entry, one byte read (the bin) and 3 operations (add
gradient, hessian and count to a cell), plus 8 bytes once (gradient and
hessian): `work.least_seconds` with the entries a row in the features'
place, against the same `peaks.json`. The rows that must be histogrammed
are `work.rows_to_histogram`'s; a kernel's own roofline is asked about
the rows the kernel was handed."""
from __future__ import annotations

import work

load_peaks = work.load_peaks
rows_to_histogram = work.rows_to_histogram


def least_seconds(hist_rows: int, nonzeros_per_row: int, peaks: dict) -> dict:
    return work.least_seconds(hist_rows, nonzeros_per_row, peaks)
