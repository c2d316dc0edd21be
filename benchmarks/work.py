"""The algorithm's least work for the trees a window grew, and the least
time a chip could take for it. Counted from the finished trees, so it is
the same number whatever implements the histogram: a later PR that takes
the one-hot contraction off the path still answers to it.

Per tree the rows that MUST be histogrammed are the root's rows plus, for
every split, the smaller child's rows (the larger child follows by
subtraction from the parent). Each such row costs, per feature, one byte
read (the bin) plus 8 bytes once (gradient and hessian), and 3
operations per feature (add gradient, hessian and count to a bin).
"""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def load_peaks(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as fh:
        table = json.load(fh)
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json "
                       f"(has {sorted(table)}): add its peaks with a source")
    return table[device_kind]


def rows_to_histogram(left_child, right_child, internal_count,
                      leaf_count) -> int:
    """Root rows + the smaller child's rows of every split. Children are
    LightGBM's encoding: >= 0 an internal node, < 0 the leaf ~child."""
    if len(leaf_count) <= 1:
        return int(leaf_count[0]) if len(leaf_count) else 0

    def count(child):
        child = int(child)
        return int(internal_count[child] if child >= 0
                   else leaf_count[~child])

    total = int(internal_count[0])
    for lc, rc in zip(left_child, right_child):
        total += min(count(lc), count(rc))
    return total


def least_seconds(hist_rows: int, features: int, peaks: dict) -> dict:
    nbytes = hist_rows * (features * 1 + 8)
    ops = hist_rows * features * 3
    t_bytes = nbytes / peaks["bytes_per_s"]
    t_ops = ops / peaks["flops_per_s"]
    return {"bytes": nbytes, "ops": ops, "seconds": max(t_bytes, t_ops),
            "bound": "bytes" if t_bytes >= t_ops else "ops"}
