"""The least work of one lambdarank gradient evaluation, and the least
time a chip could take for it. Counted from the data set (its pairs with
label_i > label_j, its documents), so it is the same number whatever
computes the lambdas: sorted buckets and a dense pair tensor today,
anything later.

One valid pair costs, by the equations at the top of `reference_rank.py`:
s_i - s_j, its magnitude, + 0.01 (3); the gain difference, the discount
difference and its magnitude, their product, x inv, / (6); 2 sigma x the
score difference, exp, 1 +, 2 / (4); dNDCG x p, 2 - p, x p, x dNDCG, x 2
(5); four accumulations (4): 22 float32 operations, a divide and an exp
counted as one each. One document costs 16 bytes: its score and label
read, its lambda and hessian written. Sorting is left out (a lower bound
stays one)."""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
OPS_PER_PAIR = 22
BYTES_PER_DOC = 16


def load_peaks(device_kind: str):
    """The chip's elementwise peaks, or None for a kind the file lacks."""
    with open(os.path.join(HERE, "peaks_rank.json")) as fh:
        return json.load(fh).get(device_kind)


def least_seconds(valid_pairs: int, docs: int, peaks: dict) -> dict:
    ops = valid_pairs * OPS_PER_PAIR
    nbytes = docs * BYTES_PER_DOC
    t_ops = ops / peaks["f32_elementwise_ops_per_s"]
    t_bytes = nbytes / peaks["bytes_per_s"]
    return {"ops": ops, "bytes": nbytes, "seconds": max(t_ops, t_bytes),
            "bound": "ops" if t_ops >= t_bytes else "bytes"}
