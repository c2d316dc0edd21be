"""HIGGS-like data (the label model of `bench.synth_higgs`, drawn in
float32): dense normal features, binary label from a nonlinear score of
the first six base columns plus logistic noise."""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import datagen  # noqa: E402


def score(X):
    return (X[:, 0] * 1.2 - X[:, 1] + 0.8 * X[:, 2] * X[:, 3]
            + 0.5 * np.abs(X[:, 4]) + 0.3 * X[:, 5] ** 2)


def generate(rows: int, features: int, seed: int, base_seed=None):
    return datagen.seeded_blocks(rows, features, seed, score, 0.5, base_seed)
