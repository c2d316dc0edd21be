"""Epsilon-like data (the label model of `bench.synth_epsilon`, drawn in
float32 by `datagen.seeded_blocks`): dense standard-normal features, the
binary label from 24 linear base columns with fixed weights, one product
term and logistic noise, shift 0, so the classes are balanced. The
weights are drawn once from a constant, not from the seed: every seed is
the same data set with its columns in another order, as `datagen.py`
explains."""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import datagen  # noqa: E402

LINEAR = 24
WEIGHTS = np.random.default_rng(2000).standard_normal(LINEAR).astype(np.float32)


def score(X):
    return X[:, :LINEAR] @ WEIGHTS + 0.5 * X[:, LINEAR] * X[:, LINEAR + 1]


def generate(rows: int, features: int, seed: int, base_seed=None):
    return datagen.seeded_blocks(rows, features, seed, score, 0.0, base_seed)
