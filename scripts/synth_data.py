"""Seeded synthetic data sets at the reference's published benchmark
shapes (docs/GPU-Performance.md:74-116: HIGGS 28 dense features, Epsilon
2000 dense-wide, Bosch 968 sparse, Expo categorical) and the row counts,
iterations and leaves the CPU-reference records were measured at
(`measure_baseline.py` -> BENCH_BASELINE*.json, `measure_accuracy.py`,
`measure_parity_sweep.py`, `chip_smoke.py`). The benchmark
(`benchmarks/`) has generators of its own; these are not to be compared
with them.
"""
from __future__ import annotations

import numpy as np

N_ROWS = 2_000_000
N_FEATURES = 28
N_ITERS = 15
NUM_LEAVES = 255
MAX_BIN = 63


def synth_higgs(n, f, seed=0):
    """Synthetic HIGGS-like: dense float features, binary label from a
    nonlinear score (matches HIGGS's structure: 28 kinematic features)."""
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f).astype(np.float32)
    score = (X[:, 0] * 1.2 - X[:, 1] + 0.8 * X[:, 2] * X[:, 3]
             + 0.5 * np.abs(X[:, 4]) + 0.3 * X[:, 5] ** 2)
    y = (score + rng.logistic(size=n) > 0.5).astype(np.float32)
    return X, y


def synth_epsilon(n, f=2000, seed=1):
    """Epsilon-like: dense WIDE float features (Epsilon is 400k x 2000
    normalized dense). Exercises the group-block-tiled histogram pass.

    The benchmark's own is `benchmarks/generators/synth_epsilon.py`: the
    same label model, seeded otherwise (there the seed orders the columns
    of one fixed data set, here it draws the values and the weights), so
    the two give different data for the same seed and are not to be
    compared."""
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f).astype(np.float32)
    w = rng.randn(24)
    score = X[:, :24] @ w + 0.5 * X[:, 24] * X[:, 25]
    y = (score + rng.logistic(size=n) > 0.0).astype(np.float32)
    return X, y


def synth_bosch(n, f=968, seed=2):
    """Bosch-like: ~80% sparse with one-hot-style mutually-exclusive
    feature blocks (the structure EFB exists for, dataset.cpp:66-211)
    plus a tail of randomly-sparse numerics."""
    rng = np.random.RandomState(seed)
    X = np.zeros((n, f), np.float32)
    # 700 features in exclusive blocks of 10: each row activates exactly
    # one feature of each block (one-hot-encoded categoricals)
    n_blocks = 70
    for b in range(n_blocks):
        pick = rng.randint(0, 10, size=n)
        vals = rng.rand(n).astype(np.float32) + 0.1
        X[np.arange(n), b * 10 + pick] = vals
    # remaining features: 80% zeros random sparse
    f_rest = f - n_blocks * 10
    R = rng.randn(n, f_rest).astype(np.float32)
    R[rng.rand(n, f_rest) < 0.8] = 0.0
    X[:, n_blocks * 10:] = R
    score = (X[:, 0] * 2.0 - X[:, 10] + X[:, 700] - 0.5 * X[:, 701]
             + X[:, 20] * X[:, 702])
    y = (score + 0.5 * rng.logistic(size=n) > 0.3).astype(np.float32)
    return X, y


def synth_multiclass(n, f=28, k=5, seed=4):
    """Multiclass shape (no reference-published analogue; exercises the
    one-program-per-iteration vmap'd class growth, gbdt.cpp:410-462)."""
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f).astype(np.float32)
    centers = rng.randn(k, 6) * 1.5
    d = ((X[:, None, :6] - centers[None]) ** 2).sum(-1)
    y = np.argmin(d + rng.gumbel(size=(n, k)), axis=1).astype(np.float32)
    return X, y


def synth_expo(n, seed=3):
    """Expo-like: mixed categorical + numeric (the reference one-hot
    encodes Expo to 700 binary columns; the native-categorical path is
    the TPU framework's analogue). 8 categoricals (cardinality 12..96)
    + 32 numerics; label depends on categories nonlinearly."""
    rng = np.random.RandomState(seed)
    cards = [12, 24, 24, 48, 48, 64, 96, 96]
    cats = [rng.randint(0, c, size=n) for c in cards]
    Xn = rng.randn(n, 32).astype(np.float32)
    X = np.column_stack([np.asarray(c, np.float32) for c in cats] + [Xn])
    score = (np.sin(cats[0] * 1.7) + (cats[3] % 5 == 0) * 1.5
             + np.cos(cats[6] * 0.4) + Xn[:, 0] - 0.5 * Xn[:, 1])
    y = (score + rng.logistic(size=n) > 0.5).astype(np.float32)
    return X, y, list(range(8))


# name -> (rows, builder() -> (X, y[, categorical_idx]), max_bin)
SHAPES = {
    "higgs": (N_ROWS, lambda n: synth_higgs(n, N_FEATURES), MAX_BIN),
    "epsilon": (200_000, synth_epsilon, 63),
    "epsilon15": (200_000, synth_epsilon, 15),
    "bosch": (500_000, synth_bosch, 63),
    "expo": (1_000_000, synth_expo, 63),
    "multiclass": (500_000, synth_multiclass, 63),
}
