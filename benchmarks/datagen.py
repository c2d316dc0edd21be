"""Seeded data for the benchmark. A configuration file names its generator
under "generator"; the generator is the file `generators/<name>.py` with
`generate(rows, features, seed, base_seed=None) -> (X float32, y float32)`.
Here is what generators share: `seeded_blocks`, which draws float32
normals block by block on a few threads, and the loader of files by path.
"""
from __future__ import annotations

import importlib.util
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


BLOCK_ROWS = 262144   # fixed, so the rows are the same on any machine
THREADS = 8
BASE_SEED = 25        # the one data set every seed reorders


def seeded_blocks(rows: int, features: int, seed: int, score_fn, shift,
                  base_seed=None):
    """X [rows, features] float32 standard normals and the label
    y = (score_fn(base columns) + logistic noise > shift).

    The VALUES are the same for every seed; the seed decides the ORDER OF
    THE COLUMNS (which feature sits where). A training job's work depends
    on its data (which splits win, how many rows each pass contracts):
    with rows drawn anew from each seed, iterations differed by 5% between
    seeds, and with the same rows in another ROW order still by 9% (the
    bin finder samples rows by position), while two runs of one seed
    agreed to 0.01% (my chip runs, PR 25). No bound under 10% survives
    that, so every seed gets the same data in another order, as the
    contract asks of traffic whose seed changes the work: the trees are
    then the same up to the features' names. Each row block comes from its
    own child of SeedSequence(BASE_SEED), on a few threads (numpy's
    generators release the GIL): 21M x 28 takes ~1.8 s of every run's
    set-up (one thread took ~11 s at 21M rows). `base_seed` (never given
    by a benchmark run) draws another data set: `readings.py` and
    `test_correct.py` use it to try the limits of `correct` on data they
    were not set from."""
    X = np.empty((rows, features), np.float32)
    y = np.empty((rows,), np.float32)
    starts = range(0, rows, BLOCK_ROWS)
    children = np.random.SeedSequence(
        BASE_SEED if base_seed is None else int(base_seed)).spawn(len(starts))
    place = np.random.default_rng(seed).permutation(features)
    source = np.argsort(place)       # column c of X is base column source[c]

    def fill(job):
        start, child = job
        rng = np.random.default_rng(child)
        n = min(BLOCK_ROWS, rows - start)
        base = rng.standard_normal((n, features), dtype=np.float32)
        noise = rng.logistic(size=n).astype(np.float32)
        y[start:start + n] = score_fn(base) + noise > shift
        np.take(base, source, axis=1, out=X[start:start + n])

    with ThreadPoolExecutor(max_workers=THREADS) as pool:
        list(pool.map(fill, zip(starts, children)))
    return X, y


def load_file_module(path: str, name: str):
    """Import one file of the benchmark by path (names may hold dots)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def generator(name: str):
    path = os.path.join(HERE, "generators", name + ".py")
    if not os.path.isfile(path):
        raise KeyError(f"no generator {name!r}: no {path}")
    return load_file_module(path, "benchmarks_generator_" + name).generate
