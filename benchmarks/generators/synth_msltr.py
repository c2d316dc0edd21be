"""MS-LTR-like ranking data (MSLR-WEB30K's shape, not its file): dense
standard-normal features in float32, graded labels 0-4, rows grouped into
queries.

    generate(rows, features, seed, base_seed=None) -> (X, y, sizes)

`sizes[q]` is the number of consecutive rows of query q. Query lengths
are lognormal (median 100, sigma 0.6), scaled so that `rows / 120`
queries (the published mean) hold exactly `rows` rows, clipped to the
published range 1-1,251; one query of 1 document and one of 1,251 are
planted, so both extremes (and the program's 2,048-wide bucket) always
exist, and the last queries take up what rounding left over. Labels are
cut from a latent relevance (24 linear base columns with fixed weights
and one product term, scaled to variance 1.5^2; a per-query shift, unit
normal, and -8 for one query in fifty, which has no answer; logistic
noise) at fixed cuts that give MSLR-WEB30K's skew, about 51 / 33 / 13 /
2 / 1 %: labels follow features, queries differ in how relevant their
documents are, and about one query in fifty is all one label.

By `datagen.py`'s contract every seed is the SAME data set (values,
labels and query boundaries, all from the base seed) with its columns in
an order drawn from the seed; `base_seed` draws another data set."""
import os
import sys
from concurrent.futures import ThreadPoolExecutor

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import datagen  # noqa: E402

MEAN_DOCS, MEDIAN_DOCS, SIGMA_DOCS = 120, 100.0, 0.6
MIN_DOCS, MAX_DOCS = 1, 1251
LINEAR = 24
NO_ANSWER = 0.02     # share of queries with no relevant document
WEIGHTS = np.random.default_rng(137).standard_normal(LINEAR).astype(np.float32)
FEATURE_SCALE = np.float32(1.5 / np.sqrt(float(WEIGHTS @ WEIGHTS) + 0.25))
# upper edges of labels 0..3 on the latent relevance: its 51 / 84 / 97 /
# 99 % points, read once off 2,097,152 rows of the base data set
CUTS = np.asarray([-0.024, 2.428, 4.770, 6.032], np.float32)


def query_sizes(rows: int, rng) -> np.ndarray:
    if rows < 2 * MAX_DOCS:
        raise ValueError(f"synth_msltr needs at least {2 * MAX_DOCS} rows "
                         f"(the planted {MAX_DOCS}-document query), got {rows}")
    queries = -(-rows // MEAN_DOCS)
    raw = rng.lognormal(np.log(MEDIAN_DOCS), SIGMA_DOCS, queries)
    short, long_ = rng.choice(queries - 1, 2, replace=False)
    raw[[short, long_]] = 0.0
    sizes = np.clip(np.rint(raw * (rows - MIN_DOCS - MAX_DOCS) / raw.sum()),
                    MIN_DOCS, MAX_DOCS).astype(np.int64)
    sizes[short], sizes[long_] = MIN_DOCS, MAX_DOCS
    left = rows - int(sizes.sum())
    for q in range(queries - 1, -1, -1):     # what rounding left over
        if left == 0:
            break
        if q in (short, long_):
            continue
        new = int(np.clip(sizes[q] + left, MIN_DOCS + 1, MAX_DOCS - 1))
        left -= new - int(sizes[q])
        sizes[q] = new
    return sizes


def relevance(base, shift, noise):
    return (FEATURE_SCALE * (base[:, :LINEAR] @ WEIGHTS
                             + 0.5 * base[:, LINEAR] * base[:, LINEAR + 1])
            + shift + noise)


def generate(rows: int, features: int, seed: int, base_seed=None):
    base_seed = datagen.BASE_SEED if base_seed is None else int(base_seed)
    layout = np.random.default_rng([base_seed, 1])
    sizes = query_sizes(rows, layout)
    shift = np.where(layout.random(len(sizes)) < NO_ANSWER, -8.0,
                     layout.standard_normal(len(sizes))).astype(np.float32)
    shift = np.repeat(shift, sizes)
    X = np.empty((rows, features), np.float32)
    y = np.empty((rows,), np.float32)
    starts = range(0, rows, datagen.BLOCK_ROWS)
    children = np.random.SeedSequence(base_seed).spawn(len(starts))
    place = np.random.default_rng(seed).permutation(features)
    source = np.argsort(place)       # column c of X is base column source[c]

    def fill(job):
        start, child = job
        rng = np.random.default_rng(child)
        n = min(datagen.BLOCK_ROWS, rows - start)
        base = rng.standard_normal((n, features), dtype=np.float32)
        noise = rng.logistic(size=n).astype(np.float32)
        y[start:start + n] = np.searchsorted(
            CUTS, relevance(base, shift[start:start + n], noise))
        np.take(base, source, axis=1, out=X[start:start + n])

    with ThreadPoolExecutor(max_workers=datagen.THREADS) as pool:
        list(pool.map(fill, zip(starts, children)))
    return X, y, sizes
