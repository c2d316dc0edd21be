"""Seeded stand-in for the Expo table of the reference's benchmark (the
Data Expo 2009 airline on-time records, every column categorical, 700
columns once one-hot coded), handed over the way a one-hot table is kept:
a `scipy.sparse` CSR matrix of float32 ones, 8 stored entries a row.

`generate(rows, features, seed, base_seed=None)` returns
`(csr, y, codes, column_map)`: the `[rows, 700]` CSR matrix (int32
indices, sorted within a row), the label, the `[rows, 8]` int16 category
codes the matrix was coded from, and for every one-hot column which
categorical and which of its values it stands for (`categorical`,
`value`: int32 `[700]`; `cards`: the eight cardinalities). The codes and
the map are for the plain reference, which never sees a one-hot matrix.

What is assumed (the public file is not here; the source gives 700
columns, all categorical, and no more):

- eight categorical columns whose cardinalities sum to 700: month 12, day
  of month 31, day of week 7, departure hour 24, carrier 22, origin 298,
  destination 298, distance band 8;
- frequencies flat in month, day, weekday, hour and distance band;
  carrier Zipf-like (14.6% down to 1.8%); origin and destination each
  Zipf-like over their 64 largest (6.3% down to 0.46%, 85% of the rows)
  with the other 234 falling evenly from 0.078% to 0.05% of the rows. No
  category passes 18% of the rows, so every one-hot column is sparse by
  the program's own rule (`sparse_threshold` 0.8: non-default in under
  20% of the rows), and the rarest holds 25 rows of the 50,000 that the
  bundler samples;
- origins beyond the 64 largest fly only to the 64 largest destinations
  (small airports serve hubs), so a tail origin's column and a tail
  destination's are exclusive in EVERY row, and a bundle that mixes them
  loses nothing;
- the first 4,768 rows are planted: eight rows of every origin (a hub's
  destination any, a small airport's a hub) and eight of every
  destination, so that every column has both its bins at any size the
  benchmark or a rehearsal runs (at least 4,768 rows);
- the label: per-category effects of the eight columns, one origin x
  carrier interaction and logistic noise, each effect centred on its
  column's frequencies, so the classes are about balanced.

Every seed gets the SAME table (codes and labels) with its 700 one-hot
columns in an order drawn from the seed (`datagen.py` says why);
`base_seed` draws another table for `readings_sparse.py --fresh-data`.
"""
from __future__ import annotations

import os
import sys
from concurrent.futures import ThreadPoolExecutor

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import datagen  # noqa: E402

NAMES = ("month", "day_of_month", "day_of_week", "dep_hour", "carrier",
         "origin", "dest", "distance_band")
CARDS = (12, 31, 7, 24, 22, 298, 298, 8)
CARRIER, ORIGIN, DEST = 4, 5, 6
HUBS = 64                 # the largest origins, and destinations
HUB_SHARE = 0.85          # of the rows
TAIL_RAREST = 0.0005      # the rarest airport's share of the rows
PLANTED_EACH = 8
PLANTED = 2 * PLANTED_EACH * CARDS[ORIGIN]


def airport_shares():
    """Shares of the 298 airports: hubs, then the tail; each sums to its
    part of the rows."""
    hubs = 1.0 / (np.arange(1, HUBS + 1) + 4.0)
    hubs *= HUB_SHARE / hubs.sum()
    tail_n = CARDS[ORIGIN] - HUBS
    first = 2.0 * (1.0 - HUB_SHARE) / tail_n - TAIL_RAREST
    return hubs, np.linspace(first, TAIL_RAREST, tail_n)


def shares():
    """Each categorical's shares of the rows, by code."""
    out = [np.full(k, 1.0 / k) for k in CARDS]
    carrier = 1.0 / (np.arange(1, CARDS[CARRIER] + 1) + 2.0)
    out[CARRIER] = carrier / carrier.sum()
    out[ORIGIN] = out[DEST] = np.concatenate(airport_shares())
    return out


def _draw(rng, share, n):
    cdf = np.cumsum(share / share.sum())
    return np.minimum(np.searchsorted(cdf, rng.random(n), side="right"),
                      len(share) - 1)


def block_codes(rng, n):
    """[n, 8] codes of n rows drawn from the shares above, a tail origin
    flying to a hub alone."""
    share = shares()
    hubs, tail = airport_shares()
    codes = np.empty((n, len(CARDS)), np.int16)
    for c, s in enumerate(share):
        if c != DEST:
            codes[:, c] = _draw(rng, s, n)
    from_hub = codes[:, ORIGIN] < HUBS
    to_tail = from_hub & (rng.random(n) < (1.0 - HUB_SHARE) / HUB_SHARE)
    codes[:, DEST] = np.where(to_tail, HUBS + _draw(rng, tail, n),
                              _draw(rng, hubs, n))
    return codes


def planted_codes(codes):
    """Overwrite the airports of the first PLANTED rows: every origin and
    every destination PLANTED_EACH times, a tail airport's partner a hub."""
    k = CARDS[ORIGIN]
    i = np.arange(PLANTED_EACH * k)
    airport = (i % k).astype(np.int16)
    partner = np.where(airport >= HUBS, (i // k + airport) % HUBS,
                       codes[:len(i), DEST]).astype(np.int16)
    codes[:len(i), ORIGIN], codes[:len(i), DEST] = airport, partner
    rest = slice(len(i), 2 * len(i))
    partner = np.where(airport >= HUBS, (i // k + airport) % HUBS,
                       codes[rest, ORIGIN]).astype(np.int16)
    codes[rest, DEST], codes[rest, ORIGIN] = airport, partner


def effects(base_seed):
    """The label's tables: one effect a code, centred on the shares, and
    the origin x carrier interaction."""
    rng = np.random.default_rng([int(base_seed), 700])
    scale = (0.25, 0.1, 0.2, 0.5, 0.6, 0.5, 0.4, 0.3)
    tables = []
    for k, s, share in zip(CARDS, scale, shares()):
        t = rng.normal(0.0, s, k)
        tables.append((t - (t * share).sum()).astype(np.float32))
    inter = rng.normal(0.0, 0.5, (CARDS[ORIGIN], CARDS[CARRIER]))
    inter *= rng.random(inter.shape) < 0.25
    return tables, inter.astype(np.float32)


def generate(rows: int, features: int, seed: int, base_seed=None):
    import scipy.sparse as sp
    if features != sum(CARDS):
        raise ValueError(f"synth_expo codes {sum(CARDS)} one-hot columns, "
                         f"not {features}")
    if rows < PLANTED:
        raise ValueError(f"synth_expo plants {PLANTED} rows; asked for {rows}")
    base = datagen.BASE_SEED if base_seed is None else int(base_seed)
    per_row = len(CARDS)
    codes = np.empty((rows, per_row), np.int16)
    y = np.empty(rows, np.float32)
    indices = np.empty((rows, per_row), np.int32)
    # canonical column offsets[c] + v sits at column place[...] of the matrix
    offsets = np.concatenate([[0], np.cumsum(CARDS)[:-1]]).astype(np.int32)
    place = np.random.default_rng(seed).permutation(features).astype(np.int32)
    tables, inter = effects(base)
    starts = range(0, rows, datagen.BLOCK_ROWS)
    children = np.random.SeedSequence(base).spawn(len(starts))

    def fill(job):
        start, child = job
        rng = np.random.default_rng(child)
        n = min(datagen.BLOCK_ROWS, rows - start)
        block = block_codes(rng, n)
        if start == 0:
            planted_codes(block)
        score = inter[block[:, ORIGIN], block[:, CARRIER]]
        for c, table in enumerate(tables):
            score = score + table[block[:, c]]
        y[start:start + n] = score + rng.logistic(size=n) > 0.0
        codes[start:start + n] = block
        indices[start:start + n] = np.sort(
            place[block.astype(np.int32) + offsets[None, :]], axis=1)

    with ThreadPoolExecutor(max_workers=datagen.THREADS) as pool:
        list(pool.map(fill, zip(starts, children)))
    csr = sp.csr_matrix(
        (np.ones(rows * per_row, np.float32), indices.reshape(-1),
         np.arange(rows + 1, dtype=np.int64 if rows * per_row >= 2 ** 31
                   else np.int32) * per_row),
        shape=(rows, features))
    csr.has_sorted_indices = True
    categorical = np.empty(features, np.int32)
    value = np.empty(features, np.int32)
    for c, (off, k) in enumerate(zip(offsets, CARDS)):
        categorical[place[off:off + k]] = c
        value[place[off:off + k]] = np.arange(k)
    return csr, y, codes, {"categorical": categorical, "value": value,
                           "cards": CARDS}
