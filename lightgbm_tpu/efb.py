"""Exclusive Feature Bundling (EFB).

TPU-native equivalent of the reference's feature-group construction
(`src/io/dataset.cpp:66-211` — `FindGroups` greedy conflict-bounded graph
coloring + `FastFeatureBundling`): mutually-(almost-)exclusive sparse
features share ONE stored column, so the dense `[rows, groups]` uint8
matrix stays narrow on Bosch/Expo-class sparse data. This is the entire
sparse story of the TPU design (dense bins + EFB replace the reference's
sparse/ordered bin variants, SURVEY.md §7).

Layout per multi-feature group (g):
  bin 0                                  = every member feature at default
  bins [offset_j, offset_j + num_bin_j)  = feature j's own bin space,
                                           shifted by offset_j
A row stores the bin of its (at most one, up to the tolerated conflict
rate) non-default member; on conflict the later feature in the group wins
— the same lossy tolerance the reference accepts (max_conflict_rate,
dataset.cpp:99-125). Feature j's histogram is the group histogram slice
[offset_j : offset_j + num_bin_j); its default-bin mass is reconstructed
from leaf totals (the FixHistogram trick, dataset.cpp:747-767).

Single-feature groups store the feature's bins unshifted (offset 0) and
need no reconstruction.

A sparse source (scipy CSR through `ingest.SparseSource`) is bundled from
its stored entries alone (`FeatureGroups.bundle_sparse`): a row's group
value starts at the group's all-default bin and each stored entry writes
its member's shifted bin, so the work is per entry, not per value, and the
dense matrix is never formed. The result is `bundle_rows`'s to the bit.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

from . import log

DEFAULT_MAX_GROUP_BINS = 256  # uint8 storage; reference GPU has the same cap


def pick_max_group_bins(num_bins: np.ndarray) -> int:
    """Bundle-capacity heuristic. The reference CPU bundles without a bin
    cap (uint16/uint32 Bin variants); its GPU caps at 256. We pay for the
    histogram width of the WIDEST group on every group (padded one-hot), so
    the cap trades bundle count against padding waste: allow ~16 features
    per bundle, minimum 256 (uint8), capped at 2048 (uint16)."""
    if len(num_bins) == 0:
        return DEFAULT_MAX_GROUP_BINS
    return int(max(DEFAULT_MAX_GROUP_BINS,
                   min(2048, 16 * (int(num_bins.max()) + 1))))


class FeatureGroups:
    """Static feature->group layout.

    Attributes (F = number of used features, G = number of groups):
      group_of:    [F] group index of each feature
      offset_of:   [F] bin offset of the feature inside its group
      is_bundled:  [F] True when the feature shares its group (histogram
                   default-bin mass must be reconstructed)
      group_num_bin: [G] total bins of each group
      groups:      list of member-feature lists
    """

    def __init__(self, groups: List[List[int]], num_bins: np.ndarray):
        f = int(num_bins.shape[0])
        self.groups = groups
        self.group_of = np.zeros(f, np.int32)
        self.offset_of = np.zeros(f, np.int32)
        self.is_bundled = np.zeros(f, bool)
        self.group_num_bin = np.zeros(len(groups), np.int32)
        #: conflicting sample rows the grouping accepted (within
        #: max_conflict_rate); 0 for a layout not found from a sample
        self.sample_conflicts = 0
        for g, members in enumerate(groups):
            if len(members) == 1:
                j = members[0]
                self.group_of[j] = g
                self.offset_of[j] = 0
                self.group_num_bin[g] = num_bins[j]
                continue
            off = 1  # bin 0 = all members at default
            for j in members:
                self.group_of[j] = g
                self.offset_of[j] = off
                self.is_bundled[j] = True
                off += int(num_bins[j])
            self.group_num_bin[g] = off

    @property
    def num_groups(self) -> int:
        return len(self.groups)

    @property
    def storage_dtype(self):
        """uint8 while the widest group fits it, else uint16."""
        return np.uint8 if int(self.group_num_bin.max(initial=1)) <= 256 \
            else np.uint16

    def to_dict(self) -> dict:
        return {"groups": [[int(j) for j in g] for g in self.groups],
                "num_bins": [0] * 0}  # groups are sufficient to rebuild

    # ------------------------------------------------------------------
    def bundle_rows(self, feature_bins: List[np.ndarray],
                    default_bins: np.ndarray) -> np.ndarray:
        """Build the [N, G] group-bin matrix from per-feature bin columns.

        feature_bins[j]: [N] integer bins of used feature j.
        """
        n = len(feature_bins[0]) if feature_bins else 0
        dtype = self.storage_dtype
        out = np.zeros((n, self.num_groups), dtype)
        for g, members in enumerate(self.groups):
            if len(members) == 1:
                j = members[0]
                out[:, g] = feature_bins[j].astype(dtype)
                continue
            col = np.zeros(n, np.int32)
            for j in members:
                nz = feature_bins[j] != default_bins[j]
                # conflict rule: later member wins (bounded by
                # max_conflict_rate at grouping time)
                col[nz] = self.offset_of[j] + feature_bins[j][nz]
            out[:, g] = col.astype(dtype)
        return out

    def bundle_sparse(self, chunk, used, mappers, default_bins: np.ndarray,
                      zero_bins: np.ndarray, pool=None):
        """`bundle_rows` for a CSR row slice, from its stored entries.

        chunk: scipy CSR [m, num_total_features]; used[u]: the column of
        used feature u; zero_bins[u]: the bin `mappers` give 0.0, what
        every row WITHOUT an entry holds. Returns ([m, G] group bins,
        entries visited). The slice is turned to CSC once (O(entries)),
        so each column's entries are one run; a group's members are
        written in their order, the later winning, as `bundle_rows` does.
        A member whose zero is not its default bin (a categorical column
        without a 0 category) is non-default in EVERY row: it alone is
        laid out as a whole column of the chunk."""
        m = chunk.shape[0]
        dtype = self.storage_dtype
        csc = chunk.tocsc()
        indptr, entry_rows, entry_vals = csc.indptr, csc.indices, csc.data
        out = np.zeros((m, self.num_groups), dtype)

        def entries(u):
            lo, hi = indptr[used[u]], indptr[used[u] + 1]
            rows = entry_rows[lo:hi]
            return rows, mappers[used[u]].values_to_bins(entry_vals[lo:hi])

        def fill(g):
            members = self.groups[g]
            if len(members) == 1:
                u = members[0]
                rows, bins = entries(u)
                col = np.full(m, zero_bins[u], dtype)
                col[rows] = bins
                out[:, g] = col
                return
            col = np.zeros(m, np.int32)
            for u in members:
                rows, bins = entries(u)
                if zero_bins[u] != default_bins[u]:
                    whole = np.full(m, zero_bins[u], np.int32)
                    whole[rows] = bins
                    nz = whole != default_bins[u]
                    col[nz] = self.offset_of[u] + whole[nz]
                    continue
                nz = bins != default_bins[u]
                col[rows[nz]] = self.offset_of[u] + bins[nz]
            out[:, g] = col

        if pool is not None:
            list(pool.map(fill, range(self.num_groups)))
        else:
            for g in range(self.num_groups):
                fill(g)
        return out, int(np.diff(indptr)[used].sum())


EFB_SAMPLE_CNT = 50_000


def efb_sample_indices(n: int, sample_cnt: int = EFB_SAMPLE_CNT,
                       seed: int = 1) -> Optional[np.ndarray]:
    """The sorted row indices `find_groups` samples to estimate feature
    exclusivity, or None when every row is used (n <= sample_cnt). Shared
    with the streaming ingest subsystem (lightgbm_tpu/ingest), which
    gathers exactly these rows from a chunk stream so streamed and
    in-memory construction agree on the bundle layout bit-for-bit."""
    if n <= sample_cnt:
        return None
    rng = np.random.RandomState(seed)
    sample = rng.choice(n, size=sample_cnt, replace=False)
    sample.sort()
    return sample


def find_groups(feature_bins: List[np.ndarray], default_bins: np.ndarray,
                num_bins: np.ndarray, *, enable_bundle: bool = True,
                max_conflict_rate: float = 0.0,
                sparse_threshold: float = 0.8,
                sample_cnt: int = EFB_SAMPLE_CNT, seed: int = 1,
                max_group_bins: Optional[int] = None) -> FeatureGroups:
    """Greedy conflict-bounded grouping (reference: FindGroups,
    dataset.cpp:66-139).

    Features whose sampled non-default rate exceeds 1 - sparse_threshold
    are dense: each gets its own group. Sparse features are ordered by
    non-default count (descending) and greedily placed into the first
    group whose accumulated conflict stays within max_conflict_rate * n
    and whose bin capacity stays within MAX_GROUP_BINS.
    """
    f = len(feature_bins)
    if f == 0:
        return FeatureGroups([], num_bins)
    n = len(feature_bins[0])
    if not enable_bundle or f == 1:
        return FeatureGroups([[j] for j in range(f)], num_bins)
    idx = efb_sample_indices(n, sample_cnt, seed)
    sampled = feature_bins if idx is None else \
        [feature_bins[j][idx] for j in range(f)]
    return find_groups_sampled(sampled, default_bins, num_bins,
                               enable_bundle=enable_bundle,
                               max_conflict_rate=max_conflict_rate,
                               sparse_threshold=sparse_threshold,
                               max_group_bins=max_group_bins)


def find_groups_sampled(sample_bins: List[np.ndarray],
                        default_bins: np.ndarray, num_bins: np.ndarray, *,
                        enable_bundle: bool = True,
                        max_conflict_rate: float = 0.0,
                        sparse_threshold: float = 0.8,
                        max_group_bins: Optional[int] = None
                        ) -> FeatureGroups:
    """The grouping core over an ALREADY-SAMPLED set of binned rows
    (`sample_bins[j]` holds feature j's bins for the sampled rows only).
    `find_groups` is the in-memory wrapper; the ingest pass-1 sketch
    calls this directly with the rows `efb_sample_indices` named."""
    f = len(sample_bins)
    if f == 0:
        return FeatureGroups([], num_bins)
    if not enable_bundle or f == 1:
        return FeatureGroups([[j] for j in range(f)], num_bins)
    if max_group_bins is None:
        max_group_bins = pick_max_group_bins(num_bins)

    s = len(sample_bins[0])

    nz_masks = [sample_bins[j] != default_bins[j] for j in range(f)]
    nz_counts = np.asarray([int(m.sum()) for m in nz_masks])

    dense = nz_counts > (1.0 - sparse_threshold) * s
    budget = max_conflict_rate * s

    # bigger-nonzero-count-first ordering (the reference tries natural and
    # count order and keeps the smaller grouping, dataset.cpp:174-178; the
    # count order wins in practice)
    order = np.argsort(-nz_counts, kind="stable")
    groups: List[List[int]] = []
    gmasks: List[np.ndarray] = []
    gconflict: List[float] = []
    gbins: List[int] = []
    gnz: List[int] = []
    for j in order:
        j = int(j)
        if dense[j]:
            groups.append([j])
            gmasks.append(None)
            gconflict.append(np.inf)
            gbins.append(int(num_bins[j]))
            gnz.append(s)
            continue
        placed = False
        for g in range(len(groups)):
            if gmasks[g] is None:
                continue
            if gbins[g] + int(num_bins[j]) > max_group_bins:
                continue
            # exclusivity budget (dataset.cpp:89-91): the group's total
            # non-default rows may not exceed the sample (+ tolerated error)
            if gnz[g] + int(nz_counts[j]) > s + budget:
                continue
            overlap = int((gmasks[g] & nz_masks[j]).sum())
            if gconflict[g] + overlap <= budget:
                groups[g].append(j)
                gmasks[g] = gmasks[g] | nz_masks[j]
                gconflict[g] += overlap
                gbins[g] += int(num_bins[j])
                gnz[g] += int(nz_counts[j]) - overlap
                placed = True
                break
        if not placed:
            groups.append([j])
            gmasks.append(nz_masks[j].copy())
            gconflict.append(0.0)
            gbins.append(1 + int(num_bins[j]))
            gnz.append(int(nz_counts[j]))

    # demote 1-member "bundles" to plain groups (no reserved bin 0)
    fg = FeatureGroups(groups, num_bins)
    fg.sample_conflicts = int(sum(
        c for c, g in zip(gconflict, groups) if len(g) > 1))
    n_bundled = sum(1 for g in groups if len(g) > 1)
    if n_bundled:
        log.info("EFB bundled %d features into %d groups "
                 "(%d multi-feature bundles)",
                 f, fg.num_groups, n_bundled)
    return fg
