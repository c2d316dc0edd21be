"""The two-pass build driver: ChunkSource -> _InnerDataset.

Pass 1 (`sketch.sketch_pass`) streams chunks to gather the bin-finding
and EFB row samples and freezes per-feature bin bounds; pass 2 re-streams
chunks, bins each against the frozen bounds, bundles it (EFB) and lands
it into a preallocated buffer — a host matrix by default, per-device
shards under a data mesh (`landing.ShardedLanding`) when asked. The full
raw float matrix never exists: peak memory is
O(samples + chunk + landed bins). A sparse source (`SparseSource`) hands
CSR row slices: pass 2 then bins and bundles their stored entries alone
(`efb.FeatureGroups.bundle_sparse`), at a cost per entry, not per value.

Bit-identity contract: every decision that shapes the result (row
samples, bin bounds, bundle layout, per-row bins) is computed by the SAME
functions the in-memory `Dataset.from_numpy` path uses, on the same rows
— so streamed construction at ANY chunk size equals in-memory
construction bit-for-bit (tests/test_ingest.py holds the matrix).
"""
from __future__ import annotations

from typing import Callable, List, Optional, Sequence

import numpy as np

from .. import log, telemetry
from .sketch import bin_sample_columns, chunk_bytes, sketch_pass, zero_bin
from .sources import ArraySource, ChunkSource, DEFAULT_CHUNK_ROWS
from .landing import HostLanding

#: feature-count floor for parallel per-feature binning inside a chunk
_POOL_MIN_FEATURES = 4
_POOL_MIN_ROWS = 100_000


def build_inner(source: ChunkSource, *,
                max_bin: int = 255, min_data_in_bin: int = 3,
                min_split_data: int = 0,
                bin_construct_sample_cnt: int = 200000,
                data_random_seed: int = 1,
                categorical_features: Optional[Sequence[int]] = None,
                use_missing: bool = True, zero_as_missing: bool = False,
                feature_names: Optional[Sequence[str]] = None,
                label=None, weight=None, group=None, init_score=None,
                reference=None, mappers=None,
                enable_bundle: bool = True,
                max_conflict_rate: float = 0.0,
                sparse_threshold: float = 0.8,
                keep_raw: bool = False,
                landing_factory: Optional[Callable] = None):
    """Build an `_InnerDataset` by streaming `source` twice.

    `reference`: reuse a training set's mappers/groups (validation data).
    `mappers`: preset BinMappers (C API sampled-column contract).
    `landing_factory(num_rows, num_groups, dtype, max_group_bin) ->
    landing`: override where pass 2 lands rows (default: preallocated
    host matrix); `max_group_bin` is the widest group's bin count — what
    the trainer's row-layout plan keys on.
    """
    from ..dataset import Dataset as InnerDataset, Metadata

    f = source.num_cols()
    n = source.num_rows()
    ds = InnerDataset()
    ds.num_total_features = f
    ds.max_bin = max_bin if reference is None else reference.max_bin
    ds.feature_names = list(feature_names) if feature_names is not None \
        else [f"Column_{i}" for i in range(f)]
    telemetry.counter_add("ingest/builds", 1)
    phase = telemetry.Phases(telemetry.DATASET_SPANS)

    # ------------------------------------------------------------- pass 1
    if reference is not None:
        if f != reference.num_total_features:
            log.fatal("Validation data feature count (%d) != train (%d)"
                      % (f, reference.num_total_features))
        ds.mappers = reference.mappers
        ds.used_features = reference.used_features
        ds.groups = reference.groups
        sketch = None
    else:
        with phase("lgbm/dataset/sketch"):
            sketch = sketch_pass(
                source, max_bin=max_bin, min_data_in_bin=min_data_in_bin,
                min_split_data=min_split_data,
                bin_construct_sample_cnt=bin_construct_sample_cnt,
                seed=data_random_seed,
                categorical_features=categorical_features,
                use_missing=use_missing, zero_as_missing=zero_as_missing,
                mappers=list(mappers) if mappers is not None else None)
        ds.mappers = sketch.mappers
        ds.used_features = [j for j, m in enumerate(ds.mappers)
                            if not m.is_trivial]
        if not ds.used_features and mappers is None:
            log.warning("All features are trivial (constant); "
                        "model will predict a constant")

    used = ds.used_features
    num_bins = np.asarray([ds.mappers[j].num_bin for j in used], np.int32)
    default_bins = np.asarray([ds.mappers[j].default_bin for j in used],
                              np.int32)

    # ------------------------------------------------ EFB bundle layout
    if ds.groups is None:
        from ..efb import find_groups_sampled
        with phase("lgbm/dataset/groups"):
            sample_cols = bin_sample_columns(sketch, used)
            ds.groups = find_groups_sampled(
                sample_cols, default_bins, num_bins,
                enable_bundle=enable_bundle,
                max_conflict_rate=max_conflict_rate,
                sparse_threshold=sparse_threshold)
            del sample_cols
    if sketch is not None:
        sketch.efb_rows = None  # free the sample before landing rows

    # ------------------------------------------------------------- pass 2
    groups = ds.groups
    g_cnt = groups.num_groups if groups is not None else 0
    max_group_bin = int(groups.group_num_bin.max(initial=1)) \
        if groups is not None and g_cnt else 1
    out_dtype = groups.storage_dtype if groups is not None else np.uint8
    landing = (landing_factory(n, g_cnt, out_dtype, max_group_bin)
               if landing_factory else HostLanding(n, g_cnt, out_dtype))

    pool = None
    if len(used) > _POOL_MIN_FEATURES and n > _POOL_MIN_ROWS:
        from concurrent.futures import ThreadPoolExecutor
        pool = ThreadPoolExecutor(max_workers=8)

    labels_out = None if label is not None or not source.has_labels \
        else np.zeros(n, np.float64)
    # ArraySource already holds the matrix — copying chunks back out
    # would double peak memory for nothing
    collect_raw = keep_raw and not isinstance(source, ArraySource)
    raw_blocks: List[np.ndarray] = []
    # what a row without a stored entry holds in each used column
    zero_bins = np.asarray([zero_bin(ds.mappers[j]) for j in used],
                           np.int32) if source.sparse else None
    values = nonzeros = n * len(used)    # a dense source: every value
    if source.sparse:
        values = nonzeros = 0            # counted chunk by chunk
    try:
        with phase("lgbm/dataset/bin"):
            lo = 0
            for chunk, chunk_labels in source.chunks():
                m = chunk.shape[0]
                if used and source.sparse:
                    block, visited = groups.bundle_sparse(
                        chunk, used, ds.mappers, default_bins, zero_bins,
                        pool)
                    landing.write(lo, block)
                    values += int(chunk.nnz)
                    nonzeros += visited
                elif used:
                    def _bin_col(j):
                        return ds.mappers[j].values_to_bins(chunk[:, j])
                    if pool is not None:
                        cols = list(pool.map(_bin_col, used))
                    else:
                        cols = [_bin_col(j) for j in used]
                    landing.write(lo, groups.bundle_rows(cols, default_bins))
                if labels_out is not None and chunk_labels is not None:
                    labels_out[lo:lo + m] = chunk_labels
                if collect_raw:
                    raw_blocks.append(np.array(
                        chunk.toarray() if source.sparse else chunk,
                        np.float64))
                lo += m
                telemetry.counter_add("ingest/rows", m)
                telemetry.counter_add("ingest/bytes", chunk_bytes(chunk))
                telemetry.counter_add("ingest/chunks", 1)
            if lo != n:
                log.fatal("Source reported %d rows but streamed %d"
                          % (n, lo))
            landed = landing.finish()
    finally:
        if pool is not None:
            pool.shutdown()

    if isinstance(landed, np.ndarray):
        ds.binned = landed
    else:  # device-resident (ShardedLanding): row-padded jax.Array
        ds.binned = None
        ds.device_binned = landed
        ds.device_layout = landing.layout
        ds._num_rows = n

    if keep_raw:
        if isinstance(source, ArraySource):
            ds.raw = source.data
        elif raw_blocks:
            ds.raw = np.concatenate(raw_blocks, axis=0)

    # ----------------------------------------------------------- metadata
    ds.metadata = Metadata(n)
    if label is None and labels_out is not None:
        label = labels_out
    if label is not None:
        ds.metadata.set_label(label)
    if weight is not None:
        ds.metadata.set_weights(weight)
    if group is not None:
        ds.metadata.set_group(group)
    if init_score is not None:
        ds.metadata.set_init_score(init_score)
    ds.construct_record = telemetry.ConstructRecord(
        *(phase.seconds[name] for name in telemetry.DATASET_SPANS),
        values=values, nonzeros=nonzeros)
    if groups is not None:
        ds.efb_counters = telemetry.EfbCounters(
            features=len(used), groups=g_cnt,
            bundles=sum(1 for g in groups.groups if len(g) > 1),
            widest_group_bins=max_group_bin,
            sample_conflicts=groups.sample_conflicts)
    telemetry.record_construct(ds.construct_record)
    return ds


def build_from_numpy(data: np.ndarray,
                     chunk_rows: int = DEFAULT_CHUNK_ROWS, **kw):
    """In-memory matrix through the same two-pass pipeline."""
    return build_inner(ArraySource(data, chunk_rows), **kw)
