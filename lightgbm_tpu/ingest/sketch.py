"""Pass 1: stream chunks once, gather the two row samples, sketch bins.

The in-memory construction (`dataset.Dataset.from_numpy`) samples rows
twice: `binning.sample_row_indices` rows for quantile bin finding and
`efb.efb_sample_indices` rows for the EFB exclusivity estimate. Pass 1
gathers EXACTLY those global rows from the chunk stream (both index sets
are deterministic in (n, seed)), so the sketched bin bounds and bundle
layout are bit-identical to the in-memory path — the sampled
bound-finding of binning.py IS the exact-small-data fast path (when
n <= bin_construct_sample_cnt the "sample" is every row, bounded by the
sample cap, never by the dataset).

Peak memory: O(bin_sample + efb_sample) rows of float64 — independent of
the dataset row count.
"""
from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np

from .. import telemetry
from ..binning import BinMapper, mappers_from_sample, sample_row_indices
from ..efb import EFB_SAMPLE_CNT, efb_sample_indices
from .sources import ChunkSource


class _RowGatherer:
    """Collect the rows of a sorted global-index set from a chunk stream."""

    def __init__(self, indices: Optional[np.ndarray]):
        self.indices = indices  # None = gather every row
        self._cursor = 0
        self.blocks: List[np.ndarray] = []

    def feed(self, global_lo: int, chunk: np.ndarray) -> None:
        if self.indices is None:
            self.blocks.append(np.array(chunk, np.float64))
            return
        hi = global_lo + len(chunk)
        c = self._cursor
        e = c + np.searchsorted(self.indices[c:], hi, side="left")
        if e > c:
            local = self.indices[c:e] - global_lo
            self.blocks.append(np.array(chunk[local], np.float64))
            self._cursor = e

    def rows(self, num_cols: int) -> np.ndarray:
        if not self.blocks:
            return np.zeros((0, num_cols), np.float64)
        return np.concatenate(self.blocks, axis=0)


class SketchResult:
    """Everything pass 2 needs: frozen mappers + the raw EFB sample rows
    (binned lazily once the used-feature set is known)."""

    def __init__(self, num_rows: int, num_cols: int,
                 mappers: List[BinMapper], efb_rows: np.ndarray,
                 total_sample_cnt: int):
        self.num_rows = num_rows
        self.num_cols = num_cols
        self.mappers = mappers
        self.efb_rows = efb_rows  # [s, num_cols] raw sampled rows
        self.total_sample_cnt = total_sample_cnt


def sketch_pass(source: ChunkSource, *, max_bin: int,
                min_data_in_bin: int = 3, min_split_data: int = 0,
                bin_construct_sample_cnt: int = 200000, seed: int = 1,
                categorical_features: Optional[Sequence[int]] = None,
                use_missing: bool = True, zero_as_missing: bool = False,
                efb_sample_cnt: int = EFB_SAMPLE_CNT,
                mappers: Optional[List[BinMapper]] = None) -> SketchResult:
    """Stream the source once, return frozen BinMappers + the EFB sample.

    With `mappers` preset (the C API sampled-column contract: bounds come
    from a caller-provided sample) the bin-sample gather is skipped and
    only the EFB rows are collected.
    """
    n = source.num_rows()
    f = source.num_cols()
    bin_gather = None if mappers is not None else _RowGatherer(
        sample_row_indices(n, bin_construct_sample_cnt, seed))
    efb_gather = _RowGatherer(efb_sample_indices(n, efb_sample_cnt, seed))

    global_lo = 0
    for chunk, _labels in source.chunks():
        if chunk.shape[1] != f:
            from .. import log
            log.fatal("Chunk at row %d has %d columns, expected %d"
                      % (global_lo, chunk.shape[1], f))
        if bin_gather is not None:
            bin_gather.feed(global_lo, chunk)
        efb_gather.feed(global_lo, chunk)
        global_lo += len(chunk)
        telemetry.counter_add("ingest/pass1_rows", len(chunk))
        telemetry.counter_add("ingest/bytes", chunk.nbytes)
        telemetry.counter_add("ingest/chunks", 1)
    if global_lo != n:
        from .. import log
        log.fatal("Source reported %d rows but streamed %d"
                  % (n, global_lo))
    if mappers is None:
        sample = bin_gather.rows(f)
        total = n if bin_gather.indices is None \
            else int(len(bin_gather.indices))
        mappers = mappers_from_sample(
            sample, total, max_bin, min_data_in_bin, min_split_data,
            categorical_features, use_missing, zero_as_missing)
        del sample
    total_sample = n if bin_gather is None or bin_gather.indices is None \
        else int(len(bin_gather.indices))

    return SketchResult(n, f, mappers, efb_gather.rows(f), total_sample)


def bin_sample_columns(sketch: SketchResult,
                       used: Sequence[int]) -> List[np.ndarray]:
    """Bin the gathered EFB sample rows for the used features — the
    columns `efb.find_groups_sampled` consumes. Row-wise binning
    commutes with row sampling, so these equal `bin(all)[sample]`."""
    return [sketch.mappers[j].values_to_bins(sketch.efb_rows[:, j])
            for j in used]
