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
the dataset row count. A sparse source's chunks are CSR row slices: the
sampled rows are picked from them and kept sparse (CSC, so a column's
entries are one run); `binning.mappers_from_sample` and
`bin_sample_columns` read a column's stored entries where the dense path
reads the column, and give the same mappers and sample bins to the bit.
"""
from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np

from .. import telemetry
from ..binning import BinMapper, mappers_from_sample, sample_row_indices
from ..efb import EFB_SAMPLE_CNT, efb_sample_indices
from .sources import ChunkSource


def _is_sparse(chunk) -> bool:
    return hasattr(chunk, "indptr")


def chunk_bytes(chunk) -> int:
    """Bytes the chunk holds: a CSR slice's three arrays, or the block."""
    if _is_sparse(chunk):
        return chunk.data.nbytes + chunk.indices.nbytes + chunk.indptr.nbytes
    return chunk.nbytes


class _RowGatherer:
    """Collect the rows of a sorted global-index set from a chunk stream."""

    def __init__(self, indices: Optional[np.ndarray]):
        self.indices = indices  # None = gather every row
        self._cursor = 0
        self.blocks: List[np.ndarray] = []

    @staticmethod
    def _keep(rows):
        return rows if _is_sparse(rows) else np.array(rows, np.float64)

    def feed(self, global_lo: int, chunk) -> None:
        if self.indices is None:
            self.blocks.append(self._keep(chunk))
            return
        hi = global_lo + chunk.shape[0]
        c = self._cursor
        e = c + np.searchsorted(self.indices[c:], hi, side="left")
        if e > c:
            local = self.indices[c:e] - global_lo
            self.blocks.append(self._keep(chunk[local]))
            self._cursor = e

    def rows(self, num_cols: int):
        """The gathered rows: [s, num_cols] float64, or a scipy CSC
        matrix of that shape where the chunks were sparse."""
        if not self.blocks:
            return np.zeros((0, num_cols), np.float64)
        if _is_sparse(self.blocks[0]):
            import scipy.sparse as sp
            return sp.vstack(self.blocks, format="csc")
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
        self.efb_rows = efb_rows  # [s, num_cols] raw sampled rows (or CSC)
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
        global_lo += chunk.shape[0]
        telemetry.counter_add("ingest/pass1_rows", chunk.shape[0])
        telemetry.counter_add("ingest/bytes", chunk_bytes(chunk))
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


def zero_bin(mapper: BinMapper) -> int:
    """The bin a row without a stored entry holds: 0.0's."""
    return int(mapper.values_to_bins(np.zeros(1))[0])


def bin_sample_columns(sketch: SketchResult,
                       used: Sequence[int]) -> List[np.ndarray]:
    """Bin the gathered EFB sample rows for the used features — the
    columns `efb.find_groups_sampled` consumes. Row-wise binning
    commutes with row sampling, so these equal `bin(all)[sample]`."""
    rows = sketch.efb_rows
    if not _is_sparse(rows):
        return [sketch.mappers[j].values_to_bins(rows[:, j]) for j in used]
    cols = []
    for j in used:
        lo, hi = rows.indptr[j], rows.indptr[j + 1]
        col = np.full(rows.shape[0], zero_bin(sketch.mappers[j]), np.int32)
        col[rows.indices[lo:hi]] = \
            sketch.mappers[j].values_to_bins(rows.data[lo:hi])
        cols.append(col)
    return cols
