"""Chunk sources: the things the two-pass ingest pipeline streams.

The reference splits ingestion between a sampling/sketching
`DatasetLoader` and a streaming `PipelineReader` (src/io/dataset_loader.cpp
+ io/pipeline_reader... PAPER.md layer 3); the TPU-native equivalent is a
re-iterable `ChunkSource`: something that can stream `[rows, features]`
float64 blocks (plus an optional per-chunk label column) more than once.
Pass 1 streams it to sketch bin bounds, pass 2 streams it again to bin
rows into the landed matrix — neither pass ever holds the full raw
matrix.

Four concrete sources:
- `ArraySource`  — an in-memory matrix served as zero-copy row views
  (the Python-API path; "streaming" it buys the shared code path and the
  bit-identity contract, not memory);
- `SparseSource` — a scipy sparse matrix served as CSR row slices: the
  one source whose chunks are NOT dense blocks (`sparse = True`). Pass 1
  keeps its row samples sparse, pass 2 bins and bundles from the stored
  entries (`efb.FeatureGroups.bundle_sparse`), so the dense
  `[rows, features]` matrix never exists;
- `FileSource`   — a delimited text file parsed chunk-by-chunk
  (CSV/TSV via the io.parser float rules; the CLI / billion-row path);
- `ChunksSource` — a held list of row blocks, for callers whose data
  already arrives pre-chunked (e.g. record batches). Note the C API
  push-rows path does NOT stream through this: its contract admits
  out-of-order and retried chunks, so `capi._PendingDataset` assembles
  the full buffer first and rides `ArraySource`.
"""
from __future__ import annotations

import os
from typing import Iterator, List, Optional, Tuple

import numpy as np

from .. import log

DEFAULT_CHUNK_ROWS = 65536

#: (features_chunk [m, F] float64, labels_chunk [m] float64 or None)
Chunk = Tuple[np.ndarray, Optional[np.ndarray]]


class ChunkSource:
    """Re-iterable stream of row chunks.

    Contract: `num_rows()` and `num_cols()` are known before the first
    full stream (files count lines up-front — cheap relative to float
    parsing), and every call to `chunks()` yields the same rows in the
    same order.
    """

    has_labels: bool = False
    #: True where chunks() yields scipy CSR row slices, not dense blocks
    sparse: bool = False

    def num_rows(self) -> int:  # pragma: no cover — interface
        raise NotImplementedError

    def num_cols(self) -> int:  # pragma: no cover — interface
        raise NotImplementedError

    def chunks(self) -> Iterator[Chunk]:  # pragma: no cover — interface
        raise NotImplementedError

    def describe(self) -> dict:
        """Stable identity facts for the binary-cache fingerprint."""
        return {"kind": type(self).__name__,
                "rows": self.num_rows(), "cols": self.num_cols()}


class ArraySource(ChunkSource):
    """Stream an in-memory `[n, f]` matrix as row-slice views."""

    def __init__(self, data: np.ndarray,
                 chunk_rows: int = DEFAULT_CHUNK_ROWS):
        data = np.asarray(data)
        if data.ndim != 2:
            log.fatal("ArraySource needs a 2-dimensional matrix")
        # float64 once (copy only if the dtype differs), chunk views
        # after. float32 is kept: its consumers (the row gatherers of pass
        # 1, `BinMapper.values_to_bins` in pass 2) widen what they touch,
        # exactly, so a wide table is never held twice
        self.data = data if data.dtype == np.float32 \
            else data.astype(np.float64, copy=False)
        self.chunk_rows = max(1, int(chunk_rows))

    def num_rows(self) -> int:
        return self.data.shape[0]

    def num_cols(self) -> int:
        return self.data.shape[1]

    def chunks(self) -> Iterator[Chunk]:
        n = self.data.shape[0]
        for lo in range(0, n, self.chunk_rows):
            yield self.data[lo:lo + self.chunk_rows], None


#: rows a chunk of a sparse source holds unless the caller says: a row is
#: a handful of stored entries, and pass 2 pays a few numpy calls a
#: COLUMN a chunk, so a chunk must hold many entries of every column
SPARSE_CHUNK_ROWS = 1 << 20


class SparseSource(ChunkSource):
    """Stream a scipy sparse matrix as CSR row slices (CSC and the other
    formats are converted once). Duplicate entries are summed first, as
    `toarray()` would; explicit zeros and NaNs stay stored entries and
    are binned like any value."""

    sparse = True

    def __init__(self, data, chunk_rows: int = SPARSE_CHUNK_ROWS):
        data = data.tocsr()
        if not data.has_canonical_format:
            data = data.copy()
            data.sum_duplicates()
        self.data = data
        self.chunk_rows = max(1, int(chunk_rows))

    def num_rows(self) -> int:
        return self.data.shape[0]

    def num_cols(self) -> int:
        return self.data.shape[1]

    def chunks(self) -> Iterator[Chunk]:
        """Row slices that VIEW the matrix's entries (scipy's own `[lo:hi]`
        copies them, 30 ns an entry a pass): only the row pointers are
        made anew."""
        import scipy.sparse as sp
        whole = self.data
        n, f = whole.shape
        for lo in range(0, n, self.chunk_rows):
            hi = min(n, lo + self.chunk_rows)
            a, b = whole.indptr[lo], whole.indptr[hi]
            yield sp.csr_matrix(
                (whole.data[a:b], whole.indices[a:b],
                 whole.indptr[lo:hi + 1] - a), shape=(hi - lo, f)), None


class ChunksSource(ChunkSource):
    """Stream a held list of pre-chunked row blocks, in order."""

    def __init__(self, blocks: List[np.ndarray]):
        if not blocks:
            log.fatal("ChunksSource needs at least one row block")
        self.blocks = [np.asarray(b, np.float64) for b in blocks]
        cols = {b.shape[1] for b in self.blocks}
        if len(cols) != 1:
            log.fatal("ChunksSource blocks disagree on column count: %s"
                      % sorted(cols))

    def num_rows(self) -> int:
        return sum(b.shape[0] for b in self.blocks)

    def num_cols(self) -> int:
        return self.blocks[0].shape[1]

    def chunks(self) -> Iterator[Chunk]:
        for b in self.blocks:
            yield b, None


def _parse_lines(lines: List[str], delim: Optional[str]) -> np.ndarray:
    """Parse one chunk of data lines. Fast path: numpy's C tokenizer
    (np.loadtxt, ~5x the Python loop and bit-identical for well-formed
    floats); any chunk it rejects (na/?/empty tokens, ragged rows) falls
    back to the io.parser float rules line-by-line."""
    try:
        return np.loadtxt(lines, delimiter=delim, comments=None,
                          dtype=np.float64, ndmin=2)
    except ValueError:
        from ..io.parser import _parse_float
        return np.asarray(
            [[_parse_float(p) for p in
              (line.split(delim) if delim else line.split())]
             for line in lines], np.float64)


def iter_raw_file_chunks(path: str, has_header: bool = False,
                         chunk_rows: int = DEFAULT_CHUNK_ROWS,
                         delim: Optional[str] = None
                         ) -> Iterator[np.ndarray]:
    """Yield `[<=chunk_rows, cols]` float64 blocks of a delimited file,
    label column INCLUDED, without materializing the whole matrix (the
    shared parser under FileSource and parallel/loader.iter_parsed_chunks
    — reference: the two-round loaders' per-block
    ExtractFeaturesFromFile, dataset_loader.cpp:630-665)."""
    with open(path) as fh:
        if has_header:
            fh.readline()
        block: List[str] = []
        for line in fh:
            line = line.strip()
            if not line:
                continue
            block.append(line)
            if len(block) >= chunk_rows:
                yield _parse_lines(block, delim)
                block = []
        if block:
            yield _parse_lines(block, delim)


class FileSource(ChunkSource):
    """Parse a delimited data file chunk-by-chunk (reference: the
    two-round loaders' per-block ExtractFeaturesFromFile,
    dataset_loader.cpp:630-665). The label column is split out of every
    chunk; LibSVM needs the whole row set to size its dense matrix, so
    it is rejected here (the in-memory loader handles it)."""

    has_labels = True

    def __init__(self, path: str, chunk_rows: int = DEFAULT_CHUNK_ROWS,
                 has_header: bool = False, label_column: int = 0):
        from ..io.parser import detect_format
        self.path = path
        self.chunk_rows = max(1, int(chunk_rows))
        self.has_header = bool(has_header)
        self.label_column = int(label_column)
        fmt = detect_format(path, has_header)
        if fmt == "libsvm":
            raise ValueError(
                "streamed ingest supports delimited files only "
                "(libsvm rows need a global column count)")
        self._delim = "," if fmt == "csv" else None
        self._n: Optional[int] = None
        self._f: Optional[int] = None

    def _count(self) -> None:
        n = 0
        with open(self.path) as fh:
            if self.has_header:
                fh.readline()
            for line in fh:
                if line.strip():
                    n += 1
        self._n = n
        if self._f is None:
            for block, _ in self.chunks(max_chunks=1):
                self._f = block.shape[1]
            if self._f is None:
                log.fatal("Data file %s is empty" % self.path)

    def num_rows(self) -> int:
        if self._n is None:
            self._count()
        return int(self._n)

    def num_cols(self) -> int:
        if self._f is None:
            self._count()
        return int(self._f)

    def chunks(self, max_chunks: Optional[int] = None) -> Iterator[Chunk]:
        emitted = 0
        for raw in iter_raw_file_chunks(self.path, self.has_header,
                                        self.chunk_rows, self._delim):
            yield self._split(raw)
            emitted += 1
            if max_chunks is not None and emitted >= max_chunks:
                return

    def _split(self, raw: np.ndarray) -> Chunk:
        labels = raw[:, self.label_column].copy()
        feats = np.ascontiguousarray(
            np.delete(raw, self.label_column, axis=1))
        return feats, labels

    def describe(self) -> dict:
        st = os.stat(self.path)
        return {"kind": "file", "path": os.path.abspath(self.path),
                "size": int(st.st_size), "mtime_ns": int(st.st_mtime_ns),
                "has_header": self.has_header,
                "label_column": self.label_column}
