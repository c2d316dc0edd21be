"""Streaming ingest subsystem: chunked two-pass binning, binary dataset
cache, per-device row sharding.

The reproduction's analogue of the reference's `DatasetLoader` /
`PipelineReader` split (PAPER.md layer 3). One import surface:

- `sources` — re-iterable chunk streams (`ArraySource`, `FileSource`,
  `ChunksSource`, `SparseSource`);
- `sketch`  — pass 1: stream once, gather the deterministic bin-finding
  + EFB row samples, freeze per-feature quantile bin bounds (reusing
  binning.py's sampled bound-finding — the exact-small-data fast path);
- `build`   — pass 2 driver: re-stream, bin against the frozen bounds,
  land chunks straight into a preallocated host matrix or per-device
  shards (`landing.ShardedLanding`) without ever holding the raw float
  matrix;
- `cache`   — versioned, checksummed, memory-mapped binary dataset
  artifact: repeated runs skip parsing AND binning (pass 1+2 never run),
  mismatched fingerprints are refused;
- `landing` — the landing implementations (the row-layout plan they
  share with the trainer is `learner.schedule.plan_row_layout`).

Everything is instrumented: `ingest/*` spans and rows/bytes/chunks
counters flow into the telemetry registry and from there into the run
log.
"""
from __future__ import annotations

from .build import build_from_numpy, build_inner
from .cache import (CacheCorrupt, CacheMismatch,
                    FORMAT_VERSION as CACHE_FORMAT_VERSION,
                    MAGIC as CACHE_MAGIC, binning_params_fingerprint_fields,
                    ingest_fingerprint, load_cache, save_cache)
from .landing import HostLanding, ShardedLanding
from .sketch import SketchResult, sketch_pass
from .sources import (ArraySource, ChunkSource, ChunksSource,
                      DEFAULT_CHUNK_ROWS, FileSource, SparseSource)

__all__ = [
    "ArraySource", "CacheCorrupt", "CacheMismatch",
    "CACHE_FORMAT_VERSION", "CACHE_MAGIC",
    "ChunkSource", "ChunksSource", "DEFAULT_CHUNK_ROWS", "FileSource",
    "HostLanding", "ShardedLanding", "SketchResult", "SparseSource",
    "binning_params_fingerprint_fields", "build_from_numpy", "build_inner",
    "ingest_fingerprint", "load_cache", "save_cache",
    "sketch_pass",
]
