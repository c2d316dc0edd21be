"""Binned training matrix + metadata.

TPU-native re-design of the reference Dataset stack
(`include/LightGBM/dataset.h:280-570`, `src/io/dataset.cpp`):

Instead of per-feature-group Bin objects with dense/sparse/4-bit variants
(dense_bin.hpp / sparse_bin.hpp / ordered_sparse_bin.hpp), the whole
training set is ONE dense `uint8`/`int32` matrix `[num_data, num_features]`
of bin indices, resident in HBM for the entire run — the analogue of the
GPU learner's `Feature4` packed device matrix (gpu_tree_learner.cpp:385-441)
generalized to the native layout XLA tiles best. Sparse features are made
dense by binning (a bin index per row costs 1 byte regardless of sparsity);
Exclusive Feature Bundling further collapses mutually-exclusive sparse
columns (dataset.cpp:66-211) so width stays manageable.

Metadata mirrors `dataset.h:36-248`: label, weights, query boundaries,
query weights, init score.
"""
from __future__ import annotations

import struct
from typing import Dict, List, Optional, Sequence

import numpy as np

from . import log
from .binning import BIN_CATEGORICAL, BIN_NUMERICAL, BinMapper

_BINARY_MAGIC = b"lightgbm_tpu.dataset.v1\n"


class Metadata:
    """Labels / weights / query info (reference: Metadata, dataset.h:36-248)."""

    def __init__(self, num_data: int = 0):
        self.num_data = num_data
        self.label: Optional[np.ndarray] = None
        self.weights: Optional[np.ndarray] = None
        self.query_boundaries: Optional[np.ndarray] = None
        self.query_weights: Optional[np.ndarray] = None
        self.init_score: Optional[np.ndarray] = None

    def set_label(self, label: Sequence[float]) -> None:
        arr = np.asarray(label, dtype=np.float32).ravel()
        if self.num_data and len(arr) != self.num_data:
            log.fatal("Length of label (%d) != num_data (%d)" % (len(arr), self.num_data))
        self.label = arr
        self.num_data = len(arr)

    def set_weights(self, weights: Optional[Sequence[float]]) -> None:
        if weights is None:
            self.weights = None
            return
        arr = np.asarray(weights, dtype=np.float32).ravel()
        if self.num_data and len(arr) != self.num_data:
            log.fatal("Length of weights (%d) != num_data (%d)" % (len(arr), self.num_data))
        self.weights = arr
        self._update_query_weights()

    def set_group(self, group: Optional[Sequence[int]]) -> None:
        """`group` is per-query sizes; converted to boundaries
        (reference: Metadata::SetQuery, metadata.cpp)."""
        if group is None:
            self.query_boundaries = None
            self.query_weights = None
            return
        sizes = np.asarray(group, dtype=np.int64).ravel()
        bounds = np.zeros(len(sizes) + 1, dtype=np.int64)
        np.cumsum(sizes, out=bounds[1:])
        if self.num_data and bounds[-1] != self.num_data:
            log.fatal("Sum of query counts (%d) != num_data (%d)" % (bounds[-1], self.num_data))
        self.query_boundaries = bounds
        self._update_query_weights()

    def set_init_score(self, init_score: Optional[Sequence[float]]) -> None:
        if init_score is None:
            self.init_score = None
            return
        self.init_score = np.asarray(init_score, dtype=np.float64).ravel()

    def _update_query_weights(self) -> None:
        # mean of row weights per query (reference: metadata.cpp query weights)
        if self.weights is not None and self.query_boundaries is not None:
            nq = len(self.query_boundaries) - 1
            qw = np.zeros(nq, dtype=np.float32)
            for i in range(nq):
                s, e = self.query_boundaries[i], self.query_boundaries[i + 1]
                qw[i] = self.weights[s:e].mean() if e > s else 0.0
            self.query_weights = qw

    @property
    def num_queries(self) -> int:
        return 0 if self.query_boundaries is None else len(self.query_boundaries) - 1


class Dataset:
    """The binned training matrix (reference: Dataset, dataset.h:280-570).

    Attributes:
      binned:  `[num_data, num_features]` int32/uint8 bin indices (dense, HBM-ready)
      mappers: per-feature BinMapper
      metadata: labels / weights / queries
      feature_names: column names
      used_features: indices of non-trivial features in the ORIGINAL column
        space (trivial features are dropped from `binned`, as the reference
        drops them from feature groups, dataset.cpp:212-260)
    """

    def __init__(self):
        self.binned: Optional[np.ndarray] = None  # [num_data, num_groups]
        self.raw: Optional[np.ndarray] = None  # kept optionally for valid-set binning
        self.mappers: List[BinMapper] = []
        self.metadata = Metadata()
        self.feature_names: List[str] = []
        self.used_features: List[int] = []
        self.num_total_features: int = 0
        self.max_bin: int = 255
        self.groups = None  # efb.FeatureGroups over used features
        # device-landed alternative to `binned` (ingest.ShardedLanding):
        # a row-padded jax.Array sharded over the data mesh; `binned`
        # stays None and `_num_rows` carries the real row count
        self.device_binned = None
        self.device_layout = None
        self._num_rows: int = 0
        # telemetry.ConstructRecord: host seconds of the build by phase
        # (ingest/build.build_inner); None for a dataset made otherwise
        self.construct_record = None
        self.efb_counters = None  # telemetry.EfbCounters (ingest/build)

    # ------------------------------------------------------------------
    @classmethod
    def from_numpy(cls, data: np.ndarray, label: Optional[Sequence[float]] = None,
                   max_bin: int = 255, min_data_in_bin: int = 3,
                   min_split_data: int = 0,
                   bin_construct_sample_cnt: int = 200000,
                   data_random_seed: int = 1,
                   categorical_features: Optional[Sequence[int]] = None,
                   use_missing: bool = True, zero_as_missing: bool = False,
                   feature_names: Optional[Sequence[str]] = None,
                   weight: Optional[Sequence[float]] = None,
                   group: Optional[Sequence[int]] = None,
                   init_score: Optional[Sequence[float]] = None,
                   reference: Optional["Dataset"] = None,
                   keep_raw: bool = False,
                   enable_bundle: bool = True,
                   max_conflict_rate: float = 0.0,
                   sparse_threshold: float = 0.8,
                   mappers: Optional[List[BinMapper]] = None,
                   chunk_rows: int = 65536,
                   landing_factory=None) -> "Dataset":
        """Build a Dataset from a dense float matrix.

        When `reference` is given, its BinMappers are reused so validation
        data lands in the same bin space (reference: Dataset::CreateValid,
        dataset.cpp + python basic.py set_reference chain).

        Construction rides the streaming ingest subsystem
        (lightgbm_tpu/ingest): the matrix is streamed in row chunks
        through the same two-pass sketch-then-bin pipeline files use, so
        in-memory and streamed construction are one code path (and
        bit-identical by construction, tests/test_ingest.py).
        """
        data = np.asarray(data)
        if data.ndim != 2:
            log.fatal("Dataset data must be 2-dimensional")
        from .ingest import ArraySource, build_inner
        return build_inner(
            ArraySource(data, chunk_rows=chunk_rows),
            max_bin=max_bin, min_data_in_bin=min_data_in_bin,
            min_split_data=min_split_data,
            bin_construct_sample_cnt=bin_construct_sample_cnt,
            data_random_seed=data_random_seed,
            categorical_features=categorical_features,
            use_missing=use_missing, zero_as_missing=zero_as_missing,
            feature_names=feature_names, label=label, weight=weight,
            group=group, init_score=init_score, reference=reference,
            mappers=mappers, enable_bundle=enable_bundle,
            max_conflict_rate=max_conflict_rate,
            sparse_threshold=sparse_threshold, keep_raw=keep_raw,
            landing_factory=landing_factory)

    # ------------------------------------------------------------------
    @property
    def num_data(self) -> int:
        if self.binned is not None:
            return self.binned.shape[0]
        # device-landed matrix: the jax.Array is row-PADDED; the real
        # row count was recorded at landing time
        if self.device_binned is not None:
            return self._num_rows
        return 0

    @property
    def num_features(self) -> int:
        """Number of used (non-trivial) LOGICAL features (the stored
        `binned` width is num_groups <= num_features after EFB)."""
        return len(self.used_features)

    @property
    def num_groups(self) -> int:
        if self.binned is not None:
            return self.binned.shape[1]
        if self.device_binned is not None:
            return int(self.device_binned.shape[1])
        return 0

    @property
    def has_bundles(self) -> bool:
        return self.groups is not None and bool(self.groups.is_bundled.any())

    def feature_mapper(self, inner_idx: int) -> BinMapper:
        return self.mappers[self.used_features[inner_idx]]

    def feature_infos(self) -> List[str]:
        """Per-ORIGINAL-column info strings for the model text header
        (reference: Dataset::feature_infos, dataset.h:518-530)."""
        used = set(self.used_features)
        return [self.mappers[j].bin_info() if j in used else "none"
                for j in range(self.num_total_features)]

    def real_feature_index(self, inner_idx: int) -> int:
        return self.used_features[inner_idx]

    def num_bins_per_feature(self) -> np.ndarray:
        return np.asarray([self.feature_mapper(j).num_bin
                           for j in range(self.num_features)], dtype=np.int32)

    def max_num_bin(self) -> int:
        """Histogram width: max bins over stored GROUPS (feature-space
        scans use per-feature num_bin from feature_meta_arrays)."""
        if self.groups is not None and self.groups.num_groups:
            return int(self.groups.group_num_bin.max())
        nb = self.num_bins_per_feature()
        return int(nb.max()) if len(nb) else 1

    def feature_meta_arrays(self) -> Dict[str, np.ndarray]:
        """Static per-feature metadata consumed by the device split finder.

        Includes the EFB layout: `group` / `offset` locate each feature's
        bin slice inside the stored group columns; `is_bundled` marks
        features whose default-bin mass must be reconstructed from leaf
        totals (FixHistogram, dataset.cpp:747-767)."""
        f = self.num_features
        num_bin = np.zeros(f, dtype=np.int32)
        missing_type = np.zeros(f, dtype=np.int32)
        default_bin = np.zeros(f, dtype=np.int32)
        is_categorical = np.zeros(f, dtype=bool)
        for j in range(f):
            m = self.feature_mapper(j)
            num_bin[j] = m.num_bin
            missing_type[j] = m.missing_type
            default_bin[j] = m.default_bin
            is_categorical[j] = m.bin_type == BIN_CATEGORICAL
        if self.groups is not None and f:
            group = self.groups.group_of.astype(np.int32)
            offset = self.groups.offset_of.astype(np.int32)
            is_bundled = self.groups.is_bundled.copy()
        else:
            group = np.arange(f, dtype=np.int32)
            offset = np.zeros(f, dtype=np.int32)
            is_bundled = np.zeros(f, dtype=bool)
        return {"num_bin": num_bin, "missing_type": missing_type,
                "default_bin": default_bin, "is_categorical": is_categorical,
                "group": group, "offset": offset, "is_bundled": is_bundled}

    # ------------------------------------------------------------------
    # binary serialization (reference: Dataset::SaveBinaryFile, dataset.h:386,
    # DatasetLoader::LoadFromBinFile, dataset_loader.cpp:265-430).
    # Writes ride the ingest cache (versioned + checksummed + mmap-able,
    # ingest/cache.py); the v1 reader below stays for old artifacts.
    def save_binary(self, filename: str, fingerprint: str = "") -> None:
        from .ingest import save_cache
        save_cache(self, filename, fingerprint=fingerprint)

    @classmethod
    def load_binary(cls, filename: str, expected_fingerprint=None,
                    mmap_binned: bool = True) -> "Dataset":
        from .ingest import CACHE_MAGIC, load_cache
        with open(filename, "rb") as fh:
            head = fh.read(max(len(CACHE_MAGIC), len(_BINARY_MAGIC)))
        if head.startswith(CACHE_MAGIC):
            return load_cache(filename,
                              expected_fingerprint=expected_fingerprint,
                              mmap_binned=mmap_binned)
        return cls._load_binary_v1(filename)

    @classmethod
    def _load_binary_v1(cls, filename: str) -> "Dataset":
        import json
        ds = cls()
        with open(filename, "rb") as fh:
            magic = fh.read(len(_BINARY_MAGIC))
            if magic != _BINARY_MAGIC:
                log.fatal("%s is not a lightgbm_tpu binary dataset" % filename)
            (mlen,) = struct.unpack("<q", fh.read(8))
            meta = json.loads(fh.read(mlen).decode())
            ds.feature_names = meta["feature_names"]
            ds.used_features = [int(x) for x in meta["used_features"]]
            ds.num_total_features = int(meta["num_total_features"])
            ds.max_bin = int(meta["max_bin"])
            ds.mappers = [BinMapper.from_dict(d) for d in meta["mappers"]]
            if meta.get("groups") is not None:
                from .efb import FeatureGroups
                num_bins = np.asarray(
                    [ds.mappers[j].num_bin for j in ds.used_features], np.int32)
                ds.groups = FeatureGroups(
                    [[int(j) for j in g] for g in meta["groups"]], num_bins)
            arrays = []
            for _ in range(5):
                code = fh.read(1)
                arrays.append(None if code == b"N" else np.load(fh, allow_pickle=False))
        ds.binned, label, weights, qb, init = arrays
        ds.metadata = Metadata(0 if ds.binned is None else ds.binned.shape[0])
        if label is not None:
            ds.metadata.set_label(label)
        if weights is not None:
            ds.metadata.set_weights(weights)
        if qb is not None:
            ds.metadata.query_boundaries = qb
            ds.metadata._update_query_weights()
        if init is not None:
            ds.metadata.set_init_score(init)
        return ds
