"""Streaming ingest subsystem (lightgbm_tpu/ingest).

The contract under test: streamed construction — any chunk size, file or
array source, host or per-device landing, or a binary-cache round trip —
is BIT-IDENTICAL to in-memory construction: same binned matrix, same bin
bounds, same EFB bundles, same trained trees, same eval history."""
import ctypes
import os

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import capi, telemetry
from lightgbm_tpu.dataset import Dataset as InnerDataset
from lightgbm_tpu.ingest import (ArraySource, CacheMismatch, ChunksSource,
                                 FileSource, build_inner)


def _train(ds_kwargs, params, X, y, rounds=5):
    """Train + eval history on a fresh Dataset built with ds_kwargs."""
    evals = {}
    ds = lgb.Dataset(X, label=y, **ds_kwargs)
    booster = lgb.train(dict(params), ds, num_boost_round=rounds,
                        valid_sets=[ds.create_valid(X, label=y)],
                        valid_names=["v"], evals_result=evals,
                        verbose_eval=False)
    return booster.model_to_string(), evals


def _datasets():
    rng = np.random.RandomState(0)
    n = 2200
    out = {}
    # binary, dense + zeros (zero bin / MISSING_ZERO paths)
    Xb = rng.randn(n, 6)
    Xb[rng.rand(n, 6) < 0.3] = 0.0
    out["binary"] = (Xb, (Xb[:, 0] + 0.5 * Xb[:, 1] > 0).astype(float),
                     {"objective": "binary"}, {})
    # multiclass
    Xm = rng.randn(n, 5)
    out["multiclass"] = (Xm, (np.abs(Xm[:, 0]).astype(int) % 3).astype(
        float), {"objective": "multiclass", "num_class": 3}, {})
    # categorical
    Xc = rng.randn(n, 5)
    Xc[:, 1] = rng.randint(0, 12, n)
    Xc[:, 3] = rng.randint(0, 5, n)
    out["categorical"] = (Xc, (Xc[:, 0] > 0).astype(float),
                          {"objective": "binary"},
                          {"categorical_feature": [1, 3]})
    # EFB: mutually-exclusive sparse one-hot blocks -> real bundles
    Xe = np.zeros((n, 12))
    hot = rng.randint(0, 6, n)
    Xe[np.arange(n), hot] = rng.rand(n) + 0.5
    dense = rng.randn(n, 6)
    dense[rng.rand(n, 6) < 0.5] = 0.0
    Xe[:, 6:] = dense
    out["efb"] = (Xe, (Xe[:, 6] > 0).astype(float),
                  {"objective": "binary"}, {})
    return out


@pytest.mark.parametrize("name", ["binary", "multiclass", "categorical",
                                  "efb"])
def test_chunked_construction_bit_identity(name):
    """Streamed construction at chunk sizes {1, 7, 64, >N} == in-memory
    (single-chunk) construction: binned matrix, mappers, bundles, and
    the trained trees + eval history all identical."""
    X, y, params, ds_kwargs = _datasets()[name]
    params = dict(params, num_leaves=15, min_data_in_leaf=5, verbose=-1)
    n = X.shape[0]
    base_kwargs = dict(ds_kwargs, params={"tpu_ingest_chunk_rows": 10 * n,
                                          **ds_kwargs.get("params", {})})
    ref_model, ref_evals = _train(base_kwargs, params, X, y)
    cats = ds_kwargs.get("categorical_feature")
    ref_inner = InnerDataset.from_numpy(
        X, y, max_bin=255, chunk_rows=10 * n,
        categorical_features=cats if isinstance(cats, list) else None)
    for chunk in (1, 7, 64):
        kw = dict(ds_kwargs,
                  params={"tpu_ingest_chunk_rows": chunk,
                          **ds_kwargs.get("params", {})})
        model, evals = _train(kw, params, X, y)
        assert model == ref_model, f"{name}: trees diverged at chunk={chunk}"
        assert evals == ref_evals, f"{name}: evals diverged at chunk={chunk}"
        inner = InnerDataset.from_numpy(
            X, y, max_bin=255, chunk_rows=chunk,
            categorical_features=cats if isinstance(cats, list) else None)
        np.testing.assert_array_equal(inner.binned, ref_inner.binned)
        assert [m.to_dict() for m in inner.mappers] == \
            [m.to_dict() for m in ref_inner.mappers]
        assert inner.groups.groups == ref_inner.groups.groups


def test_efb_bundles_actually_formed():
    """The EFB dataset above must exercise real bundling, or the matrix
    case is vacuous."""
    X, _, _, _ = _datasets()["efb"]
    inner = InnerDataset.from_numpy(X, None, max_bin=255)
    assert inner.has_bundles


def test_file_stream_matches_in_memory(tmp_path):
    """FileSource streaming == load-file-then-bin (the tpu_ingest=false
    path), both for the dataset bytes and the trained model."""
    rng = np.random.RandomState(3)
    n, f = 3000, 5
    X = rng.randn(n, f)
    X[rng.rand(n, f) < 0.2] = 0.0
    y = X[:, 0] * 2 + X[:, 1] + 0.1 * rng.randn(n)
    path = str(tmp_path / "d.tsv")
    np.savetxt(path, np.column_stack([y, X]), delimiter="\t", fmt="%.8g")
    params = {"objective": "regression", "num_leaves": 15, "verbose": -1}

    streamed = lgb.Dataset(path, params={"tpu_ingest_chunk_rows": 257})
    legacy = lgb.Dataset(path, params={"tpu_ingest": False})
    np.testing.assert_array_equal(streamed._lazy_init().binned,
                                  legacy._lazy_init().binned)
    np.testing.assert_allclose(streamed._lazy_init().metadata.label,
                               legacy._lazy_init().metadata.label)
    m1 = lgb.train(dict(params), streamed,
                   num_boost_round=5).model_to_string()
    m2 = lgb.train(dict(params), legacy,
                   num_boost_round=5).model_to_string()
    assert m1 == m2


def test_chunk_source_and_array_source_agree():
    rng = np.random.RandomState(5)
    X = rng.randn(1500, 4)
    blocks = [X[:400], X[400:401], X[401:1500]]
    a = build_inner(ArraySource(X, chunk_rows=333), max_bin=63)
    b = build_inner(ChunksSource(blocks), max_bin=63)
    np.testing.assert_array_equal(a.binned, b.binned)


# ---------------------------------------------------------------------------
# binary dataset cache
# ---------------------------------------------------------------------------

def test_cache_round_trip_trains_identically(tmp_path):
    rng = np.random.RandomState(2)
    n = 2500
    X = rng.randn(n, 6)
    y = (X[:, 0] > 0).astype(float)
    params = {"objective": "binary", "num_leaves": 15, "verbose": -1}
    ds = lgb.Dataset(X, label=y)
    ref = lgb.train(dict(params), ds, num_boost_round=5).model_to_string()

    path = str(tmp_path / "c.bin")
    ds._inner.save_binary(path, fingerprint="fp-test")
    loaded = InnerDataset.load_binary(path, expected_fingerprint="fp-test")
    np.testing.assert_array_equal(np.asarray(loaded.binned),
                                  ds._inner.binned)
    from lightgbm_tpu.basic import Dataset as PyDataset
    model = lgb.train(dict(params), PyDataset._from_inner(loaded),
                      num_boost_round=5).model_to_string()
    assert model == ref


def test_cache_skips_passes_and_counts_hit(tmp_path):
    """The cache-hit path must never run pass 1/2 — verified through the
    ingest telemetry counters, the same observable the run log gets."""
    rng = np.random.RandomState(4)
    X = rng.randn(1200, 4)
    inner = InnerDataset.from_numpy(X, (X[:, 0] > 0).astype(float))
    path = str(tmp_path / "c2.bin")
    inner.save_binary(path)

    telemetry.enable(True)
    telemetry.reset()
    try:
        loaded = InnerDataset.load_binary(path)
        reg = telemetry.registry()
        counters = {c.name: c.value for c in reg.counters.values()}
        assert counters.get("ingest/cache_hit") == 1
        assert "ingest/chunks" not in counters  # no pass streamed
        assert not any(name in reg.phases
                       for name in telemetry.DATASET_SPANS)
        np.testing.assert_array_equal(np.asarray(loaded.binned),
                                      inner.binned)
    finally:
        telemetry.reset()
        telemetry.enable(False)


def test_cache_refuses_mismatched_fingerprint(tmp_path):
    rng = np.random.RandomState(6)
    inner = InnerDataset.from_numpy(rng.randn(500, 3), None)
    path = str(tmp_path / "c3.bin")
    inner.save_binary(path, fingerprint="the-real-build")
    with pytest.raises(CacheMismatch):
        InnerDataset.load_binary(path,
                                 expected_fingerprint="something-else")
    # no expectation -> loads (checksums still verified)
    InnerDataset.load_binary(path)


def test_cache_detects_corruption(tmp_path):
    rng = np.random.RandomState(8)
    inner = InnerDataset.from_numpy(rng.randn(800, 3), None)
    path = str(tmp_path / "c4.bin")
    inner.save_binary(path)
    with open(path, "r+b") as fh:
        fh.seek(-16, os.SEEK_END)
        fh.write(b"\xff" * 8)
    with pytest.raises(Exception, match="checksum"):
        InnerDataset.load_binary(path)


def test_cache_v1_artifacts_still_load(tmp_path):
    """Old v1 binaries keep loading through the legacy reader."""
    rng = np.random.RandomState(9)
    X = rng.randn(700, 4)
    inner = InnerDataset.from_numpy(X, (X[:, 0] > 0).astype(float))
    path = str(tmp_path / "v1.bin")
    # write the v1 format by hand (the old save_binary body)
    import json
    import struct
    from lightgbm_tpu.dataset import _BINARY_MAGIC
    meta = {"feature_names": inner.feature_names,
            "used_features": inner.used_features,
            "num_total_features": inner.num_total_features,
            "max_bin": inner.max_bin,
            "mappers": [m.to_dict() for m in inner.mappers],
            "groups": [[int(j) for j in g] for g in inner.groups.groups]}
    blob = json.dumps(meta).encode()
    with open(path, "wb") as fh:
        fh.write(_BINARY_MAGIC)
        fh.write(struct.pack("<q", len(blob)))
        fh.write(blob)
        for arr, code in [(inner.binned, b"B"),
                          (inner.metadata.label, b"L"), (None, b"W"),
                          (None, b"Q"), (None, b"I")]:
            if arr is None:
                fh.write(b"N")
                continue
            fh.write(code)
            np.save(fh, np.asarray(arr), allow_pickle=False)
    loaded = InnerDataset.load_binary(path)
    np.testing.assert_array_equal(loaded.binned, inner.binned)
    np.testing.assert_allclose(loaded.metadata.label, inner.metadata.label)


# ---------------------------------------------------------------------------
# per-device row sharding
# ---------------------------------------------------------------------------

def test_device_sharded_landing_bit_identity():
    """tpu_ingest_device_shards lands the binned matrix as an 8-way
    sharded jax.Array (conftest's virtual CPU mesh) and the data-parallel
    trainer consumes it directly — trees identical to the host path."""
    rng = np.random.RandomState(11)
    n = 4000
    X = rng.randn(n, 5)
    y = X[:, 0] * 2 + X[:, 1] + 0.1 * rng.randn(n)
    params = {"objective": "regression", "tree_learner": "data",
              "num_leaves": 15, "min_data_in_leaf": 3, "verbose": -1,
              "tpu_hist_chunk": 64}
    ref = lgb.train(dict(params), lgb.Dataset(X, label=y),
                    num_boost_round=5).model_to_string()
    ds = lgb.Dataset(X, label=y,
                     params={"tpu_ingest_device_shards": True,
                             "tree_learner": "data", "tpu_hist_chunk": 64})
    model = lgb.train(dict(params), ds, num_boost_round=5).model_to_string()
    inner = ds._inner
    assert inner.device_binned is not None and inner.binned is None
    assert inner.num_data == n
    import jax
    assert len(inner.device_binned.sharding.device_set) == \
        len(jax.devices())
    assert model == ref


def test_device_landed_dataset_saves_usable_cache(tmp_path):
    """save_binary on a device-landed dataset must gather the shards
    back to host — not silently write a cache with no binned payload."""
    rng = np.random.RandomState(13)
    n = 3000
    X = rng.randn(n, 5)
    y = X[:, 0]
    ds = lgb.Dataset(X, label=y,
                     params={"tpu_ingest_device_shards": True,
                             "tree_learner": "data", "tpu_hist_chunk": 64})
    inner = ds._lazy_init()
    assert inner.device_binned is not None and inner.binned is None
    path = str(tmp_path / "dev.bin")
    inner.save_binary(path)
    loaded = InnerDataset.load_binary(path)
    assert loaded.num_data == n
    host = InnerDataset.from_numpy(X, y)
    np.testing.assert_array_equal(np.asarray(loaded.binned), host.binned)


def test_device_shards_refused_for_serial_learner():
    """Sharded landing silently falls back to host when the learner
    cannot consume it (serial), with a warning — never a broken run."""
    rng = np.random.RandomState(12)
    X = rng.randn(1000, 4)
    y = X[:, 0]
    ds = lgb.Dataset(X, label=y,
                     params={"tpu_ingest_device_shards": True})
    booster = lgb.train({"objective": "regression", "num_leaves": 7,
                         "verbose": -1}, ds, num_boost_round=3)
    assert ds._inner.device_binned is None  # landed on host
    assert booster.current_iteration() == 3


# ---------------------------------------------------------------------------
# C API chunked-push validation
# ---------------------------------------------------------------------------

def _vp():
    return ctypes.c_void_p(0)


def _pending_handle(n=60, ncol=3):
    X = np.ascontiguousarray(np.random.RandomState(0).randn(n, ncol))
    cols = [np.ascontiguousarray(X[:, j]) for j in range(ncol)]
    col_ptrs = (ctypes.c_void_p * ncol)(*[c.ctypes.data for c in cols])
    counts = np.full(ncol, n, np.int32)
    h = _vp()
    rc = capi.LGBM_DatasetCreateFromSampledColumn(
        ctypes.addressof(col_ptrs), 0, ncol, counts.ctypes.data, n, n,
        ctypes.c_char_p(b"max_bin=15"), ctypes.addressof(h))
    assert rc == 0, capi.LGBM_GetLastError()
    return h, X


def test_push_rows_rejects_ncol_mismatch():
    h, X = _pending_handle()
    bad = np.ascontiguousarray(X[:10, :2])
    rc = capi.LGBM_DatasetPushRows(
        h, bad.ctypes.data, capi.C_API_DTYPE_FLOAT64, 10, 2, 0)
    assert rc == -1
    assert "ncol" in capi.LGBM_GetLastError()
    capi.LGBM_DatasetFree(h)


def test_push_rows_rejects_dtype_flip():
    h, X = _pending_handle()
    first = np.ascontiguousarray(X[:10])
    assert capi.LGBM_DatasetPushRows(
        h, first.ctypes.data, capi.C_API_DTYPE_FLOAT64, 10, 3, 0) == 0
    flipped = np.ascontiguousarray(X[10:20].astype(np.float32))
    rc = capi.LGBM_DatasetPushRows(
        h, flipped.ctypes.data, capi.C_API_DTYPE_FLOAT32, 10, 3, 10)
    assert rc == -1
    assert "dtype" in capi.LGBM_GetLastError()
    capi.LGBM_DatasetFree(h)


def test_push_rows_rejects_out_of_range_chunk():
    h, X = _pending_handle()
    chunk = np.ascontiguousarray(X[:20])
    rc = capi.LGBM_DatasetPushRows(
        h, chunk.ctypes.data, capi.C_API_DTYPE_FLOAT64, 20, 3, 50)
    assert rc == -1
    assert "num_total_row" in capi.LGBM_GetLastError()
    capi.LGBM_DatasetFree(h)
