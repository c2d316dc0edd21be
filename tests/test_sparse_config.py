"""The sparse deployment (`expo-11mx700`, cell `expo-train-1chip`) at a
size a test run can hold: a `scipy.sparse` matrix through the normal
two-pass ingest against the same table made dense, the program against
the benchmark's plain sparse reference through `Booster.update()`, the
reference's sums by code against a dense one-hot histogram, the blocked
relabel on group values up to 255, the generator, the counters and the
scope the cell brought, its readers and what `BENCHMARK.json` says of it.
Nothing here is a device measurement."""
import os
import sys
import time

import numpy as np
import pytest
import scipy.sparse as sp

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu import telemetry
from lightgbm_tpu.ingest import SparseSource, build_inner
from lightgbm_tpu.learner.grow import _NodeTable, route
from lightgbm_tpu.learner.schedule import (pick_schedule, plan_row_layout,
                                           relabel_rows)
from lightgbm_tpu.telemetry import devtrace, layers

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
for path in (ROOT, BENCH, os.path.dirname(os.path.abspath(__file__))):
    if path not in sys.path:
        sys.path.insert(0, path)

import datagen  # noqa: E402
import faults_sparse  # noqa: E402
import reference_sparse  # noqa: E402
import run as harness  # noqa: E402
from test_relabel import M, _reference as route_per_row  # noqa: E402

CELL = "expo-train-1chip"
V5E_BYTES = 16_909_336_064
PARAMS = {"objective": "binary", "max_bin": 63, "verbose": -1}
synth_expo = datagen.load_file_module(
    os.path.join(BENCH, "generators", "synth_expo.py"), "synth_expo_tests")


# ---------------------------------------------------------------------------
# (a) sparse input against the same table made dense, to the bit
# ---------------------------------------------------------------------------
def _one_hot_table(rng, n=6000):
    cards = (12, 31, 7, 40, 60)
    codes = np.stack([rng.integers(0, k, n) for k in cards], axis=1)
    cols = codes + np.concatenate([[0], np.cumsum(cards)[:-1]])[None, :]
    place = rng.permutation(sum(cards))
    idx = np.sort(place[cols], axis=1).astype(np.int32)
    return sp.csr_matrix(
        (np.ones(idx.size, np.float32), idx.ravel(),
         np.arange(n + 1, dtype=np.int32) * len(cards)),
        shape=(n, sum(cards)))


def _numeric_table(rng, n=6000, f=40):
    """Sparse numeric columns of many distinct values (63 bins each), of
    both signs, with NaNs, explicit zeros, two columns dense enough to
    stay out of the bundles and duplicate entries to be summed."""
    dense = np.zeros((n, f))
    for j in range(f):
        rate = 0.6 if j < 2 else rng.uniform(0.01, 0.1)
        rows = np.flatnonzero(rng.random(n) < rate)
        dense[rows, j] = rng.normal(size=len(rows)) * (j + 1)
    dense[rng.integers(0, n, 50), rng.integers(2, f, 50)] = np.nan
    coo = sp.coo_matrix(dense)
    extra = rng.integers(0, coo.nnz, 30)      # duplicates, and explicit 0s
    rows = np.concatenate([coo.row, coo.row[extra], [5, 6]])
    cols = np.concatenate([coo.col, coo.col[extra], [3, 4]])
    vals = np.concatenate([coo.data, coo.data[extra], [0.0, 0.0]])
    return sp.coo_matrix((vals, (rows, cols)), shape=(n, f))


def _table_with_an_empty_column(rng, n=3000):
    table = sp.lil_matrix(_one_hot_table(rng, n))
    table[:, 17] = 0.0                         # no entry, a trivial feature
    table[:, 18] = 1.0                         # a constant, trivial too
    return table.tocsc()                       # CSC in, converted once


TABLES = {"one_hot": _one_hot_table, "numeric_63_bins": _numeric_table,
          "an_all_zero_column": _table_with_an_empty_column}


def _same_dataset(a, b):
    assert a.binned.dtype == b.binned.dtype
    np.testing.assert_array_equal(a.binned, b.binned)
    assert a.used_features == b.used_features
    assert a.groups.groups == b.groups.groups
    np.testing.assert_array_equal(a.groups.offset_of, b.groups.offset_of)
    np.testing.assert_equal([m.to_dict() for m in a.mappers],
                            [m.to_dict() for m in b.mappers])   # NaN == NaN


@pytest.mark.parametrize("name", sorted(TABLES))
@pytest.mark.parametrize("chunk_rows", [None, 1000])
def test_sparse_input_builds_the_dense_inputs_dataset(name, chunk_rows):
    table = TABLES[name](np.random.default_rng(len(name)))
    y = (np.arange(table.shape[0]) % 2).astype(np.float32)
    params = dict(PARAMS, **({} if chunk_rows is None else
                             {"tpu_ingest_chunk_rows": chunk_rows}))
    sparse = lgb.Dataset(table, y, params=params).construct()._inner
    dense = lgb.Dataset(np.asarray(table.todense()), y,
                        params=params).construct()._inner
    _same_dataset(sparse, dense)
    bundled = sparse.groups.num_groups < len(sparse.used_features)
    assert bundled
    if name == "numeric_63_bins":
        assert max(m.num_bin for m in sparse.mappers) == 63
        assert sparse.groups.num_groups > 3       # the two dense columns
    if name == "an_all_zero_column":
        assert {17, 18}.isdisjoint(sparse.used_features)
    # what pass 2 visited: the stored entries in used columns, against
    # every value of the dense input
    rec, drec = sparse.construct_record, dense.construct_record
    canon = sp.csr_matrix(table)
    canon.sum_duplicates()
    assert rec.values == canon.nnz
    assert rec.nonzeros == canon[:, sparse.used_features].nnz
    assert drec.values == drec.nonzeros == (
        table.shape[0] * len(dense.used_features))
    assert sparse.efb_counters == dense.efb_counters


def test_a_categorical_column_without_a_zero_category_is_laid_out_whole():
    """Its zero is not its default bin, so every row without an entry is
    non-default: the one member `bundle_sparse` lays out as a column."""
    rng = np.random.default_rng(3)
    n = 4000
    dense = np.zeros((n, 6))
    dense[:, 0] = rng.integers(1, 5, n)            # categorical, no 0
    for j in range(1, 6):
        rows = rng.random(n) < 0.03
        dense[rows, j] = rng.integers(1, 4, rows.sum())
    dense[::400, 0] = 0.0          # a rare fifth category, which 4 bins drop
    kw = dict(max_bin=4, categorical_features=[0], sparse_threshold=0.0,
              max_conflict_rate=1.0)
    from lightgbm_tpu.dataset import Dataset as Inner
    a = build_inner(SparseSource(sp.csr_matrix(dense), 700), **kw)
    b = Inner.from_numpy(dense, **kw)
    _same_dataset(a, b)
    assert a.mappers[0].default_bin != a.mappers[0].values_to_bins(
        np.zeros(1))[0]
    assert a.groups.num_groups < len(a.used_features)


# ---------------------------------------------------------------------------
# (b) nothing makes the matrix dense
# ---------------------------------------------------------------------------
def test_the_sparse_path_never_makes_the_matrix_dense(monkeypatch):
    table = _one_hot_table(np.random.default_rng(1))

    def refuse(self, *args, **kwargs):
        raise AssertionError("the sparse matrix was made dense")

    for cls in {type(table), type(table.tocsc()), type(table[:10])}:
        for method in ("toarray", "todense"):
            monkeypatch.setattr(cls, method, refuse)
    import lightgbm_tpu.basic as basic
    monkeypatch.setattr(basic, "_data_to_2d", refuse)
    y = (np.arange(table.shape[0]) % 2).astype(np.float32)
    ds = lgb.Dataset(table, y, params=PARAMS)
    booster = lgb.Booster(dict(PARAMS, num_leaves=7), ds)
    booster.update()
    assert booster.current_iteration() == 1
    assert ds._inner.binned.shape == (table.shape[0], 5)


def test_a_dense_input_does_not_pay_for_scipy():
    """`construct()` of a dense table must not import `scipy.sparse` to
    ask whether its input is sparse (0.7-1.3 s of every dense cell's
    set-up on the chip's host, PR 38)."""
    import subprocess
    code = ("import sys, numpy as np; sys.path.insert(0, %r); "
            "import lightgbm_tpu as lgb; "
            "lgb.Dataset(np.random.rand(200, 3).astype(np.float32), "
            "np.arange(200) %% 2, params={'verbose': -1}).construct(); "
            "print('scipy.sparse' in sys.modules)" % ROOT)
    done = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.stdout.strip().splitlines()[-1] == "False", done.stderr[-500:]


# ---------------------------------------------------------------------------
# (c) the program against the plain sparse reference, under the cell's
#     limits
# ---------------------------------------------------------------------------
class _Sparse:
    """One prepared table shared by the variants, as `readings_sparse.py`
    shares one between a seed's; the sound run is made once."""
    ROWS = 12000

    def __init__(self):
        loaded = harness.load_cell(CELL)
        self.mode = harness.load_mode(loaded["traffic"])
        self.base = {
            "cell": loaded["cell"],
            "traffic": dict(loaded["traffic"], warmup_iterations=1),
            "config": loaded["config"],
            "seed": 3000000019, "seconds": 0.0, "trace": False,
            "rows": self.ROWS, "t_start": time.perf_counter(),
            "limits": loaded["cell"]["limits"], "rehearsal": True}
        self.prepared = self.mode.prepare(self.base)
        self._sound = None

    def run(self, **extra):
        if "params_override" not in extra:
            extra["prepared"] = self.prepared
        return self.mode.run(dict(self.base, **extra))

    def sound(self):
        if self._sound is None:
            self._sound = self.run(control=True)
        return self._sound


@pytest.fixture(scope="module")
def sparse():
    return _Sparse()


def _failed(compared):
    return {n for n, row in compared.items()
            if not row["value"] <= row["limit"]}


TREE_NUMBERS = {"count_mismatch", "split_gap", "gain_gap", "leaf_gap",
                "score_gap", "loss_gap"}


def test_sparse_program_is_correct_and_control_is_not(sparse):
    out = sparse.sound()
    assert out["correct"], out["compared"]
    assert set(out["compared"]) == set(reference_sparse.COMPARED) | {
        "bundle_lost_values", "window_compiles", "stopped_iterations"}
    assert not out["control_correct"], out["control_compared"]
    assert {"gain_gap", "leaf_gap", "score_gap"} <= _failed(
        out["control_compared"])
    # what the readers are handed: the entries a row as `features`, so
    # that the dataset's record (its `values` the stored entries) fits
    assert (out["features"], out["columns"]) == (8, 700)
    assert out["nonzeros"] == 8 * _Sparse.ROWS
    efb = out["schedule"]["efb"]
    assert (efb["features"], efb["groups"], efb["bundles"]) == (700, 11, 11)
    assert efb["widest_group_bins"] == 255 and efb["sample_conflicts"] == 0
    assert out["schedule"]["max_bin"] == 255


@pytest.mark.parametrize("fault", faults_sparse.FAULTS)
def test_sparse_broken_timed_path_is_not_correct(sparse, fault):
    out = sparse.run(fault=fault)
    assert not out["correct"], out["compared"]
    failed = _failed(out["compared"])
    assert failed & TREE_NUMBERS, out["compared"]
    assert "bundle_lost_values" not in failed   # the dataset is sound
    if fault == "default_unrepaired":
        # every two-bin feature's zero side reads empty: no split is
        # valid, no tree grows, every iteration stops
        assert "stopped_iterations" in failed
        assert out["compared"]["stopped_iterations"]["value"] >= 1


def test_lossy_bundling_fails_the_dataset_layers_number(sparse):
    out = sparse.run(params_override={"max_conflict_rate": 0.05})
    assert not out["correct"]
    failed = _failed(out["compared"])
    assert "bundle_lost_values" in failed
    assert out["compared"]["bundle_lost_values"]["value"] > 100
    assert out["schedule"]["efb"]["sample_conflicts"] > 0
    assert not failed & {"bin_count_mismatch", "bin_pop_mismatch"}


def test_a_program_without_the_sparse_source_is_refused_at_once(monkeypatch):
    import lightgbm_tpu.ingest as ingest
    monkeypatch.delattr(ingest, "SparseSource")
    mode = harness.load_mode(harness.load_cell(CELL)["traffic"])
    with pytest.raises(SystemExit) as stopped:
        mode.run({})
    assert "no sparse source" in str(stopped.value.code)


# ---------------------------------------------------------------------------
# (d) the reference's own check: sums by code against a dense one-hot
# ---------------------------------------------------------------------------
def test_reference_histograms_equal_a_dense_one_hot_histogram():
    csr, y, codes, column_map = synth_expo.generate(
        synth_expo.PLANTED, 700, 77)
    n = 2000
    csr, y, codes = csr[:n], y[:n], codes[:n]
    cuts = [np.asarray([1e-35, np.inf])] * 700
    ref = reference_sparse.SparseReference(
        codes, column_map, y, cuts, num_leaves=4, learning_rate=0.1,
        min_sum_hessian_in_leaf=1.0, min_data_in_leaf=1)
    # a tree of one split, on column 0 of the matrix
    tree = {"split_feature": np.array([0]), "threshold": np.array([1e-35]),
            "left_child": np.array([-1]), "right_child": np.array([-2]),
            "leaf_value": np.zeros(2)}
    acc, leaf = ref._pass(ref.X, ref.y, ref.score, ref.lower, ref.upper,
                          *ref._tables(tree))
    hist = acc.reshape(700, reference_sparse.BINS, 4, 3)
    dense = np.asarray(csr.todense(), np.float64)           # [n, 700]
    right = dense[:, 0] > 1e-35
    np.testing.assert_array_equal(np.asarray(leaf), right.astype(np.int32))
    grad = np.where(y > 0, -0.5, 0.5)
    chans = np.stack([grad, np.full(n, 0.25), np.ones(n)], axis=1)
    for k, rows in enumerate((~right, right)):
        ones = dense[rows].T @ chans[rows]                  # [700, 3]
        zeros = chans[rows].sum(axis=0)[None, :] - ones
        np.testing.assert_allclose(hist[:, 1, k], ones, rtol=0, atol=1e-9)
        np.testing.assert_allclose(hist[:, 0, k], zeros, rtol=0, atol=1e-9)
    assert not hist[:, 2:].any() and not hist[:, :, 2:].any()
    assert ref.bin_pop_mismatch == 0
    # cut points that do not part 0 from 1: the nonzero bin holds every row
    blind = reference_sparse.SparseReference(
        codes, column_map, y, [np.asarray([1.5, np.inf])] * 3 + cuts[3:],
        num_leaves=4, learning_rate=0.1, min_sum_hessian_in_leaf=1.0,
        min_data_in_leaf=1)
    assert blind.bin_pop_mismatch == 3


def test_bundle_lost_values_counts_what_a_decode_does_not_give_back(sparse):
    inner = sparse.prepared["ds"]._inner
    csr = sparse.prepared["csr"]
    cuts = [np.asarray(m.bin_upper_bound, np.float64) for m in inner.mappers]
    layout = sparse.mode.group_layout(inner)
    count = lambda binned: reference_sparse.bundle_lost_values(
        csr, binned, layout, cuts, chunk_rows=5000)
    assert count(inner.binned) == 0
    spoiled = inner.binned.copy()
    spoiled[:100, 0] = 0              # a hundred rows lose group 0's value
    spoiled[200:250, 3] = 255         # fifty hold another member's bin
    assert count(spoiled) == 150


# ---------------------------------------------------------------------------
# (e) the blocked relabel on group values up to 255
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("K", [8, 24])
def test_blocked_relabel_is_exact_on_group_values_up_to_255(sparse, K):
    """`schedule.relabel_rows` allows the blocked form up to 256 bins on
    the ground that its bf16 one-hot product is exact to 255: both forms
    and the per-row routing agree to the bit on a bundled matrix whose
    stored values run from 0 to 254."""
    ds = sparse.prepared["ds"]
    inner = lgb.Booster(dict(PARAMS, num_leaves=31), ds)._inner
    binned = np.asarray(ds._inner.binned)
    assert binned.max() == 254 and (binned >= 128).any(axis=1).mean() > 0.2
    fm = {k: np.asarray(v) for k, v in inner._fmeta.items()}
    rng = np.random.default_rng(K)
    n = len(binned)
    lid = rng.integers(0, 2 * K, n).astype(np.int32)
    # features of the widest groups, at offsets 1..253
    wide = np.flatnonzero(fm["offset"] > 127)
    feature = rng.choice(wide, M).astype(np.int32)
    table = _NodeTable.zeros(M)._replace(
        feature=jnp.asarray(feature),
        threshold=jnp.zeros(M, jnp.int32),
        default_left=jnp.asarray(rng.integers(0, 2, M).astype(bool)),
        is_cat=jnp.zeros(M, bool))
    sel = rng.permutation(2 * K)[:K].astype(np.int32)
    valid = np.ones(K, bool)
    cl = (2 * K + 2 * np.arange(K)).astype(np.int32)
    args = (jnp.asarray(lid), jnp.asarray(binned).T,
            {k: jnp.asarray(v) for k, v in fm.items()}, table,
            jnp.asarray(sel), jnp.asarray(valid), jnp.asarray(cl),
            jnp.asarray(cl + 1))
    columns = np.asarray(route(*args, block_rows=0))
    blocked = np.asarray(route(*args, block_rows=4096))
    np.testing.assert_array_equal(blocked, columns)
    np.testing.assert_array_equal(
        columns, route_per_row(binned, fm, lid, table, sel, valid, cl,
                               cl + 1))
    assert (columns != lid).any()
    assert relabel_rows(11, 255, 24, 25_165_824) == 262_144


# ---------------------------------------------------------------------------
# (f) each limit between the file's own rows
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", ["split_gap", "gain_gap", "leaf_gap",
                                  "score_gap", "loss_gap"])
def test_each_limit_lies_between_this_shapes_two_readings(name):
    """PERF.md section 2's rule on the readings the cell's file carries:
    over the largest sound reading with room, under the smallest control
    reading where the control parts from the sound runs there, and under
    every fault's reading."""
    cell = harness.load_cell(CELL)["cell"]
    limit, read = cell["limits"][name], cell["limits_set_from"]
    sound = read["lower_largest_sound_reading"][name]
    assert 2 * sound < limit
    for values in read["sound_per_data_set"].get(name, []), \
            read["sound_benchmark_data_set"].get(name, []):
        assert all(value <= sound for value in values)
    faults = [f[name] for f in read["smallest_fault_reading"].values()
              if name in f]
    assert faults and all(limit < f for f in faults)
    control = read["control_per_data_set"].get(name)
    if name in read["control_separates"]:
        assert limit < min(control)
        assert min(control) == read["upper_smallest_control_reading"][name]


@pytest.mark.parametrize("name", ["bin_count_mismatch", "bin_pop_mismatch",
                                  "bundle_lost_values", "count_mismatch",
                                  "window_compiles", "stopped_iterations"])
def test_the_exact_numbers_are_held_to_zero(name):
    cell = harness.load_cell(CELL)["cell"]
    assert cell["limits"][name] == 0
    read = cell["limits_set_from"]
    assert read["lower_largest_sound_reading"].get(name, 0) == 0
    if name == "bundle_lost_values":
        assert read["conflict_rate_control"][name] > 0


# ---------------------------------------------------------------------------
# (g) the generator
# ---------------------------------------------------------------------------
def test_synth_expo_is_one_table_with_its_columns_reordered():
    n = 20000
    a, ya, ca, ma = synth_expo.generate(n, 700, 1)
    b, yb, cb, mb = synth_expo.generate(n, 700, 2 ** 31 + 5)
    np.testing.assert_array_equal(ya, yb)
    np.testing.assert_array_equal(ca, cb)
    assert (a != b).nnz > 0
    for csr, cmap in ((a, ma), (b, mb)):
        assert csr.dtype == np.float32 and csr.indices.dtype == np.int32
        assert csr.has_canonical_format and csr.nnz == 8 * n
        np.testing.assert_array_equal(np.diff(csr.indptr), 8)
        # the map gives back the codes: column -> (categorical, value)
        cols = csr.indices.reshape(n, 8)
        order = np.argsort(cmap["categorical"][cols], axis=1)
        cols = np.take_along_axis(cols, order, axis=1)
        np.testing.assert_array_equal(cmap["categorical"][cols],
                                      np.tile(np.arange(8), (n, 1)))
        np.testing.assert_array_equal(cmap["value"][cols], ca)
    fresh = synth_expo.generate(n, 700, 1, base_seed=9)
    assert (fresh[2] != ca).any()
    assert 0.45 < ya.mean() < 0.55
    with pytest.raises(ValueError):
        synth_expo.generate(1000, 700, 1)
    with pytest.raises(ValueError):
        synth_expo.generate(n, 28, 1)


def test_synth_expo_keeps_what_the_configuration_assumes():
    n = 200000
    csr, y, codes, cmap = synth_expo.generate(n, 700, 5)
    assert tuple(cmap["cards"]) == synth_expo.CARDS
    assert sum(synth_expo.CARDS) == 700
    share = np.asarray(csr.sum(axis=0)).ravel() / n
    assert share.max() < 0.18               # sparse by the program's rule
    assert (np.asarray((csr != 0).sum(axis=0)).ravel() >= 8).all()
    for s in synth_expo.shares():
        assert abs(s.sum() - 1.0) < 1e-12 and s.max() < 0.18
    hubs, tail = synth_expo.airport_shares()
    assert tail[-1] == pytest.approx(0.0005) and hubs[-1] > 5 * tail[0]
    origin, dest = codes[:, synth_expo.ORIGIN], codes[:, synth_expo.DEST]
    # small airports serve hubs: a tail origin never meets a tail
    # destination, in any row
    assert not ((origin >= synth_expo.HUBS) & (dest >= synth_expo.HUBS)).any()
    assert ((origin >= synth_expo.HUBS).mean()
            == pytest.approx(0.15, abs=0.01))
    assert (dest >= synth_expo.HUBS).mean() == pytest.approx(0.15, abs=0.01)
    for airport in (origin, dest):
        assert (np.bincount(airport[:synth_expo.PLANTED], minlength=298)
                >= synth_expo.PLANTED_EACH).all()


# ---------------------------------------------------------------------------
# (h) the schedule of the cell's shape, the scope, the counters
# ---------------------------------------------------------------------------
def test_the_cells_shape_is_a_rung_and_takes_the_cache_at_twenty_four():
    config = harness.load_cell(CELL)["config"]
    rows = int(config["rows"])
    layout = plan_row_layout(rows, 11, 255)
    assert (layout.n_pad, layout.chunk) == (rows, 65536)
    picked = pick_schedule(11, 255, rows, rows, 65536, num_leaves=255,
                           learner="serial", bundled=True,
                           device_bytes=V5E_BYTES, cache_groups=11)
    assert (picked.wide, picked.subtract, picked.compact) == (
        False, True, False)
    assert (picked.batch_k, picked.table_mult) == (24, 12)
    assert relabel_rows(11, 255, picked.batch_k, rows) == 262_144
    assert relabel_rows(11, 257, picked.batch_k, rows) == 0


def test_the_extract_scope_is_in_the_program_and_charged_to_itself():
    assert "lgbm/split/extract" in layers.SCOPES
    path = ("jit(f)/lgbm/split/scan/vmap()/lgbm/split/extract/gather")
    assert devtrace.scope_of(path) == "lgbm/split/extract"
    assert devtrace.scope_of("jit(f)/lgbm/split/scan/cumsum") \
        == "lgbm/split/scan"
    from lightgbm_tpu.learner import grow
    cfg = type("Cfg", (), {"feature_bins": 2, "max_bins": 4})
    fmeta = {"group": jnp.zeros(3, jnp.int32),
             "offset": jnp.asarray([1, 3, 5], jnp.int32),
             "num_bin": jnp.full(3, 2, jnp.int32),
             "default_bin": jnp.zeros(3, jnp.int32),
             "is_bundled": jnp.ones(3, bool)}
    # a bundle's histogram: the slot of a member's default bin is empty
    group_hist = jnp.arange(1 * 8 * 3, dtype=jnp.float32).reshape(
        1, 8, 3).at[0, jnp.asarray([1, 3, 5])].set(0.0)
    lowered = jax.jit(
        lambda h: grow._extract_feature_hist(h, 100.0, 50.0, jnp.int32(40),
                                             fmeta, cfg)).lower(group_hist)
    assert "lgbm/split/extract" in lowered.as_text(debug_info=True)
    assert "lgbm/split/extract" not in lowered.as_text()
    fh = np.asarray(grow._extract_feature_hist(
        group_hist, 100.0, 50.0, jnp.int32(40), fmeta, cfg))
    # the default bin is the totals less the feature's other bin
    np.testing.assert_allclose(fh[0, 1], [6.0, 7.0, 8.0])
    np.testing.assert_allclose(fh[0, 0], [100.0 - 6, 50.0 - 7, 40.0 - 8])


def test_dense_input_counts_every_value_and_no_bundle_counters_line(capsys):
    rng = np.random.default_rng(0)
    X = rng.normal(size=(500, 6)).astype(np.float32)
    ds = lgb.Dataset(X, (X[:, 0] > 0).astype(np.float32), params=PARAMS)
    inner = lgb.Booster(dict(PARAMS, num_leaves=7), ds)._inner
    rec = ds._inner.construct_record
    assert rec.values == rec.nonzeros == 500 * 6
    assert ds._inner.efb_counters == telemetry.EfbCounters(6, 6, 0, 63, 0)
    assert "efb" not in inner._schedule_info
    assert telemetry.ConstructRecord(1.0, 2.0, 3.0, values=7).nonzeros == 0


# ---------------------------------------------------------------------------
# (i) the readers the cell brought, and what BENCHMARK.json says
# ---------------------------------------------------------------------------
def _reader(name):
    return datagen.load_file_module(
        os.path.join(BENCH, "layer_metrics", name + ".py"),
        "reader_" + name.replace(".", "_")).read


def test_the_new_readers_on_a_hand_made_ctx():
    scopes = {"lgbm/hist/contract": 3.0, "lgbm/hist/operand": 1.0,
              "lgbm/split/scan": 0.1, "lgbm/split/extract": 0.3,
              "lgbm/grow/relabel": 0.6, "unscoped": 3.0}
    record = [0.0] * len(telemetry.TreeRecord._fields)
    record[telemetry.TreeRecord._fields.index("rows_contracted")] = 1e9
    ctx = {"rows": 1000, "features": 8, "nonzeros": 8000,
           "schedule": {"efb": {"features": 700, "groups": 11}},
           "trace_scopes": {"/device:TPU:0": scopes},
           "pass_log_window": [record] * 4, "traced_trees": [1, 3],
           "peaks": {"flops_per_s": 197e12, "bytes_per_s": 819e9}}
    assert _reader("dataset.groups_per_feature")(ctx) == 11 / 700
    assert _reader("split.device_share")(ctx) == pytest.approx(5.0)
    # two traced trees of 1e9 rows at 16 bytes a row over 819 GB/s,
    # against the contraction's and the operand's four seconds
    assert _reader("hist.roofline_share")(ctx) == pytest.approx(
        100.0 * 2 * 16e9 / 819e9 / 4.0)
    telemetry.record_construct(telemetry.ConstructRecord(
        1.5, 0.25, 4.0, values=8000, nonzeros=8000))
    assert _reader("dataset.groups_s")(ctx) == 0.25
    assert _reader("dataset.bin_ns_per_nonzero")(ctx) == pytest.approx(5e5)
    assert _reader("dataset.sketch_s")(ctx) == 1.5    # the record fits
    # the parent's record has no such field; a dense cell's mode hands
    # no `nonzeros`, no scopes and no counters
    telemetry.record_construct(telemetry.ConstructRecord(
        1.5, 0.25, 4.0, values=8000))
    assert _reader("dataset.bin_ns_per_nonzero")(ctx) is None
    telemetry.record_construct(telemetry.ConstructRecord(
        1.5, 0.25, 4.0, values=28000, nonzeros=28000))   # a dense build
    dense = {"rows": 1000, "features": 28, "traced_trees": [1, 3],
             "schedule": {"num_shards": 1}, "peaks": ctx["peaks"],
             "pass_log_window": [record] * 4}
    assert _reader("dataset.groups_s")(dense) == 0.25     # every cell
    for name in ("dataset.groups_per_feature", "split.device_share",
                 "hist.roofline_share", "dataset.bin_ns_per_nonzero"):
        assert _reader(name)(dense) is None
        assert _reader(name)({}) is None
    ranked = dict(dense, trace_scopes=ctx["trace_scopes"])
    assert _reader("split.device_share")(ranked) is None
    assert _reader("hist.roofline_share")(ranked) is None
    assert _reader("hist.roofline_share")(dict(ctx, traced_trees=None)) is None
    assert _reader("hist.roofline_share")(dict(ctx, peaks=None)) is None


def test_benchmark_json_names_the_configuration_the_cell_and_the_readers():
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    loaded = harness.load_cell(CELL)
    entry, config = loaded["entry"], loaded["config"]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "expo-11mx700", "train_steady_sparse", 1)
    assert [w["name"] for w in bench["workloads"]][-1] == CELL
    assert len(bench["workloads"]) == 5
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    declared = bench["configs"][-1]
    assert declared["name"] == "expo-11mx700"
    assert declared["reduced"] == config["reduced"] == ["rows"]
    assert "GPU-Performance.rst" in declared["source"]
    assert "11,000,000 x 700" in declared["source"]
    assert all(len(e["why"]) <= 200 for e in (declared, entry))
    assert len(declared["source"]) <= 200
    assert "8 entries a row" in entry["why"]
    higgs = harness.load_cell("higgs-train-1chip")["config"]
    assert config["params"] == higgs["params"]
    assert not any(k.startswith("tpu_") or k in (
        "enable_bundle", "max_conflict_rate", "sparse_threshold")
        for k in config["params"])
    assert (config["features"], config["rows_published"]) == (700, 11_000_000)
    assert config["rows"] in (25_165_824, 29_360_128, 33_554_432)
    assert config["nonzeros_per_row"] == 8
    assert "lossless bundling" in config["guarantees"]
    assert {"rows", "columns", "frequencies", "exclusivity", "label",
            "count_cell", "data"} <= set(config["assumed"])
    steady = harness.load_json(BENCH, "traffic", "train_steady.json")
    for key in ("warmup_iterations", "checked_iterations", "off_in_window"):
        assert loaded["traffic"][key] == steady[key]
    mine = bench["per_layer"][-5:]
    assert [m["name"] for m in mine] == [
        "dataset.groups_s", "dataset.bin_ns_per_nonzero",
        "dataset.groups_per_feature", "split.device_share",
        "hist.roofline_share"]
    assert "workloads" not in mine[0]
    # the mode hands `trace_scopes`, so the gradient layer's share (the
    # one reader that asks nothing else) reads here too: the cell was
    # appended to its list, and to no other
    listed = [m["name"] for m in bench["per_layer"][:-5]
              if CELL in m.get("workloads", [])]
    assert listed == ["gradients.device_share"]
    assert all(m["workloads"] == [CELL] for m in mine[1:])
    assert [(m["layer"], m["moves"]) for m in mine] == [
        ("dataset", "setup_s"), ("dataset", "setup_s"),
        ("dataset", "train_mrow_iters_per_s"),
        ("grower", "train_mrow_iters_per_s"),
        ("grower", "train_mrow_iters_per_s")]
    for m in mine:
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           m["name"] + ".py"))
    assert set(loaded["cell"]["limits"]) == set(reference_sparse.COMPARED) | {
        "bundle_lost_values", "window_compiles", "stopped_iterations"}
