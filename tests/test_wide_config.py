"""The wide dense deployment (`epsilon-400kx2000`, cell
`epsilon-train-1chip`) at a size a test run can hold: the schedule the
program picks for such a shape, the benchmark's generator, the dataset
layer's `ConstructRecord`, the readers the cell brought, and the program
against the benchmark's plain reference on a table wide enough to take
a wide schedule (gather-compaction on; the subtraction cache off at 255
leaves inside the CPU's fixed budget, on at 31) with no `tpu_*` option.
Nothing here is a device measurement."""
import json
import os
import sys
import time

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import telemetry
from lightgbm_tpu.config import Config
from lightgbm_tpu.learner import schedule
from lightgbm_tpu.learner.grow import GrowerConfig
from lightgbm_tpu.learner.schedule import (COMPACT_FRACTION_MAX,
                                           compact_capacity, pick_schedule,
                                           plan_row_layout,
                                           subtract_cache_fits)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
for path in (ROOT, BENCH):
    if path not in sys.path:
        sys.path.insert(0, path)

import datagen  # noqa: E402
import run as harness  # noqa: E402

CELL = "epsilon-train-1chip"
# `bytes_limit` of the TPU v5e's `memory_stats()` (PERF.md's head), which
# `GBDT.init` hands `pick_schedule` there; the CPU backend reports none
V5E_BYTES = 16_909_336_064


def _config():
    with open(os.path.join(BENCH, "configs", "epsilon-400kx2000.json")) as fh:
        return json.load(fh)


# ---------------------------------------------------------------------------
# (b) the schedule as a function of the shape, no data built
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("rows", [1_048_576, 1_310_720, "the configuration's"])
def test_the_cells_shape_takes_the_wide_schedule(rows):
    config = _config()
    if not isinstance(rows, int):
        rows = int(config["rows"])
    groups, bins = int(config["features"]), int(config["params"]["max_bin"])
    layout = plan_row_layout(rows, groups, bins)
    assert layout.chunk == 8192
    assert layout.n_pad == rows, "the rung leaves no padded rows"
    picked = pick_schedule(groups, bins, rows, layout.n_pad, layout.chunk,
                           num_leaves=int(config["params"]["num_leaves"]),
                           device_bytes=V5E_BYTES)
    assert picked.wide and picked.subtract
    assert picked.compact
    assert picked.compact_fraction == COMPACT_FRACTION_MAX == 0.25
    assert picked.batch_k == 8 and picked.table_mult == 6
    assert not any(key.startswith("tpu_") for key in config["params"])
    # where the backend reports no memory the cache of 2.39 GB and more
    # stays out, and the direct path takes both children of 12 nodes
    unseen = pick_schedule(groups, bins, rows, layout.n_pad, layout.chunk,
                           num_leaves=int(config["params"]["num_leaves"]))
    assert unseen == picked._replace(subtract=False, batch_k=12,
                                     table_mult=12)


@pytest.mark.parametrize("device_bytes", [0, V5E_BYTES])
def test_the_other_cells_shape_sits_on_the_other_side(device_bytes):
    """`higgs-train-1chip`: the index build never pays, so the two cells
    guard the two sides of the compaction switch; its cache fits any
    budget."""
    layout = plan_row_layout(21_000_000, 28, 63)
    picked = pick_schedule(28, 63, 21_000_000, layout.n_pad, layout.chunk,
                           num_leaves=255, device_bytes=device_bytes)
    assert (layout.chunk, layout.n_pad) == (65536, 25_165_824)
    assert picked.subtract and not picked.compact and not picked.wide
    assert picked.batch_k == 24 and picked.compact_fraction == 0.0
    assert picked.table_mult == 12


@pytest.mark.parametrize("device_bytes,features,max_bin,subtract,compact", [
    # no memory reported: the fixed 256 MB is missed from 225 stored groups
    (0, 137, 63, True, False), (0, 200, 63, True, True),
    (0, 224, 63, True, True), (0, 225, 63, False, True),
    (0, 700, 63, False, True),
    # the v5e: a third of what the binned matrix leaves. At `max_bin` 63
    # every width of the cell's rows keeps the cache; at 255 it is missed
    # from 1,086 stored groups, so 2000 x 255 is on the direct path
    (V5E_BYTES, 137, 63, True, False), (V5E_BYTES, 225, 63, True, True),
    (V5E_BYTES, 700, 63, True, True), (V5E_BYTES, 2000, 63, True, True),
    (V5E_BYTES, 1085, 255, True, True), (V5E_BYTES, 1086, 255, False, True),
    (V5E_BYTES, 2000, 255, False, True)])
def test_where_the_two_switches_turn(device_bytes, features, max_bin,
                                     subtract, compact):
    """At 255 leaves and 2^20 rows: where the cache's budget is missed,
    on a backend that reports no memory and on the v5e, and that the
    pass-cost model compacts from 158 groups at `max_bin` 63."""
    rows = 1 << 20
    layout = plan_row_layout(rows, features, max_bin)
    picked = pick_schedule(features, max_bin, rows, layout.n_pad,
                           layout.chunk, num_leaves=255,
                           device_bytes=device_bytes)
    assert (picked.subtract, picked.compact) == (subtract, compact)
    assert picked.batch_k == (8 if subtract else 12), "all of them wide"


NARROW, WIDE = (28, 63, 25_165_824), (2000, 63, 1 << 20)


@pytest.mark.parametrize(
    "shape,learner,classes,subtract,batch_k,table_mult,compact", [
        # the serial and the data-parallel learner keep the cache (one
        # class tree, and it fits the v5e at both widths)
        (NARROW, "serial", 1, True, 24, 12, False),
        (NARROW, "data", 1, True, 24, 12, False),
        (WIDE, "serial", 1, True, 8, 6, True),
        (WIDE, "data", 1, True, 8, 6, True),
        # voting drops the cache itself (grow.py), the feature-parallel
        # learner never ran with it: the direct path, as the parent of PR
        # 33 gave every one of these
        (NARROW, "voting", 1, False, 12, 12, False),
        (NARROW, "feature", 1, False, 12, 12, False),
        (WIDE, "voting", 1, False, 12, 12, True),
        (WIDE, "feature", 1, False, 12, 12, False),
        # class trees run one after another, each on one tree's schedule:
        # the one-class answer of their learner
        (NARROW, "serial", 3, True, 24, 12, False),
        (NARROW, "data", 3, True, 24, 12, False),
        (NARROW, "voting", 3, False, 12, 12, False),
        (NARROW, "feature", 3, False, 12, 12, False),
        (WIDE, "serial", 3, True, 8, 6, True),
        (WIDE, "data", 3, True, 8, 6, True),
        (WIDE, "voting", 3, False, 12, 12, True),
        (WIDE, "feature", 3, False, 12, 12, False)])
def test_which_learner_takes_the_cache(shape, learner, classes, subtract,
                                       batch_k, table_mult, compact):
    """Learner kind x class trees x shape on the v5e's memory, at 255
    leaves: one shard's shape as `GBDT.init` hands it over. A job of
    `classes` trees an iteration asks the same question: the schedule is
    one tree's, and its class trees take it one after another."""
    groups, bins, rows = shape
    layout = plan_row_layout(rows, groups, bins)
    picked = pick_schedule(groups, bins, rows, layout.n_pad, layout.chunk,
                           num_leaves=255, learner=learner,
                           device_bytes=V5E_BYTES)
    assert (picked.subtract, picked.batch_k, picked.table_mult,
            picked.compact) == (subtract, batch_k, table_mult, compact)
    assert picked.wide == (shape is WIDE)
    assert picked.compact_fraction == (0.25 if shape is WIDE else 0.0)
    if learner == "data":
        # the data learner's answer is the serial learner's for the shape
        assert picked == pick_schedule(
            groups, bins, rows, layout.n_pad, layout.chunk, num_leaves=255,
            device_bytes=V5E_BYTES)


@pytest.mark.parametrize("bins,device_bytes,whole_fits", [
    # 2000 groups over 4 shards. At `max_bin` 255 on the v5e the whole
    # cache (9.68 GB at `table_mult` 6) misses a third of what the binned
    # shard leaves and the owned slice of 500 groups (2.42 GB) fits; at 63
    # the same on a device of 4 GiB (2.39 GB against 0.60); at 63 on the
    # v5e both fit
    (255, V5E_BYTES, False), (63, 4 << 30, False), (63, V5E_BYTES, True)])
def test_the_budget_is_judged_at_the_width_the_cache_has(bins, device_bytes,
                                                         whole_fits):
    groups, rows, shards = 2000, 1 << 20, 4
    layout = plan_row_layout(rows, groups, bins, tree_learner="data",
                             ndev=shards)
    shape = (groups, bins, rows // shards, layout.n_pad // shards,
             layout.chunk)
    asked = dict(num_leaves=255, learner="data", device_bytes=device_bytes)
    # the scatter merge: a device keeps ceil(groups / shards) of them
    owned = pick_schedule(*shape, cache_groups=500, **asked)
    assert owned.subtract and (owned.batch_k, owned.table_mult) == (8, 6)
    assert subtract_cache_fits(500, bins, 255, 6, rows_padded=shape[3],
                               device_bytes=device_bytes)
    # allreduce: every group on every device
    whole = pick_schedule(*shape, **asked)
    assert whole == pick_schedule(*shape, cache_groups=groups, **asked)
    assert whole.subtract == whole_fits == subtract_cache_fits(
        groups, bins, 255, 6, rows_padded=shape[3],
        device_bytes=device_bytes)
    assert whole.batch_k == (8 if whole_fits else 12)
    assert whole.table_mult == (6 if whole_fits else 12)
    # the contraction's cost model keeps the shard's full width
    assert owned.wide and whole.wide
    assert owned.compact_model == whole.compact_model
    assert (owned.compact, owned.compact_fraction) \
        == (whole.compact, whole.compact_fraction)


@pytest.mark.parametrize("reduce,owned_groups,subtract", [
    ("scatter", 100, True), ("allreduce", 400, False)])
def test_the_trainer_hands_over_the_width_its_cache_has(reduce, owned_groups,
                                                        subtract):
    """`GBDT.init` on 4 of conftest's CPU devices (256 MB, no memory
    reported): 400 groups x 255 bins at 31 leaves keep a cache of 291 MB
    whole at `table_mult` 6, and of 130 MB at the owned slice at 12."""
    import jax
    rng = np.random.RandomState(11)
    X = rng.randn(4096, 400).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 31, "max_bin": 255,
              "min_data_in_leaf": 1, "tree_learner": "data",
              "tpu_hist_reduce": reduce, "tpu_hist_chunk": 512,
              "verbose": -1}
    patch = pytest.MonkeyPatch()
    four = jax.devices()[:4]
    patch.setattr(jax, "devices", lambda *a, **k: four)
    try:
        info = lgb.Booster(params, lgb.Dataset(
            X, (X[:, 0] > 0).astype(np.float32),
            params=params))._inner._schedule_info
    finally:
        patch.undo()
    assert (info["groups"], info["max_bin"]) == (400, 255)
    assert (info["num_shards"], info["hist_reduce"]) == (4, reduce)
    assert info["owned_groups"] == owned_groups
    assert info["subtract"] == subtract
    table_mult = info["table_mult"]
    assert (info["batch_k"], table_mult) == (8 if subtract else 12, 12)
    assert info["subtract_cache_bytes"] == (
        schedule.subtract_cache_bytes(100, 255, 31, table_mult)
        if subtract else 0)
    assert not subtract_cache_fits(400, 255, 31, 6)
    assert subtract_cache_fits(100, 255, 31, 12)


def test_what_the_user_set_wins_over_the_shape():
    shape = (2000, 63, 1 << 20, 1 << 20, 8192)
    assert not pick_schedule(*shape, num_leaves=255,
                             compact_fraction=0.0).compact
    given = pick_schedule(*shape, num_leaves=255, compact_fraction=0.1)
    assert given.compact and given.compact_fraction == 0.1
    assert given.batch_k == 12 and given.compact_model.fraction == 0.25
    # one chunk of rows has nothing to skip
    assert not pick_schedule(2000, 63, 8192, 8192, 8192,
                             num_leaves=255).compact


# what the grower is handed for the two cells' shapes on the v5e:
# RowLayout, Schedule, the schedule fields of GrowerConfig, the compaction
# buffer's rows. HIGGS as the parent commit of PR 30 (9f626d5) had it;
# Epsilon as PR 31 left it (the cache, and 8 smaller children a pass)
GOLDEN = {
    "higgs-train-1chip": (
        (21_000_000, 28),
        (65536, 65536, 25_165_824, 1, 1),
        (False, True, 12, False, 0.0,
         (0.0, 3.4, 8.987794285714285, 46.9), 24),
        {"chunk": 65536, "batch_k": 24, "hist_subtract": True,
         "hist_compact": False, "compact_fraction": 0.0, "table_mult": 12},
        0),
    "epsilon-train-1chip": (
        (1_048_576, 2000),
        (8192, 8192, 1_048_576, 1, 1),
        (True, True, 6, True, 0.25, (0.25, 181.4, 7.5, 64.5), 8),
        {"chunk": 8192, "batch_k": 8, "hist_subtract": True,
         "hist_compact": True, "compact_fraction": 0.25, "table_mult": 6},
        262_144),
    # PR 34: 137 groups x 63 bins is just past the wide line (8,631 of
    # 8,192 columns): the cache at 8 smaller children a pass, no
    # compaction, no padded row
    "msltr-rank-1chip": (
        (12_582_912, 137),
        (65536, 65536, 12_582_912, 1, 1),
        (True, True, 9, False, 0.0, (0.0, 8.5, 7.5, 49.9), 8),
        {"chunk": 65536, "batch_k": 8, "hist_subtract": True,
         "hist_compact": False, "compact_fraction": 0.0, "table_mult": 9},
        0),
}


@pytest.mark.parametrize("cell", sorted(GOLDEN))
def test_the_cells_schedules_are_pinned_to_the_field(cell):
    """No dataset is built: layout, schedule and the grower's static
    schedule fields for `max_bin` 63, 255 leaves, serial, one class, on
    the v5e's memory."""
    (rows, features), layout_want, picked_want, fields_want, cap_want = \
        GOLDEN[cell]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        declared = json.load(fh)
    config_name, = [w["config"] for w in declared["workloads"]
                    if w["name"] == cell]
    config_file, = [c["file"] for c in declared["configs"]
                    if c["name"] == config_name]
    with open(os.path.join(ROOT, config_file)) as fh:
        config = json.load(fh)
    assert (int(config["rows"]), int(config["features"])) == (rows, features)
    assert (config["params"]["max_bin"], config["params"]["num_leaves"]) \
        == (63, 255)
    layout = plan_row_layout(rows, features, 63)
    assert tuple(layout) == layout_want
    picked = pick_schedule(features, 63, rows, layout.n_pad, layout.chunk,
                           num_leaves=255, device_bytes=V5E_BYTES)
    assert tuple(picked._replace(
        compact_model=tuple(picked.compact_model))) == picked_want
    cfg = GrowerConfig(
        num_leaves=255, max_bins=63, lambda_l1=0.0, lambda_l2=0.0,
        min_gain_to_split=0.0, min_data_in_leaf=1,
        min_sum_hessian_in_leaf=100.0, max_depth=-1,
        **picked.grower_fields(layout.chunk))
    assert {key: getattr(cfg, key) for key in fields_want} == fields_want
    assert [type(getattr(cfg, key)) for key in fields_want] \
        == [type(value) for value in fields_want.values()]
    assert compact_capacity(cfg, layout.n_pad) == cap_want


def test_the_schedule_module_loads_without_jax():
    """The ingest side plans a landing's rows without the grower."""
    import subprocess
    code = (
        "import sys\n"
        "sys.modules['jax'] = sys.modules['jaxlib'] = None\n"
        "from lightgbm_tpu.learner.schedule import plan_row_layout\n"
        "print(plan_row_layout(21_000_000, 28, 63).n_pad)\n"
        "assert not [m for m in sys.modules if m.split('.')[0] == 'jax'"
        " and sys.modules[m] is not None]\n")
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True,
        text=True, timeout=120,
        env=dict(os.environ, LIGHTGBM_TPU_COMPILE_CACHE="0"))
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == ["25165824"]


SMALL = {"objective": "binary", "num_leaves": 4, "max_bin": 15,
         "min_data_in_leaf": 1, "verbose": -1, "tpu_hist_chunk": 256}


def _small_booster(**params):
    rng = np.random.RandomState(3)
    X = rng.randn(1024, 4).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    params = dict(SMALL, **params)
    return lgb.Booster(params, lgb.Dataset(X, y, params=params))


@pytest.mark.parametrize("kind,name,value", [
    ("option", "tpu_hist_pallas", "true"),
    ("option", "tpu_double_precision", "true"),
    ("option", "tpu_hist_subtract", "false"),
    ("option", "tpu_hist_compact", "false"),
    ("option", "tpu_batch_k", "4"),
    # each value changed the schedule at the parent of PR 30
    ("env", "LGBM_TPU_TABLE_MULT", "4"),
    ("env", "LGBM_TPU_FORCE_SUBTRACT", "0"),
    ("env", "LGBM_TPU_FORCE_COMPACT", "1"),
    ("env", "LGBM_TPU_NO_PIPELINE", "1"),
])
def test_a_removed_switch_switches_nothing(kind, name, value, monkeypatch):
    if kind == "option":
        # refused as any key the program never heard of is
        for key in (name, "tpu_no_such_option"):
            with pytest.raises(lgb.log.LightGBMError,
                               match="Unknown parameter: " + key):
                Config.from_params({key: value})
        return
    want = _small_booster()._inner
    monkeypatch.setenv(name, value)
    booster = _small_booster()
    assert booster._inner._schedule_info == want._schedule_info
    assert booster._inner._grower_cfg == want._grower_cfg
    if name == "LGBM_TPU_NO_PIPELINE":
        booster.update()
        assert booster._inner._pending_small is not None, \
            "the tree is still fetched one iteration late"


@pytest.mark.parametrize("models,device_bytes,fits", [
    (6, 0, True), (7, 0, False), (7, V5E_BYTES, True)])
def test_the_sweep_and_the_schedule_ask_one_predicate(models, device_bytes,
                                                      fits, monkeypatch):
    """120 groups x 63 bins x 31 leaves: one cache is 38.5 MB at
    `table_mult` 12, so six copies fit the 256 MiB of a backend that
    reports no memory and seven do not; a third of the v5e holds 146. A
    single job subtracts either way."""
    from lightgbm_tpu.boosting import gbdt as gbdt_mod
    from lightgbm_tpu.boosting import sweep as sweep_mod
    assert sweep_mod.subtract_cache_fits is subtract_cache_fits
    seen = {"rows_padded": 512, "device_bytes": device_bytes}
    assert subtract_cache_fits(120, 63, 31, 12, copies=models, **seen) == fits
    if device_bytes:
        assert subtract_cache_fits(120, 63, 31, 12, copies=146, **seen)
        assert not subtract_cache_fits(120, 63, 31, 12, copies=147, **seen)
    monkeypatch.setattr(gbdt_mod, "_device_memory_bytes",
                        lambda device: device_bytes)
    rng = np.random.RandomState(3)
    X = rng.randn(512, 120).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 31, "max_bin": 63,
              "min_data_in_leaf": 1, "verbose": -1}
    trainer = sweep_mod.SweepTrainer(
        [dict(params, lambda_l2=float(k)) for k in range(models)],
        lgb.Dataset(X, y, params=params), 1)
    info = trainer.lead._schedule_info
    assert (info["groups"], info["max_bin"]) == (120, 63)
    assert info["subtract"] and info["table_mult"] == 12
    # the cache's bytes and the memory it was judged against are on record
    assert info["subtract_cache_bytes"] == 120 * 63 * 12 * (12 * 31 + 52)
    assert info["device_bytes"] == device_bytes
    assert trainer.cfg.hist_subtract == fits
    # and `pick_schedule` has no arithmetic of its own beside it
    monkeypatch.setattr(schedule, "subtract_cache_fits",
                        lambda *a, **k: False)
    assert not pick_schedule(120, 63, 512, 512, 512, num_leaves=31).subtract


# ---------------------------------------------------------------------------
# (c) the generator
# ---------------------------------------------------------------------------
def test_synth_epsilon_reorders_one_data_set():
    generate = datagen.generator("synth_epsilon")
    rows, features = 4000, 40
    Xa, ya = generate(rows, features, 7)
    Xb, yb = generate(rows, features, 3000000019)
    assert Xa.dtype == ya.dtype == np.float32 and Xa.shape == (rows, features)
    np.testing.assert_array_equal(ya, yb)
    assert not np.array_equal(Xa, Xb)
    # the same columns, in another order
    order_a = np.lexsort(Xa[:8])
    order_b = np.lexsort(Xb[:8])
    np.testing.assert_array_equal(Xa[:, order_a], Xb[:, order_b])
    Xc, yc = generate(rows, features, 7, base_seed=99)
    assert not np.array_equal(np.sort(Xa, axis=1), np.sort(Xc, axis=1))
    assert not np.array_equal(ya, yc)
    for y in (ya, yc):
        assert 0.45 < float(y.mean()) < 0.55
    # a label the features explain: the 24 linear columns carry it
    Xd, yd = generate(20000, 30, 1)
    agree = max(np.mean((Xd[:, j] > 0) == (yd > 0)) for j in range(30))
    assert agree > 0.6


# ---------------------------------------------------------------------------
# (d) the dataset layer's phases
# ---------------------------------------------------------------------------
def test_construct_record_accounts_for_construct():
    rng = np.random.default_rng(5)
    X = rng.standard_normal((200_000, 48)).astype(np.float32)
    X[:, 7] = 1.0                               # a trivial column: unused
    y = (X[:, 0] > 0).astype(np.float32)
    params = {"objective": "binary", "max_bin": 63, "verbose": -1}
    lgb.Dataset(X[:2000], y[:2000], params=params).construct()  # imports
    t = time.perf_counter()
    ds = lgb.Dataset(X, y, params=params).construct()
    host_s = time.perf_counter() - t
    rec = ds._lazy_init().construct_record
    assert isinstance(rec, telemetry.ConstructRecord)
    assert rec is telemetry.last_construct()
    assert rec._fields == ("sketch_s", "groups_s", "bin_s", "values",
                           "nonzeros")
    assert rec.nonzeros == rec.values          # dense: every value visited
    assert len(telemetry.DATASET_SPANS) == 3
    assert rec.values == 200_000 * 47 == X.shape[0] * len(
        ds._lazy_init().used_features)
    phases = rec.sketch_s + rec.groups_s + rec.bin_s
    assert min(rec[:3]) >= 0.0 and rec.sketch_s > 0 and rec.bin_s > 0
    assert phases <= host_s
    assert phases >= 0.95 * host_s, (rec, host_s)
    # a float32 table is binned as it is, chunk by chunk, to the same
    # bins a float64 copy of it gets
    wide64 = lgb.Dataset(X[:30000].astype(np.float64), y[:30000],
                         params=params).construct()._lazy_init()
    wide32 = lgb.Dataset(X[:30000], y[:30000],
                         params=params).construct()._lazy_init()
    np.testing.assert_array_equal(wide32.binned, wide64.binned)
    for m32, m64 in zip(wide32.mappers, wide64.mappers):
        np.testing.assert_array_equal(m32.bin_upper_bound,
                                      m64.bin_upper_bound)
    # a validation set reuses the training set's bins: no sketch, no
    # groups; its record is its own, and the training set keeps its own
    valid = lgb.Dataset(X[:5000], y[:5000], reference=ds).construct()
    valid = valid._lazy_init().construct_record
    assert valid.sketch_s == valid.groups_s == 0.0 and valid.bin_s > 0
    assert valid.values == 5000 * 47
    assert valid is telemetry.last_construct()
    assert ds._lazy_init().construct_record is rec


def test_dataset_spans_land_in_the_profiler_trace(tmp_path):
    """Telemetry off: a profiler session alone turns the spans on."""
    import glob

    import jax
    from jax.profiler import ProfileData
    assert not telemetry.enabled()
    rng = np.random.default_rng(6)
    X = rng.standard_normal((3000, 6))
    with jax.profiler.trace(str(tmp_path)):
        lgb.Dataset(X, (X[:, 0] > 0).astype(np.float32)).construct()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    names = {ev.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events}
    assert set(telemetry.DATASET_SPANS) <= names
    assert not {"ingest/pass1", "ingest/pass2"} & names   # gone, PR 36
    # and with telemetry on the same names accumulate host seconds
    telemetry.enable(True)
    try:
        telemetry.reset()
        lgb.Dataset(X, (X[:, 0] > 0).astype(np.float32)).construct()
        phases = telemetry.registry().phases
        assert set(telemetry.DATASET_SPANS) <= set(phases)
    finally:
        telemetry.reset()
        telemetry.enable(False)


# ---------------------------------------------------------------------------
# (e) the readers the cell brought
# ---------------------------------------------------------------------------
def _reader(name):
    return datagen.load_file_module(
        os.path.join(BENCH, "layer_metrics", name + ".py"),
        "reader_" + name.replace(".", "_")).read


def test_the_new_readers_on_a_hand_made_ctx(monkeypatch):
    rec = telemetry.TreeRecord(30, 700, 5e6, 0.0, 0.0, full_passes=9,
                               compact_passes=21, rows_indexed=21000,
                               rows_gathered=1500, dispatch_s=0.25,
                               fetch_wait_s=4.0, build_tree_s=0.5)
    other = rec._replace(compact_passes=23, rows_gathered=2500)
    new = {"rows": 500, "pass_log_window": [list(rec), list(other)]}
    old = {"rows": 500, "pass_log_window": [list(rec)[:5]]}
    assert _reader("grower.gathered_per_row")(new) == pytest.approx(4.0)
    assert _reader("grower.gathered_per_row")(old) is None
    assert _reader("grower.gathered_per_row")({}) is None
    # a schedule that compacts nothing reads 0, not nothing
    full = rec._replace(compact_passes=0, rows_gathered=0)
    none = {"rows": 500, "pass_log_window": [list(full)]}
    assert _reader("grower.gathered_per_row")(none) == 0.0

    # the run's table: 500 rows of 40 features, 38 of them used
    run = {"rows": 500, "features": 40}
    built = telemetry.ConstructRecord(12.5, 1.0, 80.25, values=500 * 38)
    monkeypatch.setattr(telemetry, "_LAST_CONSTRUCT", built)
    assert _reader("dataset.sketch_s")(run) == 12.5
    assert _reader("dataset.bin_s")(run) == 80.25
    # another dataset built since (a validation set, a control) is not the
    # run's: nothing to read, where a wrong number would be
    for values in (120 * 38, 500 * 41):
        monkeypatch.setattr(telemetry, "_LAST_CONSTRUCT",
                            built._replace(values=values))
        assert _reader("dataset.sketch_s")(run) is None
    # a program that built no dataset, or has no such record (the parent)
    monkeypatch.setattr(telemetry, "_LAST_CONSTRUCT", None)
    assert _reader("dataset.sketch_s")(run) is None
    monkeypatch.delattr(telemetry, "last_construct")
    assert _reader("dataset.sketch_s")(run) is None
    assert _reader("dataset.bin_s")(run) is None


def test_benchmark_json_names_the_cell_and_its_readers():
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    loaded = harness.load_cell(CELL)
    assert loaded["entry"]["config"] == "epsilon-400kx2000"
    assert loaded["entry"]["traffic"] == "train_steady"
    assert loaded["entry"]["chips"] == 1
    assert loaded["config"]["rows"] in (1_048_576, 1_310_720)
    assert loaded["config"]["reduced"] == ["rows"]
    higgs = harness.load_cell("higgs-train-1chip")["config"]
    assert loaded["config"]["params"] == higgs["params"]
    assert loaded["config"]["guarantees"] == higgs["guarantees"]
    names = [m["name"] for m in bench["per_layer"]]
    assert names[10:13] == ["grower.gathered_per_row",
                            "dataset.sketch_s", "dataset.bin_s"]
    for m in bench["per_layer"]:
        # every cell produces what this cell's readers read; the metrics
        # with a list of cells came with higgs-train-dp4 (the merge),
        # msltr-rank-1chip (the ranking gradients), expo-train-1chip
        # (bundles and the sparse ingest) and covtype-multiclass-1chip
        # (the class trees)
        assert ("workloads" in m) == (
            m["name"].startswith("merge.")
            or m["name"].startswith("gradients.")
            or m["name"] in ("dataset.bin_ns_per_nonzero",
                             "dataset.groups_per_feature",
                             "split.device_share", "hist.roofline_share",
                             "grower.class_pass_waste",
                             "hist.class_roofline_share"))
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           m["name"] + ".py"))
    assert set(loaded["cell"]["limits"]) == set(
        harness.load_cell("higgs-train-1chip")["cell"]["limits"])


@pytest.mark.parametrize("name", ["split_gap", "gain_gap", "leaf_gap",
                                  "score_gap", "loss_gap", "bin_pop_gap"])
def test_each_limit_lies_between_this_shapes_two_readings(name):
    """PERF.md section 2's rule, on the readings the cell's file carries:
    room over the largest sound reading and under the smallest control
    reading (or fault reading, where the control has none), and the
    control over the limit on every data set it ran on."""
    cell = harness.load_cell(CELL)["cell"]
    limit, read = cell["limits"][name], cell["limits_set_from"]
    assert 3 * read["lower_largest_sound_reading"][name] < limit
    upper = read["upper_smallest_control_reading"][name]
    faults = [f[name] for f in read["smallest_fault_reading"].values()
              if name in f]
    if upper > 0:
        assert 2.5 * limit < upper
    else:                               # split_gap: no control reading
        assert faults and 2.5 * limit < min(faults)
    assert all(limit < f for f in faults)
    for value in read.get("control_per_data_set", {}).get(name, []):
        assert value > limit


# ---------------------------------------------------------------------------
# (a) the program against the plain reference under the cell's KIND of
# schedule and the cell's limits, as benchmarks/test_correct.py does at
# HIGGS width
# ---------------------------------------------------------------------------
# Wide enough that the program, asked for nothing, leaves the subtraction
# cache out (225+ stored groups at max_bin 63 and 255 leaves) and turns
# gather-compaction on (158+ groups AND two histogram chunks of rows: the
# chunk is 2^30 / (groups x bins) rounded down to a power of two, at
# least 8,192, so few rows need many features).
WIDE_FEATURES, WIDE_ROWS = 1056, 16384


class _Wide:
    """One prepared data set shared by the variants, as `readings.py`
    shares one between a seed's; the sound run is made once."""

    def __init__(self):
        loaded = harness.load_cell(CELL)
        self.mode = harness.load_mode(loaded["traffic"])
        # the cell's traffic with one warm-up step, so that a run is two
        # steps: the reference follows the first from a zero score and the
        # window's one tree from the program's score at its opening
        traffic = dict(loaded["traffic"], warmup_iterations=1)
        self.base = {
            "cell": loaded["cell"], "traffic": traffic,
            "config": dict(loaded["config"], features=WIDE_FEATURES),
            "seed": 3000000019, "seconds": 0.0, "trace": False,
            "rows": WIDE_ROWS, "t_start": time.perf_counter(),
            "limits": loaded["cell"]["limits"], "rehearsal": True}
        self.prepared = self.mode.prepare(self.base)
        self._sound = None

    def run(self, **extra):
        return self.mode.run(dict(self.base, prepared=self.prepared,
                                  **extra))

    def sound(self):
        if self._sound is None:
            self._sound = self.run(control=True)
        return self._sound


@pytest.fixture(scope="module")
def wide():
    return _Wide()


def test_wide_program_is_correct_and_control_is_not(wide):
    out = wide.sound()
    schedule = out["schedule"]
    assert not schedule["subtract"] and schedule["compact"]
    assert schedule["compact_fraction"] == 0.25
    assert (schedule["batch_k"], schedule["chunk"]) == (12, 8192)
    assert schedule["rows_padded"] == WIDE_ROWS
    assert out["correct"], out["compared"]
    assert not out["control_correct"], out["control_compared"]
    # the mechanism the cell is for did run
    compact = [e[telemetry.TreeRecord._fields.index("compact_passes")]
               for e in out["pass_log_window"]]
    assert min(compact) > 0


def test_wide_broken_timed_path_is_not_correct(wide):
    out = wide.run(fault="half_batch")
    assert not out["correct"], out["compared"]
    failed = [n for n, row in out["compared"].items()
              if not row["value"] <= row["limit"]]
    assert len(failed) >= 2, out["compared"]


def test_wide_trees_do_not_depend_on_compaction(wide):
    """Compaction is a pure scheduling choice: switched off, the same
    splits, and leaf values up to float32 summation order."""
    with_compaction = wide.sound()["trees_window"]
    params = dict(wide.base["config"]["params"], tpu_compact_threshold=0)
    booster = lgb.Booster(params, wide.prepared["ds"])
    for _ in range(1 + len(with_compaction)):
        booster.update()
    booster.current_iteration()
    inner = booster._inner
    assert not inner._schedule_info["compact"]
    assert not inner._schedule_info["subtract"]
    assert all(rec.compact_passes == 0 for rec in inner.pass_log)
    without = [wide.mode.tree_arrays(t) for t in inner.models[1:]]
    assert len(without) == len(with_compaction) >= 1
    for a, b in zip(with_compaction, without):
        for key in ("split_feature", "threshold", "left_child",
                    "right_child", "internal_count", "leaf_count"):
            np.testing.assert_array_equal(a[key], b[key])
        np.testing.assert_allclose(a["leaf_value"], b["leaf_value"],
                                   rtol=1e-4, atol=1e-7)


# ---------------------------------------------------------------------------
# (f) the cell's schedule since PR 31, at a size the CPU's 256 MB holds: at
# 31 leaves the same table keeps the subtraction cache (238 slots of 0.8
# MB), so cache, compaction and `wide` are all on, as on the chip at 255
# ---------------------------------------------------------------------------
CACHED_LEAVES = 31


def _cached_booster(wide, **extra):
    params = dict(wide.base["config"]["params"], num_leaves=CACHED_LEAVES,
                  **extra)
    return lgb.Booster(params, wide.prepared["ds"])


def test_wide_cached_trees_do_not_depend_on_compaction(wide):
    """With the cache a compacted pass gathers the smaller children alone;
    switched off, the same splits, and leaf values up to float32
    summation order."""
    trees = {}
    for name, extra in (("on", {}), ("off", {"tpu_compact_threshold": 0})):
        booster = _cached_booster(wide, **extra)
        for _ in range(3):
            booster.update()
        booster.current_iteration()
        inner = booster._inner
        info = inner._schedule_info
        assert info["wide"] and info["subtract"]
        assert (info["batch_k"], info["chunk"]) == (8, 8192)
        assert info["subtract_cache_bytes"] <= 256 << 20
        assert info["device_bytes"] == 0, "the CPU reports no memory"
        assert info["compact"] == (name == "on")
        compacted = [rec.compact_passes for rec in inner.pass_log]
        assert (min(compacted) > 0) if name == "on" else not any(compacted)
        if name == "on":
            # a smaller child holds at most half its parent's rows, and
            # the nodes of a pass share no row
            assert all(rec.rows_gathered <= rec.compact_passes * WIDE_ROWS / 2
                       for rec in inner.pass_log)
        trees[name] = [wide.mode.tree_arrays(t) for t in inner.models]
    assert len(trees["on"]) == len(trees["off"]) == 3
    for a, b in zip(trees["on"], trees["off"]):
        for key in ("split_feature", "threshold", "left_child",
                    "right_child", "internal_count", "leaf_count"):
            np.testing.assert_array_equal(a[key], b[key])
        np.testing.assert_allclose(a["leaf_value"], b["leaf_value"],
                                   rtol=1e-4, atol=1e-7)


def test_a_cached_compacted_pass_contracts_the_smaller_children(wide):
    """Pass by pass, on the grower itself with every pass forced through
    the gather (`compact_fraction` 1): with the cache a pass contracts
    half the rows it relabelled or fewer; without it, all of them."""
    import jax.numpy as jnp
    from lightgbm_tpu.learner.grow import FMETA_KEYS, grow_tree
    inner = _cached_booster(wide)._inner
    y = jnp.asarray(wide.prepared["y"][:WIDE_ROWS])
    grad, hess = 0.5 - y, jnp.full(WIDE_ROWS, 0.25, jnp.float32)
    weight = jnp.ones(WIDE_ROWS, jnp.float32)
    mask = jnp.ones(inner._num_features_padded, bool)
    grown = {}
    for subtract in (True, False):
        cfg = inner._grower_cfg._replace(hist_subtract=subtract,
                                         compact_fraction=1.0)
        grown[subtract] = grow_tree(
            inner._binned, grad, hess, weight, mask,
            *[inner._fmeta[k] for k in FMETA_KEYS], cfg)
    cached, direct = (
        np.asarray(out.pass_rows)[:int(out.num_passes)]
        for out in (grown[True], grown[False]))
    assert int(grown[True].num_leaves_used) > CACHED_LEAVES // 2
    assert len(cached) == len(direct) > 2
    assert cached[0] == direct[0] == WIDE_ROWS          # the root's
    assert (2 * cached[1:] <= direct[1:]).all(), (cached, direct)
    # the first pass splits the root alone: all its rows relabelled, its
    # smaller child contracted, to the row
    out = grown[True]
    node_count, leaf_count = np.asarray(out.node_count), np.asarray(out.count)
    root_children = [
        int(leaf_count[~kid] if kid < 0 else node_count[kid])
        for kid in (int(out.node_left[0]), int(out.node_right[0]))]
    assert direct[1] == sum(root_children) == WIDE_ROWS
    assert cached[1] == min(root_children)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_rows_of_nodes_is_isin(seed):
    import jax.numpy as jnp
    from lightgbm_tpu.learner.grow import rows_of_nodes
    rng = np.random.default_rng(seed)
    leaf_id = rng.integers(0, 400, size=5000).astype(np.int32)
    # a pass's ids: some of the fresh children, -1 in the empty slots
    nodes = rng.choice(np.arange(300, 400), size=24, replace=False)
    nodes[rng.random(24) < 0.3] = -1
    got = np.asarray(rows_of_nodes(jnp.asarray(leaf_id),
                                   jnp.asarray(nodes.astype(np.int32))))
    np.testing.assert_array_equal(got, np.isin(leaf_id, nodes[nodes >= 0]))
    assert got.dtype == bool and 0 < got.sum() < got.size
