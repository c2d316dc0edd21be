"""The data-parallel deployment (`higgs-10m5x28-dp4`, cell
`higgs-train-dp4`) at a size a test run can hold: node row counts exact
in int32 past 2^24 rows (fed histograms, no rows), the scatter merge's
broadcast of the left count on 4 of conftest's 8 CPU devices, the program
with `tree_learner=data` against the benchmark's plain reference under
the cell's limits, and the cell's files. Nothing here is a device
measurement."""
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from lightgbm_tpu.binning import MISSING_NAN, MISSING_NONE, MISSING_ZERO
from lightgbm_tpu.learner import grow
from lightgbm_tpu.learner.grow import GrowerConfig, GrowParams
from lightgbm_tpu.learner.schedule import (pick_schedule, plan_row_layout,
                                           subtract_cache_bytes)
from lightgbm_tpu.ops import split as split_ops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
for path in (ROOT, BENCH):
    if path not in sys.path:
        sys.path.insert(0, path)

import datagen  # noqa: E402
import run as harness  # noqa: E402

CELL = "higgs-train-dp4"
V5E_BYTES = 16_909_336_064
BINS, GROUPS = 63, 8
# what the root of the cell holds plus one, and the first count float32
# cannot hold
TOTALS = [84_000_001, 2 ** 24 + 1]


# ---------------------------------------------------------------------------
# (a) counts past 2^24 without the rows
# ---------------------------------------------------------------------------
def _count_bins(total, seed, bins=BINS):
    """`bins` integers under 2^24 that sum to `total`, none of them even
    in size: a float32 running sum of them goes wrong."""
    rng = np.random.RandomState(seed)
    cuts = np.sort(rng.randint(1, total, bins - 1))
    cells = np.diff(np.concatenate([[0], cuts, [total]])).astype(np.int64)
    assert cells.sum() == total and cells.max() < 2 ** 24
    return cells


def _histogram(total, missing=MISSING_NONE, categorical=False):
    """[GROUPS, BINS, 3] float32 (g, h, count) whose every feature holds
    `total` rows; feature 5 carries the gradient signal, so it is chosen."""
    hist = np.zeros((GROUPS, BINS, 3), np.float32)
    for f in range(GROUPS):
        cells = _count_bins(total, 100 + f)
        hist[f, :, 2] = cells
        hist[f, :, 1] = 0.25 * cells
        slope = (np.arange(BINS) - 31.0) / 31.0 if f == 5 else 1e-4
        if categorical and f == 5:
            slope = np.where(np.arange(BINS) == 17, 1.0, -0.01)
        hist[f, :, 0] = slope * cells * 0.1
    fmeta = {
        "num_bin": np.full(GROUPS, BINS, np.int32),
        "missing_type": np.full(GROUPS, missing, np.int32),
        "default_bin": np.full(GROUPS, 9, np.int32),
        "is_categorical": np.full(GROUPS, categorical, bool),
        "group": np.arange(GROUPS, dtype=np.int32),
        "offset": np.zeros(GROUPS, np.int32),
        "is_bundled": np.zeros(GROUPS, bool),
    }
    return hist, fmeta


def _cfg(**kw):
    return GrowerConfig(num_leaves=255, max_bins=BINS, chunk=256,
                        lambda_l1=0.0, lambda_l2=0.0, min_gain_to_split=0.0,
                        min_data_in_leaf=1, min_sum_hessian_in_leaf=100.0,
                        max_depth=-1, **kw)


def _left_rows(cells, threshold, default_left, is_cat, missing, default_bin):
    """int64 rows left of the split, by the scan's rules."""
    cells = np.asarray(cells, np.int64)
    if is_cat:
        return int(cells[threshold])
    moving = {MISSING_NAN: BINS - 1, MISSING_ZERO: default_bin}.get(missing)
    left = sum(int(c) for b, c in enumerate(cells)
               if b <= threshold and b != moving)
    if moving is not None and default_left:
        left += int(cells[moving])
    return left


VARIANTS = [("plain", MISSING_NONE, False), ("nan", MISSING_NAN, False),
            ("zero", MISSING_ZERO, False), ("categorical", MISSING_NONE, True)]


@pytest.mark.parametrize("total", TOTALS)
@pytest.mark.parametrize("name,missing,categorical", VARIANTS)
def test_the_chosen_splits_left_count_is_the_int64_sum(total, name, missing,
                                                       categorical):
    hist, fmeta = _histogram(total, missing, categorical)
    cfg = _cfg()
    fmj = {k: jnp.asarray(v) for k, v in fmeta.items()}
    count = split_ops.exact_count(jnp.asarray(hist[0, :, 2]))
    assert count.dtype == jnp.int32 and int(count) == total
    assert int(np.float32(hist[0, :, 2]).sum(dtype=np.float32)) != total \
        or total < 2 ** 24, "float32 would have done"
    tot = hist[0].sum(axis=0)
    gain, feat, thr, dl, cat, lg, lh, left = grow._leaf_best_split(
        jnp.asarray(hist), tot[0], tot[1], count, jnp.int32(0),
        jnp.ones(GROUPS, bool), fmj, cfg, GrowParams.from_config(cfg))
    assert left.dtype == jnp.int32 and float(gain) > 0
    assert int(feat) == 5 and bool(cat) == categorical
    want = _left_rows(hist[5, :, 2], int(thr), bool(dl), bool(cat), missing, 9)
    assert int(left) == want
    # parent minus left is the right child's count, exactly
    assert int(count - left) == total - want
    assert 0 < want < total


@pytest.mark.parametrize("total", TOTALS)
def test_a_bundled_features_default_bin_is_the_parent_less_the_rest(total):
    """A bundle's default bin holds no rows of its own (efb.py): the left
    count takes it as the node's int32 count less the other bins."""
    cells = _count_bins(total, 7, bins=20)
    held = np.zeros(BINS, np.float32)
    held[:20] = cells
    held[3] = 0.0                       # the default bin, as stored
    left = split_ops.exact_left_count(
        jnp.asarray(held), jnp.int32(total), jnp.int32(11), True, False,
        jnp.int32(20), jnp.int32(MISSING_NONE), jnp.int32(3), True)
    assert left.dtype == jnp.int32
    assert int(left) == int(cells[:12].sum())


@pytest.mark.parametrize("total", TOTALS)
def test_the_scatter_merge_broadcasts_the_same_left_count(total):
    """4 of conftest's 8 CPU devices, each holding its owned slice of the
    merged histogram: the winner's int32 left count reaches every shard
    (an int32 psum of one non-zero term) and is the serial scan's."""
    hist, fmeta = _histogram(total)
    devices = jax.devices()[:4]
    assert len(devices) == 4, "conftest gives 8 host devices"
    mesh = Mesh(np.asarray(devices), ("data",))
    cfg = _cfg(data_axis="data", num_data_shards=4, hist_scatter=True)
    gp = GrowParams.from_config(cfg)
    fmj = {k: jnp.asarray(v) for k, v in fmeta.items()}
    gl = GROUPS // 4
    owned = jnp.asarray(np.arange(GROUPS, dtype=np.int32).reshape(4, gl))
    tot = hist[0].sum(axis=0)
    count = jnp.int32(total)

    def body(h):
        s = jax.lax.axis_index("data")
        vals = grow._scattered_best_split(
            h, tot[0], tot[1], count, jnp.int32(0), jnp.ones(GROUPS, bool),
            fmj, owned[s], s * gl, cfg, gp)
        return tuple(v[None] for v in vals)

    out = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=P("data", None, None),
        out_specs=P("data"), check_vma=False))(jnp.asarray(hist))
    serial = grow._leaf_best_split(
        jnp.asarray(hist), tot[0], tot[1], count, jnp.int32(0),
        jnp.ones(GROUPS, bool), fmj, _cfg(), gp)
    left = np.asarray(out[7])
    assert left.dtype == np.int32 and left.shape == (4,)
    assert set(left.tolist()) == {int(serial[7])}
    assert set(np.asarray(out[1]).tolist()) == {5}
    want = _left_rows(hist[5, :, 2], int(serial[2]), bool(serial[3]), False,
                      MISSING_NONE, 9)
    assert int(left[0]) == want


def test_the_growers_counts_are_int32_and_sum_to_the_rows():
    """The hand-off: root, table, carry and finished tree keep int32."""
    rng = np.random.RandomState(1)
    n, f = 4096, 6
    binned = rng.randint(0, 15, (n, f)).astype(np.uint8)
    grad = (binned[:, 0] / 7.0 - 1.0 + 0.2 * rng.randn(n)).astype(np.float32)
    cfg = GrowerConfig(num_leaves=15, max_bins=15, chunk=256, lambda_l1=0.0,
                       lambda_l2=0.0, min_gain_to_split=0.0,
                       min_data_in_leaf=5, min_sum_hessian_in_leaf=1e-3,
                       max_depth=-1)
    fmeta = {"num_bin": np.full(f, 15, np.int32),
             "missing_type": np.zeros(f, np.int32),
             "default_bin": np.zeros(f, np.int32),
             "is_categorical": np.zeros(f, bool),
             "group": np.arange(f, dtype=np.int32),
             "offset": np.zeros(f, np.int32),
             "is_bundled": np.zeros(f, bool)}
    state = grow.make_grower(cfg)(
        jnp.asarray(binned), jnp.asarray(grad), jnp.ones(n, jnp.float32),
        jnp.ones(n, jnp.float32), jnp.ones(f, bool),
        {k: jnp.asarray(v) for k, v in fmeta.items()})
    nl = int(state.num_leaves_used)
    assert state.count.dtype == state.node_count.dtype == jnp.int32
    assert nl == 15 and int(state.count[:nl].sum()) == n
    assert int(state.node_count[0]) == n
    leaves = np.bincount(np.asarray(state.leaf_id), minlength=15)
    np.testing.assert_array_equal(leaves[:nl], np.asarray(state.count)[:nl])
    # the root's fullest count cell, for the host's warning past 2^24
    cells = np.stack([np.bincount(binned[:, j], minlength=15)
                      for j in range(f)])
    assert float(state.root_cell_max) == cells.max()
    assert divmod(int(state.root_cell_at), 15) == tuple(
        int(v) for v in np.unravel_index(cells.argmax(), cells.shape))


def test_a_count_cell_past_2_24_is_said_once_naming_the_feature():
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(2)
    X = rng.randn(600, 3).astype(np.float32)
    X[:, 1] = (X[:, 1] > 0)                    # a two-bin feature
    params = {"objective": "binary", "num_leaves": 4, "max_bin": 15,
              "min_data_in_leaf": 1, "verbose": 1}
    ds = lgb.Dataset(X, (X[:, 0] > 0).astype(np.float32), params=params,
                     feature_name=["pt", "is_tagged", "eta"])
    inner = lgb.Booster(params, ds)._inner
    group = int(inner.train_data.feature_meta_arrays()["group"][1])
    seen = []
    real = lgb.log.warning
    lgb.log.warning = lambda msg, *a: seen.append(msg % a if a else msg)
    try:
        class Small:
            root_cell_max = np.float32(2.0 ** 24 - 1)
            root_cell_at = np.int32(group * inner._max_bins + 1)
        inner._warn_count_cell(Small)
        assert seen == []
        Small.root_cell_max = np.float32(2.0 ** 24)
        inner._warn_count_cell(Small)
        inner._warn_count_cell(Small)
    finally:
        lgb.log.warning = real
    assert len(seen) == 1 and "is_tagged" in seen[0] and "2^24" in seen[0]


# ---------------------------------------------------------------------------
# (c) the cell's files
# ---------------------------------------------------------------------------
def _loaded():
    return harness.load_cell(CELL)


def test_the_configuration_is_the_one_chip_configuration_sharded():
    loaded = _loaded()
    config, entry = loaded["config"], loaded["entry"]
    higgs = harness.load_cell("higgs-train-1chip")["config"]
    assert config["params"] == dict(higgs["params"], tree_learner="data")
    assert not any(key.startswith("tpu_") for key in config["params"])
    assert (config["features"], config["generator"]) \
        == (higgs["features"], higgs["generator"])
    assert config["rows"] == 4 * higgs["rows"] == 84_000_000
    assert config["reduced"] == ["rows", "machines"]
    assert config["chips_sharing_the_job"] == entry["chips"] == 4
    assert config["architecture"] is None
    assert (entry["config"], entry["traffic"]) \
        == ("higgs-10m5x28-dp4", "train_steady")
    bench = loaded["bench"]
    declared, = [c for c in bench["configs"] if c["name"] == entry["config"]]
    assert declared["source"] == config["source"]
    assert len(declared["source"]) <= 200
    assert declared["reduced"] == config["reduced"]
    assert [w["name"] for w in bench["workloads"] if w["chips"] == 4] \
        == [CELL], "the benchmark's one four-chip cell"
    assert set(loaded["cell"]["limits"]) == set(
        harness.load_cell("higgs-train-1chip")["cell"]["limits"])


def test_one_shards_shape_takes_the_cache():
    """What `GBDT.init` asks `pick_schedule` for this cell: one shard's
    rows, the data learner, the v5e's memory, and the cache at the width
    one device keeps of it (7 owned groups under the scatter merge, all
    28 under allreduce)."""
    config = _loaded()["config"]
    rows, groups = int(config["rows"]), int(config["features"])
    bins = int(config["params"]["max_bin"])
    leaves = int(config["params"]["num_leaves"])
    layout = plan_row_layout(rows, groups, bins, tree_learner="data", ndev=4)
    # one row multiple of padding, not the one-shard layout's granule:
    # a shard of 321 chunks, where higgs-train-1chip pads to 384
    assert (layout.chunk, layout.n_pad) == (65536, 84_148_224)
    assert layout.n_pad - rows < layout.row_multiple == 4 * 65536
    assert layout.n_pad // 4 == 21_037_056 == 321 * 65536
    assert plan_row_layout(21_000_000, 28, 63).n_pad == 384 * 65536
    shards = layout.row_multiple // layout.chunk
    assert shards == 4
    shape = (groups, bins, rows // shards, layout.n_pad // shards,
             layout.chunk)
    owned_groups = -(-groups // shards)
    picked = pick_schedule(*shape, num_leaves=leaves, learner="data",
                           device_bytes=V5E_BYTES, cache_groups=owned_groups)
    assert picked.subtract and not picked.compact and not picked.wide
    assert (picked.batch_k, picked.table_mult) == (24, 12)
    # under allreduce a device keeps every group: the same schedule, and
    # it is the one-chip cell's for the same padded shard
    whole = pick_schedule(*shape, num_leaves=leaves, learner="data",
                          device_bytes=V5E_BYTES)
    assert whole == picked
    assert whole == pick_schedule(28, 63, 21_000_000, layout.n_pad // 4,
                                  65536, num_leaves=leaves,
                                  device_bytes=V5E_BYTES)
    at_owned_slice = subtract_cache_bytes(owned_groups, bins, leaves, 12)
    at_every_group = subtract_cache_bytes(groups, bins, leaves, 12)
    assert (owned_groups, at_owned_slice) == (7, 16_468_704)
    assert at_every_group == 65_874_816 == 4 * at_owned_slice
    # padding is a global suffix: the real rows each device holds, the
    # last short by under a chunk (the configuration's `assumed` block
    # tells of the granule-padded layout: 25,165,824 x 3 + 8,502,528)
    per = layout.n_pad // 4
    real = [min(per, max(0, rows - d * per)) for d in range(4)]
    assert real == [21_037_056] * 3 + [20_888_832]
    assert sum(real) == rows


# (rows, stored groups, widest bins) of each cell's shape and a few
# thousand rows; `CELL_PADS` pins what the one-shard layouts pad them to
LAYOUT_SHAPES = [(84_000_000, 28, 63), (21_000_000, 28, 63),
                 (14_680_064, 14, 81), (29_360_128, 11, 255),
                 (12_582_912, 137, 63), (1_048_576, 2000, 63),
                 (6_000, 12, 63)]
CELL_PADS = {21_000_000: 25_165_824, 14_680_064: 14_680_064,
             29_360_128: 29_360_128, 12_582_912: 12_582_912,
             1_048_576: 1_048_576}


def _granule_pad(rows, row_multiple):
    """Rows padded to a row multiple, then up to a coarse power-of-two
    granule of them: the one-shard layouts' plan."""
    m = -(-rows // row_multiple)
    if m > 1:
        g = max(1, (1 << (m - 1).bit_length()) // 8)
        m = -(-m // g) * g
    return m * row_multiple


@pytest.mark.parametrize("rows, groups, bins", LAYOUT_SHAPES)
@pytest.mark.parametrize("ndev", [1, 2, 4, 8])
@pytest.mark.parametrize("learner", ["serial", "data", "voting", "feature"])
def test_a_row_sharded_layout_pads_one_row_multiple(learner, ndev, rows,
                                                    groups, bins):
    """Rows sharded over devices pad to one row multiple, so the last
    shard is short by under `ndev` chunks; every other layout keeps the
    granule, and the one-chip cells their padded rows."""
    from lightgbm_tpu.telemetry import layers
    layout = plan_row_layout(rows, groups, bins, tree_learner=learner,
                             ndev=ndev)
    assert layout.n_pad % layout.row_multiple == 0
    if learner in ("data", "voting") and ndev > 1:
        assert layout.row_multiple == ndev * layout.chunk
        assert 0 <= layout.n_pad - rows < layout.row_multiple
        real = layers.shard_rows(rows, layout.n_pad, ndev)
        assert sum(real) == rows
        shard = layout.n_pad // ndev
        if shard > ndev * layout.chunk:
            assert min(real) / max(real) >= 1 - ndev * layout.chunk / shard
        if (rows, ndev) == (84_000_000, 4):          # higgs-train-dp4
            assert layout.n_pad == 84_148_224
            assert min(real) / max(real) > 0.99
    else:
        assert layout.row_multiple == layout.chunk
        assert layout.n_pad == _granule_pad(rows, layout.chunk)
        if rows in CELL_PADS and learner == "serial":
            assert layout.n_pad == CELL_PADS[rows]


def test_the_merge_reader_reads_the_record_and_nothing_on_one_device():
    from lightgbm_tpu import telemetry
    read = datagen.load_file_module(
        os.path.join(BENCH, "layer_metrics", "merge.comm_mb_per_tree.py"),
        "reader_merge_comm").read
    rec = telemetry.TreeRecord(30, 700, 5e6, 9.0e5, 3.6e6)
    other = rec._replace(comm_bytes=4.0e6)
    ctx = {"schedule": {"num_shards": 4},
           "pass_log_window": [list(rec), list(other)]}
    assert read(ctx) == pytest.approx(3.8)
    assert read(dict(ctx, schedule={"num_shards": 1})) is None
    assert read({"schedule": {"num_shards": 4}}) is None
    assert read({}) is None
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    entry, balance = [m for m in bench["per_layer"]
                      if "workloads" in m and m["layer"] == "data-parallel"]
    assert balance["name"] == "merge.shard_balance"     # PR 36
    assert balance["workloads"] == [CELL]
    assert entry["name"] == "merge.comm_mb_per_tree"
    assert entry["workloads"] == [CELL]
    assert (entry["layer"], entry["moves"], entry["source"]) == (
        "data-parallel", "train_mrow_iters_per_s", "program_counter")


@pytest.mark.parametrize("name", ["split_gap", "gain_gap", "leaf_gap",
                                  "score_gap", "loss_gap", "bin_pop_gap"])
def test_each_limit_lies_between_this_shapes_two_readings(name):
    """PERF.md section 2's rule on the readings the cell's file carries
    (tests/test_wide_config.py holds Epsilon's to the same)."""
    cell = _loaded()["cell"]
    limit, read = cell["limits"][name], cell["limits_set_from"]
    assert 3 * read["lower_largest_sound_reading"][name] < limit
    upper = read["upper_smallest_control_reading"][name]
    faults = [f[name] for f in read["smallest_fault_reading"].values()
              if name in f]
    assert 2 * limit < upper            # every number has a control reading
    assert all(limit < f for f in faults)
    for value in read.get("control_per_data_set", {}).get(name, []):
        assert value > limit


# ---------------------------------------------------------------------------
# (b) the program with tree_learner=data on 4 CPU devices against the plain
# reference, under the cell's limits
# ---------------------------------------------------------------------------
# 14,000 x 28 at 255 leaves. `tpu_hist_chunk` 2048 is the test's steering,
# not the deployment's: the row plan then gives four shards of 4,096 rows,
# three of them full and the last holding 1,712 real rows and the padding,
# which is the cell's layout (a global suffix); at the default chunk every
# real row of so small a table would sit on the first device.
DP_ROWS, DP_CHUNK = 14_000, 2048


class _Sharded:
    def __init__(self):
        loaded = harness.load_cell(CELL)
        self.mode = harness.load_mode(loaded["traffic"])
        traffic = dict(loaded["traffic"], warmup_iterations=1)
        self.base = {
            "cell": loaded["cell"], "traffic": traffic,
            "config": loaded["config"],
            "params_override": {"tpu_hist_chunk": DP_CHUNK},
            "seed": 3000000019, "seconds": 0.0, "trace": False,
            "rows": DP_ROWS, "t_start": time.perf_counter(),
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
def sharded():
    """The cell's four devices out of conftest's eight, for this module's
    boosters: the program spreads the rows over every device it finds."""
    patch = pytest.MonkeyPatch()
    four = jax.devices()[:4]
    patch.setattr(jax, "devices", lambda *a, **k: four)
    try:
        yield _Sharded()
    finally:
        patch.undo()


def test_sharded_program_is_correct_and_control_is_not(sharded):
    out = sharded.sound()
    info = out["schedule"]
    assert (info["tree_learner"], info["num_shards"], info["hist_reduce"]) \
        == ("data", 4, "scatter")
    assert info["owned_groups"] == 7 and info["rows_padded"] == 4 * 4096
    assert info["subtract"] and not info["compact"]
    assert (info["batch_k"], info["table_mult"]) == (24, 12)
    assert info["subtract_cache_bytes"] == 16_468_704, "7 owned groups"
    assert out["correct"], out["compared"]
    assert out["compared"]["count_mismatch"]["value"] == 0
    assert not out["control_correct"], out["control_compared"]
    from lightgbm_tpu import telemetry
    records = [telemetry.TreeRecord(*e) for e in out["pass_log_window"]]
    assert min(r.comm_bytes for r in records) > 0, "the merge ran"
    # what one device keeps of a pass's merge is the K smaller children
    # at its 7 owned groups, not both children of every node; the root's
    # pass merges one histogram
    # (with the root's three totals)
    kept = 7 * 63 * 3 * 4
    for r in records:
        assert r.comm_bytes == ((r.num_passes - 1) * 24 + 1) * kept + 12
        assert r.rows_contracted == r.num_passes * DP_ROWS
    wait = [e[telemetry.TreeRecord._fields.index("fetch_wait_s")]
            for e in out["pass_log_window"]]
    assert min(wait) > 0, "the tree is fetched one iteration late"


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
def test_sharded_broken_timed_path_is_not_correct(sharded, fault):
    """The planted faults wrap `gbdt._grow_and_update`; the data-parallel
    learner's fused program is dispatched through it."""
    out = sharded.run(fault=fault)
    assert not out["correct"], out["compared"]
    failed = [n for n, row in out["compared"].items()
              if not row["value"] <= row["limit"]]
    assert len(failed) >= 2, out["compared"]


def test_sharded_trees_are_the_serial_trees(sharded):
    """Up to float32 summation order: the same splits, the same exact row
    counts, the landing's span and seconds on record."""
    import lightgbm_tpu as lgb
    dp = sharded.sound()["trees_window"]
    params = dict(sharded.base["config"]["params"], tree_learner="serial",
                  tpu_hist_chunk=DP_CHUNK)
    booster = lgb.Booster(params, sharded.prepared["ds"])
    for _ in range(1 + len(dp)):
        booster.update()
    booster.current_iteration()
    serial = [sharded.mode.tree_arrays(t) for t in booster._inner.models[1:]]
    assert len(serial) == len(dp) >= 1
    for a, b in zip(dp, serial):
        for key in ("split_feature", "threshold", "left_child",
                    "right_child", "internal_count", "leaf_count"):
            np.testing.assert_array_equal(a[key], b[key])
        assert int(a["leaf_count"].sum()) == DP_ROWS
        np.testing.assert_allclose(a["leaf_value"], b["leaf_value"],
                                   rtol=1e-4, atol=1e-7)
    assert booster._inner.init_record.shard_rows == (DP_ROWS,)
    sharded_booster = lgb.Booster(
        dict(sharded.base["config"]["params"], tpu_hist_chunk=DP_CHUNK),
        sharded.prepared["ds"])
    inner = sharded_booster._inner
    assert inner.init_record.land_s > 0.0 and inner._row_sharded
    assert len(inner._binned.addressable_shards) == 4
    assert inner._score.sharding.spec == P(None, "data")


# 6,000 x 12 at 31 leaves through `lgb.train`; `tpu_hist_chunk` 512 steers
# the row plan to four shards of 1,536 rows, the last holding 1,392 real
# ones (see DP_CHUNK above)
TRAIN_ROWS, TRAIN_ROUNDS = 6000, 4


_TRAINED = {}


def _trained(sharded, **extra):
    """(trees as arrays, pass records, schedule) of one `lgb.train` run,
    kept: the serial side is the same for both merges."""
    key = tuple(sorted(extra.items()))
    if key not in _TRAINED:
        _TRAINED[key] = _train(sharded, extra)
    return _TRAINED[key]


def _train(sharded, extra):
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(5)
    X = rng.randn(TRAIN_ROWS, 12).astype(np.float32)
    margin = X[:, 0] + 0.6 * X[:, 1] * X[:, 2] - 0.4 * np.abs(X[:, 3])
    y = (margin + 0.5 * rng.randn(TRAIN_ROWS) > 0).astype(np.float32)
    params = dict({"objective": "binary", "num_leaves": 31, "max_bin": 63,
                   "min_data_in_leaf": 5, "learning_rate": 0.1,
                   "tpu_hist_chunk": 512, "verbose": -1}, **extra)
    booster = lgb.train(params, lgb.Dataset(X, y, params=params),
                        num_boost_round=TRAIN_ROUNDS, verbose_eval=False)
    inner = booster._inner
    return ([sharded.mode.tree_arrays(t) for t in inner.models],
            list(inner.pass_log), inner._schedule_info)


@pytest.mark.parametrize("bagging", [
    {}, {"bagging_fraction": 0.5, "bagging_freq": 1, "bagging_seed": 3}],
    ids=["all_rows", "bagging"])
@pytest.mark.parametrize("reduce", ["scatter", "allreduce"])
def test_the_trained_data_learner_keeps_the_cache_and_the_serial_trees(
        sharded, reduce, bagging):
    """The program, not `grow_tree` alone: `lgb.train` with the fused
    sharded program on the cache's path against the serial learner, which
    takes the same schedule for the same shape."""
    dp, dp_log, info = _trained(sharded, tree_learner="data",
                                tpu_hist_reduce=reduce, **bagging)
    serial, serial_log, serial_info = _trained(sharded, **bagging)
    assert (info["tree_learner"], info["num_shards"], info["hist_reduce"]) \
        == ("data", 4, reduce)
    assert info["rows_padded"] == 4 * 1536
    assert info["owned_groups"] == (3 if reduce == "scatter" else 12)
    assert info["subtract"] and serial_info["subtract"]
    assert (info["batch_k"], info["table_mult"]) \
        == (serial_info["batch_k"], serial_info["table_mult"]) == (24, 12)
    assert info["subtract_cache_bytes"] * 12 \
        == serial_info["subtract_cache_bytes"] * info["owned_groups"]
    assert len(dp) == len(serial) == TRAIN_ROUNDS
    for a, b in zip(dp, serial):
        for key in ("split_feature", "threshold", "left_child",
                    "right_child", "internal_count", "leaf_count"):
            np.testing.assert_array_equal(a[key], b[key])
        np.testing.assert_allclose(a["leaf_value"], b["leaf_value"],
                                   rtol=1e-4, atol=1e-7)
    assert len(dp_log) == len(serial_log) == TRAIN_ROUNDS
    for rec, want in zip(dp_log, serial_log):
        # the smaller child of each of K nodes a pass, every pass full
        assert rec.num_passes == want.num_passes
        assert rec.rows_contracted == rec.num_passes * TRAIN_ROWS \
            == want.rows_contracted
        assert (rec.full_passes, rec.compact_passes) == (rec.num_passes, 0)
        assert rec.comm_bytes > 0 == want.comm_bytes


# One row past 16 row multiples of 4 x 256: the power-of-two granule
# padded this to 20 of them and left the last shard 1,025 real rows of
# 5,120; one row multiple of padding leaves it 3,329 of 4,352
EVEN_ROWS, EVEN_CHUNK, EVEN_SHARDS = 16_385, 256, (4_352,) * 3 + (3_329,)


def _even_data():
    rng = np.random.RandomState(9)
    X = rng.randn(EVEN_ROWS, 12).astype(np.float32)
    y = (X[:, 0] - 0.7 * X[:, 1] * X[:, 4] + 0.5 * rng.randn(EVEN_ROWS)
         > 0).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 31, "max_bin": 63,
              "min_data_in_leaf": 5, "learning_rate": 0.1,
              "tpu_hist_chunk": EVEN_CHUNK, "verbose": 0}
    return X, y, params


def test_the_data_learner_evens_its_shards_and_keeps_the_serial_trees(
        sharded):
    """`tree_learner=data` on four devices at a row count whose granule
    left the last shard mostly padding: the shards hold real rows to
    within four chunks, the serial learner keeps its granule, and the
    trees are the serial learner's."""
    import lightgbm_tpu as lgb
    X, y, params = _even_data()
    inners = {}
    for learner in ("data", "serial"):
        p = dict(params, tree_learner=learner)
        inners[learner] = lgb.train(p, lgb.Dataset(X, y, params=p),
                                    num_boost_round=3,
                                    verbose_eval=False)._inner
    dp, serial = inners["data"], inners["serial"]
    assert dp._n_pad == 17 * 4 * EVEN_CHUNK
    assert serial._n_pad == 80 * EVEN_CHUNK
    shards = dp.init_record.shard_rows
    assert shards == EVEN_SHARDS and sum(shards) == EVEN_ROWS
    assert max(shards) - min(shards) < 4 * EVEN_CHUNK
    assert serial.init_record.shard_rows == (EVEN_ROWS,)
    assert len(dp.models) == len(serial.models) == 3
    for a, b in zip(dp.models, serial.models):
        a, b = (sharded.mode.tree_arrays(t) for t in (a, b))
        for key in ("split_feature", "threshold", "left_child",
                    "right_child", "internal_count", "leaf_count"):
            np.testing.assert_array_equal(a[key], b[key])
        assert int(a["leaf_count"].sum()) == EVEN_ROWS
        np.testing.assert_allclose(a["leaf_value"], b["leaf_value"],
                                   rtol=1e-4, atol=1e-7)


def test_a_sharded_landing_of_the_even_layout_is_reused(sharded):
    """`tpu_ingest_device_shards` plans its landing with the trainer's
    row plan, so the landed shards are the trainer's matrix as they
    stand: no gather to the host, no re-pad."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu import log
    X, y, params = _even_data()
    params = dict(params, tree_learner="data")
    lines = []
    log.register_callback(lines.append)
    try:
        ds = lgb.Dataset(X, y, params=dict(
            params, tpu_ingest_device_shards=True))
        landed = ds.construct()._inner.device_binned
        assert landed is not None and landed.shape[0] == 17 * 4 * EVEN_CHUNK
        inner = lgb.Booster(params, ds)._inner
    finally:
        log.register_callback(None)
    assert not [line for line in lines if "re-padding" in line]
    assert inner._binned is landed
    assert inner.init_record.shard_rows == EVEN_SHARDS
    assert len(inner._binned.addressable_shards) == 4


def test_the_landing_span_is_in_the_profilers_trace(sharded, tmp_path):
    """`lgbm/init/land` (telemetry.INIT_SPANS) times the upload itself,
    waited for: a profiler session around `Booster` finds it beside the
    other phases of `GBDT.init`, and its seconds are `init_record.land_s`."""
    import glob

    import lightgbm_tpu as lgb
    from lightgbm_tpu import telemetry
    from lightgbm_tpu.telemetry import devtrace
    ran = set(telemetry.INIT_SPANS) - {"lgbm/init/gate"}   # no quantisation
    params = dict(sharded.base["config"]["params"], tpu_hist_chunk=DP_CHUNK)
    with jax.profiler.trace(str(tmp_path)):
        inner = lgb.Booster(params, sharded.prepared["ds"])._inner
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = devtrace.host_spans(path)
    assert ran <= set(spans)
    assert 0.0 < spans["lgbm/init/land"] \
        <= inner.init_record.land_s * 1.5 + 1e-3
