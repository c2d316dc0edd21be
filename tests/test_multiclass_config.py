"""The multiclass deployment (`covtype-581kx54`, cell
`covtype-multiclass-1chip`) at a size a test run can hold: the program
against the benchmark's plain reference class by class through
`Booster.update()`, the pipelined multiclass loop against the same
loop drained at once, the schedule the configuration's shape takes, the
per-iteration record and its readers, the faults the limits must catch,
the generator and what `BENCHMARK.json` says of the cell. Nothing here is
a device measurement."""
import os
import sys
import time

import numpy as np
import pytest

import jax

import lightgbm_tpu as lgb
from lightgbm_tpu import telemetry
from lightgbm_tpu.learner.schedule import (RELABEL_BLOCK, pick_schedule,
                                           plan_row_layout, relabel_rows,
                                           subtract_cache_bytes)
from lightgbm_tpu.telemetry import devtrace, layers

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
for path in (ROOT, BENCH):
    if path not in sys.path:
        sys.path.insert(0, path)

import datagen  # noqa: E402
import faults_multiclass  # noqa: E402
import reference_multiclass  # noqa: E402
import run as harness  # noqa: E402

CELL = "covtype-multiclass-1chip"
V5E_BYTES = 16_909_336_064
synth_covtype = datagen.load_file_module(
    os.path.join(BENCH, "generators", "synth_covtype.py"),
    "synth_covtype_tests")


def _failed(compared):
    return {n for n, row in compared.items()
            if not row["value"] <= row["limit"]}


TREE_NUMBERS = set(reference_multiclass.COMPARED)


# ---------------------------------------------------------------------------
# (a) the program against the plain reference, class by class
# ---------------------------------------------------------------------------
class _Multiclass:
    """The cell's mode at 6,000 rows of the cell's own table, one warm-up
    iteration, the data built once."""
    ROWS = 6000

    def __init__(self):
        loaded = harness.load_cell(CELL)
        self.mode = harness.load_mode(loaded["traffic"])
        self.base = {
            "cell": loaded["cell"],
            "traffic": dict(loaded["traffic"], warmup_iterations=1),
            "config": loaded["config"],
            "seed": 3000000040, "seconds": 0.0, "trace": False,
            "rows": self.ROWS, "t_start": time.perf_counter(),
            "limits": loaded["cell"]["limits"], "rehearsal": True}
        self.prepared = self.mode.prepare(self.base)
        self._sound = None

    def run(self, **extra):
        return self.mode.run(dict(self.base, prepared=self.prepared, **extra))

    def sound(self):
        if self._sound is None:
            self._sound = self.run(control=True)
        return self._sound


@pytest.fixture(scope="module")
def multiclass():
    return _Multiclass()


def test_multiclass_program_is_correct_and_control_is_not(multiclass):
    out = multiclass.sound()
    assert out["correct"], out["compared"]
    assert set(out["compared"]) == set(reference_multiclass.COMPARED) | {
        "window_compiles", "stopped_iterations"}
    assert not out["control_correct"], out["control_compared"]
    assert {"score_gap", "loss_gap"} <= _failed(out["control_compared"])
    # the warm-up iteration and the window's last, all seven class trees
    # of each followed
    assert len(out["steps"]) == 2
    for step in out["steps"]:
        assert len(step["classes"]) == 7
        for tree in step["classes"]:
            assert tree["count_mismatch"] == 0
            assert tree["split_gap"] == 0.0
            assert tree["leaf_gap"] < 1e-5
            assert tree["leaves"] > 1
        assert step["loss_gap"] < 1e-6
        assert step["loss_program"] < np.log(7.0)
    assert out["classes"] == 7
    assert len(out["trees_window"]) == 7 * out["iterations"]
    assert len(out["pass_log_window"]) == out["iterations"]


def test_split_and_gain_allowance_is_read_with_and_without(multiclass):
    """Each class's reading carries the gaps with no allowance beside
    the judged ones, and the nodes whose best gain lies inside it: the
    rare classes' first-iteration nodes, not every node."""
    for step in multiclass.sound()["steps"]:
        for tree in step["classes"]:
            for gap in ("split_gap", "gain_gap"):
                assert tree[gap] <= tree[gap + "_bare"]
            assert 0 <= tree["zero_gain_nodes"] < tree["leaves"] - 1
    first = multiclass.sound()["steps"][0]["classes"]
    # the largest class's first tree splits on real gains alone
    assert first[1]["zero_gain_nodes"] == 0
    assert max(tree["gain_gap_bare"] for tree in first) > 1e-3


@pytest.mark.parametrize("fault", faults_multiclass.FAULTS)
def test_multiclass_broken_timed_path_is_not_correct(multiclass, fault):
    out = multiclass.run(fault=fault)
    assert not out["correct"], out["compared"]
    assert _failed(out["compared"]) & TREE_NUMBERS, out["compared"]
    if fault == "class_uncoupled":
        # the sigmoid of a class's own score is no softmax: every leaf
        # value and the loss move, whatever the splits
        assert {"leaf_gap", "score_gap", "loss_gap"} <= _failed(
            out["compared"])


def test_class_uncoupled_is_undone_after_the_run(multiclass):
    with faults_multiclass.planted("class_uncoupled"):
        pass
    out = multiclass.run()
    assert out["correct"], out["compared"]


def test_reference_reads_a_one_leaf_class_tree_by_its_root():
    """A class tree the program left at one leaf is judged by whether the
    reference's root could split: not where no split meets the hessian
    floor, infinitely where one does."""
    X, y = synth_covtype.generate(1000, 54, 5)
    cuts = [np.unique(X[:, j]).astype(np.float64) for j in range(54)]
    cuts = [np.append(c[:62], np.inf) for c in cuts]
    leaf = {"split_feature": np.zeros(0, np.int64),
            "threshold": np.zeros(0), "left_child": np.zeros(0, np.int64),
            "right_child": np.zeros(0, np.int64),
            "split_gain": np.zeros(0), "internal_count": np.zeros(0, np.int64),
            "leaf_value": np.zeros(1), "leaf_count": np.asarray([1000])}
    for floor, want in ((1e9, 0.0), (1.0, np.inf)):
        ref = reference_multiclass.MulticlassReference(
            X, y, cuts, num_class=7, num_leaves=4, learning_rate=0.1,
            min_sum_hessian_in_leaf=floor, min_data_in_leaf=1)
        step = ref.follow([leaf] * 7, np.zeros((7, 1000), np.float32))
        assert step["split_gap"] == want
        assert step["leaf_gap"] == 0.0 and step["score_gap"] == 0.0
        assert step["loss_gap"] == 0.0


# ---------------------------------------------------------------------------
# (b) the pipelined multiclass loop against the same loop drained
# ---------------------------------------------------------------------------
PARAMS = {"objective": "multiclass", "num_class": 7, "num_leaves": 15,
          "max_bin": 63, "learning_rate": 0.1, "min_data_in_leaf": 1,
          "min_sum_hessian_in_leaf": 100.0, "verbose": -1}


@pytest.fixture(scope="module")
def table():
    return synth_covtype.generate(3000, 54, 7, base_seed=40)


def _booster(table, sync, **params):
    X, y = table
    ds = lgb.Dataset(X, y, params=dict(PARAMS, **params))
    booster = lgb.Booster(dict(PARAMS, **params), ds)
    if sync:
        booster._inner._supports_pipeline = False
    return booster


def test_pipelined_multiclass_equals_the_synchronous_loop(table):
    texts, logs = [], []
    for sync in (False, True):
        booster = _booster(table, sync)
        inner = booster._inner
        for i in range(5):
            assert booster.update() is False
            # the chip runs iteration i while the host builds i-1's trees
            assert (inner._pending_small is not None) == (not sync)
            assert len(inner.models) == 7 * (i + (1 if sync else 0))
        texts.append(booster.model_to_string())
        logs.append(list(inner.pass_log))
    assert texts[0] == texts[1]
    assert len(logs[0]) == len(logs[1]) == 5
    for piped, synced in zip(*logs):
        assert piped.classes == synced.classes == 7
        assert piped.class_passes == synced.class_passes
        assert len(piped.class_passes) == 7
        # a count of the record is the mean over the class trees grown one
        # after another: x classes it is what the program ran
        assert piped.classes * piped.num_passes == pytest.approx(
            sum(piped.class_passes), rel=1e-15)
        assert piped.rows_contracted == synced.rows_contracted
        assert piped.full_passes == piped.num_passes
        assert piped.compact_passes == piped.rows_gathered == 0


def test_an_iteration_in_which_no_class_grows_is_rolled_back_whole(table):
    """No class tree can split (the hessian floor is over the table's),
    so the first iteration is rolled back with its seven trees; the
    pipelined loop reports it one call late, as the one-class loop
    does, and unwinds the speculative iteration dispatched meanwhile."""
    sync = _booster(table, True, min_sum_hessian_in_leaf=1e9)
    assert sync.update() is True
    piped = _booster(table, False, min_sum_hessian_in_leaf=1e9)
    before = np.asarray(piped._inner._score)
    assert piped.update() is False        # dispatched, not yet seen
    assert piped.update() is True         # seen: rolled back
    for booster in (sync, piped):
        inner = booster._inner
        assert inner.models == [] and booster.current_iteration() == 0
        assert inner._pending_small is None
        assert all(r.classes == 7 and max(r.class_passes) == 1
                   for r in inner.pass_log)
    np.testing.assert_array_equal(np.asarray(piped._inner._score), before)
    assert piped.model_to_string() == sync.model_to_string()


def test_the_stop_is_reported_once_and_training_may_resume(table):
    piped = _booster(table, False, min_sum_hessian_in_leaf=1e9)
    piped.update()
    piped._inner.finalize_training()     # a drain sees the stop first
    assert piped.update() is True        # ... and the next call reports it
    assert piped._inner.models == []


def test_a_validation_set_keeps_multiclass_synchronous(table):
    X, y = table
    train = lgb.Dataset(X, y, params=PARAMS)
    booster = lgb.Booster(dict(PARAMS), train)
    booster.update()
    assert booster._inner._pending_small is not None
    booster.add_valid(lgb.Dataset(X[:500], y[:500], reference=train,
                                  params=PARAMS), "valid")
    booster.update()                     # drains the pending iteration first
    inner = booster._inner
    assert inner._pending_small is None and len(inner.models) == 14
    assert [r.classes for r in inner.pass_log] == [7, 7]


def test_the_class_trees_are_k_calls_of_the_one_class_program(table):
    """The multiclass program grows class tree c as the one-class
    grow+update does on class c's gradients under the same
    `GrowerConfig`, one class after another: the same trees to the bit
    (model text), the same small state, the same score."""
    import jax.numpy as jnp
    from lightgbm_tpu.boosting import gbdt
    from lightgbm_tpu.learner.grow import FMETA_KEYS
    from lightgbm_tpu.tree import Tree

    booster = _booster(table, True)
    inner = booster._inner
    booster.update()                 # a score at which the classes differ
    k, cfg = 7, inner._grower_cfg
    assert cfg.hist_subtract and cfg.relabel_rows > 0
    grad, hess = inner._compute_gradients(inner._score)
    grad, hess = grad.reshape(k, -1), hess.reshape(k, -1)
    rng = np.random.default_rng(41)
    masks = rng.random((k, inner._num_features_padded)) < 0.8
    fmeta = [inner._fmeta[key] for key in FMETA_KEYS]
    common = (inner._binned, grad, hess, inner._base_weight,
              jnp.asarray(masks), 0.1, inner._n, fmeta)
    score, small = gbdt._grow_and_update_multi(inner._score, *common, cfg)
    multi = gbdt._HostState(jax.device_get(small))

    one_score = inner._score
    for c in range(k):
        one_score, one = gbdt._grow_and_update(
            one_score, inner._binned, grad[c], hess[c], inner._base_weight,
            jnp.asarray(masks[c]), 0.1, inner._n, fmeta, c, cfg)
        one = jax.device_get(one)
        mine = gbdt._class_state(multi, c)
        for key, value in one.items():
            np.testing.assert_array_equal(getattr(mine, key), value,
                                          err_msg=f"class {c}: {key}")
        text = Tree.from_grower_state(mine, inner.train_data).to_string()
        assert text == Tree.from_grower_state(
            gbdt._HostState(one), inner.train_data).to_string()
        assert int(mine.num_leaves_used) > 1
    np.testing.assert_array_equal(np.asarray(score), np.asarray(one_score))


# ---------------------------------------------------------------------------
# (c) the schedule of the configuration's own shape
# ---------------------------------------------------------------------------
def test_the_cells_shape_takes_the_direct_path():
    """A schedule is one tree's: the cell's 7 class trees run one after
    another, each on the one-class schedule of 14 groups x 81 bins, so
    each takes the subtraction cache at 24 nodes a pass and the blocked
    relabel, as the one-class cells do; the direct path is gone."""
    config = harness.load_cell(CELL)["config"]
    rows = int(config["rows"])
    layout = plan_row_layout(rows, 14, 81)
    assert (layout.n_pad, layout.chunk) == (rows, 65536)   # no padded row
    picked = pick_schedule(14, 81, rows, rows, 65536, num_leaves=255,
                           learner="serial", bundled=True,
                           device_bytes=V5E_BYTES, cache_groups=14)
    # the cache with the larger node table, no compaction
    assert (picked.subtract, picked.compact, picked.table_mult) == (
        True, False, 12)
    assert picked.batch_k == 24
    assert picked.compact_model.fraction == 0.0
    # (12 x 255 + 52) slots x 14 groups x 81 bins x 3 float32: 42 MB, one
    # class tree's, which the program holds once for the seven
    assert subtract_cache_bytes(14, 81, 255, picked.table_mult) \
        == 42_348_096
    assert relabel_rows(14, 81, picked.batch_k, rows) == RELABEL_BLOCK


# ---------------------------------------------------------------------------
# (d) the record, its readers, the softmax scope
# ---------------------------------------------------------------------------
def test_tree_record_carries_the_classes_and_their_passes():
    fields = telemetry.TreeRecord._fields
    assert fields[-2:] == ("classes", "class_passes")
    rec = telemetry.TreeRecord(3, 9, 1e3, 0.0, 0.0)
    assert (rec.classes, rec.class_passes) == (1, ())


def test_a_one_class_booster_records_one_class(table):
    X, y = table
    params = {"objective": "binary", "num_leaves": 7, "verbose": -1}
    booster = lgb.Booster(params, lgb.Dataset(X, (y == 1).astype(
        np.float32), params=params))
    for _ in range(2):
        booster.update()
    booster._inner.finalize_training()
    for rec in booster._inner.pass_log:
        assert rec.classes == 1 and rec.class_passes == (rec.num_passes,)


def _reader(name):
    return datagen.load_file_module(
        os.path.join(BENCH, "layer_metrics", name + ".py"),
        "reader_" + name.replace(".", "_")).read


def test_a_sequential_iterations_record_counts_what_the_program_ran(table):
    """One record of K class trees grown one after another: `classes` x
    `num_passes` is the sum of `class_passes` and `classes` x
    `rows_contracted` the rows the K trees contracted, so no pass is
    counted for a finished tree and `grower.class_pass_waste` reads 0."""
    from lightgbm_tpu.boosting import gbdt
    booster = _booster(table, True)
    inner = booster._inner
    width = 8
    rows = np.zeros((3, width), np.int32)
    rows[0, :2] = (3000, 1200)
    rows[1, :4] = (3000, 1700, 900, 250)
    rows[2, :3] = (3000, 800, 333)
    ones = np.ones(3)
    inner._log_pass_economics(gbdt._HostState({
        "num_passes": np.asarray([2, 4, 3], np.int32), "pass_rows": rows,
        "next_free": np.asarray([5, 11, 7], np.int32),
        "comm_elems": 0 * ones, "root_cell_max": 0 * ones,
        "root_cell_at": np.zeros(3, np.int32)}))
    rec = inner.pass_log[-1]
    assert (rec.classes, rec.class_passes) == (3, (2, 4, 3))
    assert rec.num_passes == 3.0 and rec.full_passes == 3.0
    assert 3 * rec.rows_contracted == pytest.approx(
        4200 + 5850 + 4133, rel=1e-15)
    assert rec.table_high_water == 11
    assert rec.compact_passes == rec.rows_gathered == 0

    for _ in range(3):
        booster.update()
    window = [list(r) for r in inner.pass_log[1:]]
    assert all(r[15] == 7 for r in window)
    read = _reader("grower.class_pass_waste")
    assert read({"pass_log_window": window,
                 "traced_iterations": [0, 3]}) == pytest.approx(0.0, abs=1e-12)


def _record(**values):
    rec = telemetry.TreeRecord(0, 0, 0.0, 0.0, 0.0)._replace(**values)
    return list(rec)


def test_class_pass_waste_is_the_share_of_passes_of_finished_trees():
    window = [_record(num_passes=10, classes=7,
                      class_passes=(10, 9, 8, 10, 5, 6, 7)),
              _record(num_passes=12, classes=7,
                      class_passes=(12,) * 7),
              _record(num_passes=20, classes=7, class_passes=(1,) * 7)]
    read = _reader("grower.class_pass_waste")
    ctx = {"pass_log_window": window, "traced_iterations": [0, 2]}
    assert read(ctx) == pytest.approx(100.0 * (1 - (55 + 84) / (7 * 22)))
    assert read(dict(ctx, traced_iterations=[1, 2])) == 0.0
    assert read(dict(ctx, traced_iterations=None)) is None
    one = [_record(num_passes=10, classes=1, class_passes=(10,))] * 3
    assert read({"pass_log_window": one, "traced_iterations": [0, 2]}) is None
    # a record without the fields (the parent's) has nothing to read
    assert read({"pass_log_window": [r[:15] for r in window],
                 "traced_iterations": [0, 2]}) is None


def test_class_roofline_share_counts_every_class_at_the_stored_groups():
    window = [_record(rows_contracted=1e9, classes=7)] * 3
    scopes = {"lgbm/hist/contract": 3.0, "lgbm/hist/operand": 1.0,
              "lgbm/grow/relabel": 2.0}
    peaks = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    ctx = {"pass_log_window": window, "traced_iterations": [1, 3],
           "schedule": {"groups": 14}, "peaks": peaks,
           "trace_scopes": {"/device:TPU:0": scopes}}
    # two iterations of 1e9 rows: 14 bytes of bins once, 8 a class
    least = 2 * 1e9 * (14 + 8 * 7) / 819e9
    assert _reader("hist.class_roofline_share")(ctx) == pytest.approx(
        100.0 * least / 4.0)
    for drop in ("peaks", "trace_scopes", "schedule", "traced_iterations"):
        assert _reader("hist.class_roofline_share")(
            dict(ctx, **{drop: None})) is None
    one = dict(ctx, pass_log_window=[_record(rows_contracted=1e9)] * 3)
    assert _reader("hist.class_roofline_share")(one) is None


def test_the_softmax_runs_under_its_own_scope():
    assert "lgbm/gradients/softmax" in layers.SCOPES
    assert devtrace.scope_of(
        "jit(f)/lgbm/gradients/lgbm/gradients/softmax/exp") \
        == "lgbm/gradients/softmax"
    from lightgbm_tpu.boosting.gbdt import _gradient_jit
    X, y = synth_covtype.generate(300, 54, 1)
    booster = lgb.Booster(dict(PARAMS), lgb.Dataset(X, y, params=PARAMS))
    inner = booster._inner
    fn, keys = _gradient_jit(inner.objective)
    arrs = {k: getattr(inner.objective, k) for k in keys}
    text = fn.lower(inner._score, arrs).as_text(debug_info=True)
    assert "lgbm/gradients/softmax" in text


def test_a_program_without_the_pipelined_multiclass_loop_is_refused(
        monkeypatch):
    from typing import NamedTuple

    class Old(NamedTuple):
        num_passes: int

    monkeypatch.setattr(telemetry, "TreeRecord", Old)
    mode = harness.load_mode(harness.load_cell(CELL)["traffic"])
    with pytest.raises(SystemExit) as stopped:
        mode.run({})
    assert "no pipelined multiclass loop" in str(stopped.value.code)


# ---------------------------------------------------------------------------
# (e) the generator
# ---------------------------------------------------------------------------
def test_synth_covtype_is_one_table_with_its_one_hot_blocks_reordered():
    n = 20000
    a, ya = synth_covtype.generate(n, 54, 1)
    b, yb = synth_covtype.generate(n, 54, 2 ** 31 + 5)
    np.testing.assert_array_equal(ya, yb)
    np.testing.assert_array_equal(a[:, :10], b[:, :10])
    assert (a[:, 14:] != b[:, 14:]).any()
    for X in (a, b):
        assert X.dtype == np.float32 and X.shape == (n, 54)
        np.testing.assert_array_equal(X[:, 10:14].sum(axis=1), 1.0)
        np.testing.assert_array_equal(X[:, 14:].sum(axis=1), 1.0)
        assert np.isin(X[:, 10:], (0.0, 1.0)).all()
        np.testing.assert_array_equal(X[:, :10], np.rint(X[:, :10]))
        assert (X[:, :10] >= synth_covtype.LOW).all()
        assert (X[:, :10] <= synth_covtype.HIGH).all()
    fresh, yf = synth_covtype.generate(n, 54, 1, base_seed=9)
    assert (yf != ya).any()
    with pytest.raises(ValueError):
        synth_covtype.generate(100, 54, 1)
    with pytest.raises(ValueError):
        synth_covtype.generate(n, 28, 1)


def test_synth_covtype_keeps_what_the_configuration_assumes():
    n = 300_000
    X, y = synth_covtype.generate(n, 54, 5)
    published = np.asarray(synth_covtype.CLASS_COUNTS) / 581_012
    assert sum(synth_covtype.CLASS_COUNTS) == 581_012
    np.testing.assert_allclose(np.bincount(y.astype(int)) / n, published,
                               atol=0.003)
    share = (X[:, 10:] != 0).mean(axis=0)
    wild, soil = np.sort(share[:4]), share[4:]
    assert wild[2] > 0.35 and wild[1] < 0.1   # two dense, two sparse
    assert soil.max() < 0.18 and soil.min() >= 0.0004
    # every (class, soil) pair among the planted rows
    planted = synth_covtype.PLANTED
    pairs = y[:planted].astype(int) * 40 + np.argmax(
        X[:planted, 14:], axis=1)
    assert len(np.unique(pairs)) == planted == 7 * 40
    for base in (25, 2300000140, 7, 99):
        table = synth_covtype.soil_table(base)
        marginal = synth_covtype.class_shares() @ table
        assert marginal.max() < 0.18 and table.min() >= 0.0004


def test_the_table_is_bundled_into_fourteen_groups():
    X, y = synth_covtype.generate(20000, 54, 3)
    ds = lgb.Dataset(X, y, params=PARAMS)
    ds.construct()
    groups = ds._inner.groups
    widths = sorted(int(w) for w in groups.group_num_bin)
    assert groups.num_groups == 14
    assert sorted(len(g) for g in groups.groups)[-2:] == [2, 40]
    assert widths[-1] == 81 and widths[:3] == [2, 2, 5]
    assert ds._inner.efb_counters.sample_conflicts == 0


# ---------------------------------------------------------------------------
# (f) what BENCHMARK.json and the cell's files say
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", ["split_gap", "gain_gap", "leaf_gap",
                                  "score_gap", "loss_gap"])
def test_each_limit_lies_between_the_readings_it_was_set_from(name):
    """PERF.md section 2's rule on the readings the cell's file carries:
    over the largest sound reading with room, and over the sound reading
    carried to the cell's rows by the slope; under every fault's reading,
    and under the control's for each number that parts it from the sound
    runs, which leaf_gap alone does not."""
    cell = harness.load_cell(CELL)["cell"]
    limit, read = cell["limits"][name], cell["limits_set_from"]
    assert 2 * read["lower_largest_sound_reading"][name] < limit
    assert read["sound_at_the_cell_size_by_slope"].get(name, 0.0) < limit
    faults = [float(f[name]) for f in read["smallest_fault_reading"].values()
              if name in f]
    assert faults and all(limit < f for f in faults)
    separates = read["control_separates"]
    assert (name in separates) == (name != "leaf_gap")
    if name in separates:
        assert limit < read["upper_smallest_control_reading"][name]


def test_benchmark_json_names_the_configuration_the_cell_and_the_readers():
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    loaded = harness.load_cell(CELL)
    entry, config = loaded["entry"], loaded["config"]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "covtype-581kx54", "train_steady_multiclass", 1)
    assert [w["name"] for w in bench["workloads"]][5] == CELL
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    declared, = [c for c in bench["configs"] if c["name"] == entry["config"]]
    assert declared["reduced"] == config["reduced"] == ["rows"]
    assert declared["source"] == config["source"]
    assert all(len(e["why"]) <= 200 for e in (declared, entry))
    assert len(declared["source"]) <= 200
    higgs = harness.load_cell("higgs-train-1chip")["config"]
    assert config["params"] == dict(higgs["params"], objective="multiclass",
                                    num_class=7)
    assert (config["features"], config["classes"]) == (54, 7)
    assert config["rows_published"] == 581_012
    assert config["rows"] in (12_582_912, 14_680_064, 16_777_216)
    assert {"rows", "columns", "classes", "class_structure", "wilderness",
            "soil", "groups", "count_cell", "data"} <= set(config["assumed"])
    steady = harness.load_json(BENCH, "traffic", "train_steady.json")
    for key in ("warmup_iterations", "checked_iterations", "off_in_window"):
        assert loaded["traffic"][key] == steady[key]
    mine = bench["per_layer"][-2:]
    assert [m["name"] for m in mine] == [
        "grower.class_pass_waste", "hist.class_roofline_share"]
    for m in mine:
        assert (m["layer"], m["moves"], m["workloads"]) == (
            "grower", "train_mrow_iters_per_s", [CELL])
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           m["name"] + ".py"))
    listed = [m["name"] for m in bench["per_layer"][:-2]
              if CELL in m.get("workloads", [])]
    assert listed == ["gradients.device_share"]
    assert set(loaded["cell"]["limits"]) == TREE_NUMBERS | {
        "window_compiles", "stopped_iterations"}
    for name in ("count_mismatch", "window_compiles", "stopped_iterations"):
        assert loaded["cell"]["limits"][name] == 0
