"""The ranking deployment (`msltr-2m27x137`, cell `msltr-rank-1chip`) at a
size a test run can hold: the program against the benchmark's plain
ranking reference through `Booster.update()` on a `Dataset` built with
`group=`, the reference's gradients against the double loop, the
gradient program's arguments, the schedule of the cell's shape, the
generator, the layer's scopes, the readers the cell brought and what
`BENCHMARK.json` says of it. Nothing here is a device measurement."""
import contextlib
import os
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.boosting import gbdt as gbdt_mod
from lightgbm_tpu.config import Config
from lightgbm_tpu.dataset import Metadata
from lightgbm_tpu.learner.schedule import (pick_schedule, plan_row_layout,
                                           subtract_cache_bytes)
from lightgbm_tpu.objectives import LambdarankNDCG
from lightgbm_tpu.telemetry import devtrace, layers

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
for path in (ROOT, BENCH, os.path.dirname(os.path.abspath(__file__))):
    if path not in sys.path:
        sys.path.insert(0, path)

import datagen  # noqa: E402
import faults_rank  # noqa: E402
import reference_rank  # noqa: E402
import run as harness  # noqa: E402
from test_ranking import bruteforce_lambdas  # noqa: E402

CELL = "msltr-rank-1chip"
V5E_BYTES = 16_909_336_064
RANK_SCOPES = tuple(s for s in layers.SCOPES
                    if s.startswith("lgbm/gradients/"))


# ---------------------------------------------------------------------------
# (a) the program against the plain reference, under the cell's limits
# ---------------------------------------------------------------------------
class _Ranked:
    """One prepared data set shared by the variants, as `readings.py`
    shares one between a seed's; the sound run is made once."""
    ROWS, FEATURES = 12000, 32

    def __init__(self):
        loaded = harness.load_cell(CELL)
        self.mode = harness.load_mode(loaded["traffic"])
        # one warm-up step, so that a run is two steps: the reference
        # follows the first from a zero score (every score tied) and the
        # window's one tree from the program's score at its opening
        self.base = {
            "cell": loaded["cell"],
            "traffic": dict(loaded["traffic"], warmup_iterations=1),
            "config": dict(loaded["config"], features=self.FEATURES),
            "seed": 3000000019, "seconds": 0.0, "trace": False,
            "rows": self.ROWS, "t_start": time.perf_counter(),
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
def ranked():
    return _Ranked()


def _failed(compared):
    return {n for n, row in compared.items()
            if not row["value"] <= row["limit"]}


def test_the_shape_holds_the_queries_that_break_things(ranked):
    sizes, y = ranked.prepared["sizes"], ranked.prepared["y"]
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    assert sizes.min() == 1 and sizes.max() > 1024
    assert any(s > 1 and len(set(y[a:a + s])) == 1
               for a, s in zip(bounds, sizes))
    assert ranked.prepared["ds"].get_group() is sizes


def test_ranked_program_is_correct_and_control_is_not(ranked):
    out = ranked.sound()
    assert out["correct"], out["compared"]
    assert set(out["compared"]) == set(reference_rank.COMPARED) | {
        "lambda_gap", "hess_gap", "window_compiles", "stopped_iterations"}
    assert not out["control_correct"], out["control_compared"]
    assert {"gain_gap", "leaf_gap", "score_gap"} <= _failed(
        out["control_compared"])
    rank = out["schedule"]["rank"]
    assert (rank["queries"], rank["docs"]) == (out["queries"], _Ranked.ROWS)
    assert rank["buckets"]["2048"] == [1, 1]
    # padded rows: the objective's arrays were padded to the row plan
    assert out["schedule"]["rows_padded"] > _Ranked.ROWS
    # a steady window: the gradient program was not compiled again
    assert out["compared"]["window_compiles"]["value"] == 0


@pytest.mark.parametrize("fault", faults_rank.FAULTS)
def test_ranked_broken_timed_path_is_not_correct(ranked, fault):
    out = ranked.run(fault=fault)
    assert not out["correct"], out["compared"]
    failed = _failed(out["compared"])
    tree_numbers = {"count_mismatch", "split_gap", "gain_gap", "leaf_gap",
                    "score_gap", "ndcg_gap"}
    assert failed & tree_numbers, out["compared"]
    if fault == "pairs_dropped":
        assert {"lambda_gap", "hess_gap"} <= failed
    else:       # the tree step's faults leave the gradient layer alone
        assert not failed & {"lambda_gap", "hess_gap"}


# ---------------------------------------------------------------------------
# (b) the reference's gradients: the double loop, and the program's
# ---------------------------------------------------------------------------
def _small_queries(seed=5):
    rng = np.random.RandomState(seed)
    sizes = np.concatenate([rng.randint(1, 40, size=14), [1, 7]])
    lab = rng.randint(0, 5, size=int(sizes.sum()))
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    lab[bounds[3]:bounds[4]] = 2            # a query of one label
    return sizes, bounds, lab, rng.randn(int(sizes.sum()))


def _reference(sizes, lab):
    n = int(sizes.sum())
    return reference_rank.RankReference(
        np.zeros((n, 1), np.float32), lab.astype(np.float32), sizes,
        [np.asarray([np.inf])], num_leaves=2, learning_rate=0.1,
        min_sum_hessian_in_leaf=1.0, min_data_in_leaf=1)


@pytest.mark.parametrize("ties", [True, False])
def test_reference_gradients_match_the_double_loop_and_the_program(ties):
    sizes, bounds, lab, score = _small_queries()
    if ties:
        score = np.zeros_like(score)          # the tie rule decides all
    else:
        score[bounds[5]:bounds[6]] = 0.25     # one query's scores all equal
    n = len(lab)
    md = Metadata(n)
    md.set_label(lab.astype(np.float32))
    md.set_group(sizes)
    obj = LambdarankNDCG(Config.from_params({"objective": "lambdarank"}))
    obj.init(md, n)
    ref = _reference(sizes, lab)
    g, h = (np.asarray(a, np.float64) for a in ref._grads(
        jnp.asarray(score, jnp.float32), ref.y))
    bg, bh = bruteforce_lambdas(bounds, lab, score, obj._inv_max_dcg_np)
    assert np.abs(g - bg).max() < 1e-4 and np.abs(h - bh).max() < 1e-4
    assert np.abs(bg).max() > 0.1
    pg, ph = obj.get_gradients(jnp.asarray(score, jnp.float32))
    gaps = ref.gradient_gaps(np.asarray(pg), np.asarray(ph), score)
    assert gaps["lambda_gap"] < 1e-5 and gaps["hess_gap"] < 1e-5
    # a lambda lost anywhere shows; a query of one label must read 0
    broken = np.asarray(pg).copy()
    broken[np.argmax(np.abs(broken))] = 0.0
    assert ref.gradient_gaps(broken, np.asarray(ph), score)["lambda_gap"] \
        == pytest.approx(1.0, abs=1e-5)
    broken = np.asarray(pg).copy()
    broken[bounds[3]] = 1e-3
    assert ref.gradient_gaps(broken, np.asarray(ph), score)["lambda_gap"] \
        == np.inf


def test_reference_ndcg_is_the_metrics_ndcg():
    from lightgbm_tpu.metrics import NDCGMetric
    sizes, bounds, lab, score = _small_queries(11)
    lab[bounds[6]:bounds[7]] = 0             # no relevant document: counts 1
    ref = _reference(sizes, lab)
    mine = float(np.asarray(ref._loss_blocks(
        jnp.asarray(score, jnp.float32), ref.y), np.float64).sum()) \
        / len(sizes)
    md = Metadata(len(lab))
    md.set_label(lab.astype(np.float32))
    md.set_group(sizes)
    metric = NDCGMetric(Config.from_params(
        {"objective": "lambdarank", "metric": "ndcg",
         "ndcg_eval_at": [reference_rank.EVAL_AT]}))
    metric.init(md, len(lab))
    theirs = dict(metric.eval(score.astype(np.float32), None))["ndcg@10"]
    assert mine == pytest.approx(theirs, abs=1e-6)


# ---------------------------------------------------------------------------
# (c) the pair layout reaches the gradient program as arguments
# ---------------------------------------------------------------------------
def _objective(rows, seed=0):
    X, y, sizes = datagen.generator("synth_msltr")(rows, 26, seed)
    md = Metadata(rows)
    md.set_label(y)
    md.set_group(sizes)
    obj = LambdarankNDCG(Config.from_params({"objective": "lambdarank"}))
    obj.init(md, rows)
    return obj


def _lowered_gradients(obj):
    fn, keys = gbdt_mod._gradient_jit(obj)
    arrs = {k: getattr(obj, k) for k in keys}
    return fn, fn.lower(jnp.zeros((1, obj.num_data), jnp.float32), arrs)


def test_the_gradient_program_holds_no_bucket_array():
    small, large = _objective(3000), _objective(24000)
    assert small._bucket_shapes != large._bucket_shapes
    keys = gbdt_mod.objective_array_keys(large)
    assert {"_pair_gather", "_pair_lab", "_pair_mask", "_pair_inv_max_dcg",
            "_gain_table", "label"} <= set(keys)
    rest = {k: v for k, v in vars(large).items() if k not in keys}
    assert all(gbdt_mod._is_plain(v) for v in rest.values()), rest.keys()
    per_bucket = []
    for obj in (small, large):
        text = _lowered_gradients(obj)[1].as_text()
        # no literal grows with the data: the layout's arrays alone would
        # be megabytes of text at 24000 rows, in hexadecimal
        assert "dense<\"0x" not in text
        per_bucket.append(len(text) / len(obj._bucket_shapes))
    # eight times the rows: the same text a bucket, to a tenth
    assert max(per_bucket) < 40_000
    assert abs(per_bucket[1] - per_bucket[0]) < 0.1 * per_bucket[0]


def test_two_boosters_on_one_data_set_share_one_gradient_program():
    a, b = _objective(3000), _objective(3000)
    fa, fb = _lowered_gradients(a)[0], _lowered_gradients(b)[0]
    assert fa is fb
    # another data set of other bucket shapes gets its own
    assert _lowered_gradients(_objective(24000))[0] is not fa


# ---------------------------------------------------------------------------
# (d) the schedule of the cell's shape
# ---------------------------------------------------------------------------
def test_the_cells_shape_is_a_rung_and_takes_the_cache_at_eight():
    config = harness.load_cell(CELL)["config"]
    rows, features = int(config["rows"]), int(config["features"])
    assert features == 137 and rows % 65536 == 0
    layout = plan_row_layout(rows, features, 63)
    assert tuple(layout) == (65536, 65536, rows, 1, 1)   # no padded row
    picked = pick_schedule(features, 63, rows, layout.n_pad, layout.chunk,
                           num_leaves=255, device_bytes=V5E_BYTES)
    assert tuple(picked._replace(
        compact_model=tuple(picked.compact_model))) == (
        True, True, 9, False, 0.0, (0.0, 8.5, 7.5, 49.9), 8)
    assert picked.grower_fields(layout.chunk) == {
        "chunk": 65536, "batch_k": 8, "hist_subtract": True,
        "hist_compact": False, "compact_fraction": 0.0, "table_mult": 9}
    assert subtract_cache_bytes(137, 63, 255, 9) == 243_083_484
    # the published row count is no rung: 351,144 padded rows
    assert plan_row_layout(2_270_296, 137, 63).n_pad == 2_621_440


# ---------------------------------------------------------------------------
# (e) the generator
# ---------------------------------------------------------------------------
def test_synth_msltr_reorders_one_data_set():
    generate = datagen.generator("synth_msltr")
    rows, features = 30000, 40
    Xa, ya, sa = generate(rows, features, 7)
    Xb, yb, sb = generate(rows, features, 3000000019)
    assert Xa.dtype == ya.dtype == np.float32 and Xa.shape == (rows, features)
    np.testing.assert_array_equal(ya, yb)
    np.testing.assert_array_equal(sa, sb)
    assert not np.array_equal(Xa, Xb)
    order_a, order_b = np.lexsort(Xa[:8]), np.lexsort(Xb[:8])
    np.testing.assert_array_equal(Xa[:, order_a], Xb[:, order_b])
    # rows exact, the published extremes present, the published mean
    assert int(sa.sum()) == rows and (sa.min(), sa.max()) == (1, 1251)
    assert len(sa) == rows // 120 and 60 < np.median(sa) < 140
    # MSLR-WEB30K's skew, and a label the features explain
    share = np.bincount(ya.astype(int), minlength=5) / rows
    assert np.abs(share - [0.51, 0.33, 0.13, 0.02, 0.01]).max() < 0.03
    assert max(abs(np.corrcoef(Xa[:, j], ya)[0, 1])
               for j in range(features)) > 0.15
    Xc, yc, sc = generate(rows, features, 7, base_seed=99)
    assert not np.array_equal(sa, sc) and not np.array_equal(ya, yc)
    assert int(sc.sum()) == rows and (sc.min(), sc.max()) == (1, 1251)
    with pytest.raises(ValueError):
        generate(2000, features, 7)


# ---------------------------------------------------------------------------
# (f) the layer's scopes: in the program, as metadata only, and charged
# ---------------------------------------------------------------------------
def test_the_rank_scopes_are_in_the_program_as_metadata_only():
    assert RANK_SCOPES == ("lgbm/gradients/rank_sort",
                           "lgbm/gradients/rank_pairs",
                           "lgbm/gradients/rank_scatter")
    obj = _objective(3000)
    lowered = _lowered_gradients(obj)[1]
    debug, plain = lowered.as_text(debug_info=True), lowered.as_text()
    for name in RANK_SCOPES:
        assert name in debug and name not in plain
    real = jax.named_scope
    gbdt_mod._shared_gradient_jit.cache_clear()
    try:
        jax.named_scope = lambda name: contextlib.nullcontext()
        bare = _lowered_gradients(obj)[1]
        assert layers.PREFIX not in bare.as_text(debug_info=True)
        assert bare.as_text() == plain
    finally:
        jax.named_scope = real
        gbdt_mod._shared_gradient_jit.cache_clear()


def test_an_event_goes_to_the_sub_scope_it_was_emitted_under():
    path = ("jit(f)/lgbm/gradients/jit(_lambdarank)/while/body/"
            "lgbm/gradients/rank_sort/sort")
    assert devtrace.scope_of(path) == "lgbm/gradients/rank_sort"
    assert devtrace.scope_of("jit(f)/lgbm/gradients/add") == "lgbm/gradients"
    assert devtrace.scope_of(
        "jit(f)/lgbm/gradients/lgbm/gradients/rank_scatter/scatter-add") \
        == "lgbm/gradients/rank_scatter"
    reduced = devtrace.reduce_events({"/device:TPU:0": [
        ("sort.1", 0, 300, path),
        ("fusion.2", 300, 1000, "jit(f)/lgbm/gradients/lgbm/gradients/"
         "rank_pairs/reduce_sum"),
        ("fusion.3", 1000, 1100, "jit(f)/lgbm/gradients/add"),
        ("fusion.4", 1100, 2000, "jit(f)/lgbm/hist/contract/dot_general"),
    ]}, {})
    assert reduced["scopes"] == pytest.approx({
        "lgbm/gradients/rank_sort": 3e-7, "lgbm/gradients/rank_pairs": 7e-7,
        "lgbm/gradients": 1e-7, "lgbm/hist/contract": 9e-7})


def test_the_binary_gradient_program_knows_nothing_of_ranking():
    rng = np.random.RandomState(3)
    X = rng.randn(4000, 6).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 7, "verbose": -1}
    inner = lgb.Booster(params, lgb.Dataset(
        X, (X[:, 0] > 0).astype(np.float32), params=params))._inner
    inner._compute_gradients(inner._score)
    assert inner._jit_grads_keys == ("label",)
    text = inner._jit_grads.lower(
        inner._score, {"label": inner.objective.label}).as_text(
            debug_info=True)
    assert "lgbm/gradients" in text and "gradients/rank_" not in text
    assert "rank" not in inner._schedule_info


# ---------------------------------------------------------------------------
# (g) the readers the cell brought, on a hand-made ctx
# ---------------------------------------------------------------------------
def _reader(name):
    return datagen.load_file_module(
        os.path.join(BENCH, "layer_metrics", name + ".py"),
        "reader_" + name.replace(".", "_")).read


def test_the_new_readers_on_a_hand_made_ctx():
    scopes = {"lgbm/gradients": 0.02, "lgbm/gradients/rank_sort": 0.30,
              "lgbm/gradients/rank_pairs": 0.50,
              "lgbm/gradients/rank_scatter": 0.18,
              "lgbm/hist/contract": 2.6, "unscoped": 0.4}
    rank = {"pair_slots": 6000, "valid_pairs": 1_000_000_000,
            "docs": 12_000_000}
    ctx = {"rows": 500, "schedule": {"rank": rank},
           "trace_scopes": {"/device:TPU:0": scopes},
           "traced_trees": [1, 3], "device_kind": "TPU v5 lite"}
    assert _reader("gradients.device_share")(ctx) == pytest.approx(25.0)
    assert _reader("gradients.pair_slots_per_row")(ctx) == 12.0
    # two traced iterations of 22e9 operations at 6.144e12 a second over
    # the layer's one second
    assert _reader("gradients.roofline_share")(ctx) == pytest.approx(
        100.0 * 2 * 22e9 / 6.144e12)
    # where bytes bound: 16 bytes a document at 819 GB/s
    few = dict(ctx, schedule={"rank": dict(rank, valid_pairs=1000)})
    assert _reader("gradients.roofline_share")(few) == pytest.approx(
        100.0 * 2 * 16 * 12e6 / 819e9)
    # two planes: the mean of each side
    two = dict(ctx, trace_scopes={
        "a": scopes, "b": dict(scopes, **{"lgbm/hist/contract": 6.6})})
    assert _reader("gradients.device_share")(two) == pytest.approx(
        100.0 * 2.0 / 12.0)
    # the other cells, the parent, an untraced run, another chip
    for name in ("gradients.device_share", "gradients.pair_slots_per_row",
                 "gradients.roofline_share"):
        assert _reader(name)({}) is None
        assert _reader(name)({"rows": 500, "schedule": {"num_shards": 1},
                              "traced_trees": [1, 3]}) is None
    assert _reader("gradients.roofline_share")(
        dict(ctx, device_kind="cpu")) is None
    assert _reader("gradients.roofline_share")(
        dict(ctx, traced_trees=None)) is None
    # no gradient scope in the trace (a cache written before the scopes)
    bare = dict(ctx, trace_scopes={"a": {"unscoped": 4.0}})
    assert _reader("gradients.device_share")(bare) == 0.0
    assert _reader("gradients.roofline_share")(bare) is None


# ---------------------------------------------------------------------------
# (h) what BENCHMARK.json and the cell's files say
# ---------------------------------------------------------------------------
def test_benchmark_json_names_the_configuration_the_cell_and_the_readers():
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    loaded = harness.load_cell(CELL)
    entry, config = loaded["entry"], loaded["config"]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "msltr-2m27x137", "train_steady_rank", 1)
    assert [w["name"] for w in bench["workloads"]][3] == CELL
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    declared, = [c for c in bench["configs"] if c["name"] == entry["config"]]
    assert declared["reduced"] == config["reduced"] == ["rows"]
    assert "GPU-Performance.rst" in declared["source"]
    assert "2,270,296 x 137" in declared["source"]
    higgs = harness.load_cell("higgs-train-1chip")["config"]
    assert config["params"] == dict(higgs["params"], objective="lambdarank")
    assert not any(k.startswith("tpu_") for k in config["params"])
    assert (config["features"], config["rows_published"]) == (137, 2_270_296)
    assert config["rows"] in (12_582_912, 14_680_064, 20_971_520)
    assert config["queries"] == -(-config["rows"] // 120)
    defaults = config["objective_defaults"]
    assert (defaults["sigmoid"], defaults["max_position"]) == (1.0, 20)
    assert defaults["label_gain"] == [float(2 ** i - 1) for i in range(31)]
    assert {"rows", "queries", "labels", "features", "iterations"} \
        <= set(config["assumed"])
    steady = harness.load_json(BENCH, "traffic", "train_steady.json")
    for key in ("warmup_iterations", "checked_iterations", "off_in_window"):
        assert loaded["traffic"][key] == steady[key]
    assert (loaded["cell"]["trace_after_iterations"],
            loaded["cell"]["trace_iterations"]) == (1, 2)
    mine = [m for m in bench["per_layer"] if m["name"].startswith("gradients.")]
    assert [m["name"] for m in mine] == [
        "gradients.device_share", "gradients.pair_slots_per_row",
        "gradients.roofline_share"] == [m["name"]
                                        for m in bench["per_layer"][14:17]]
    for m in mine:
        # the sparse cell's mode hands scopes too: PR 38 appended it to
        # the one reader that needs nothing but scopes
        assert (m["layer"], m["moves"], m["workloads"]) == (
            "gradients", "train_mrow_iters_per_s",
            [CELL] + ["expo-train-1chip"] * (m["name"].endswith(
                "device_share")))
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           m["name"] + ".py"))
    assert set(loaded["cell"]["limits"]) == set(reference_rank.COMPARED) | {
        "lambda_gap", "hess_gap", "window_compiles", "stopped_iterations"}


@pytest.mark.parametrize("name", ["split_gap", "gain_gap", "leaf_gap",
                                  "score_gap", "ndcg_gap", "bin_pop_gap",
                                  "lambda_gap", "hess_gap"])
def test_each_limit_lies_between_this_shapes_two_readings(name):
    """PERF.md section 2's rule, on the readings the cell's file carries.
    At this shape the bf16 control sits close to the sound runs (the
    gradients of a query sum to nearly zero), so ONE number, `gain_gap`,
    stands between the sound and the control readings, and the control is
    over it on every data set; every other limit stands between the sound
    readings and the smallest fault's (or coarse bins')."""
    cell = harness.load_cell(CELL)["cell"]
    limit, read = cell["limits"][name], cell["limits_set_from"]
    sound = read["lower_largest_sound_reading"][name]
    faults = [f[name] for f in read["smallest_fault_reading"].values()
              if name in f]
    assert faults or name == "bin_pop_gap"
    assert all(limit < f for f in faults)
    if name == "gain_gap":
        control = read["control_per_data_set"][name]
        assert 1.8 * sound < limit and 1.25 * limit < min(control)
        assert min(control) == read["upper_smallest_control_reading"][name]
        assert 2 * max(read["sound_benchmark_data_set"][name]) < limit
    elif name == "bin_pop_gap":
        assert 3 * sound < limit
        assert 2.5 * limit < read["upper_smallest_control_reading"][name]
    else:
        assert 3 * sound < limit and 2.5 * limit < min(faults)
    for values in (read["sound_per_data_set"].get(name, []),
                   read["sound_benchmark_data_set"].get(name, [])):
        assert all(value <= sound for value in values)
