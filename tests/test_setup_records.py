"""Set-up told from inside the program (PR 36): `telemetry.InitRecord`
(`GBDT.init` by phase, the rows a shard holds), the observer's count of
jax's whole compile path by program, the compile-path fields of
`TreeRecord`, `telemetry.last_run()`, and the seven benchmark readers
that read them, on a CPU rehearsal of a cell. Nothing here is a device
measurement."""
import gc
import os
import sys
import time

import jax
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import telemetry
from lightgbm_tpu.telemetry import layers

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
for path in (ROOT, BENCH):
    if path not in sys.path:
        sys.path.insert(0, path)

import datagen  # noqa: E402
import run as harness  # noqa: E402

ROWS = 6000
PARAMS = {"objective": "binary", "verbose": -1, "num_leaves": 15,
          "min_data_in_leaf": 5}


def _table(rows=ROWS, features=8, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(rows, features).astype(np.float32)
    return X, (X[:, 0] + X[:, 1] > 0).astype(np.float32)


def _booster(rows=ROWS, **params):
    X, y = _table(rows)
    return lgb.Booster(dict(PARAMS, **params), lgb.Dataset(X, y))


@pytest.fixture()
def obs():
    """The process's observer, installed and empty; left installed (the
    modes install it for good) and empty."""
    o = telemetry.install_observer()
    o.reset()
    yield o
    o.reset()


# ---------------------------------------------------------------------------
# InitRecord
# ---------------------------------------------------------------------------
def test_every_init_span_has_a_field_in_its_order():
    fields = telemetry.InitRecord._fields
    want = tuple(name.rsplit("/", 1)[1] + "_s"
                 for name in telemetry.INIT_SPANS)
    assert fields[:len(want)] == want
    assert fields[len(want)] == "total_s"
    assert {"rows", "binned_bytes", "shard_rows",
            "trace_lower_s", "backend_s", "cache_hits",
            "cache_misses"} <= set(fields)
    assert not hasattr(_booster()._inner, "land_s")


def test_the_phases_account_for_init():
    rec = _booster()._inner.init_record
    phases = sum(getattr(rec, f) for f in rec._fields[:len(
        telemetry.INIT_SPANS)])
    assert rec.total_s > 0.0
    assert 0.0 <= rec.total_s - phases < 0.05 * rec.total_s
    assert rec.gate_s == 0.0            # no quantisation, no gate
    assert rec.objective_s > 0.0 and rec.schedule_s > 0.0 \
        and rec.state_s > 0.0 and rec.land_s > 0.0


def test_the_gate_is_a_phase_of_its_own():
    rec = _booster(tpu_hist_quantize="int8")._inner.init_record
    assert rec.gate_s > 0.0
    assert rec.total_s - sum(rec[:len(telemetry.INIT_SPANS)]) \
        < 0.05 * rec.total_s


def test_shard_rows_on_one_device_are_the_rows():
    inner = _booster()._inner
    rec = inner.init_record
    assert rec.shard_rows == (ROWS,) and rec.rows == ROWS
    assert rec.binned_bytes == inner._binned.nbytes > 0


def test_shard_rows_under_the_data_learner_are_uneven():
    """The padding is one suffix of the global row axis, so the last
    devices hold fewer real rows, down to none."""
    inner = _booster(rows=14_000, tree_learner="data",
                     tpu_hist_chunk=2048)._inner
    rec = inner.init_record
    devices = len(jax.devices())
    assert len(rec.shard_rows) == devices == 8
    assert sum(rec.shard_rows) == rec.rows == 14_000
    block = inner._n_pad // devices
    assert rec.shard_rows == (block,) * 6 + (14_000 - 6 * block, 0)
    assert rec.land_s > 0.0 and inner._row_sharded


@pytest.mark.parametrize("rows, padded, shards, want", [
    (21_000_000, 25_165_824, 1, (21_000_000,)),
    (84_000_000, 100_663_296, 4, (25_165_824,) * 3 + (8_502_528,)),
    (8, 8, 4, (2, 2, 2, 2)),
    (3, 8, 4, (2, 1, 0, 0)),
])
def test_shard_rows_of_the_cells(rows, padded, shards, want):
    assert layers.shard_rows(rows, padded, shards) == want


def test_a_serial_init_waits_for_nothing(monkeypatch):
    """The record costs `perf_counter` pairs: on one device `init` asks
    for no `block_until_ready`, with the record as without."""
    calls = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: calls.append(1) or real(x))
    inner = _booster()._inner
    assert calls == [] and inner.init_record.land_s > 0.0


def test_phases_in_stretches_and_in_blocks_add_up():
    with telemetry.Phases(("a", "b")) as phase:
        phase("a")
        time.sleep(0.002)
        phase("b")
        phase("a")
        time.sleep(0.002)
    closed = dict(phase.seconds)
    time.sleep(0.002)                   # between phases: nobody's
    with phase("b"):
        time.sleep(0.002)
    assert phase.seconds["a"] == closed["a"] >= 0.004
    assert phase.seconds["b"] >= closed["b"] + 0.002
    with pytest.raises(KeyError):
        phase("c")


def test_a_phase_left_by_an_exception_closes_its_span():
    telemetry.enable(True)
    try:
        with pytest.raises(ZeroDivisionError):
            with telemetry.Phases(("test/phase",)) as phase:
                phase("test/phase")
                assert telemetry.current_site() == "test/phase"
                1 / 0
        assert telemetry.current_site() is None
        assert phase.seconds["test/phase"] > 0.0
    finally:
        telemetry.enable(False)
        telemetry.reset()


# ---------------------------------------------------------------------------
# the observer
# ---------------------------------------------------------------------------
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
CACHE = "/jax/compilation_cache/"


def test_each_synthetic_event_lands_in_its_total_and_its_program(obs):
    from jax import monitoring
    before = obs.totals()
    assert before == telemetry.CompileTotals() and isinstance(before, tuple)
    monitoring.record_event_duration_secs(TRACE, 0.25, fun_name="prog_a")
    monitoring.record_event_duration_secs(LOWER, 0.5, fun_name="jit(prog_a)")
    monitoring.record_event(CACHE + "cache_misses", fun_name="jit(prog_a)")
    monitoring.record_event_duration_secs(BACKEND, 2.0,
                                          fun_name="jit(prog_a)")
    monitoring.record_event_duration_secs(LOWER, 0.125,
                                          fun_name="jit(prog_b)")
    monitoring.record_event(CACHE + "cache_hits", fun_name="jit(prog_b)")
    monitoring.record_event_duration_secs(
        CACHE + "cache_retrieval_time_sec", 0.0625, fun_name="jit(prog_b)")
    # no reader, so no listener: jax's estimate of what a hit saved
    monitoring.record_event_duration_secs(
        CACHE + "compile_time_saved_sec", 3.0, fun_name="jit(prog_b)")
    monitoring.record_event_duration_secs(BACKEND, 0.0625,
                                          fun_name="jit(prog_b)")
    monitoring.record_event_duration_secs("/jax/some/other_event", 9.0)
    monitoring.record_event("/jax/some/other_event")
    now = obs.totals()
    assert now == telemetry.CompileTotals(
        trace_s=0.25, lower_s=0.625, backend_s=2.0625,
        cache_hits=1, cache_misses=1, compiles=2)
    assert now.trace_lower_s == 0.875
    programs = obs.snapshot()["programs"]
    assert programs == {
        "prog_a": {"traces": 1, "trace_s": 0.25, "lower_s": 0.5,
                   "backend_s": 2.0, "cache_hits": 0, "cache_misses": 1,
                   "retrieval_s": 0.0},
        "prog_b": {"traces": 0, "trace_s": 0.0, "lower_s": 0.125,
                   "backend_s": 0.0625, "cache_hits": 1, "cache_misses": 0,
                   "retrieval_s": 0.0625}}
    assert telemetry.compile_path_since(before) == (0.875, 2.0625, 1, 1)


def test_nameless_cache_events_go_to_the_compile_that_closes_next(obs):
    """As jax fires them: inside the backend-compile interval, no name."""
    from jax import monitoring
    monitoring.record_event(CACHE + "cache_hits")
    monitoring.record_event_duration_secs(
        CACHE + "cache_retrieval_time_sec", 0.5)
    monitoring.record_event_duration_secs(BACKEND, 0.75, fun_name="jit(one)")
    monitoring.record_event(CACHE + "cache_misses")
    monitoring.record_event_duration_secs(BACKEND, 4.0, fun_name="jit(two)")
    programs = obs.snapshot()["programs"]
    assert (programs["one"]["cache_hits"], programs["one"]["retrieval_s"],
            programs["one"]["cache_misses"]) == (1, 0.5, 0)
    assert (programs["two"]["cache_hits"], programs["two"]["cache_misses"]) \
        == (0, 1)
    assert obs.totals().cache_hits == obs.totals().cache_misses == 1


@pytest.mark.parametrize("enabled", [False, True])
def test_compiles_seconds_sites_and_retraces_mean_what_they_did(obs, enabled):
    """The same backend-compile events as before this observer counted
    anything else: by span with telemetry enabled, `(no-span)` without."""
    from jax import monitoring
    telemetry.enable(enabled)
    try:
        with telemetry.span("test/site"):
            for seconds in (1.0, 2.0, 4.0):
                monitoring.record_event_duration_secs(
                    BACKEND, seconds, fun_name="jit(f)")
        monitoring.record_event_duration_secs(TRACE, 8.0, fun_name="f")
    finally:
        telemetry.enable(False)
        telemetry.reset()
    snap = obs.snapshot()
    assert set(snap) == {"total_compiles", "total_seconds", "retraces",
                         "sites", "programs"}
    assert (obs.total_compiles, obs.total_seconds) == (3, 7.0)
    assert (snap["total_compiles"], snap["total_seconds"]) == (3, 7.0)
    site = "test/site" if enabled else "(no-span)"
    assert snap["sites"] == {site: {"compiles": 3, "seconds": 7.0}}
    assert obs.retraces("test/site") == (2 if enabled else 0)
    assert obs.retraces() == snap["retraces"] == (2 if enabled else 0)


def test_the_phases_of_init_hold_many_programs_and_no_retrace(obs, capfd):
    """A dozen small eager programs compile once each under one
    `lgbm/init/*` span: as in `(no-span)`, that is no entry point seeing
    new signatures, whatever `retrace_warn` is."""
    from jax import monitoring
    telemetry.enable(True)
    try:
        for site in telemetry.INIT_SPANS:
            with telemetry.span(site):
                for i in range(obs.retrace_warn + 2):
                    monitoring.record_event_duration_secs(
                        BACKEND, 0.5, fun_name=f"jit(small_{i})")
        # two fresh boosters of shapes nothing else here has compiled
        for rows in (9001, 11003):
            _booster(rows=rows, tpu_hist_chunk=1024)
        snap = obs.snapshot()
        assert obs.retraces() == snap["retraces"] == 0
        for site in telemetry.INIT_SPANS:
            assert snap["sites"][site]["compiles"] >= obs.retrace_warn + 2
            assert obs.retraces(site) >= obs.retrace_warn + 1   # on request
        assert "Retrace storm" not in capfd.readouterr().err
        # any other site still storms
        with telemetry.span("test/entry_point"):
            for _ in range(obs.retrace_warn + 1):
                monitoring.record_event_duration_secs(
                    BACKEND, 0.5, fun_name="jit(f)")
        assert obs.retraces() == obs.retrace_warn
        assert "Retrace storm at 'test/entry_point'" in capfd.readouterr().err
    finally:
        telemetry.enable(False)
        telemetry.reset()


def test_nested_traces_are_counted_once_and_only_programs_get_rows(obs):
    import jax.numpy as jnp

    @jax.jit
    def setup_records_probe(x):
        for _ in range(20):                 # every jnp call is a jit
            x = jnp.clip(jnp.where(x > 0, x + 1, x - 1), -5, 5)
        return x

    x = np.ones(7, np.float32)
    obs.reset()
    t = time.perf_counter()
    jax.block_until_ready(setup_records_probe(x))
    wall = time.perf_counter() - t
    totals, programs = obs.totals(), obs.snapshot()["programs"]
    row = programs["setup_records_probe"]
    assert row["traces"] == 1 and row["trace_s"] > 0.0
    assert row["lower_s"] > 0.0 and row["backend_s"] > 0.0
    assert "clip" not in programs and "_where" not in programs
    # the nested traces are inside the outermost one's seconds, and
    # what the lowering traces is inside the lowering's
    assert totals.trace_s == row["trace_s"]
    assert totals.trace_s + totals.lower_s + totals.backend_s <= wall
    # nothing compiles the second time
    before = obs.totals()
    jax.block_until_ready(setup_records_probe(x))
    assert telemetry.compile_path_since(before) == (0.0, 0.0, 0, 0)


# ---------------------------------------------------------------------------
# TreeRecord
# ---------------------------------------------------------------------------
def test_tree_record_keeps_its_first_twelve_fields():
    assert telemetry.TreeRecord._fields[:12] == (
        "num_passes", "table_high_water", "rows_contracted", "comm_elems",
        "comm_bytes", "full_passes", "compact_passes", "rows_indexed",
        "rows_gathered", "dispatch_s", "fetch_wait_s", "build_tree_s")
    assert telemetry.TreeRecord._fields[12:] == (
        "trace_lower_s", "backend_s", "cache_misses")
    rec = telemetry.TreeRecord(3, 9, 1e3, 0.0, 0.0)
    assert (rec.trace_lower_s, rec.backend_s, rec.cache_misses) == (0, 0, 0)


def test_the_first_tree_carries_the_compile_path_and_the_next_none(obs):
    # a shape no other test of this process has compiled
    booster = _booster(rows=ROWS + 37, num_leaves=13)
    for _ in range(3):
        booster.update()
    booster.current_iteration()
    first, second, third = booster._inner.pass_log
    assert first.trace_lower_s > 0.0 and first.backend_s > 0.0
    assert first.dispatch_s >= first.trace_lower_s + first.backend_s
    for later in (second, third):
        assert (later.trace_lower_s, later.backend_s, later.cache_misses) \
            == (0.0, 0.0, 0)
    assert booster._inner.init_record.trace_lower_s >= 0.0


def test_without_an_observer_the_compile_path_reads_zero():
    o = telemetry.observer()
    o.reset()
    o.uninstall()
    try:
        booster = _booster(rows=ROWS + 41, num_leaves=11)
        booster.update()
        booster.current_iteration()
        rec, = booster._inner.pass_log
        assert (rec.trace_lower_s, rec.backend_s, rec.cache_misses) \
            == (0.0, 0.0, 0)
        init = booster._inner.init_record
        assert (init.trace_lower_s, init.backend_s, init.cache_hits,
                init.cache_misses) == (0.0, 0.0, 0, 0)
        assert rec.dispatch_s > 0.0 and init.total_s > 0.0
    finally:
        o.install()


# ---------------------------------------------------------------------------
# last_run()
# ---------------------------------------------------------------------------
def test_last_run_outlives_its_booster_and_the_next_replaces_it():
    booster = _booster()
    booster.update()
    booster.current_iteration()
    inner = booster._inner
    construct, init, trees = telemetry.last_run()
    assert init is inner.init_record and trees is inner.pass_log
    assert construct is inner.train_data.construct_record
    assert construct is telemetry.last_construct()
    assert len(trees) == 1 and isinstance(trees[0], telemetry.TreeRecord)
    del booster, inner
    gc.collect()
    again = telemetry.last_run()
    assert again[1] is init and again[2] is trees and len(again[2]) == 1
    other = _booster(rows=ROWS // 2)
    assert telemetry.last_run()[1] is other._inner.init_record
    assert telemetry.last_run()[1].rows == ROWS // 2
    assert telemetry.last_run()[2] == []


def test_the_run_logs_header_carries_the_init_record(tmp_path):
    X, y = _table(400, 5)
    try:
        lgb.train(dict(PARAMS, tpu_telemetry_dir=str(tmp_path)),
                  lgb.Dataset(X, y), num_boost_round=2, verbose_eval=False)
    finally:
        telemetry.enable(False)
        telemetry.reset()
        telemetry.observer().reset()
    header = telemetry.read_records(
        os.path.join(str(tmp_path), "runlog_r0.jsonl"))[0]
    assert header["type"] == "header"
    assert set(header["init_record"]) == set(telemetry.InitRecord._fields)
    assert header["init_record"]["shard_rows"] == [400]
    assert header["init_record"]["total_s"] > 0.0


# ---------------------------------------------------------------------------
# the readers, on a CPU rehearsal of a cell
# ---------------------------------------------------------------------------
READERS = ("loop.init_s", "loop.init_objective_s", "loop.init_land_s",
           "loop.warmup_overhead_s", "compile.trace_lower_s",
           "compile.cache_misses", "merge.shard_balance")


def _reader(name):
    return datagen.load_file_module(
        os.path.join(BENCH, "layer_metrics", name + ".py"),
        "reader_" + name.replace(".", "_")).read


@pytest.fixture(scope="module")
def rehearsal():
    """`higgs-train-1chip` by its own mode at 5,000 rows, and what every
    new reader reads from the result straight after (a later booster of
    this process would replace `last_run()`). One leaf fewer than the
    configuration: a grow program no other test of this process has
    traced, so the first warm-up tree holds its trace and its compile
    whichever files this worker ran before."""
    loaded = harness.load_cell("higgs-train-1chip")
    mode = harness.load_mode(loaded["traffic"])
    telemetry.observer().reset()
    out = mode.run({
        "cell": loaded["cell"], "traffic": loaded["traffic"],
        "config": loaded["config"], "seed": 3000000019, "seconds": 0.0,
        "trace": False, "rows": 5000, "t_start": time.perf_counter(),
        "limits": loaded["cell"]["limits"], "rehearsal": True,
        "params_override": {"num_leaves": 254}})
    read = {name: _reader(name)(out) for name in READERS}
    run = telemetry.last_run()
    telemetry.observer().reset()
    return out, read, run


def test_the_benchmark_declares_the_seven_and_only_appends():
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    names = [m["name"] for m in bench["per_layer"]]
    assert tuple(names[17:24]) == READERS
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS[:-1]:
        assert by_name[name]["moves"] == "setup_s"
        assert "workloads" not in by_name[name]
    assert by_name["merge.shard_balance"]["workloads"] == ["higgs-train-dp4"]
    assert by_name["merge.shard_balance"]["moves"] == "train_mrow_iters_per_s"
    layers_named = {m["layer"] for m in bench["per_layer"][:17]}
    assert {by_name[n]["layer"] for n in READERS} <= layers_named


@pytest.mark.parametrize("name", READERS)
def test_each_reader_on_the_rehearsal(rehearsal, name):
    out, read, (construct, init, trees) = rehearsal
    assert out["correct"], out["compared"]
    value = read[name]
    warmup = trees[:len(trees) - len(out["pass_log_window"])]
    assert len(warmup) == 2
    if name == "merge.shard_balance":
        assert value is None            # the rows are on one device
    elif name == "loop.init_s":
        assert value == init.total_s > 0.0
        # the books: construct_s holds the dataset's phases and init
        assert out["construct_s"] >= construct.sketch_s + construct.bin_s \
            + value
    elif name == "loop.init_objective_s":
        assert value == init.objective_s > 0.0
    elif name == "loop.init_land_s":
        assert value == init.land_s > 0.0
    elif name == "loop.warmup_overhead_s":
        took = [t.dispatch_s + t.fetch_wait_s + t.build_tree_s
                for t in trees]
        window = took[len(warmup):]
        assert value == pytest.approx(
            sum(took[:2]) - 2 * sum(window) / len(window))
        # the first tree holds the trace, the lowering and the compile
        assert value > warmup[0].trace_lower_s > 0.0
    elif name == "compile.trace_lower_s":
        assert value == pytest.approx(
            init.trace_lower_s + sum(t.trace_lower_s for t in warmup))
        assert value > 0.0
        assert all(t.trace_lower_s == 0.0 for t in trees[2:])
    elif name == "compile.cache_misses":
        assert value == init.cache_misses + sum(
            t.cache_misses for t in warmup) >= 0
        assert isinstance(value, int)


def test_the_readers_read_nothing_of_another_booster_or_an_older_program(
        rehearsal, monkeypatch):
    out, _, run = rehearsal
    monkeypatch.setattr(telemetry, "_LAST_RUN", run)
    assert _reader("loop.init_s")(out) == run[1].total_s
    for name in READERS:
        assert _reader(name)(dict(out, rows=out["rows"] + 1)) is None
        assert _reader(name)({}) is None
    # a window that is not known leaves the warm-up trees unknown
    for name in ("loop.warmup_overhead_s", "compile.trace_lower_s",
                 "compile.cache_misses"):
        assert _reader(name)(dict(out, pass_log_window=[])) is None
    # the parent program: no last_run at all
    monkeypatch.delattr(telemetry, "last_run")
    for name in READERS:
        assert _reader(name)(out) is None


def test_shard_balance_reads_the_layout_init_landed():
    inner = _booster(rows=14_000, tree_learner="data",
                     tpu_hist_chunk=2048)._inner
    read = _reader("merge.shard_balance")
    assert read({"rows": 14_000}) == 0.0            # a device with no row
    four = telemetry.InitRecord(
        *([0.0] * 6), 84_000_000, 0,
        layers.shard_rows(84_000_000, 100_663_296, 4))
    telemetry.record_run(None, four, [])
    assert read({"rows": 84_000_000}) == pytest.approx(0.338, abs=5e-4)
    assert read({"rows": 84_000_000}) == 8_502_528 / 25_165_824
    even = four._replace(shard_rows=(21_000_000,) * 4)
    telemetry.record_run(None, even, [])
    assert read({"rows": 84_000_000}) == 1.0
    del inner
