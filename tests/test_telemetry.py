"""Unified telemetry subsystem tests (lightgbm_tpu/telemetry/):
registry semantics, run-log schema round-trip, the disabled-path zero-allocation contract, and the
compile/retrace observer."""
import json
import os
import tracemalloc

import numpy as np
import pytest

from lightgbm_tpu import telemetry
from lightgbm_tpu.telemetry import export as telemetry_export
from lightgbm_tpu.telemetry import metrics as telemetry_metrics


@pytest.fixture()
def clean_registry():
    """Telemetry on, empty registry; restores the disabled default."""
    telemetry.enable(True)
    telemetry.reset()
    telemetry.observer().reset()
    yield telemetry.registry()
    telemetry.enable(False)
    telemetry.reset()
    telemetry.observer().reset()


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------
def test_counter_labels_are_distinct_series(clean_registry):
    telemetry.counter_add("requests", 2, {"model": "a"})
    telemetry.counter_add("requests", 3, {"model": "b"})
    telemetry.counter_add("requests", 5, {"model": "a"})
    reg = clean_registry
    a = reg.counter("requests", {"model": "a"})
    b = reg.counter("requests", {"model": "b"})
    assert a.value == 7 and a.events == 2
    assert b.value == 3 and b.events == 1


def test_gauge_last_write_wins(clean_registry):
    telemetry.gauge_set("depth", 4)
    telemetry.gauge_set("depth", 2)
    assert clean_registry.gauge("depth").value == 2


def test_histogram_quantiles_bucket_resolution(clean_registry):
    h = clean_registry.histogram("lat", bounds=(1, 2, 4, 8, 16))
    for v in [0.5] * 50 + [3.0] * 40 + [10.0] * 9 + [100.0]:
        h.observe(v)
    assert h.count == 100
    # p50 falls in the <=1 bucket, p90 in (2,4], p99 in (8,16]
    assert h.quantile(0.50) <= 1.0
    assert 2.0 <= h.quantile(0.90) <= 4.0
    assert 8.0 <= h.quantile(0.99) <= 16.0
    # overflow observations cap at the observed max, not +Inf
    assert h.quantile(1.0) == 100.0
    snap = h.snapshot()
    assert sum(snap["buckets"]) == 100
    assert snap["min"] == 0.5 and snap["max"] == 100.0


def test_span_timer_accumulates_under_name(clean_registry):
    with telemetry.span("phase/x"):
        pass
    with telemetry.span("phase/x"):
        pass
    acc = clean_registry.phases["phase/x"]
    assert acc.count == 2
    assert acc.total >= 0.0


def test_span_nesting_tracks_current_site(clean_registry):
    assert telemetry.current_site() is None
    with telemetry.span("outer"):
        assert telemetry.current_site() == "outer"
        with telemetry.span("inner"):
            assert telemetry.current_site() == "inner"
        assert telemetry.current_site() == "outer"
    assert telemetry.current_site() is None


# ---------------------------------------------------------------------------
# disabled path: zero allocation, zero instruments
# ---------------------------------------------------------------------------
def test_disabled_path_allocates_nothing():
    telemetry.enable(False)
    telemetry.reset()
    # singleton no-op span: every disabled span() call returns the SAME
    # object (no generator/closure allocation per call)
    assert telemetry.span("a") is telemetry.span("b")
    # warm up any lazy interning, then measure
    for _ in range(3):
        telemetry.counter_add("x", 1)
        with telemetry.span("x"):
            pass
        telemetry.gauge_set("y", 1.0)
        telemetry.observe("z", 0.5)
    tracemalloc.start()
    try:
        for _ in range(100):
            telemetry.counter_add("x", 1)
            with telemetry.span("x"):
                pass
            telemetry.gauge_set("y", 1.0)
            telemetry.observe("z", 0.5)
        current, _peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert current == 0, f"disabled path retained {current} bytes"
    # and nothing was registered
    reg = telemetry.registry()
    assert not reg.counters and not reg.phases \
        and not reg.gauges and not reg.histograms


# ---------------------------------------------------------------------------
# run-log schema round-trip
# ---------------------------------------------------------------------------
def _write_and_read(tmp_path, records):
    rl = telemetry.RunLog(str(tmp_path), rank=0)
    for rec in records:
        rl.write(dict(rec))
    rl.close()
    return telemetry.read_records(rl.path)


def test_runlog_schema_roundtrip(tmp_path):
    header = {"type": "header", "schema": telemetry.SCHEMA_VERSION,
              "rank": 0, "world": 1, "run_id": "t0",
              "fingerprint": "f" * 64,
              "devices": {"platform": "cpu", "num_devices": 8},
              "versions": {"jax": "0"}}
    iteration = {"type": "iteration", "iteration": 0,
                 "metrics": {"valid_0/auc": 0.9},
                 "phases": {"tree/grow": {"seconds": 0.1, "count": 1}},
                 "counters": {"boosting/bagging_refresh": 1.0},
                 "compile": {"compiles": 2, "seconds": 1.5, "retraces": 0}}
    event = {"type": "event", "kind": "checkpoint_saved", "iteration": 0}
    summary = {"type": "summary", "iterations": 1, "phases": {},
               "compile": {}}
    got = _write_and_read(tmp_path, [header, iteration, event, summary])
    assert [r["type"] for r in got] == ["header", "iteration", "event",
                                       "summary"]
    for rec in got:
        telemetry.validate_record(rec)  # survives JSON round-trip
    assert got[1]["metrics"]["valid_0/auc"] == 0.9
    assert got[1]["phases"]["tree/grow"]["count"] == 1


def test_runlog_rejects_malformed_records(tmp_path):
    rl = telemetry.RunLog(str(tmp_path), rank=0)
    with pytest.raises(ValueError):
        rl.write({"type": "nonsense"})
    with pytest.raises(ValueError):
        rl.write({"type": "iteration", "iteration": "three",
                  "metrics": {}, "phases": {}, "counters": {},
                  "compile": {}})
    with pytest.raises(ValueError):
        rl.write({"type": "header", "schema": telemetry.SCHEMA_VERSION + 1,
                  "rank": 0, "world": 1, "run_id": "x", "fingerprint": "",
                  "devices": {}, "versions": {}})
    rl.close()


def test_runlog_torn_tail_is_dropped(tmp_path):
    path = os.path.join(str(tmp_path), "runlog_r0.jsonl")
    with open(path, "w") as fh:
        fh.write(json.dumps({"type": "event", "kind": "a",
                             "time": 0.0}) + "\n")
        fh.write('{"type": "event", "kind": "tr')  # preemption mid-write
    recs = telemetry.read_records(path)
    assert len(recs) == 1 and recs[0]["kind"] == "a"


def test_train_run_emits_schema_valid_log(tmp_path):
    """End-to-end: a real training run with tpu_telemetry_dir set leaves
    header + one record per iteration + summary, all schema-valid, and
    the report script's digest parses it."""
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(0)
    X = rng.randn(300, 5)
    y = (X[:, 0] + X[:, 1] > 0).astype(np.float32)
    ds = lgb.Dataset(X, y)
    td = str(tmp_path / "telemetry")
    try:
        lgb.train({"objective": "binary", "verbose": -1,
                   "tpu_telemetry_dir": td, "min_data_in_leaf": 5},
                  ds, num_boost_round=4, valid_sets=[ds],
                  verbose_eval=False)
    finally:
        telemetry.enable(False)
        telemetry.reset()
        telemetry.observer().reset()
    recs = telemetry.read_records(os.path.join(td, "runlog_r0.jsonl"))
    for rec in recs:
        telemetry.validate_record(rec)
    types = [r["type"] for r in recs]
    assert types[0] == "header" and types[-1] == "summary"
    iters = [r for r in recs if r["type"] == "iteration"]
    assert [r["iteration"] for r in iters] == [0, 1, 2, 3]
    assert iters[0]["metrics"]  # eval metrics recorded
    assert iters[0]["compile"]["compiles"] > 0  # first iter compiles
    hdr = recs[0]
    assert hdr["devices"]["platform"] == "cpu"
    import jax
    assert hdr["devices"]["device_kind"] == jax.devices()[0].device_kind
    assert hdr["schedule"]["grower"]["num_leaves"] == 31
    # Prometheus exposition written alongside
    prom = os.path.join(td, "metrics_r0.prom")
    assert os.path.exists(prom)
    text = open(prom).read()
    assert "lgbmtpu_phase_seconds_total" in text
    assert 'rank="0"' in text
    # the report script renders it
    import subprocess
    import sys
    res = subprocess.run(
        [sys.executable, "scripts/telemetry_report.py", td, "--json"],
        capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert res.returncode == 0, res.stderr
    digest = json.loads(res.stdout)
    assert digest["runs"][0]["iterations"] == 4


# ---------------------------------------------------------------------------
# compile/retrace observer
# ---------------------------------------------------------------------------
def test_retrace_observer_counts_forced_retrace(clean_registry):
    import jax
    import jax.numpy as jnp

    obs = telemetry.install_observer()
    obs.reset()

    @jax.jit
    def f(x):
        return x * 2 + 1

    # inputs built OUTSIDE the span: their own fill programs compile
    # too and must not be charged to the probed site
    x3 = jnp.ones(3)
    x7 = jnp.ones(7)
    jax.block_until_ready((x3, x7))
    obs.reset()
    telemetry.reset()
    site = "test/retrace_site"
    with telemetry.span(site):
        f(x3).block_until_ready()   # first trace+compile
        f(x3).block_until_ready()   # cache hit: no compile
        f(x7).block_until_ready()   # new shape -> forced retrace
    snap = obs.snapshot()
    assert snap["sites"][site]["compiles"] == 2
    assert obs.retraces(site) == 1
    assert snap["sites"][site]["seconds"] > 0
    # attribution also lands in labeled registry counters
    c = clean_registry.counter("compile/count", {"site": site})
    assert c.value == 2


def test_observer_uninstall_stops_counting(clean_registry):
    import jax
    import jax.numpy as jnp

    obs = telemetry.install_observer()
    obs.reset()
    obs.uninstall()
    jax.jit(lambda x: x + 3)(jnp.ones(5)).block_until_ready()
    assert obs.total_compiles == 0
    obs.install()


# ---------------------------------------------------------------------------
# export
# ---------------------------------------------------------------------------
def test_prometheus_exposition_shape(clean_registry):
    telemetry.counter_add("predict/chunks", 3)
    telemetry.gauge_set("heartbeat/iteration", 7, {"phase": "train"})
    h = clean_registry.histogram("serving/latency_seconds",
                                 bounds=(0.001, 0.01, 0.1))
    h.observe(0.0005)
    h.observe(0.05)
    with telemetry.span("tree/grow"):
        pass
    text = telemetry_export.prometheus_text(
        clean_registry.snapshot(), extra_labels={"rank": "3"})
    assert ('lgbmtpu_counter_total{name="predict/chunks",rank="3"} 3'
            in text)
    assert 'phase="train"' in text and 'rank="3"' in text
    assert 'lgbmtpu_serving_latency_seconds_bucket{le="0.001",rank="3"} 1' \
        in text
    assert 'lgbmtpu_serving_latency_seconds_bucket{le="+Inf",rank="3"} 2' \
        in text
    assert "lgbmtpu_serving_latency_seconds_count" in text
    assert 'lgbmtpu_phase_seconds_total{phase="tree/grow",rank="3"}' in text


def test_merge_snapshots_sums_counters_keeps_gauges_per_rank():
    r0 = {"counters": [{"name": "c", "labels": [], "value": 2.0,
                        "events": 1}],
          "phases": [{"name": "p", "seconds": 1.0, "count": 1}],
          "histograms": [{"name": "h", "labels": [], "bounds": [1.0],
                          "buckets": [1, 0], "count": 1, "sum": 0.5,
                          "min": 0.5, "max": 0.5}],
          "gauges": [{"name": "heartbeat/iteration", "labels": [],
                      "value": 9.0, "updated_at": 0.0}]}
    r1 = {"counters": [{"name": "c", "labels": [], "value": 3.0,
                        "events": 2}],
          "phases": [{"name": "p", "seconds": 2.0, "count": 1}],
          "histograms": [{"name": "h", "labels": [], "bounds": [1.0],
                          "buckets": [0, 1], "count": 1, "sum": 2.0,
                          "min": 2.0, "max": 2.0}],
          "gauges": [{"name": "heartbeat/iteration", "labels": [],
                      "value": 4.0, "updated_at": 0.0}]}
    merged = telemetry_export.merge_snapshots([r0, r1])
    assert merged["counters"][0]["value"] == 5.0
    assert merged["phases"][0]["seconds"] == 3.0
    assert merged["histograms"][0]["buckets"] == [1, 1]
    assert merged["histograms"][0]["min"] == 0.5
    assert merged["histograms"][0]["max"] == 2.0
    # per-rank gauges survive with rank labels — a summed heartbeat
    # would destroy exactly the evidence it exists for
    gauges = {tuple(map(tuple, g["labels"])): g["value"]
              for g in merged["gauges"]}
    assert gauges[(("rank", "0"),)] == 9.0
    assert gauges[(("rank", "1"),)] == 4.0


def test_allgather_bytes_single_process():
    from lightgbm_tpu.parallel.multihost import allgather_bytes
    assert allgather_bytes(b"abc") == [b"abc"]


# ---------------------------------------------------------------------------
# heartbeat
# ---------------------------------------------------------------------------
def test_heartbeat_file_written_atomically(tmp_path, clean_registry):
    hb = str(tmp_path / "hb_r0.json")
    telemetry.set_heartbeat_file(hb)
    try:
        telemetry.heartbeat(41, phase="train", rank=0)
        telemetry.heartbeat(42, phase="train", rank=0)
        with open(hb) as fh:
            rec = json.load(fh)
        assert rec["iteration"] == 42 and rec["phase"] == "train"
        assert clean_registry.gauge("heartbeat/iteration",
                                    {"phase": "train"}).value == 42.0
    finally:
        telemetry.set_heartbeat_file("")


# ---------------------------------------------------------------------------
# serving percentile surface (satellite: Predictor.stats from histogram)
# ---------------------------------------------------------------------------
def test_predictor_stats_percentiles_from_histogram():
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(1)
    X = rng.randn(200, 4)
    y = (X[:, 0] > 0).astype(np.float32)
    b = lgb.train({"objective": "binary", "verbose": -1,
                   "min_data_in_leaf": 5}, lgb.Dataset(X, y),
                  num_boost_round=3, verbose_eval=False)
    pred = b.serving_predictor()
    for _ in range(8):
        pred.predict(X[:4])
    stats = pred.stats()
    assert stats["requests"] == 8 and stats["rows"] == 32
    assert stats["p50_latency_ms"] is not None
    assert stats["p50_latency_ms"] <= stats["p95_latency_ms"] \
        <= stats["p99_latency_ms"] <= stats["max_latency_ms"]
    assert stats["rows_per_second"] > 0
    assert stats["stack_restacks"] == 1
