"""The layer names of PR 26 (lightgbm_tpu/telemetry/layers.py, devtrace.py):
named scopes in the jitted programs are metadata only, the reducer's
rules on hand-made events, the per-tree record's identities, and host
spans that land in the profiler's trace and never wait for the device.
Counts and control flow on the CPU; nothing here is a device number."""
import contextlib
import glob
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu import telemetry
from lightgbm_tpu.boosting import gbdt as gbdt_mod
from lightgbm_tpu.learner.grow import FMETA_KEYS, GrowerConfig, grow_tree
from lightgbm_tpu.telemetry import devtrace, layers

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PARAMS = {"objective": "binary", "num_leaves": 31, "max_bin": 63,
          "min_data_in_leaf": 1, "verbose": -1}


def _booster(rows=20000, features=8, **params):
    rng = np.random.RandomState(3)
    X = rng.randn(rows, features).astype(np.float32)
    y = (X[:, 0] - X[:, 1] ** 2 + 0.3 * rng.randn(rows) > 0).astype(
        np.float32)
    p = dict(PARAMS, **params)
    return lgb.Booster(p, lgb.Dataset(X, y, params=p))


# ---------------------------------------------------------------------------
# named scopes: every name is in the program, and only as metadata
# ---------------------------------------------------------------------------
def _lower_serial(inner):
    """(gradient program, grow+update program) of a small serial booster,
    lowered afresh."""
    grad, hess = inner._compute_gradients(inner._score)
    arrs = {k: getattr(inner.objective, k) for k in inner._jit_grads_keys}
    grads = inner._jit_grads.lower(inner._score, arrs)
    grow = jax.jit(gbdt_mod._grow_and_update_impl,
                   static_argnames=("cls", "cfg")).lower(
        inner._score, inner._binned, grad, hess, inner._base_weight,
        jnp.ones(inner._num_features_padded, bool), jnp.float32(0.1),
        jnp.int32(inner._n), tuple(inner._fmeta[k] for k in FMETA_KEYS),
        cls=0, cfg=inner._grower_cfg)
    return grads, grow


def _lower_data_parallel():
    """grow_tree under a 2-device data axis (psum merge), lowered."""
    n, f, b = 2048, 4, 16
    rng = np.random.RandomState(0)
    cfg = GrowerConfig(num_leaves=7, max_bins=b, chunk=256, lambda_l1=0.0,
                       lambda_l2=0.0, min_gain_to_split=0.0,
                       min_data_in_leaf=1, min_sum_hessian_in_leaf=1e-3,
                       max_depth=-1, batch_k=2, data_axis="data",
                       num_data_shards=2)
    fmeta = [jnp.full(f, b, jnp.int32), jnp.zeros(f, jnp.int32),
             jnp.zeros(f, jnp.int32), jnp.zeros(f, bool),
             jnp.arange(f, dtype=jnp.int32), jnp.zeros(f, jnp.int32),
             jnp.zeros(f, bool)]
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    from lightgbm_tpu.learner.grow import TreeGrowerState
    spec = TreeGrowerState(**{k: P() for k in TreeGrowerState._fields})
    spec = spec._replace(leaf_id=P("data"))
    fn = jax.jit(jax.shard_map(
        lambda bn, g, h, w, fm, *rest: grow_tree(bn, g, h, w, fm, *rest, cfg),
        mesh=mesh, in_specs=(P("data", None), P("data"), P("data"),
                             P("data"), P(None)) + (P(None),) * 7,
        out_specs=spec, check_vma=False))
    return fn.lower(
        jnp.asarray(rng.randint(0, b, size=(n, f)).astype(np.uint8)),
        jnp.asarray(rng.randn(n).astype(np.float32)), jnp.ones(n),
        jnp.ones(n), jnp.ones(f, bool), *fmeta)


@pytest.fixture(scope="module")
def lowered():
    """Debug text of the serial programs (compaction and subtraction on)
    and of the data-parallel grower; the serial text also without debug
    info, once with scopes and once with `jax.named_scope` made a no-op."""
    inner = _booster(tpu_hist_chunk=2048, tpu_compact_threshold=0.25)._inner
    assert inner._grower_cfg.hist_compact and inner._grower_cfg.hist_subtract
    grads, grow = _lower_serial(inner)
    out = {"serial": grads.as_text(debug_info=True)
           + grow.as_text(debug_info=True),
           "plain": grads.as_text() + grow.as_text(),
           "parallel": _lower_data_parallel().as_text(debug_info=True)}
    real = jax.named_scope
    jax.clear_caches()
    inner._jit_grads = None
    gbdt_mod._shared_gradient_jit.cache_clear()
    try:
        jax.named_scope = lambda name: contextlib.nullcontext()
        grads, grow = _lower_serial(inner)
        out["unscoped_debug"] = grads.as_text(debug_info=True) \
            + grow.as_text(debug_info=True)
        out["unscoped_plain"] = grads.as_text() + grow.as_text()
    finally:
        jax.named_scope = real
        # the unscoped traces must not serve a later test
        jax.clear_caches()
        inner._jit_grads = None
        gbdt_mod._shared_gradient_jit.cache_clear()
    return out


def _lower_rank_gradients():
    """The gradient program of a small lambdarank booster, lowered."""
    rng = np.random.RandomState(5)
    sizes = rng.randint(1, 40, size=30)
    rows = int(sizes.sum())
    p = dict(PARAMS, objective="lambdarank")
    inner = lgb.Booster(p, lgb.Dataset(
        rng.randn(rows, 4).astype(np.float32),
        rng.randint(0, 5, size=rows).astype(np.float32), group=sizes,
        params=p))._inner
    inner._compute_gradients(inner._score)
    arrs = {k: getattr(inner.objective, k) for k in inner._jit_grads_keys}
    return inner._jit_grads.lower(inner._score, arrs)


@pytest.mark.parametrize("name", layers.SCOPES)
def test_scope_is_in_the_lowered_program(lowered, name):
    if name.startswith("lgbm/gradients/rank_"):     # lambdarank's alone
        assert name not in lowered["serial"]
        assert name in _lower_rank_gradients().as_text(debug_info=True)
        return
    where = "parallel" if name == "lgbm/hist/merge" else "serial"
    assert name in lowered[where]


def test_scopes_are_metadata_only(lowered):
    assert layers.PREFIX not in lowered["unscoped_debug"]
    assert layers.PREFIX not in lowered["plain"]
    # the same operations in the same order: equal text without locations
    assert lowered["plain"] == lowered["unscoped_plain"]
    ops = re.findall(r"= (?:stablehlo|func|chlo)\.[a-z_.]+", lowered["plain"])
    assert len(ops) > 500


def test_scope_refuses_a_name_not_in_the_list():
    with pytest.raises(KeyError):
        layers.scope("lgbm/grow/typo")
    assert len(set(layers.SCOPES)) == len(layers.SCOPES)
    assert all(n.startswith(layers.PREFIX) for n in
               layers.SCOPES + layers.ITER_SPANS)


# ---------------------------------------------------------------------------
# devtrace on hand-made events (ns)
# ---------------------------------------------------------------------------
BODY = "jit(f)/jit(grow_tree)/while/body/"
DEVICE = {"/device:TPU:0": [
    ("while.1", 0, 1000, "jit(f)/jit(grow_tree)/while"),
    ("fusion.7", 100, 400, BODY + "lgbm/hist/contract/while/body/"
     "lgbm/hist/gather/gather"),
    ("fusion.8", 400, 900, BODY + "lgbm/hist/contract/dot_general"),
    ("copy.3", 1500, 1700, ""),
    ("fusion.9", 3000, 3400, "jit(f)/lgbm/score/update/add"),
]}
HOST = {"/host:CPU/python": [
    ("lgbm/iter/dispatch", 900, 1200),
    ("lgbm/iter/fetch", 1200, 3300),
    ("lgbm/iter/build_tree", 3300, 3350),
]}


def test_scope_of_takes_the_innermost_scope():
    assert devtrace.scope_of(DEVICE["/device:TPU:0"][1][3]) \
        == "lgbm/hist/gather"
    assert devtrace.scope_of("jit(f)/vmap(lgbm/split/scan)/cumsum") \
        == "lgbm/split/scan"
    assert devtrace.scope_of("jit(f)/while") == layers.UNSCOPED
    assert devtrace.scope_of("") == layers.UNSCOPED


MERGE_OP = ("%fusion.395 = f32[64,8,63]{2,1,0:T(8,128)S(1)} fusion(f32[24,28"
            ",63,3]{2,0,3,1:T(8,128)S(1)} %copy.222), kind=kCustom, "
            "calls=%all-reduce-scatter.clone.clone")


@pytest.mark.parametrize("path,op,want", [
    # the merge as XLA:TPU emits it on four chips: no op_name at all
    ("", MERGE_OP, "lgbm/hist/merge"),
    ("", "%all-reduce.28 = f32[256,8,63] all-reduce(%pad.341)",
     "lgbm/hist/merge"),
    # a collective that kept its path stays where the path puts it
    ("jit(f)/while/body/vmap(lgbm/split/scan)/pmax", "%pmax.36 = f32[24] "
     "all-reduce(%fusion.414)", "lgbm/split/scan"),
    ("", "%copy.3 = f32[8] copy(%p)", layers.UNSCOPED),
    ("jit(f)/while", MERGE_OP, "lgbm/hist/merge"),
])
def test_an_unscoped_collective_is_the_merge(path, op, want):
    assert devtrace.scope_of(path, op) == want


def test_devtrace_keeps_each_planes_own_seconds():
    """The shards of a data-parallel job do unequal work: the mean over
    planes beside each plane's busy seconds and scopes, and the table
    `scripts/profile_train.py` prints."""
    slow = [("fusion.8", 0, 1000, "jit(f)/lgbm/hist/contract/dot"),
            (MERGE_OP, 1000, 1100, "")]
    fast = [("fusion.8", 0, 300, "jit(f)/lgbm/hist/contract/dot"),
            (MERGE_OP, 300, 1100, "")]
    out = devtrace.reduce_events(
        {"/device:TPU:0": slow, "/device:TPU:3": fast}, HOST)
    assert out["scopes"]["lgbm/hist/merge"] == pytest.approx(450e-9)
    planes = out["planes"]
    assert planes["/device:TPU:3"]["scopes"]["lgbm/hist/merge"] \
        == pytest.approx(800e-9)
    assert planes["/device:TPU:0"]["scopes"]["lgbm/hist/contract"] \
        == pytest.approx(1000e-9)
    assert planes["/device:TPU:0"]["busy_s"] == pytest.approx(1100e-9)
    table = devtrace.layer_table(out)
    assert "| Scope, self s by device plane | 0 | 3 |" in table
    one = devtrace.layer_table(devtrace.reduce_events(DEVICE, HOST))
    assert "by device plane" not in one


def test_devtrace_while_encloses_its_body():
    out = devtrace.reduce_events(DEVICE, HOST)
    ops = {name: (s, scope) for name, s, scope in out["ops"]}
    assert ops["while.1"] == (pytest.approx(200e-9), layers.UNSCOPED)
    assert ops["fusion.8"] == (pytest.approx(500e-9), "lgbm/hist/contract")
    assert out["scopes"]["lgbm/hist/gather"] == pytest.approx(300e-9)
    # scopes + unscoped == busy == the union of the intervals
    assert out["busy_s"] == pytest.approx(1600e-9)
    assert sum(out["scopes"].values()) == pytest.approx(out["busy_s"])
    assert out["window_s"] == pytest.approx(3400e-9)


def test_devtrace_operation_without_scope_is_unscoped():
    out = devtrace.reduce_events(DEVICE, HOST)
    assert out["scopes"][layers.UNSCOPED] == pytest.approx(400e-9)
    assert ["copy.3", pytest.approx(200e-9), layers.UNSCOPED] in out["ops"]


def test_devtrace_gap_goes_to_the_innermost_program_span():
    out = devtrace.reduce_events(DEVICE, HOST)
    # 1000-1500 lies in dispatch|fetch by its middle (1250: fetch),
    # 1700-3000 in fetch
    assert out["idle_gaps"] == {"lgbm/iter/fetch": pytest.approx(1800e-9)}
    nested = {"t": HOST["/host:CPU/python"] + [("lgbm/outer", 0, 5000)]}
    out = devtrace.reduce_events(DEVICE, nested)
    assert out["idle_gaps"] == {"lgbm/iter/fetch": pytest.approx(1800e-9)}
    assert out["host_spans"]["lgbm/outer"] == pytest.approx(2550e-9)
    assert out["host_spans"]["lgbm/iter/fetch"] == pytest.approx(2100e-9)
    out = devtrace.reduce_events(DEVICE, {})
    assert out["idle_gaps"] == {devtrace.NO_SPAN: pytest.approx(1800e-9)}


def test_devtrace_means_over_device_planes_and_refuses_an_empty_trace():
    two = dict(DEVICE, **{"/device:TPU:1": DEVICE["/device:TPU:0"]})
    one, out = devtrace.reduce_events(DEVICE, HOST), \
        devtrace.reduce_events(two, HOST)
    assert out["busy_s"] == pytest.approx(one["busy_s"])
    assert out["scopes"] == pytest.approx(one["scopes"])
    with pytest.raises(ValueError):
        devtrace.reduce_events({"/device:TPU:0": []}, HOST)
    assert "| `lgbm/hist/contract` |" in devtrace.layer_table(one)


XSPACE = """
planes {
  name: "/device:TPU:0"
  lines { name: "XLA Modules" timestamp_ns: 1000
          events { metadata_id: 1 offset_ps: 0 duration_ps: 3400000 } }
  lines { name: "XLA Ops" timestamp_ns: 1000
          events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000 }
          events { metadata_id: 2 offset_ps: 100000 duration_ps: 300000 }
          events { metadata_id: 3 offset_ps: 400000 duration_ps: 500000 }
          events { metadata_id: 4 offset_ps: 1500000 duration_ps: 200000 } }
  event_metadata { key: 1 value { id: 1 name: "%while.1 = while()"
      stats { metadata_id: 7 str_value: "jit(f)/jit(grow_tree)/while:" } } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.7 = gather"
      stats { metadata_id: 8 str_value: "custom fusion" }
      stats { metadata_id: 7 ref_value: 9 } } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.8 = dot"
      stats { metadata_id: 7
              str_value: "jit(f)/while/body/lgbm/hist/contract/dot_general:" }
  } }
  event_metadata { key: 4 value { id: 4 name: "%copy.3 = copy" } }
  stat_metadata { key: 7 value { id: 7 name: "tf_op" } }
  stat_metadata { key: 8 value { id: 8 name: "hlo_category" } }
  stat_metadata { key: 9 value { id: 9
      name: "jit(f)/while/body/lgbm/hist/contract/lgbm/hist/gather/gather:" } }
}
planes {
  name: "/host:CPU"
  lines { name: "python" timestamp_ns: 1000
          events { metadata_id: 1 offset_ps: 900000 duration_ps: 1200000
                   stats { metadata_id: 1 int64_value: 5 } }
          events { metadata_id: 2 offset_ps: 0 duration_ps: 50000 } }
  event_metadata { key: 1 value { id: 1 name: "lgbm/iter/fetch" } }
  event_metadata { key: 2 value { id: 2 name: "bench/update" } }
  stat_metadata { key: 1 value { id: 1 name: "iteration" } }
}
"""


def test_devtrace_reads_the_scope_off_the_event_metadata(tmp_path):
    """The wire-format reader on a file jaxlib serialised from text: the
    scope is the `tf_op` stat of the event's METADATA (a string or a
    reference), only the "XLA Ops" line counts, only `lgbm/` host spans."""
    from jax.profiler import ProfileData
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    out = devtrace.reduce_xplane(str(path))
    assert out["events"] == 4 and out["lines"] == {
        "/device:TPU:0": ["XLA Modules", "XLA Ops"]}
    assert out["scopes"] == {
        "lgbm/hist/contract": pytest.approx(500e-9),
        layers.UNSCOPED: pytest.approx(400e-9),
        "lgbm/hist/gather": pytest.approx(300e-9)}
    assert out["busy_s"] == pytest.approx(1200e-9)
    assert out["host_spans"] == {"lgbm/iter/fetch": pytest.approx(1200e-9)}
    assert out["idle_gaps"] == {"lgbm/iter/fetch": pytest.approx(500e-9)}
    with pytest.raises(ValueError):
        host_only = XSPACE[XSPACE.index('planes {\n  name: "/host:CPU"'):]
        path.write_bytes(
            ProfileData.text_proto_to_serialized_xspace(host_only))
        devtrace.reduce_xplane(str(path))


# ---------------------------------------------------------------------------
# the per-tree record
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("pass_rows,num_passes,cap,want", [
    ([100, 100, 20, 30, 0, 0], 4, 25, (3, 1, 20)),     # mixed
    ([100, 100, 20, 30, 0, 0], 4, 0, (4, 0, 0)),       # compaction off
    ([100, 100, 20, 30, 0, 0], 4, 128, (1, 3, 150)),   # forced on
    ([100, 0, 0], 1, 25, (1, 0, 0)),                   # a stump
])
def test_split_passes(pass_rows, num_passes, cap, want):
    assert layers.split_passes(pass_rows, num_passes, cap) == want


@pytest.mark.parametrize("params,compacts", [
    ({"tpu_hist_chunk": 2048, "tpu_compact_threshold": 0.25}, True),
    ({"tpu_hist_chunk": 65536, "tpu_compact_threshold": 0.25}, False),
    # unset: 8 groups x 63 bins is narrower than any table whose full
    # pass costs more than the index build (schedule.compact_threshold)
    ({"tpu_hist_chunk": 2048}, False),
])
def test_tree_record_identities(params, compacts):
    booster = _booster(**params)
    for _ in range(3):
        booster.update()
    booster.current_iteration()
    inner = booster._inner
    assert bool(inner._grower_cfg.hist_compact) == compacts
    assert len(inner.pass_log) == 3
    for rec in inner.pass_log:
        assert isinstance(rec, telemetry.TreeRecord)
        assert rec._fields[:5] == ("num_passes", "table_high_water",
                                   "rows_contracted", "comm_elems",
                                   "comm_bytes")
        first_five = list(rec)[:5]
        assert first_five == [rec[0], rec[1], rec[2], rec[3], rec[4]]
        assert rec[0] == rec.num_passes >= 2 and rec[2] > 0
        assert rec.full_passes + rec.compact_passes == rec.num_passes
        assert rec.rows_gathered + rec.full_passes * inner._n \
            == rec.rows_contracted
        assert rec.rows_indexed == rec.compact_passes * inner._n_pad
        assert (rec.compact_passes > 0) == compacts
        assert rec.dispatch_s > 0 and rec.fetch_wait_s > 0 \
            and rec.build_tree_s > 0


def test_benchmark_readers_read_the_record_and_nothing_before_it():
    import importlib.util

    def reader(name):
        spec = importlib.util.spec_from_file_location(
            "reader_" + name.replace(".", "_"), os.path.join(
                ROOT, "benchmarks", "layer_metrics", name + ".py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read

    rec = telemetry.TreeRecord(18, 700, 5e6, 0.0, 0.0, full_passes=12,
                               compact_passes=6, rows_indexed=6000,
                               rows_gathered=900, dispatch_s=0.25,
                               fetch_wait_s=4.0, build_tree_s=0.5)
    new = {"rows": 500, "pass_log_window": [list(rec), list(rec)]}
    old = {"rows": 500, "pass_log_window": [list(rec)[:5]]}
    want = {"grower.full_passes_per_tree": 12.0,
            "grower.indexed_per_row": 12.0,
            "loop.host_s_per_tree": 0.75,
            "loop.fetch_wait_s_per_tree": 4.0}
    for name, value in want.items():
        assert reader(name)(new) == pytest.approx(value)
        assert reader(name)(old) is None
        assert reader(name)({}) is None
    assert reader("grower.passes_per_tree")(new) == 18.0


# ---------------------------------------------------------------------------
# host spans: in the profiler's trace, and never waiting for the device
# ---------------------------------------------------------------------------
def test_iteration_spans_land_in_the_profiler_trace(tmp_path):
    """With telemetry off, a profiler session alone turns the spans on;
    the dispatch of tree i and its fetch, one call later, share `i`."""
    from jax.profiler import ProfileData
    assert not telemetry.enabled()
    booster = _booster()
    booster.update()
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(3):
            booster.update()
        booster.current_iteration()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    seen = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("lgbm/iter/"):
                    seen.setdefault(ev.name, []).append(
                        dict(ev.stats)["iteration"])
    assert set(seen) == set(layers.ITER_SPANS)
    # the program's own reader finds the same spans in the same file
    own = [name for plane in devtrace.read_xspace(path)
           for line in plane["lines"] for name, *_ in line["events"]
           if name.startswith("lgbm/iter/")]
    assert sorted(own) == sorted(n for n, ids in seen.items() for _ in ids)
    assert seen["lgbm/iter/dispatch"] == [1, 2, 3]
    assert seen["lgbm/iter/fetch"] == [0, 1, 2, 3]
    assert seen["lgbm/iter/build_tree"] == seen["lgbm/iter/fetch"]
    # and once no session is open the disabled path is the singleton again
    assert telemetry.span("a") is telemetry.span("b", iteration=1)


def test_no_training_span_waits_for_the_device(monkeypatch):
    """Enabled path, three pipelined iterations: no block_until_ready is
    called while a span is open, and the phases are host seconds."""
    under_span = []
    real = jax.block_until_ready

    def spy(x):
        if telemetry.current_site() is not None:
            under_span.append(telemetry.current_site())
        return real(x)

    booster = _booster()
    booster.update()
    monkeypatch.setattr(jax, "block_until_ready", spy)
    telemetry.enable(True)
    telemetry.reset()
    try:
        for _ in range(3):
            booster.update()
        phases = {k: v.count for k, v in telemetry.registry().phases.items()}
    finally:
        telemetry.enable(False)
        telemetry.reset()
    assert under_span == []
    assert phases["lgbm/iter/dispatch"] == 3
    assert phases["lgbm/iter/gradients"] == 3
    assert phases["lgbm/iter/fetch"] == 3


@pytest.mark.parametrize("package", ["boosting", "learner"])
def test_no_span_in_the_package_asks_to_block(package):
    pattern = re.compile(r"block=|tracing\.block|telemetry\.block|\.block\(")
    for path in glob.glob(os.path.join(ROOT, "lightgbm_tpu", package,
                                       "*.py")):
        with open(path) as fh:
            hits = [ln for ln in fh if pattern.search(ln)]
        assert hits == [], (path, hits)
