"""Traffic mode `train_steady`: one training job, warm, iterating.

Set-up builds ONE object — a `lightgbm_tpu.Booster` on a constructed
`lightgbm_tpu.Dataset` — drives it through the traffic's warm-up
iterations by the window's own call, `Booster.update()` (what
`engine.train` loops on), and hands that same object to the window. The
window counts whole iterations until `--seconds` have passed, then drains
the pipeline; `train_mrow_iters_per_s` is all rows x iterations over all
of the window's seconds. After the window has closed, peak memory has
been read and the program's state is freed, `reference.Reference` follows
the first warm-up step(s) from a zero score of its own and then the LAST
tree of the window, its score seeded from the program's at the window's
opening plus the program's leaf values of the window's earlier trees; the
numbers it reads are held to the cell's limits.
"""
from __future__ import annotations

import contextlib
import gc
import os
import shutil
import sys
import tempfile
import time

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import datagen  # noqa: E402
import reference  # noqa: E402
import trace_reduce  # noqa: E402


def tree_arrays(tree) -> dict:
    """The public content of one trained tree as plain arrays."""
    m = max(int(tree.num_leaves) - 1, 0)
    nl = int(tree.num_leaves)
    return {
        "split_feature": np.asarray(tree.split_feature[:m], np.int64),
        "threshold": np.asarray(tree.threshold[:m], np.float64),
        "left_child": np.asarray(tree.left_child[:m], np.int64),
        "right_child": np.asarray(tree.right_child[:m], np.int64),
        "split_gain": np.asarray(tree.split_gain[:m], np.float64),
        "internal_count": np.asarray(tree.internal_count[:m], np.int64),
        "leaf_value": np.asarray(tree.leaf_value[:nl], np.float64),
        "leaf_count": np.asarray(tree.leaf_count[:nl], np.int64),
    }


def judge(compared: dict, limits: dict):
    """Each number beside its limit; correct iff every one holds."""
    rows, ok = {}, True
    for name, value in compared.items():
        if name not in limits:
            raise KeyError(f"no limit for compared number {name!r}")
        limit = limits[name]
        good = bool(np.isfinite(value)) and value <= limit
        ok = ok and good
        rows[name] = {"value": value, "limit": limit}
    return ok, rows


def prepare(ctx: dict) -> dict:
    """Data from the seed and the constructed Dataset (host binning).
    `readings.py` shares one of these between the variants of a seed."""
    import lightgbm_tpu as lgb
    config = ctx["config"]
    rows, features = int(ctx["rows"]), int(config["features"])
    t = time.perf_counter()
    fresh = ({"base_seed": ctx["data_seed"]} if "data_seed" in ctx else {})
    X, y = datagen.generator(config["generator"])(
        rows, features, int(ctx["seed"]) % 2 ** 63, **fresh)
    generate_s = time.perf_counter() - t
    t = time.perf_counter()
    ds = lgb.Dataset(X, y, params=dict(config["params"],
                                       **ctx.get("params_override", {})))
    ds.construct()
    return {"X": X, "y": y, "ds": ds, "generate_s": generate_s,
            "construct_host_s": time.perf_counter() - t}


def run(ctx: dict) -> dict:
    """ctx: cell, config, traffic (dicts), seed, seconds, trace (bool),
    rows, t_start (perf_counter at process start), startup_s; optional
    fault (a name of faults.FAULTS), control (bool), data_seed (another
    data set than the benchmark's) and params_override (the program run
    otherwise than the configuration states: a control)."""
    import jax
    import lightgbm_tpu as lgb
    from jax.profiler import TraceAnnotation
    from lightgbm_tpu import telemetry

    cell, config, traffic = ctx["cell"], ctx["config"], ctx["traffic"]
    rows, features = int(ctx["rows"]), int(config["features"])
    params = dict(config["params"], **ctx.get("params_override", {}))
    warmup = int(traffic["warmup_iterations"])
    checked = min(int(traffic.get("checked_iterations", warmup)), warmup)
    out = {"phases": {}}

    obs = telemetry.install_observer()
    prepared = ctx.get("prepared") or prepare(ctx)
    X, y, ds = prepared["X"], prepared["y"], prepared["ds"]
    out["phases"]["generate_s"] = prepared["generate_s"]

    if ctx.get("fault"):
        import faults
        plant = faults.planted(ctx["fault"])
    else:
        plant = contextlib.nullcontext()

    with plant:
        t = time.perf_counter()
        booster = lgb.Booster(dict(params), ds)
        inner = booster._inner
        jax.block_until_ready(inner._binned)
        construct_s = prepared["construct_host_s"] + time.perf_counter() - t

        def drain():
            booster.current_iteration()       # flushes the pipelined tree
            jax.block_until_ready(inner._score)

        t = time.perf_counter()
        scores = []
        for i in range(warmup):
            booster.update()
            drain()
            if i < checked or i == warmup - 1:
                scores.append(np.asarray(inner._score[0, :rows]))
        out["phases"]["warmup_s"] = time.perf_counter() - t
        setup = obs.snapshot()

        # ---- the window ------------------------------------------------
        trace_dir = None
        tracing = False
        trace_from = int(cell.get("trace_after_iterations", 2))
        trace_len = int(cell.get("trace_iterations", 3))
        traced_span = None
        iterations = stopped = 0
        returned = []   # seconds into the window at which each update() returned
        seconds = float(ctx["seconds"])
        t0 = time.perf_counter()
        while True:
            if ctx["trace"] and trace_dir is None and iterations == trace_from:
                with TraceAnnotation("bench/drain"):
                    drain()
                trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
                jax.profiler.start_trace(trace_dir)
                tracing = True
                traced_span = TraceAnnotation("bench/traced")
                traced_span.__enter__()
                traced_t0 = time.perf_counter()
            with TraceAnnotation("bench/update"):
                stopped += bool(booster.update())
            iterations += 1
            returned.append(time.perf_counter() - t0)
            if tracing and iterations == trace_from + trace_len:
                with TraceAnnotation("bench/drain"):
                    drain()
                out["traced_host_s"] = time.perf_counter() - traced_t0
                traced_span.__exit__(None, None, None)
                jax.profiler.stop_trace()
                tracing = False
            if not tracing and time.perf_counter() - t0 >= seconds:
                break
        with TraceAnnotation("bench/drain"):
            drain()
        t1 = time.perf_counter()
        after = obs.snapshot()
        score_close = np.asarray(inner._score[0, :rows])

    window_s = t1 - t0
    models = list(inner.models)
    pass_log = list(getattr(inner, "pass_log", []))
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    peak = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)
    limit = max((s.get("bytes_limit", 0) for s in stats), default=0)
    mappers = ds._lazy_init().mappers
    cuts = [np.asarray(mappers[j].bin_upper_bound, np.float64)
            for j in range(features)]
    schedule = dict(getattr(inner, "_schedule_info", {}))
    schedule.pop("grower", None)

    out.update({
        "rows": rows, "features": features,
        "iterations": iterations, "stopped": stopped,
        "window_s": window_s, "update_returned_s": returned,
        "setup_s": (t0 - ctx["t_start"]) + ctx.get("startup_s", 0.0),
        "construct_s": construct_s,
        "compile_setup": {"count": setup["total_compiles"],
                          "seconds": setup["total_seconds"]},
        "window_compiles": after["total_compiles"] - setup["total_compiles"],
        "memory_peak_bytes": int(peak), "memory_limit_bytes": int(limit),
        "schedule": schedule,
        "pass_log_window": [list(e) for e in pass_log[warmup:]],
        "trees_window": [tree_arrays(t) for t in models[warmup:]],
        "traced_trees": ([trace_from, trace_from + trace_len]
                         if "traced_host_s" in out else None),
        "train_mrow_iters_per_s": rows * iterations / window_s / 1e6,
    })
    checked_trees = [tree_arrays(t) for t in models[:checked]]

    # ---- free the program's state, then the reference -------------------
    del booster, inner, ds, models, mappers, prepared
    gc.collect()

    if trace_dir is not None:
        t = time.perf_counter()
        try:
            out["trace"] = trace_reduce.reduce_xplane(
                trace_reduce.newest_xplane(trace_dir))
        except ValueError:
            if not ctx.get("rehearsal"):   # the CPU has no device plane
                raise
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        out["phases"]["trace_reduce_s"] = time.perf_counter() - t

    t = time.perf_counter()
    ref = reference.Reference(
        X, y, cuts, num_leaves=int(params["num_leaves"]),
        learning_rate=float(params["learning_rate"]),
        min_sum_hessian_in_leaf=float(params["min_sum_hessian_in_leaf"]),
        min_data_in_leaf=int(params["min_data_in_leaf"]),
        lambda_l2=float(params.get("lambda_l2", 0.0)),
        max_bin=int(config["params"]["max_bin"]),
        control=bool(ctx.get("control")))
    window_trees = out["trees_window"]
    if len(checked_trees) < checked or not window_trees:
        raise RuntimeError("the program produced fewer trees than checked")
    steps = [ref.follow(tree, score)
             for tree, score in zip(checked_trees, scores)]
    ref.seed_score(scores[-1], window_trees[:-1])
    steps.append(ref.follow(window_trees[-1], score_close))
    del ref
    gc.collect()
    out["phases"]["reference_s"] = time.perf_counter() - t
    out["steps"] = steps

    compared = reference.worst_over_steps(steps)
    compared["window_compiles"] = out["window_compiles"]
    compared["stopped_iterations"] = stopped
    out["correct"], out["compared"] = judge(compared, ctx["limits"])
    if ctx.get("control"):
        ctl = reference.worst_over_steps(steps, "ctl_")
        ctl["count_mismatch"] = 0
        out["control_correct"], out["control_compared"] = judge(
            ctl, {k: ctx["limits"][k] for k in ctl})
    return out
