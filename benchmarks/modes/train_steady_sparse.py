"""Traffic mode `train_steady_sparse`: `train_steady` for a table handed
over as a `scipy.sparse` matrix.

The same window as `modes/train_steady.py` (ONE `lightgbm_tpu.Booster`,
warm-up iterations through `Booster.update()`, whole iterations for
`--seconds`, a drain; `train_mrow_iters_per_s` is all rows x iterations
over all of the window's seconds), with what a sparse one-hot table
changes:

- the generator returns the CSR matrix, the label, and for the reference
  the category codes the matrix was coded from with the map of its
  columns; `lgb.Dataset` is given the CSR matrix and nothing else. Before
  anything is built the mode asks the program for its sparse source
  (`lightgbm_tpu.ingest.SparseSource`): a program without it would make
  the matrix dense (tens of GB at this size), so the mode exits non-zero
  with one line instead;
- the reference is `reference_sparse.SparseReference`, from the codes;
  the dataset layer is held to three numbers of its own:
  `bin_count_mismatch`, `bin_pop_mismatch`, and `bundle_lost_values`, the
  program's stored matrix (taken after the window) decoded by its own
  group layout against the CSR matrix's entries;
- readers are handed `features` = the stored entries a row (8), the least
  work of an algorithm that knows the table is sparse, so `iter_mfu`
  cannot read high from 692 zeros a row; `columns` is the table's width;
- a traced run hands its readers `trace_scopes`, as `train_steady_rank`
  does, and set-up runs under that mode's guard.
"""
from __future__ import annotations

import contextlib
import gc
import os
import resource
import shutil
import sys
import tempfile
import time

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import datagen  # noqa: E402
import reference_sparse  # noqa: E402
import trace_reduce  # noqa: E402

_rank = datagen.load_file_module(
    os.path.join(HERE, "modes", "train_steady_rank.py"),
    "benchmarks_mode_train_steady_rank")
tree_arrays, judge = _rank.tree_arrays, _rank.judge
SetupGuard, scopes_by_plane = _rank.SetupGuard, _rank.scopes_by_plane

# From the entry of `run()` to the window's opening the cell takes 68-80 s
# with a warm compile cache (`setup_s` 78.5-89.6 less ~10 s of start-up;
# the two warm-up trees alone are 38 s) and 14-17 s more when the 255-bin
# grow program compiles (my chip runs, PR 38): the limit is 2.5 times the
# second
SETUP_LIMIT_S = 240.0


def host_rss_bytes() -> int:
    """What the process holds of the host's memory now."""
    with open("/proc/self/statm") as fh:
        return int(fh.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")


def require_sparse_source() -> None:
    try:
        from lightgbm_tpu.ingest import SparseSource  # noqa: F401
    except ImportError:
        raise SystemExit(
            "train_steady_sparse: this program has no sparse source "
            "(lightgbm_tpu.ingest.SparseSource): lgb.Dataset would make "
            "the CSR matrix dense; nothing was built or measured")


def prepare(ctx: dict, guard=None) -> dict:
    """The table from the seed and the constructed Dataset (host binning
    from the stored entries). `readings_sparse.py` shares one of these
    between the variants of a seed."""
    import lightgbm_tpu as lgb
    require_sparse_source()
    config = ctx["config"]
    rows, columns = int(ctx["rows"]), int(config["features"])
    t = time.perf_counter()
    fresh = ({"base_seed": ctx["data_seed"]} if "data_seed" in ctx else {})
    csr, y, codes, column_map = datagen.generator(config["generator"])(
        rows, columns, int(ctx["seed"]) % 2 ** 63, **fresh)
    generate_s = time.perf_counter() - t
    if guard:
        guard.at("Dataset.construct()")
    t = time.perf_counter()
    ds = lgb.Dataset(csr, y, params=dict(config["params"],
                                         **ctx.get("params_override", {})))
    ds.construct()
    return {"csr": csr, "y": y, "codes": codes, "column_map": column_map,
            "ds": ds, "generate_s": generate_s,
            "construct_host_s": time.perf_counter() - t}


def group_layout(inner) -> dict:
    """The program's stored layout as plain arrays, one entry a used
    feature: what `reference_sparse.bundle_lost_values` decodes by."""
    used = list(inner.used_features)
    groups = inner.groups
    return {"used": np.asarray(used, np.int64),
            "group": np.asarray(groups.group_of, np.int64),
            "offset": np.asarray(groups.offset_of, np.int64),
            "bundled": np.asarray(groups.is_bundled, bool),
            "num_bin": np.asarray([inner.mappers[j].num_bin for j in used],
                                  np.int64)}


def run(ctx: dict) -> dict:
    """ctx: as `modes/train_steady.py`'s; `fault` is a name of
    `faults_sparse.FAULTS`."""
    require_sparse_source()
    import jax
    import lightgbm_tpu as lgb
    from jax.profiler import TraceAnnotation
    from lightgbm_tpu import telemetry

    cell, config, traffic = ctx["cell"], ctx["config"], ctx["traffic"]
    rows, columns = int(ctx["rows"]), int(config["features"])
    params = dict(config["params"], **ctx.get("params_override", {}))
    warmup = int(traffic["warmup_iterations"])
    checked = min(int(traffic.get("checked_iterations", warmup)), warmup)
    out = {"phases": {}}
    rss = out["phases"]["host_rss_bytes_after"] = {}

    obs = telemetry.install_observer()
    rss["start"] = host_rss_bytes()   # the interpreter, jax, the TPU client
    if ctx.get("fault"):
        import faults_sparse
        plant = faults_sparse.planted(ctx["fault"])
    else:
        plant = contextlib.nullcontext()

    with plant:
        with SetupGuard(SETUP_LIMIT_S) as guard:
            guard.at("data from the seed")
            prepared = ctx.get("prepared") or prepare(ctx, guard)
            csr, y, codes, column_map, ds = (
                prepared[k] for k in ("csr", "y", "codes", "column_map", "ds"))
            out["phases"]["generate_s"] = prepared["generate_s"]
            rss["construct"] = host_rss_bytes()

            guard.at("Booster(params, dataset)")
            t = time.perf_counter()
            booster = lgb.Booster(dict(params), ds)
            inner = booster._inner
            jax.block_until_ready(inner._binned)
            construct_s = (prepared["construct_host_s"]
                           + time.perf_counter() - t)
            rss["booster"] = host_rss_bytes()

            def drain():
                booster.current_iteration()   # flushes the pipelined tree
                jax.block_until_ready(inner._score)

            t = time.perf_counter()
            scores = []
            for i in range(warmup):
                guard.at(f"warm-up iteration {i + 1} of {warmup}")
                booster.update()
                drain()
                if i < checked or i == warmup - 1:
                    scores.append(np.asarray(inner._score[0, :rows]))
            out["phases"]["warmup_s"] = time.perf_counter() - t
            rss["warmup"] = host_rss_bytes()
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
        stats = [d.memory_stats() or {} for d in jax.local_devices()]
        rss["window"] = host_rss_bytes()

    window_s = t1 - t0
    models = list(inner.models)
    pass_log = list(getattr(inner, "pass_log", []))
    peak = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)
    limit = max((s.get("bytes_limit", 0) for s in stats), default=0)
    built = ds._lazy_init()
    cuts = [np.asarray(built.mappers[j].bin_upper_bound, np.float64)
            for j in range(columns)]
    schedule = dict(getattr(inner, "_schedule_info", {}))
    schedule.pop("grower", None)

    # the dataset layer's answer, the stored matrix, against the entries
    # it was built from
    t = time.perf_counter()
    lost = reference_sparse.bundle_lost_values(
        csr, built.binned, group_layout(built), cuts)
    out["phases"]["bundle_decode_s"] = time.perf_counter() - t
    rss["bundle_decode"] = host_rss_bytes()
    per_row = csr.nnz // rows

    out.update({
        "rows": rows, "features": per_row, "columns": columns,
        "nonzeros": int(csr.nnz),
        "device_kind": jax.devices()[0].device_kind,
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
    del booster, inner, ds, built, models, prepared, csr
    gc.collect()

    if trace_dir is not None:
        t = time.perf_counter()
        try:
            xplane = trace_reduce.newest_xplane(trace_dir)
            out["trace"] = trace_reduce.reduce_xplane(xplane)
            by_plane = scopes_by_plane(xplane)
            if by_plane:
                out["trace_scopes"] = by_plane
        except ValueError:
            if not ctx.get("rehearsal"):   # the CPU has no device plane
                raise
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        out["phases"]["trace_reduce_s"] = time.perf_counter() - t

    t = time.perf_counter()
    ref = reference_sparse.SparseReference(
        codes, column_map, y, cuts, num_leaves=int(params["num_leaves"]),
        learning_rate=float(params["learning_rate"]),
        min_sum_hessian_in_leaf=float(params["min_sum_hessian_in_leaf"]),
        min_data_in_leaf=int(params["min_data_in_leaf"]),
        lambda_l2=float(params.get("lambda_l2", 0.0)),
        control=bool(ctx.get("control")))
    window_trees = out["trees_window"]
    steps = []
    if len(checked_trees) >= checked and window_trees:
        steps = [ref.follow(tree, score)
                 for tree, score in zip(checked_trees, scores)]
        ref.seed_score(scores[-1], window_trees[:-1])
        steps.append(ref.follow(window_trees[-1], score_close))
    del ref
    gc.collect()
    out["phases"]["reference_s"] = time.perf_counter() - t
    rss["reference"] = host_rss_bytes()
    # the process's high-water mark on the host, after everything
    out["phases"]["host_peak_rss_bytes"] = 1024 * resource.getrusage(
        resource.RUSAGE_SELF).ru_maxrss
    out["steps"] = steps

    # a program that grew no tree to follow (it stopped: every iteration
    # is in `stopped_iterations`) has no tree number under any limit
    compared = (reference_sparse.worst_over_steps(steps) if steps else
                dict.fromkeys(reference_sparse.COMPARED, sys.float_info.max))
    compared["bundle_lost_values"] = lost
    compared["window_compiles"] = out["window_compiles"]
    compared["stopped_iterations"] = stopped
    out["correct"], out["compared"] = judge(compared, ctx["limits"])
    if ctx.get("control") and steps:
        ctl = reference_sparse.worst_over_steps(steps, "ctl_")
        ctl["count_mismatch"] = 0
        out["control_correct"], out["control_compared"] = judge(
            ctl, {k: ctx["limits"][k] for k in ctl})
    return out
