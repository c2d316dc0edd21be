"""Traffic mode `train_steady_rank`: `train_steady` for a ranking job.

The same window as `modes/train_steady.py` (ONE `lightgbm_tpu.Booster`,
warm-up iterations through `Booster.update()`, whole iterations for
`--seconds`, a drain; `train_mrow_iters_per_s` is all rows x iterations
over all of the window's seconds), with what a ranking job changes:

- the generator returns query sizes beside rows and labels, and the
  `Dataset` is built with `group=`;
- the reference is `reference_rank.RankReference` (lambdarank's gradients
  computed its own way, NDCG@10 in the log-loss's place); besides the
  steps it follows, it is asked for the gradients at the window-opening
  score, and the program's own, taken after the window by the call an
  iteration makes (`GBDT._compute_gradients`), are held to them
  (`lambda_gap`, `hess_gap`);
- a traced run hands its readers `trace_scopes`, device self seconds by
  the program's own scope names for each device plane
  (`lightgbm_tpu.telemetry.devtrace`'s rule), read before the trace is
  deleted; where the program has no such reducer there is no such key;
- set-up runs under a limit of its own, `SETUP_LIMIT_S` from the entry
  of `run()`: a program that cannot set this deployment up in that time
  exits 4 with one line saying where it stood, where it would otherwise
  be killed at the caller's limit with nothing said.
"""
from __future__ import annotations

import contextlib
import faulthandler
import gc
import os
import shutil
import sys
import tempfile
import threading
import time

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import datagen  # noqa: E402
import reference_rank  # noqa: E402
import trace_reduce  # noqa: E402

_steady = datagen.load_file_module(
    os.path.join(HERE, "modes", "train_steady.py"),
    "benchmarks_mode_train_steady")
tree_arrays, judge = _steady.tree_arrays, _steady.judge

# From the entry of `run()` to the window's opening the cell takes 61-62 s
# with a warm compile cache and 90-95 s when the gradient and grow programs
# compile (my chip runs, PR 34: `setup_s` 69.03 / 69.36 / 69.80 / 69.84 and
# 98.22 / 102.41, less ~8 s of start-up). The limit is about three times
# the first and over twice the second, and a run that hits it still ends
# inside twice a whole run of the cell (140-190 s)
SETUP_LIMIT_S = 240.0


class SetupGuard:
    """Ends the process once set-up has outlived its limit. The timer's
    thread needs the interpreter; `faulthandler`'s does not, and ends a
    process stuck inside one long call half a minute later."""

    def __init__(self, limit_s: float):
        self.limit_s, self.where = limit_s, "start"
        self._timer = threading.Timer(limit_s, self._expired)
        self._timer.daemon = True
        self._timer.start()
        faulthandler.dump_traceback_later(limit_s + 30.0, exit=True)

    def at(self, where: str) -> None:
        self.where = where

    def _expired(self):
        print(f"train_steady_rank: set-up outlived its limit of "
              f"{self.limit_s:.0f} s in: {self.where}; nothing was measured",
              file=sys.stderr, flush=True)
        os._exit(4)

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> None:
        self._timer.cancel()
        faulthandler.cancel_dump_traceback_later()


def prepare(ctx: dict, guard=None) -> dict:
    """Data from the seed and the constructed Dataset (host binning).
    `readings.py` shares one of these between the variants of a seed."""
    import lightgbm_tpu as lgb
    config = ctx["config"]
    rows, features = int(ctx["rows"]), int(config["features"])
    t = time.perf_counter()
    fresh = ({"base_seed": ctx["data_seed"]} if "data_seed" in ctx else {})
    X, y, sizes = datagen.generator(config["generator"])(
        rows, features, int(ctx["seed"]) % 2 ** 63, **fresh)
    generate_s = time.perf_counter() - t
    if guard:
        guard.at("Dataset.construct()")
    t = time.perf_counter()
    ds = lgb.Dataset(X, y, group=sizes,
                     params=dict(config["params"],
                                 **ctx.get("params_override", {})))
    ds.construct()
    return {"X": X, "y": y, "sizes": sizes, "ds": ds,
            "generate_s": generate_s,
            "construct_host_s": time.perf_counter() - t}


def scopes_by_plane(xplane_path: str):
    """{device plane: {scope: self seconds}} by the program's own reducer,
    or None where the program has none or the trace no device plane."""
    try:
        from lightgbm_tpu.telemetry import devtrace
        planes = devtrace.reduce_xplane(xplane_path)["planes"]
    except (ImportError, AttributeError, KeyError, ValueError):
        return None
    return {name: dict(plane["scopes"]) for name, plane in planes.items()}


def run(ctx: dict) -> dict:
    """ctx: as `modes/train_steady.py`'s; `fault` is a name of
    `faults_rank.FAULTS`."""
    import jax
    import jax.numpy as jnp
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
    if ctx.get("fault"):
        import faults_rank
        plant = faults_rank.planted(ctx["fault"])
    else:
        plant = contextlib.nullcontext()

    with plant:
        with SetupGuard(SETUP_LIMIT_S) as guard:
            guard.at("data from the seed")
            prepared = ctx.get("prepared") or prepare(ctx, guard)
            X, y, sizes, ds = (prepared["X"], prepared["y"],
                               prepared["sizes"], prepared["ds"])
            out["phases"]["generate_s"] = prepared["generate_s"]

            guard.at("Booster(params, dataset)")
            t = time.perf_counter()
            booster = lgb.Booster(dict(params), ds)
            inner = booster._inner
            jax.block_until_ready(inner._binned)
            construct_s = (prepared["construct_host_s"]
                           + time.perf_counter() - t)

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

        # the program's own gradients at the window-opening score, by the
        # call an iteration makes
        opening = np.zeros(inner._score.shape, np.float32)
        opening[0, :rows] = scores[-1]
        grad, hess = inner._compute_gradients(jnp.asarray(opening))
        grad = np.asarray(grad).reshape(-1)[:rows]
        hess = np.asarray(hess).reshape(-1)[:rows]

    window_s = t1 - t0
    models = list(inner.models)
    pass_log = list(getattr(inner, "pass_log", []))
    peak = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)
    limit = max((s.get("bytes_limit", 0) for s in stats), default=0)
    mappers = ds._lazy_init().mappers
    cuts = [np.asarray(mappers[j].bin_upper_bound, np.float64)
            for j in range(features)]
    schedule = dict(getattr(inner, "_schedule_info", {}))
    schedule.pop("grower", None)

    out.update({
        "rows": rows, "features": features, "queries": len(sizes),
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
    del booster, inner, ds, models, mappers, prepared, opening
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
    defaults = config["objective_defaults"]   # lambdarank's, as stated
    ref = reference_rank.RankReference(
        X, y, sizes, cuts,
        sigmoid=float(defaults["sigmoid"]),
        max_position=int(defaults["max_position"]),
        label_gain=defaults["label_gain"],
        num_leaves=int(params["num_leaves"]),
        learning_rate=float(params["learning_rate"]),
        min_sum_hessian_in_leaf=float(params["min_sum_hessian_in_leaf"]),
        min_data_in_leaf=int(params["min_data_in_leaf"]),
        lambda_l2=float(params.get("lambda_l2", 0.0)),
        max_bin=int(config["params"]["max_bin"]),
        control=bool(ctx.get("control")))
    window_trees = out["trees_window"]
    if len(checked_trees) < checked or not window_trees:
        raise RuntimeError("the program produced fewer trees than checked")
    gaps = ref.gradient_gaps(grad, hess, scores[-1])
    del grad, hess
    steps = [ref.follow(tree, score)
             for tree, score in zip(checked_trees, scores)]
    ref.seed_score(scores[-1], window_trees[:-1])
    steps.append(ref.follow(window_trees[-1], score_close))
    del ref
    gc.collect()
    out["phases"]["reference_s"] = time.perf_counter() - t
    out["steps"] = steps

    compared = reference_rank.worst_over_steps(steps)
    compared.update(gaps)
    compared["window_compiles"] = out["window_compiles"]
    compared["stopped_iterations"] = stopped
    out["correct"], out["compared"] = judge(compared, ctx["limits"])
    if ctx.get("control"):
        ctl = reference_rank.worst_over_steps(steps, "ctl_")
        ctl["count_mismatch"] = 0
        out["control_correct"], out["control_compared"] = judge(
            ctl, {k: ctx["limits"][k] for k in ctl})
    return out
