"""Trace N warm training iterations of one configuration on the attached
accelerator and print where the device and the host spent them, by this
program's layers (`lightgbm_tpu/telemetry/devtrace.py` does the
reduction; this file only drives the run).

    python scripts/profile_train.py --config benchmarks/configs/higgs-10m5x28.json

A configuration with `tree_learner=data` (`higgs-10m5x28-dp4.json`) runs
over every attached chip; the table then also gives each scope's self
seconds and the busy seconds by device plane.

`--config` is a benchmark configuration file (`rows`, `features`,
`params`, `generator`); `--rows` overrides its row count, for a
rehearsal. The layer table goes to standard output and the whole
reduction, with the per-tree records of the traced trees, to
`<out>/<name>.json` (`--out`, default `chiprun_out/`, the directory the
chip tool brings back; `--name`, default `profile_train`). `--ops N`
keeps the N longest device operations (default 10); `--stats N`
also prints every stat of the first N device events, and `--keep-trace`
keeps the `.xplane.pb`: the by-hand look that tells which stat carries
the scope on a new libtpu (`devtrace.SCOPE_STATS`).
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "benchmarks"))


def print_stats(path: str, count: int) -> None:
    from jax.profiler import ProfileData
    from lightgbm_tpu.telemetry import devtrace
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            print(f"plane {plane.name!r} line {line.name!r}: "
                  f"{len(list(line.events))} events", file=sys.stderr)
            if not (plane.name.startswith("/device:")
                    and line.name.lower() == devtrace.OP_LINE):
                continue
            for ev in list(line.events)[:count]:
                print(json.dumps({"name": ev.name, "stats": {
                    k: (v if isinstance(v, (int, float, str)) else repr(v))
                    for k, v in ev.stats}})[:2000], file=sys.stderr)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default=os.path.join(
        REPO, "benchmarks", "configs", "higgs-10m5x28.json"))
    ap.add_argument("--rows", type=int, default=0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--iterations", type=int, default=2)
    ap.add_argument("--out", default=os.path.join(REPO, "chiprun_out"))
    ap.add_argument("--stats", type=int, default=0)
    ap.add_argument("--ops", type=int, default=10,
                    help="how many device operations the table keeps")
    ap.add_argument("--param", action="append", default=[],
                    metavar="KEY=VALUE", help="override one parameter of "
                    "the configuration (an experiment, not the cell)")
    ap.add_argument("--name", default="profile_train")
    ap.add_argument("--keep-trace", action="store_true",
                    help="copy the .xplane.pb beside the JSON")
    args = ap.parse_args(argv)

    import jax
    import datagen
    import lightgbm_tpu as lgb
    from lightgbm_tpu import telemetry
    from lightgbm_tpu.telemetry import devtrace

    # the compile path's totals feed InitRecord and TreeRecord
    telemetry.install_observer()

    # jax's persistent compile cache keys a program WITHOUT its metadata,
    # so an executable cached before a scope was added or renamed would be
    # loaded as it was, names missing. A profile is only as good as its
    # names: key on the metadata here (one compile the first time).
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)

    with open(args.config) as fh:
        config = json.load(fh)
    rows = args.rows or int(config["rows"])
    config["params"].update(kv.split("=", 1) for kv in args.param)
    # a ranking configuration's generator hands query sizes over too; the
    # sparse one's (X a scipy CSR matrix) the codes and the column map,
    # which are the reference's alone
    X, y, *group = datagen.generator(config["generator"])(
        rows, int(config["features"]), args.seed)
    if "lambdarank" not in str(config["params"].get("objective")):
        group = []
    # set-up, as the benchmark's `dataset.construct_s` splits it: the
    # host's seconds in `construct()` (its phases are the ConstructRecord)
    # and from there to the binned matrix being on the device (`Booster`:
    # row padding, upload, GBDT.init)
    jax.devices()   # the client's start-up (~8 s on the chip) is not set-up
    t = time.perf_counter()
    ds = lgb.Dataset(X, y, group=group[0] if group else None,
                     params=dict(config["params"])).construct()
    construct_host_s = time.perf_counter() - t
    t = time.perf_counter()
    booster = lgb.Booster(dict(config["params"]), ds)
    inner = booster._inner
    jax.block_until_ready(inner._binned)
    setup = dict(ds._lazy_init().construct_record._asdict(),
                 construct_host_s=construct_host_s,
                 booster_to_device_s=time.perf_counter() - t)

    def line(title, fields):
        print(title + ": " + ", ".join(
            f"{k} {v:.2f}" if isinstance(v, float) else f"{k} {v}"
            for k, v in fields.items()), flush=True)

    # `GBDT.init` by phase, the rows a device's shard holds and what jax
    # traced, loaded and compiled meanwhile: the booster's own InitRecord
    line("set-up, host seconds", setup)
    line("set-up, GBDT.init", inner.init_record._asdict())

    def drain():
        booster.current_iteration()       # flushes the pipelined tree
        jax.block_until_ready(inner._score)

    for _ in range(args.warmup):
        booster.update()
    drain()
    # the warm-up trees hold the tracing, the cache loads and the first
    # run of each program: their TreeRecords' host and compile-path fields
    for i, rec in enumerate(inner.pass_log[:args.warmup]):
        line(f"set-up, warm-up tree {i}", {
            k: getattr(rec, k) for k in (
                "dispatch_s", "fetch_wait_s", "build_tree_s",
                "trace_lower_s", "backend_s", "cache_misses")})
    # which programs the set-up's compile path went to, by what each cost
    programs = telemetry.observer().snapshot()["programs"]
    for name in sorted(programs, key=lambda p: -sum(
            programs[p][k] for k in ("trace_s", "lower_s", "backend_s")))[:6]:
        line(f"set-up, program {name}", programs[name])
    trace_dir = tempfile.mkdtemp(prefix="profile_train_")
    try:
        with jax.profiler.trace(trace_dir):
            for _ in range(args.iterations):
                booster.update()
            drain()
        xplane = devtrace.newest_xplane(trace_dir)
        if args.stats:
            print_stats(xplane, args.stats)
        os.makedirs(args.out, exist_ok=True)
        if args.keep_trace:
            shutil.copy(xplane, os.path.join(args.out,
                                             args.name + ".xplane.pb"))
        reduced = devtrace.reduce_xplane(xplane, top=args.ops)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)

    dev = jax.devices()[0]
    reduced.update({
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": jax.device_count()},
        "rows": rows, "features": int(config["features"]),
        "params": config["params"],
        "traced_trees": [r._asdict()
                         for r in inner.pass_log[args.warmup:]],
        # the dataset layer's host phases (set-up, not in the trace) and
        # the schedule the program picked for this shape
        "construct": setup,
        "init": inner.init_record._asdict(),
        "warmup_trees": [r._asdict() for r in inner.pass_log[:args.warmup]],
        "programs": programs,
        "schedule": {k: v for k, v in inner._schedule_info.items()
                     if k != "grower"},
    })
    with open(os.path.join(args.out, args.name + ".json"), "w") as fh:
        json.dump(reduced, fh, indent=1)
    print(f"{dev.platform} {dev.device_kind}: {rows} x {config['features']},"
          f" {args.iterations} traced iterations, device busy "
          f"{reduced['busy_s']:.4f} s of {reduced['window_s']:.4f} s")
    print(devtrace.layer_table(reduced))
    return 0


if __name__ == "__main__":
    sys.exit(main())
