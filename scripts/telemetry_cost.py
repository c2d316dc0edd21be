"""What tracing costs when it is on: the steady training rate of one
configuration with telemetry off and on, and with a profiler session
closed and open, in one process on one constructed Dataset.

    python scripts/telemetry_cost.py --config benchmarks/configs/higgs-10m5x28.json

Every setting gets a fresh Booster on the same Dataset, so all four
train the same trees: `--warmup` iterations, a drain, then `--iterations`
timed ones ending in a drain. "on" is `telemetry.enable(True)` with the
compile observer installed, which is what `tpu_telemetry=true` arms.
One JSON line per setting on standard output (rate in Mrow-iters/s, the
registry's phases in seconds per iteration), all of them again in
`<out>/telemetry_cost.json`. `--repo` imports `lightgbm_tpu` from
another checkout (the parent commit), to read the cost before a change.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default=os.path.join(
        REPO, "benchmarks", "configs", "higgs-10m5x28.json"))
    ap.add_argument("--repo", default=REPO)
    ap.add_argument("--rows", type=int, default=0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--iterations", type=int, default=4)
    ap.add_argument("--out", default=os.path.join(REPO, "chiprun_out"))
    ap.add_argument("--tag", default="change")
    ap.add_argument("--reverse", action="store_true",
                    help="run the four settings in the opposite order")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.abspath(args.repo))
    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    import jax
    import datagen
    import lightgbm_tpu as lgb
    from lightgbm_tpu import telemetry

    with open(args.config) as fh:
        config = json.load(fh)
    rows = args.rows or int(config["rows"])
    X, y = datagen.generator(config["generator"])(
        rows, int(config["features"]), args.seed)
    ds = lgb.Dataset(X, y, params=dict(config["params"]))
    ds.construct()
    dev = jax.devices()[0]
    telemetry.install_observer()

    results = []
    settings = [(False, False), (True, False), (False, True), (True, True)]
    for on, profiled in settings[::-1] if args.reverse else settings:
        booster = lgb.Booster(dict(config["params"]), ds)
        inner = booster._inner

        def drain():
            booster.current_iteration()
            jax.block_until_ready(inner._score)

        telemetry.enable(on)
        telemetry.reset()
        for _ in range(args.warmup):
            booster.update()
        drain()
        telemetry.reset()
        trace_dir = tempfile.mkdtemp(prefix="telemetry_cost_")
        if profiled:
            jax.profiler.start_trace(trace_dir)
        t0 = time.perf_counter()
        for _ in range(args.iterations):
            booster.update()
        drain()
        seconds = time.perf_counter() - t0
        if profiled:
            jax.profiler.stop_trace()
        shutil.rmtree(trace_dir, ignore_errors=True)
        phases = {name: acc.total / args.iterations
                  for name, acc in telemetry.registry().phases.items()}
        telemetry.enable(False)
        results.append({
            "tag": args.tag, "telemetry": on, "profiler": profiled,
            "mrow_iters_per_s": rows * args.iterations / seconds / 1e6,
            "seconds": seconds, "iterations": args.iterations, "rows": rows,
            "phases_s_per_iteration": phases,
            "device": {"platform": dev.platform, "kind": dev.device_kind}})
        print(json.dumps(results[-1]), flush=True)
        del booster, inner
        gc.collect()

    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out,
                           f"telemetry_cost_{args.tag}.json"), "w") as fh:
        json.dump(results, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
