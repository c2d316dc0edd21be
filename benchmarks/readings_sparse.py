"""`readings.py` for the sparse cell (not a benchmark run; the driver never
calls this). For each seed, in one process on the chip at the cell's own
size: the program as configured, followed by the reference with the
control's channels on in the same pass; for the first `--fault-seeds`
seeds each fault of `faults_sparse.FAULTS` (the three of the tree step
and `default_unrepaired`); for the first `--conflict-seeds` seeds the
control of the dataset layer's numbers, the program run with
`max_conflict_rate` 0.05 where the configuration states lossless
bundling. `--fresh-data` gives every seed a table of its own. One JSON
line per (seed, variant) on standard output and in `--out`.

    python benchmarks/readings_sparse.py --workload expo-train-1chip \\
        --seeds 11,12,13,14 --fault-seeds 1 --conflict-seeds 1 \\
        --fresh-data --out chiprun_out/readings_sparse.jsonl
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

import run as harness  # noqa: E402

CONFLICT_RATE = 0.05


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--fault-seeds", type=int, default=0)
    ap.add_argument("--conflict-seeds", type=int, default=0)
    ap.add_argument("--fresh-data", action="store_true")
    ap.add_argument("--fault-seconds", type=float, default=1.0)
    ap.add_argument("--seconds", type=float, default=1.0,
                    help="window of the sound runs")
    ap.add_argument("--rehearse-rows", type=int, default=0)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    loaded = harness.load_cell(args.workload)
    import jax
    import faults_sparse
    harness.keep_every_program(jax)
    device = harness.device_info(jax)
    if not args.rehearse_rows and device["platform"] != "tpu":
        print(f"readings_sparse.py: needs a TPU, found {device}",
              file=sys.stderr)
        return 2
    mode = harness.load_mode(loaded["traffic"])
    sink = open(args.out, "a") if args.out else None

    def tell(seed, variant, out, took):
        line = {"workload": args.workload, "seed": seed, "variant": variant,
                "device": device["kind"], "correct": out["correct"],
                "compared": {n: r["value"]
                             for n, r in out["compared"].items()},
                "steps": out["steps"], "iterations": out["iterations"],
                "schedule": out["schedule"],
                "reference_s": out["phases"]["reference_s"], "seconds": took}
        if "control_compared" in out:
            line["control_correct"] = out["control_correct"]
            line["control"] = {n: r["value"]
                               for n, r in out["control_compared"].items()}
        text = json.dumps(line)
        print(text, flush=True)
        if sink:
            sink.write(text + "\n")
            sink.flush()

    try:
        for k, seed in enumerate(int(s) for s in args.seeds.split(",")):
            base = {"cell": loaded["cell"], "config": loaded["config"],
                    "traffic": loaded["traffic"], "seed": seed,
                    "seconds": args.seconds, "trace": False,
                    "rows": (args.rehearse_rows
                             or int(loaded["config"]["rows"])),
                    "rehearsal": bool(args.rehearse_rows),
                    "t_start": time.perf_counter(),
                    "limits": loaded["cell"]["limits"]}
            if args.fresh_data:
                base["data_seed"] = seed
            prepared = mode.prepare(base)
            t = time.perf_counter()
            tell(seed, "sound",
                 mode.run(dict(base, prepared=prepared, control=True)),
                 time.perf_counter() - t)
            for fault in (faults_sparse.FAULTS if k < args.fault_seeds
                          else ()):
                t = time.perf_counter()
                tell(seed, fault,
                     mode.run(dict(base, prepared=prepared, fault=fault,
                                   seconds=args.fault_seconds)),
                     time.perf_counter() - t)
            del prepared
            gc.collect()
            if k < args.conflict_seeds:
                t = time.perf_counter()
                tell(seed, "conflict_rate_%g" % CONFLICT_RATE, mode.run(dict(
                    base, seconds=args.fault_seconds, params_override={
                        "max_conflict_rate": CONFLICT_RATE})),
                    time.perf_counter() - t)
                gc.collect()
    finally:
        if sink:
            sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
