"""Readings that the limits of `correct` are set from (not a benchmark
run; the driver never calls this). For each seed, in one process on the
chip at the cell's own size: the program as configured, followed by the
reference (the lower readings), with the control's channels switched on
in the same reference pass (the upper readings); for the first
`--fault-seeds` seeds also each planted fault of `faults.py`, and for the
first `--bins-seeds` seeds the program run with coarser bins than the
configuration states (`max_bin` halved: the control of the bin table's
numbers). `--fresh-data` gives every seed a data set of its own, where a
benchmark run reorders the columns of one. One JSON line per (seed,
variant) on standard output and in `--out`.

    python benchmarks/readings.py --workload higgs-train-1chip \\
        --seeds 11,12,13 --fault-seeds 3 --out chiprun_out/readings.jsonl
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--fault-seeds", type=int, default=0)
    ap.add_argument("--bins-seeds", type=int, default=0)
    ap.add_argument("--fresh-data", action="store_true")
    ap.add_argument("--fault-seconds", type=float, default=1.0)
    ap.add_argument("--seconds", type=float, default=1.0,
                    help="window of the sound runs")
    ap.add_argument("--rehearse-rows", type=int, default=0)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    loaded = harness.load_cell(args.workload)
    import jax
    import faults
    harness.keep_every_program(jax)
    device = harness.device_info(jax)
    if not args.rehearse_rows and device["platform"] != "tpu":
        print(f"readings.py: needs a TPU, found {device}", file=sys.stderr)
        return 2
    traffic = loaded["traffic"]
    mode = harness.load_mode(traffic)
    sink = open(args.out, "a") if args.out else None
    try:
        for k, seed in enumerate(int(s) for s in args.seeds.split(",")):
            base = {"cell": loaded["cell"], "config": loaded["config"],
                    "traffic": traffic, "seed": seed,
                    "seconds": args.seconds, "trace": False,
                    "rows": args.rehearse_rows or int(loaded["config"]["rows"]),
                    "t_start": time.perf_counter(),
                    "limits": loaded["cell"]["limits"]}
            if args.fresh_data:
                base["data_seed"] = seed
            prepared = mode.prepare(base)
            variants = [None] + (list(faults.FAULTS)
                                 if k < args.fault_seeds else [])
            if k < args.bins_seeds:
                variants.append("coarse_bins")
            for fault in variants:
                t = time.perf_counter()
                if fault == "coarse_bins":
                    del prepared
                    gc.collect()
                    half = int(loaded["config"]["params"]["max_bin"]) // 2
                    out = mode.run(dict(base, seconds=args.fault_seconds,
                                        params_override={"max_bin": half}))
                    prepared = None
                elif fault:
                    out = mode.run(dict(base, prepared=prepared, fault=fault,
                                        seconds=args.fault_seconds))
                else:
                    out = mode.run(dict(base, prepared=prepared, control=True))
                line = {"workload": args.workload, "seed": seed,
                        "variant": fault or "sound", "device": device["kind"],
                        "correct": out["correct"],
                        "compared": {n: r["value"]
                                     for n, r in out["compared"].items()},
                        "steps": out["steps"],
                        "iterations": out["iterations"],
                        "reference_s": out["phases"]["reference_s"],
                        "seconds": time.perf_counter() - t}
                if fault is None:
                    line["control_correct"] = out["control_correct"]
                    line["control"] = {n: r["value"] for n, r
                                       in out["control_compared"].items()}
                text = json.dumps(line)
                print(text, flush=True)
                if sink:
                    sink.write(text + "\n")
                    sink.flush()
            del prepared
            gc.collect()
    finally:
        if sink:
            sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
