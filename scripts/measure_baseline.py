"""Measure the reference LightGBM's training throughput on this machine.

Builds /root/reference out-of-tree (its CMakeLists drops binaries into the
source dir via EXECUTABLE_OUTPUT_PATH; we redirect both output paths into
the build dir so the read-only reference tree stays pristine), generates
the synthetic datasets of scripts/synth_data.py, trains with the same
hyperparameters through the reference CLI, and records the measured
mrow_iters/s:

- BENCH_BASELINE.json        — the HIGGS-like headline shape (legacy
                               layout, kept for round-over-round compat)
- BENCH_BASELINE_SHAPES.json — {shape: {...}} for the wide/sparse/
                               categorical shapes

Usage: python scripts/measure_baseline.py [shape ...]
       (default: higgs; "all" = every shape of synth_data.SHAPES)

The recorded `mrows_per_sec` is max(measured-here, REFERENCE_8T_FLOOR)
for the higgs shape: this box may expose fewer cores than the reference's
benchmark setup (docs/GPU-Performance.md:96-116 used 28 threads), and an
undersized baseline would flatter vs_baseline. REFERENCE_8T_FLOOR is the
8-thread measurement of this exact workload recorded in round 1's review
(20.2 s train on 500k x 28 x 20 iters = 0.495 mrow_iters/s).
Other shapes record the raw measurement (threads = all visible cores).

MUST run on an otherwise-idle machine: this box exposes ONE cpu to the
process, and a concurrently-running test suite silently tripled the
reference's per-iteration time in round 2's first measurement.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REFERENCE = "/root/reference"
BUILD_DIR = os.environ.get("REF_BUILD_DIR", "/tmp/lgbm_ref_build")
REFERENCE_8T_FLOOR = 0.495  # mrow_iters/s, 8 threads, measured in round 1

sys.path.insert(0, REPO)


def build_reference() -> str:
    exe = os.path.join(BUILD_DIR, "lightgbm")
    if os.path.exists(exe):
        return exe
    os.makedirs(BUILD_DIR, exist_ok=True)
    subprocess.run(
        ["cmake", REFERENCE, "-DCMAKE_BUILD_TYPE=Release",
         f"-DEXECUTABLE_OUTPUT_PATH={BUILD_DIR}",
         f"-DLIBRARY_OUTPUT_PATH={BUILD_DIR}"],
        cwd=BUILD_DIR, check=True, capture_output=True)
    subprocess.run(["make", f"-j{os.cpu_count() or 1}"], cwd=BUILD_DIR,
                   check=True, capture_output=True)
    # older CMakeLists may ignore the output-path cache vars for one target
    if not os.path.exists(exe) and os.path.exists(os.path.join(REFERENCE, "lightgbm")):
        os.replace(os.path.join(REFERENCE, "lightgbm"), exe)
        for lib in ("lib_lightgbm.so",):
            src = os.path.join(REFERENCE, lib)
            if os.path.exists(src):
                os.replace(src, os.path.join(BUILD_DIR, lib))
    return exe


def _write_tsv(path: str, y, X) -> None:
    """Fast-enough TSV writer for wide matrices (np.savetxt is a Python
    loop; pandas' C writer is ~10x faster and keeps full precision
    unnecessary for binned training)."""
    import numpy as np
    X = np.round(np.asarray(X, np.float64), 4)
    try:
        import pandas as pd
        df = pd.DataFrame(np.column_stack([np.asarray(y, np.float64), X]))
        df.to_csv(path, sep="\t", header=False, index=False)
    except ImportError:
        np.savetxt(path, np.column_stack([y, X]), fmt="%.4g", delimiter="\t")


def measure_shape(exe: str, shape: str) -> dict:
    from scripts import synth_data

    n_rows, builder, max_bin = synth_data.SHAPES[shape]
    built = builder(n_rows)
    cat_idx = built[2] if len(built) == 3 else None
    X, y = built[0], built[1]

    # TSV cache keyed by (builder, rows): epsilon and epsilon15 share the
    # same matrix (they differ only in max_bin) — only the .bin cache
    # below needs the per-shape key
    data_path = os.path.join(
        BUILD_DIR, f"bench_{builder.__name__}_{n_rows}.train")
    if not os.path.exists(data_path):
        _write_tsv(data_path, y, X)

    conf = {
        "task": "train", "objective": "binary", "metric": "auc",
        "data": data_path, "num_trees": synth_data.N_ITERS,
        "learning_rate": 0.1, "num_leaves": synth_data.NUM_LEAVES,
        "max_bin": max_bin, "min_data_in_leaf": 1,
        "min_sum_hessian_in_leaf": 100.0, "verbosity": 1,
        "num_threads": os.cpu_count() or 1,
        "output_model": os.path.join(BUILD_DIR, f"bench_{shape}_model.txt"),
    }
    if cat_idx is not None:
        conf["categorical_feature"] = ",".join(str(c) for c in cat_idx)
    if shape == "multiclass":
        conf.update(objective="multiclass", num_class=5,
                    metric="multi_logloss")

    # one untimed run loads/caches the binned dataset file; the timed run
    # then measures training alone (construct untimed).
    # NOTE: the binary caches max_bin/categorical config, so the cache is
    # keyed per shape (epsilon vs epsilon15 differ only in max_bin).
    bin_path = data_path + f".{shape}.bin"
    if not os.path.exists(bin_path):
        warm = [exe, f"data={data_path}", "task=train", "num_trees=1",
                f"max_bin={max_bin}", "save_binary=true",
                f"objective={conf['objective']}", "min_data_in_leaf=1",
                f"output_model={os.path.join(BUILD_DIR, 'warm_model.txt')}"]
        if conf.get("num_class"):
            warm.append(f"num_class={conf['num_class']}")
        if cat_idx is not None:
            warm.append("categorical_feature=" + ",".join(str(c) for c in cat_idx))
        subprocess.run(warm, check=True, capture_output=True, cwd=BUILD_DIR)
        os.replace(data_path + ".bin", bin_path)
    conf["data"] = bin_path
    args = [exe] + [f"{k}={v}" for k, v in conf.items()]

    t0 = time.time()
    out = subprocess.run(args, check=True, capture_output=True, text=True)
    wall = time.time() - t0
    # exclude data-load time using the reference's own log timestamps if
    # present; otherwise charge the full wall time to training
    train_time = wall
    for line in out.stdout.splitlines():
        if "seconds elapsed, finished iteration" in line:
            try:
                train_time = float(line.split()[1])
            except (ValueError, IndexError):
                pass

    measured = n_rows * synth_data.N_ITERS / train_time / 1e6
    rec = measured if shape != "higgs" else max(measured, REFERENCE_8T_FLOOR)
    return {
        "mrows_per_sec": round(rec, 4),
        "measured_here": round(measured, 4),
        "train_seconds": round(train_time, 3),
        "wall_seconds": round(wall, 3),
        "threads": os.cpu_count() or 1,
        "rows": n_rows, "features": int(X.shape[1]),
        "iters": synth_data.N_ITERS,
        "num_leaves": synth_data.NUM_LEAVES, "max_bin": max_bin,
    }


def main():
    from scripts import synth_data

    shapes = sys.argv[1:] or ["higgs"]
    if shapes == ["all"]:
        shapes = list(synth_data.SHAPES)
    exe = build_reference()

    shapes_path = os.path.join(REPO, "BENCH_BASELINE_SHAPES.json")
    all_results = {}
    if os.path.exists(shapes_path):
        with open(shapes_path) as fh:
            all_results = json.load(fh)

    for shape in shapes:
        result = measure_shape(exe, shape)
        if shape == "higgs":
            result["reference_8thread_floor"] = REFERENCE_8T_FLOOR
            with open(os.path.join(REPO, "BENCH_BASELINE.json"), "w") as fh:
                json.dump(result, fh, indent=1)
        else:
            all_results[shape] = result
            with open(shapes_path, "w") as fh:
                json.dump(all_results, fh, indent=1)
        print(shape, json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
