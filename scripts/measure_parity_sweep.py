"""Throughput sweep (PARITY_SWEEP.json): ours vs the reference binary
across row scales, plus a 500-iteration amortized point and a lambdarank
ranking point.

Usage:
  python scripts/measure_parity_sweep.py ours 500000 2000000 ...
  python scripts/measure_parity_sweep.py ref 500000 2000000 ...
  python scripts/measure_parity_sweep.py ours-amortized [rows iters]
  python scripts/measure_parity_sweep.py ref-amortized [rows iters]
  python scripts/measure_parity_sweep.py ours-ranking / ref-ranking

Results accumulate in PARITY_SWEEP.json (merged per key, so ours/ref can
run separately — the reference needs the CPU to itself).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "scripts"))
OUT = os.path.join(REPO, "PARITY_SWEEP.json")

PARAMS = {"objective": "binary", "metric": "auc", "verbose": -1,
          "max_bin": 63, "num_leaves": 255, "learning_rate": 0.1,
          "min_data_in_leaf": 1, "min_sum_hessian_in_leaf": 100.0}


def _load():
    if os.path.exists(OUT):
        return json.load(open(OUT))
    return {}


def _save(data):
    with open(OUT, "w") as fh:
        json.dump(data, fh, indent=1)
    print(json.dumps(data, indent=1))


def _rank_data(n, f=28, qlen=100, seed=0):
    import numpy as np
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f).astype(np.float32)
    score = X[:, 0] * 1.5 + X[:, 1] - 0.5 * X[:, 2] * X[:, 3]
    nq = n // qlen
    y = np.zeros(n, np.float32)
    for q in range(nq):
        s = slice(q * qlen, (q + 1) * qlen)
        ranks = np.argsort(np.argsort(-(score[s] + rng.randn(qlen))))
        y[s] = np.clip(4 - ranks // 25, 0, 4)
    return X, y, nq, qlen


def ours(rows_list, iters=15):
    import numpy as np

    from scripts import synth_data
    import lightgbm_tpu as lgb
    data = _load()
    for rows in rows_list:
        rows = int(rows)
        X, y = synth_data.synth_higgs(rows, 28)
        ds = lgb.Dataset(X, y, params=dict(PARAMS))
        ds.construct()
        lgb.train(dict(PARAMS), ds, num_boost_round=1, verbose_eval=False)
        times, last = [], [None]

        def cb(env):
            now = time.time()
            if last[0] is not None:
                times.append(now - last[0])
            last[0] = now

        lgb.train(dict(PARAMS), ds, num_boost_round=iters,
                  verbose_eval=False, callbacks=[cb])
        steady = float(np.mean(times[1:]))
        data.setdefault("ours", {})[str(rows)] = {
            "s_per_iter": round(steady, 4),
            "mrow_iters_per_s": round(rows / steady / 1e6, 3)}
        _save(data)
        del X, y, ds


def ref(rows_list, iters=15):
    from measure_baseline import BUILD_DIR, build_reference
    import numpy as np

    from scripts import synth_data
    exe = build_reference()
    data = _load()
    for rows in rows_list:
        rows = int(rows)
        path = os.path.join(BUILD_DIR, f"bench_{rows}.train")
        if not os.path.exists(path):
            X, y = synth_data.synth_higgs(rows, 28)
            np.savetxt(path, np.column_stack([y, X]), fmt="%.6g",
                       delimiter="\t")
        binp = path + ".bin"
        if not os.path.exists(binp):
            subprocess.run(
                [exe, f"data={path}", "task=train", "num_trees=1",
                 "max_bin=63", "save_binary=true", "objective=binary",
                 "min_data_in_leaf=1",
                 f"output_model={BUILD_DIR}/warm.txt"],
                check=True, capture_output=True, cwd=BUILD_DIR)
        conf = dict(PARAMS)
        conf.pop("verbose")
        conf.update(task="train", data=binp, num_trees=iters, verbosity=1,
                    output_model=f"{BUILD_DIR}/sweep_model.txt",
                    num_threads=os.cpu_count() or 1)
        args = [exe] + [f"{k}={v}" for k, v in conf.items()]
        t0 = time.time()
        out = subprocess.run(args, check=True, capture_output=True,
                             text=True)
        train_time = time.time() - t0
        for line in out.stdout.splitlines():
            if "seconds elapsed, finished iteration" in line:
                try:
                    train_time = float(line.split()[1])
                except (ValueError, IndexError):
                    pass
        data.setdefault("ref", {})[str(rows)] = {
            "s_per_iter": round(train_time / iters, 4),
            "mrow_iters_per_s": round(rows * iters / train_time / 1e6, 3)}
        _save(data)


def ours_amortized(rows=2_000_000, iters=500):
    from scripts import synth_data
    import lightgbm_tpu as lgb
    X, y = synth_data.synth_higgs(int(rows), 28)
    ds = lgb.Dataset(X, y, params=dict(PARAMS))
    t0 = time.time()
    ds.construct()
    lgb.train(dict(PARAMS), ds, num_boost_round=int(iters),
              verbose_eval=False)
    wall = time.time() - t0
    data = _load()
    data["ours_amortized"] = {
        "rows": int(rows), "iters": int(iters),
        "wall_s": round(wall, 1),
        "mrow_iters_per_s": round(rows * iters / wall / 1e6, 3)}
    _save(data)


def ref_amortized(rows=2_000_000, iters=500):
    ref([rows], iters=int(iters))
    data = _load()
    data["ref_amortized"] = dict(data["ref"][str(int(rows))],
                                 rows=int(rows), iters=int(iters))
    _save(data)


def ours_ranking(rows=2_000_000, iters=15):
    import numpy as np

    import lightgbm_tpu as lgb
    X, y, nq, qlen = _rank_data(int(rows))
    params = dict(PARAMS, objective="lambdarank", metric="ndcg")
    ds = lgb.Dataset(X, y, params=dict(params))
    ds.set_group(np.full(nq, qlen, np.int32))
    ds.construct()
    lgb.train(dict(params), ds, num_boost_round=1, verbose_eval=False)
    t0 = time.time()
    lgb.train(dict(params), ds, num_boost_round=int(iters),
              verbose_eval=False)
    wall = time.time() - t0
    data = _load()
    data["ours_ranking"] = {
        "rows": int(rows), "iters": int(iters), "wall_s": round(wall, 1),
        "mrow_iters_per_s": round(rows * iters / wall / 1e6, 3)}
    _save(data)


def ref_ranking(rows=2_000_000, iters=15):
    from measure_baseline import BUILD_DIR, build_reference
    import numpy as np
    exe = build_reference()
    rows = int(rows)
    X, y, nq, qlen = _rank_data(rows)
    path = os.path.join(BUILD_DIR, f"rank_{rows}.train")
    if not os.path.exists(path):
        np.savetxt(path, np.column_stack([y, X]), fmt="%.6g",
                   delimiter="\t")
        with open(path + ".query", "w") as fh:
            fh.write("\n".join([str(qlen)] * nq))
    conf = dict(PARAMS)
    conf.pop("verbose")
    conf.update(task="train", objective="lambdarank", metric="ndcg",
                data=path, num_trees=int(iters), verbosity=1,
                output_model=f"{BUILD_DIR}/rank_model.txt",
                num_threads=os.cpu_count() or 1)
    args = [exe] + [f"{k}={v}" for k, v in conf.items()]
    t0 = time.time()
    out = subprocess.run(args, check=True, capture_output=True, text=True)
    train_time = time.time() - t0
    for line in out.stdout.splitlines():
        if "seconds elapsed, finished iteration" in line:
            try:
                train_time = float(line.split()[1])
            except (ValueError, IndexError):
                pass
    data = _load()
    data["ref_ranking"] = {
        "rows": rows, "iters": int(iters),
        "wall_s": round(train_time, 1),
        "mrow_iters_per_s": round(rows * iters / train_time / 1e6, 3)}
    _save(data)


def _predict_fixture(rows=500_000, trees=100):
    """Shared file fixture for the prediction race: OUR model text (the
    formats cross-load, tests/test_reference_parity.py) + a TSV to score.
    Returns (model_path, data_path)."""
    from measure_baseline import BUILD_DIR
    import numpy as np

    from scripts import synth_data
    os.makedirs(BUILD_DIR, exist_ok=True)
    model = os.path.join(BUILD_DIR, f"predict_model_{rows}_{trees}.txt")
    data = os.path.join(BUILD_DIR, f"predict_data_{rows}.tsv")
    if not os.path.exists(data):
        X, y = synth_data.synth_higgs(rows, 28, seed=7)
        np.savetxt(data, np.column_stack([y, X]), fmt="%.6g",
                   delimiter="\t")
    if not os.path.exists(model):
        import lightgbm_tpu as lgb
        X, y = synth_data.synth_higgs(rows, 28, seed=7)
        ds = lgb.Dataset(X, y, params=dict(PARAMS))
        booster = lgb.train(dict(PARAMS), ds, num_boost_round=trees,
                            verbose_eval=False)
        booster.save_model(model)
    return model, data


def ours_predict(rows=500_000, trees=100):
    """Prediction throughput through OUR CLI file path (the reference's
    Predictor analogue, predictor.hpp:24-205)."""
    import numpy as np
    model, data_path = _predict_fixture(int(rows), int(trees))
    out_path = os.path.join(os.path.dirname(model), "ours_preds.txt")
    from lightgbm_tpu.cli import main as cli_main
    walls = []
    # 1 cold (jit compile) + 5 warm; the committed figure is the warm
    # MEDIAN (a single shot swung 2x between sessions)
    for _ in range(6):
        t0 = time.time()
        cli_main([f"task=predict", f"data={data_path}",
                  f"input_model={model}", f"output_result={out_path}"])
        walls.append(time.time() - t0)
    med = float(np.median(walls[1:]))
    data = _load()
    data["ours_predict"] = {
        "rows": int(rows), "trees": int(trees),
        "wall_s": round(med, 2),
        "wall_s_warm_min": round(min(walls[1:]), 2),
        "wall_s_warm_max": round(max(walls[1:]), 2),
        "wall_s_incl_compile": round(walls[0], 2),
        "mrows_per_s": round(int(rows) / med / 1e6, 3)}
    _save(data)


def ref_predict(rows=500_000, trees=100):
    from measure_baseline import BUILD_DIR, build_reference
    exe = build_reference()
    model, data_path = _predict_fixture(int(rows), int(trees))
    out_path = os.path.join(BUILD_DIR, "ref_preds.txt")
    args = [exe, "task=predict", f"data={data_path}",
            f"input_model={model}", f"output_result={out_path}",
            f"num_threads={os.cpu_count() or 1}"]
    t0 = time.time()
    subprocess.run(args, check=True, capture_output=True, text=True)
    wall = time.time() - t0
    data = _load()
    data["ref_predict"] = {
        "rows": int(rows), "trees": int(trees), "wall_s": round(wall, 2),
        "mrows_per_s": round(int(rows) / wall / 1e6, 3)}
    _save(data)


if __name__ == "__main__":
    mode = sys.argv[1]
    rest = sys.argv[2:]
    if mode == "ours":
        ours([int(float(r)) for r in rest])
    elif mode == "ref":
        ref([int(float(r)) for r in rest])
    elif mode == "ours-amortized":
        ours_amortized(*[int(float(r)) for r in rest])
    elif mode == "ref-amortized":
        ref_amortized(*[int(float(r)) for r in rest])
    elif mode == "ours-ranking":
        ours_ranking(*[int(float(r)) for r in rest])
    elif mode == "ref-ranking":
        ref_ranking(*[int(float(r)) for r in rest])
    elif mode == "ours-predict":
        ours_predict(*[int(float(r)) for r in rest])
    elif mode == "ref-predict":
        ref_predict(*[int(float(r)) for r in rest])
    else:
        raise SystemExit(f"unknown mode {mode}")
