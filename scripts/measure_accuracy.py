"""Per-round accuracy anchors: train ours on the real chip and the
reference binary on the same synthetic splits at 500-iteration scale,
and record the metric deltas side by side in ACCURACY_r{N}.json.

Three tasks (round-5 verdict item 9 widened this from binary-only):
- binary: HIGGS-shape holdout AUC (the reference anchors its quality
  story at HIGGS AUC 0.845239 @ 63 bins / 500 iters,
  docs/GPU-Performance.md:134; on synthetic data the absolute number
  differs, so the artifact records the DELTA against the reference
  binary trained with identical hyperparameters on identical rows)
- categorical: Expo-shape binary AUC with native categorical features
  on both sides (categorical_feature=0..7)
- ranking: lambdarank NDCG@10 on 100-doc queries

Usage: python scripts/measure_accuracy.py [round_no] [rows] [iters] [task ...]
       (reference half needs the CPU otherwise idle)
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

PARAMS = {"objective": "binary", "metric": "auc", "verbose": -1,
          "max_bin": 63, "num_leaves": 255, "learning_rate": 0.1,
          "min_data_in_leaf": 1, "min_sum_hessian_in_leaf": 100.0}


def _auc(y, p):
    import numpy as np
    order = np.argsort(p)
    ranks = np.empty(len(p))
    ranks[order] = np.arange(1, len(p) + 1)
    pos = y > 0
    n_pos, n_neg = pos.sum(), (~pos).sum()
    return (ranks[pos].sum() - n_pos * (n_pos + 1) / 2) / (n_pos * n_neg)


def _ndcg_at(y, p, qsizes, k=10):
    import numpy as np
    off, total, nq = 0, 0.0, 0
    for s in qsizes:
        yy, pp = y[off:off + s], p[off:off + s]
        off += s
        order = np.argsort(-pp)[:k]
        gains = (2.0 ** yy[order] - 1) / np.log2(np.arange(2, len(order) + 2))
        ideal = np.sort(yy)[::-1][:k]
        idcg = ((2.0 ** ideal - 1) / np.log2(np.arange(2, len(ideal) + 2))).sum()
        if idcg > 0:
            total += gains.sum() / idcg
            nq += 1
    return total / max(nq, 1)


def _ref_train_predict(exe, build_dir, tag, tr, te, conf, iters,
                       extra_train=(), raw=True):
    model = os.path.join(build_dir, f"acc_{tag}_model.txt")
    c = dict(conf)
    c.pop("verbose", None)
    c.update(task="train", data=tr, num_trees=iters, verbosity=1,
             output_model=model, num_threads=os.cpu_count() or 1)
    t0 = time.time()
    subprocess.run([exe] + [f"{k}={v}" for k, v in c.items()]
                   + list(extra_train), check=True, capture_output=True)
    wall = time.time() - t0
    preds = os.path.join(build_dir, f"acc_{tag}_preds.txt")
    args = [exe, "task=predict", f"data={te}", f"input_model={model}",
            f"output_result={preds}"]
    if raw:
        args.append("predict_raw_score=true")
    subprocess.run(args, check=True, capture_output=True)
    import numpy as np
    return np.loadtxt(preds), wall


def _binary_task(rows, iters, exe, build_dir):
    import numpy as np

    from scripts import synth_data
    import lightgbm_tpu as lgb

    n_test = rows // 5
    X, y = synth_data.synth_higgs(rows + n_test, 28, seed=11)
    Xtr, ytr, Xte, yte = X[:rows], y[:rows], X[rows:], y[rows:]

    ds = lgb.Dataset(Xtr, ytr, params=dict(PARAMS))
    t0 = time.time()
    booster = lgb.train(dict(PARAMS), ds, num_boost_round=iters,
                        verbose_eval=False)
    ours_wall = time.time() - t0
    ours = float(_auc(yte, booster.predict(Xte, raw_score=True)))

    tr = os.path.join(build_dir, f"acc_{rows}.train")
    te = os.path.join(build_dir, f"acc_{rows}.test")
    if not os.path.exists(tr):
        np.savetxt(tr, np.column_stack([ytr, Xtr]), fmt="%.6g", delimiter="\t")
        np.savetxt(te, np.column_stack([yte, Xte]), fmt="%.6g", delimiter="\t")
    preds, ref_wall = _ref_train_predict(exe, build_dir, "bin", tr, te,
                                         PARAMS, iters)
    ref = float(_auc(yte, preds))
    return {"metric": "auc", "ours": round(ours, 6), "ref": round(ref, 6),
            "delta": round(ours - ref, 6),
            "ours_train_wall_s": round(ours_wall, 1),
            "ref_train_wall_s": round(ref_wall, 1),
            "rows": rows, "iters": iters}


def _categorical_task(rows, iters, exe, build_dir):
    import numpy as np

    from scripts import synth_data
    import lightgbm_tpu as lgb

    n_test = rows // 5
    X, y, cat_idx = synth_data.synth_expo(rows + n_test, seed=13)
    Xtr, ytr, Xte, yte = X[:rows], y[:rows], X[rows:], y[rows:]
    params = dict(PARAMS, categorical_feature=cat_idx)

    ds = lgb.Dataset(Xtr, ytr, params=dict(params))
    t0 = time.time()
    booster = lgb.train(dict(params), ds, num_boost_round=iters,
                        verbose_eval=False)
    ours_wall = time.time() - t0
    ours = float(_auc(yte, booster.predict(Xte, raw_score=True)))

    tr = os.path.join(build_dir, f"acc_cat_{rows}.train")
    te = os.path.join(build_dir, f"acc_cat_{rows}.test")
    if not os.path.exists(tr):
        np.savetxt(tr, np.column_stack([ytr, Xtr]), fmt="%.6g", delimiter="\t")
        np.savetxt(te, np.column_stack([yte, Xte]), fmt="%.6g", delimiter="\t")
    cats = "categorical_feature=" + ",".join(str(c) for c in cat_idx)
    preds, ref_wall = _ref_train_predict(exe, build_dir, "cat", tr, te,
                                         PARAMS, iters, extra_train=[cats])
    ref = float(_auc(yte, preds))
    return {"metric": "auc", "ours": round(ours, 6), "ref": round(ref, 6),
            "delta": round(ours - ref, 6),
            "ours_train_wall_s": round(ours_wall, 1),
            "ref_train_wall_s": round(ref_wall, 1),
            "rows": rows, "iters": iters, "categorical": len(cat_idx)}


def _ranking_task(rows, iters, exe, build_dir):
    import numpy as np

    import lightgbm_tpu as lgb
    from measure_parity_sweep import _rank_data

    qlen = 100
    n_test = rows // 5
    X, y, nq, _ = _rank_data(rows + n_test, qlen=qlen, seed=17)
    ntr = (rows // qlen) * qlen
    Xtr, ytr, Xte, yte = X[:ntr], y[:ntr], X[ntr:], y[ntr:]
    qtr = [qlen] * (ntr // qlen)
    qte = [qlen] * (len(yte) // qlen)

    params = {"objective": "lambdarank", "metric": "ndcg", "verbose": -1,
              "max_bin": 63, "num_leaves": 255, "learning_rate": 0.1,
              "min_data_in_leaf": 1, "min_sum_hessian_in_leaf": 100.0}
    ds = lgb.Dataset(Xtr, ytr, group=qtr, params=dict(params))
    t0 = time.time()
    booster = lgb.train(dict(params), ds, num_boost_round=iters,
                        verbose_eval=False)
    ours_wall = time.time() - t0
    ours = float(_ndcg_at(yte, booster.predict(Xte, raw_score=True), qte))

    tr = os.path.join(build_dir, f"acc_rank_{rows}.train")
    te = os.path.join(build_dir, f"acc_rank_{rows}.test")
    if not os.path.exists(tr):
        np.savetxt(tr, np.column_stack([ytr, Xtr]), fmt="%.6g", delimiter="\t")
        np.savetxt(te, np.column_stack([yte, Xte]), fmt="%.6g", delimiter="\t")
        with open(tr + ".query", "w") as fh:
            fh.write("\n".join(str(q) for q in qtr))
        with open(te + ".query", "w") as fh:
            fh.write("\n".join(str(q) for q in qte))
    preds, ref_wall = _ref_train_predict(exe, build_dir, "rank", tr, te,
                                         params, iters, raw=True)
    ref = float(_ndcg_at(yte, preds, qte))
    return {"metric": "ndcg@10", "ours": round(ours, 6),
            "ref": round(ref, 6), "delta": round(ours - ref, 6),
            "ours_train_wall_s": round(ours_wall, 1),
            "ref_train_wall_s": round(ref_wall, 1),
            "rows": ntr, "iters": iters, "query_len": qlen}


def main(round_no: int = 5, rows: int = 500_000, iters: int = 500,
         tasks=("binary", "categorical", "ranking")):
    from measure_baseline import BUILD_DIR, build_reference
    exe = build_reference()
    os.makedirs(BUILD_DIR, exist_ok=True)

    out = os.path.join(REPO, f"ACCURACY_r{round_no:02d}.json")
    result = {}
    if os.path.exists(out):
        result = json.load(open(out))
    result.setdefault(
        "reference_published_anchor",
        "HIGGS AUC 0.845239 @63 bins/500 iters (docs/GPU-Performance.md:134)")
    fns = {"binary": _binary_task, "categorical": _categorical_task,
           "ranking": _ranking_task}
    for t in tasks:
        result[t] = fns[t](rows, iters, exe, BUILD_DIR)
        with open(out, "w") as fh:
            json.dump(result, fh, indent=1)
        print(t, json.dumps(result[t]))


if __name__ == "__main__":
    nums = [int(float(a)) for a in sys.argv[1:] if a.replace(".", "").isdigit()]
    names = [a for a in sys.argv[1:] if not a.replace(".", "").isdigit()]
    main(*nums, tasks=tuple(names) if names else ("binary", "categorical",
                                                  "ranking"))
