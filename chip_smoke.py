"""chip_smoke.py — does the training main path start and give right
answers on the chip?

One process, no subprocesses, no network, data from a seed. Drives
`lightgbm_tpu.train` -> `Booster.predict` -> `serving.Predictor` once at
the full width of the reference's published HIGGS configuration
(BASELINE.md: 28 dense features, max_bin=63, num_leaves=255, lr=0.1,
min_data_in_leaf=1, min_sum_hessian_in_leaf=100, no `tpu_*` flag); rows
are the only knob. Each phase prints one line; any failed check or
exception ends the run non-zero with no result line. The line before
last, `[chip_smoke] summary: {...}`, carries what the run saw; every time
in it is a SMOKE TIMING of one cold or warm start, not a benchmark
result. The last line of stdout is the verdict and nothing else:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

    python chip_smoke.py                      # on the chip (expects tpu)
    python chip_smoke.py --modes all          # + the non-default programs
    JAX_PLATFORMS=cpu python chip_smoke.py --rows 50000 --holdout 5000 \\
        --expect-platform cpu                 # CPU rehearsal

A platform other than the expected one fails before any training: a
"chip run" that landed on the CPU must never exit 0.
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import sys
import time
import traceback

import numpy as np

FEATURES = 28
TRAIN_PARAMS = {
    "objective": "binary", "metric": "binary_logloss", "max_bin": 63,
    "num_leaves": 255, "learning_rate": 0.1, "min_data_in_leaf": 1,
    "min_sum_hessian_in_leaf": 100.0,
}
DEFAULT_ROWS = 2_000_000
DEFAULT_HOLDOUT = 200_000
ITERATIONS = 10
REPEAT_ITERATIONS = 2
PARALLEL_ITERATIONS = 3
MODE_ITERATIONS = 2

# device forest vs host traversal, raw score: the device sums ITERATIONS
# f32 leaf values where the host sums the same values in f64, so the gap
# is a few f32 ulps of an O(1) score (~1e-6). An f16/bf16 leaf table —
# the precision below the one configured — misses by ~1e-3 and fails.
RAW_SCORE_TOL = 1e-5
SAMPLE_ROWS = 256
# predict() vs the host's f64 sigmoid of the same raw scores: the
# transform runs on the device in f32, where the TPU's exp is a few ulps
# off; a bf16 transform would miss by ~4e-3
PROB_TOL = 1e-5
# holdout AUC after ITERATIONS trees; the seeded generator's CPU
# rehearsal gives 0.80 at 50k rows and more with more rows, so a model
# under 0.75 is a broken model, not an unlucky seed
AUC_FLOOR = 0.75
# data-parallel vs serial after PARALLEL_ITERATIONS: the trees differ
# only by f32 summation order (tests/test_scatter_reduce.py), which
# moves a 3-tree log-loss by ~1e-7
PARALLEL_LOGLOSS_TOL = 1e-4

MODES = ("hist_int8", "hist_int16", "linear_tree", "multiclass",
         "lambdarank", "sweep", "predict_f16", "predict_int8")


class SmokeFailure(Exception):
    """A check on the program's output did not hold."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def auc(label: np.ndarray, score: np.ndarray) -> float:
    """Rank-sum AUC with average ranks over ties."""
    order = np.argsort(score, kind="stable")
    s = score[order]
    edges = np.flatnonzero(np.r_[True, s[1:] != s[:-1], True])
    rank = np.empty(len(s), np.float64)
    rank[order] = np.repeat((edges[:-1] + edges[1:] + 1) / 2.0,
                            np.diff(edges))
    pos = label > 0.5
    n1 = int(pos.sum())
    n0 = len(label) - n1
    return float((rank[pos].sum() - n1 * (n1 + 1) / 2.0) / (n1 * n0))


def trees_text(booster, num_iteration: int = -1) -> str:
    """Model text up to the importance footer (which counts every tree
    whatever `num_iteration` says)."""
    return booster.model_to_string(num_iteration=num_iteration).split(
        "feature importances")[0]


def same_structure(trees_a, trees_b) -> bool:
    """Same splits in the same node order, tree by tree."""
    return all(a.num_leaves == b.num_leaves
               and np.array_equal(a.split_feature, b.split_feature)
               and np.array_equal(a.threshold_in_bin, b.threshold_in_bin)
               for a, b in zip(trees_a, trees_b))


def cache_entries(directory) -> list:
    if not directory or not os.path.isdir(directory):
        return []
    return sorted(f for f in os.listdir(directory) if f.endswith("-cache"))


def program_names(entries) -> list:
    """jit module names of cache entries (`<module>-<key>-cache`)."""
    return sorted({e.rsplit("-", 2)[0] for e in entries})


class Smoke:
    """The phases, in order; state one phase leaves for the next lives
    here. `run()` wraps each in the compile accounting."""

    def __init__(self, args):
        self.args = args
        self.summary = {"ok": False}
        self.phases = {}

    # -- plumbing -------------------------------------------------------
    def run(self, name, fn):
        before = self.observer.snapshot()
        t0 = time.perf_counter()
        detail = fn()
        wall = time.perf_counter() - t0
        after = self.observer.snapshot()
        rec = {"smoke_wall_s": round(wall, 2),
               "compiles": after["total_compiles"] - before["total_compiles"],
               "compile_s": round(after["total_seconds"]
                                  - before["total_seconds"], 2)}
        self.phases[name] = rec
        print(f"[chip_smoke] {name}: ok wall={rec['smoke_wall_s']}s "
              f"compiles={rec['compiles']} compile_s={rec['compile_s']} "
              f"{detail}", flush=True)

    def train(self, ds, iters, params=None, **kw):
        import lightgbm_tpu as lgb
        evals = {}
        booster = lgb.train(dict(params or TRAIN_PARAMS), ds,
                            num_boost_round=iters, valid_sets=[ds],
                            valid_names=["train"], evals_result=evals,
                            verbose_eval=False, **kw)
        return booster, evals["train"]

    # -- phases ---------------------------------------------------------
    def device(self):
        """Initialize jax WITHOUT naming a platform in code; refuse any
        platform but the expected one."""
        from importlib import metadata

        import jax
        devs = jax.devices()
        dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
               "count": len(devs)}
        try:
            libtpu = metadata.version("libtpu")
        except metadata.PackageNotFoundError:
            libtpu = "not installed"
        self.summary.update(device=dev, jax=jax.__version__,
                            jaxlib=metadata.version("jaxlib"), libtpu=libtpu)
        print(f"[chip_smoke] device: jax={jax.__version__} libtpu={libtpu} "
              f"platform={dev['platform']} device_kind={dev['kind']} "
              f"count={dev['count']}", flush=True)
        check(dev["platform"] == self.args.expect_platform,
              f"platform is {dev['platform']!r}, expected "
              f"{self.args.expect_platform!r}; nothing was trained")
        # armed from the start: every later compile is charged to a phase
        from lightgbm_tpu import telemetry
        self.observer = telemetry.install_observer()
        self.cache_events = {"hits": 0, "misses": 0}

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.cache_events["hits"] += 1
            elif name == "/jax/compilation_cache/cache_misses":
                self.cache_events["misses"] += 1

        jax.monitoring.register_event_listener(on_event)
        self.cache_dir = jax.config.jax_compilation_cache_dir
        self.cache_before = cache_entries(self.cache_dir)
        print(f"[chip_smoke] cache: dir={self.cache_dir} "
              f"entries_before={len(self.cache_before)}", flush=True)

    def data(self):
        import lightgbm_tpu as lgb
        from scripts.synth_data import synth_higgs
        a = self.args
        X, y = synth_higgs(a.rows + a.holdout, FEATURES, seed=a.seed)
        self.X_hold, self.y_hold = X[a.rows:], y[a.rows:]
        self.X_train, self.y_train = X[:a.rows], y[:a.rows]
        self.ds = lgb.Dataset(self.X_train, self.y_train,
                              params=dict(TRAIN_PARAMS))
        self.ds.construct()
        return f"rows={a.rows}x{FEATURES} holdout={a.holdout}"

    def train_full_width(self):
        self.booster, ev = self.train(self.ds, ITERATIONS)
        info = self.booster._inner._schedule_info
        losses = [float(v) for v in ev["binary_logloss"]]
        leaves = [t.num_leaves for t in self.booster._inner.models]
        sched = {k: info[k] for k in ("subtract", "compact", "batch_k",
                                      "table_mult", "chunk", "rows_padded",
                                      "max_bin", "groups")}
        self.summary.update(schedule=sched, train_logloss=losses,
                            tree_leaves=leaves)
        check(info["max_bin"] == TRAIN_PARAMS["max_bin"]
              and info["groups"] == FEATURES,
              f"histogram width is not {FEATURES} x max_bin=63: {sched}")
        check(info["subtract"], f"sibling subtraction not selected: {sched}")
        # at this width a full pass is cheaper than the index build of a
        # compacted one (schedule.compact_threshold), so the schedule keeps
        # every pass full and the program has no compacted branch
        check(info["compact_model"]["fraction"] == 0.0
              and not info["compact"],
              f"gather-compaction selected={info['compact']} at "
              f"{FEATURES} x 63, where the pass-cost model says "
              f"{info['compact_model']}")
        check(len(leaves) == ITERATIONS and min(leaves) > 1,
              f"expected {ITERATIONS} trees that all split, got {leaves}")
        check(all(np.isfinite(losses))
              and all(b < a for a, b in zip(losses, losses[1:])),
              f"training log-loss is not falling monotonically: {losses}")
        return (f"max_bin=63 num_leaves={TRAIN_PARAMS['num_leaves']} "
                f"subtract={info['subtract']} compact={info['compact']} "
                f"batch_k={info['batch_k']} trees={len(leaves)} "
                f"leaves={min(leaves)}..{max(leaves)} "
                f"logloss={losses[0]:.5f}->{losses[-1]:.5f}")

    def predict(self):
        prob = self.booster.predict(self.X_hold)
        raw = self.booster.predict(self.X_hold, raw_score=True)
        check(prob.shape == (self.args.holdout,) and raw.shape == prob.shape
              and np.isfinite(prob).all() and np.isfinite(raw).all(),
              "predict returned a wrong shape or non-finite values")
        sig_err = float(np.abs(prob - 1.0 / (1.0 + np.exp(-raw))).max())
        check(sig_err <= PROB_TOL,
              f"predict() differs from sigmoid(raw_score) by {sig_err:.3g} "
              f"(tolerance {PROB_TOL})")
        idx = np.random.RandomState(self.args.seed + 1).choice(
            self.args.holdout, size=min(SAMPLE_ROWS, self.args.holdout),
            replace=False)
        trees = self.booster._inner.models
        host = np.array([sum(t.predict_row(row) for t in trees)
                         for row in self.X_hold[idx].astype(np.float64)])
        err = float(np.abs(raw[idx] - host).max())
        check(err <= RAW_SCORE_TOL,
              f"device raw scores differ from Tree.predict_row by {err:.3g} "
              f"(tolerance {RAW_SCORE_TOL})")
        self.holdout_prob = prob
        self.holdout_auc = auc(self.y_hold, raw)
        self.summary.update(holdout_auc=round(self.holdout_auc, 5),
                            predict_max_abs_err_vs_host=err,
                            predict_max_abs_err_vs_sigmoid=sig_err)
        check(self.holdout_auc >= AUC_FLOOR,
              f"holdout AUC {self.holdout_auc:.4f} < floor {AUC_FLOOR}")
        return (f"rows={self.args.holdout} auc={self.holdout_auc:.4f} "
                f"max|device-host|={err:.2e} on {len(idx)} rows "
                f"(tol {RAW_SCORE_TOL}) max|prob-sigmoid|={sig_err:.2e}")

    def serve(self):
        from lightgbm_tpu.serving import Predictor
        predictor = Predictor(self.booster)
        warm = predictor.warmup()
        ones = [predictor.predict_one(self.X_hold[i]) for i in range(3)]
        n = min(4096, self.args.holdout)
        batch = predictor.predict(self.X_hold[:n])
        predictor.close()
        check(all(ones[i] == self.booster.predict(self.X_hold[i:i + 1])[0]
                  for i in range(3)),
              "Predictor.predict_one differs from Booster.predict")
        check(np.array_equal(batch, self.booster.predict(self.X_hold[:n])),
              "Predictor.predict differs from Booster.predict")
        bulk_err = float(np.abs(batch - self.holdout_prob[:n]).max())
        check(bulk_err <= PROB_TOL,
              f"a served batch differs from the bulk predict of the same "
              f"rows by {bulk_err:.3g}")
        return (f"warmup_buckets={len(warm['buckets'])} predict_one=3 "
                f"batch_rows={n} equal_to_booster_predict=True")

    def repeat_train(self):
        """2 more iterations on the same Dataset in the same process
        must reuse every compiled program."""
        before = self.observer.snapshot()["total_compiles"]
        booster, ev = self.train(self.ds, REPEAT_ITERATIONS)
        compiled = self.observer.snapshot()["total_compiles"] - before
        self.summary["repeat_train_compiles"] = compiled
        check(booster.num_trees() == REPEAT_ITERATIONS,
              "repeat train did not grow its trees")
        check(ev["binary_logloss"] == self.summary["train_logloss"][
            :REPEAT_ITERATIONS], "repeat train is not deterministic")
        sites = {s: r["compiles"]
                 for s, r in self.observer.snapshot()["sites"].items()}
        check(compiled == 0,
              f"repeat train compiled {compiled} program(s); sites: {sites}")
        return f"iterations={REPEAT_ITERATIONS} compiles=0"

    def data_parallel(self):
        """tree_learner=data (default scatter merge) over every device:
        rows spread evenly, and the model agrees with the serial run's
        first trees."""
        import jax
        ndev = jax.device_count()
        params = dict(TRAIN_PARAMS, tree_learner="data")
        seen = [self.observer.snapshot()["total_compiles"]]

        def count_compiles(env):
            seen.append(self.observer.snapshot()["total_compiles"])

        booster, ev = self.train(self.ds, PARALLEL_ITERATIONS, params,
                                 callbacks=[count_compiles])
        per_iter = [b - a for a, b in zip(seen, seen[1:])]
        check(per_iter[-1] == 0,
              f"the data-parallel dispatch still compiles at iteration "
              f"{PARALLEL_ITERATIONS - 1}: compiles per iteration {per_iter}")
        inner = booster._inner
        info = inner._schedule_info
        check(info["hist_reduce"] == "scatter" and info["num_shards"] == ndev,
              f"not the default scatter merge over {ndev} devices: {info}")
        # the serial run's schedule for one shard's shape: the cache, at
        # the owned slice's width on more than one device
        check(info["subtract"],
              f"sibling subtraction not selected under the data axis: "
              f"{info}")
        shards = inner._binned.addressable_shards
        per_dev = sorted((str(s.device), int(s.data.shape[0]))
                         for s in shards)
        check(len({d for d, _ in per_dev}) == ndev
              and all(r == info["rows_padded"] // ndev for _, r in per_dev),
              f"binned rows are not spread 1/{ndev} per device: {per_dev}")
        leaves = [t.num_leaves for t in inner.models]
        check(len(leaves) == PARALLEL_ITERATIONS and min(leaves) > 1,
              f"data-parallel trees did not all split: {leaves}")
        serial = self.booster._inner.models[:PARALLEL_ITERATIONS]
        structure_equal = same_structure(inner.models, serial)
        # node row counts are int32 sums, exact at any size: every tree's
        # leaves hold all the rows, and a tree that took the serial run's
        # splits holds the serial run's rows leaf by leaf
        held = [int(t.leaf_count[:t.num_leaves].sum()) for t in inner.models]
        check(held == [info["rows"]] * len(held),
              f"leaf_count does not sum to the {info['rows']} rows: {held}")
        for i, (a, b) in enumerate(zip(inner.models, serial)):
            check(not same_structure([a], [b])
                  or (np.array_equal(a.leaf_count, b.leaf_count)
                      and np.array_equal(a.internal_count,
                                         b.internal_count)),
                  f"tree {i} takes the serial run's splits with other row "
                  f"counts")
        text_equal = trees_text(booster) == trees_text(
            self.booster, PARALLEL_ITERATIONS)

        # how far apart, when not equal: splits (feature, bin threshold,
        # rows in the node) the two runs share, tree by tree — node
        # numbering shifts after the first near-tie that falls the other
        # way, so arrays are compared as multisets
        def splits(t):
            m = t.num_leaves - 1
            return collections.Counter(zip(
                t.split_feature[:m].tolist(),
                t.threshold_in_bin[:m].tolist(),
                t.internal_count[:m].tolist()))

        shared = [sum((splits(a) & splits(b)).values())
                  for a, b in zip(inner.models, serial)]
        n_hold = min(20000, self.args.holdout)
        auc_dp = auc(self.y_hold[:n_hold], booster.predict(
            self.X_hold[:n_hold], raw_score=True))
        auc_serial = auc(self.y_hold[:n_hold], self.booster.predict(
            self.X_hold[:n_hold], raw_score=True,
            num_iteration=PARALLEL_ITERATIONS))
        want = self.summary["train_logloss"][PARALLEL_ITERATIONS - 1]
        got = float(ev["binary_logloss"][-1])
        check(abs(got - want) <= PARALLEL_LOGLOSS_TOL,
              f"data-parallel log-loss {got} vs serial {want} after "
              f"{PARALLEL_ITERATIONS} trees")
        self.summary["data_parallel"] = {
            "devices": ndev, "hist_reduce": "scatter",
            "subtract": info["subtract"], "batch_k": info["batch_k"],
            "rows_per_device": per_dev[0][1],
            "compiles_per_iteration": per_iter,
            "leaf_count_sums_to_rows": True,
            "model_text_equal_to_serial": text_equal,
            "tree_structure_equal_to_serial": structure_equal,
            "splits_shared_with_serial_per_tree": shared,
            "splits_per_tree": [t.num_leaves - 1 for t in inner.models],
            "holdout_auc": round(auc_dp, 6),
            "serial_holdout_auc": round(auc_serial, 6),
            "logloss": got, "serial_logloss": want}
        return (f"devices={ndev} rows_per_device={per_dev[0][1]} "
                f"subtract={info['subtract']} batch_k={info['batch_k']} "
                f"compiles_per_iteration={per_iter} "
                f"text_equal={text_equal} structure_equal={structure_equal} "
                f"splits_shared={shared} of {[n - 1 for n in leaves]} "
                f"auc={auc_dp:.5f} (serial {auc_serial:.5f}) "
                f"logloss={got:.6f} (serial {want:.6f})")

    # -- the non-default programs (--modes) ------------------------------
    def _mode_train(self, extra, ds=None, metric="binary_logloss",
                    rising=False):
        params = dict(TRAIN_PARAMS, **extra)
        booster, ev = self.train(ds or self.ds, MODE_ITERATIONS, params)
        leaves = [t.num_leaves for t in booster._inner.models]
        vals = [float(v) for v in ev[metric]]
        check(min(leaves) > 1, f"a tree did not split: {leaves}")
        check(all(np.isfinite(vals))
              and (vals[-1] > vals[0] if rising else vals[-1] < vals[0]),
              f"{metric} not finite and "
              f"{'rising' if rising else 'falling'}: {vals}")
        return booster, f"leaves={leaves} {metric}={vals}"

    def mode_hist_int8(self):
        return self._mode_train({"tpu_hist_quantize": "int8"})[1]

    def mode_hist_int16(self):
        return self._mode_train({"tpu_hist_quantize": "int16"})[1]

    def mode_linear_tree(self):
        import lightgbm_tpu as lgb
        params = {"linear_tree": True}
        ds = lgb.Dataset(self.X_train, self.y_train,
                         params=dict(TRAIN_PARAMS, **params))
        booster, detail = self._mode_train(params, ds)
        pred = booster.predict(self.X_hold[:4096])
        check(np.isfinite(pred).all(), "linear_tree predict is not finite")
        return detail

    def mode_multiclass(self):
        import lightgbm_tpu as lgb
        score = self.X_train[:, 0] * 1.2 - self.X_train[:, 1]
        y = np.digitize(score, np.quantile(score, [0.2, 0.4, 0.6, 0.8]))
        params = {"objective": "multiclass", "num_class": 5,
                  "metric": "multi_logloss"}
        ds = lgb.Dataset(self.X_train, y.astype(np.float32),
                         params=dict(TRAIN_PARAMS, **params))
        booster, detail = self._mode_train(params, ds, "multi_logloss")
        pred = booster.predict(self.X_hold[:4096])
        check(pred.shape == (min(4096, self.args.holdout), 5)
              and np.allclose(pred.sum(axis=1), 1.0, atol=1e-5),
              "multiclass predict is not a [rows, 5] distribution")
        return detail

    def mode_lambdarank(self):
        """Query lengths drawn over 8..128 so every length bucket of the
        pairwise objective (16, 32, 64, 128) is populated."""
        import lightgbm_tpu as lgb
        rng = np.random.RandomState(self.args.seed + 2)
        sizes = []
        while sum(sizes) < self.args.rows:
            sizes.append(int(rng.randint(8, 129)))
        sizes[-1] -= sum(sizes) - self.args.rows
        if sizes[-1] <= 0:
            sizes.pop()
        n = sum(sizes)
        score = self.X_train[:n, 0] * 1.2 - self.X_train[:n, 1] \
            + rng.logistic(size=n)
        rel = np.digitize(score, np.quantile(score, [0.5, 0.75, 0.9, 0.97]))
        params = {"objective": "lambdarank", "metric": "ndcg",
                  "ndcg_eval_at": [10]}
        ds = lgb.Dataset(self.X_train[:n], rel.astype(np.float32),
                         group=sizes, params=dict(TRAIN_PARAMS, **params))
        detail = self._mode_train(params, ds, "ndcg@10", rising=True)[1]
        return f"queries={len(sizes)} {detail}"

    def mode_sweep(self):
        from lightgbm_tpu.engine import train_sweep
        plist = [dict(TRAIN_PARAMS, learning_rate=0.1 + 0.02 * k,
                      lambda_l2=0.5 * k) for k in range(4)]
        boosters = train_sweep(plist, self.ds,
                               num_boost_round=MODE_ITERATIONS)
        aucs = []
        for b in boosters:
            leaves = [t.num_leaves for t in b._inner.models]
            check(len(leaves) == MODE_ITERATIONS and min(leaves) > 1,
                  f"a sweep model's trees did not all split: {leaves}")
            aucs.append(auc(self.y_hold[:20000],
                            b.predict(self.X_hold[:20000], raw_score=True)))
        check(min(aucs) > 0.6, f"sweep models do not rank: {aucs}")
        # model 0 has the main run's parameters: the sweep==solo
        # byte-identity contract (tests/test_sweep.py), reported here
        equal = trees_text(boosters[0]) == trees_text(self.booster,
                                                      MODE_ITERATIONS)
        pairs = list(zip(boosters[0]._inner.models,
                         self.booster._inner.models))
        structure = same_structure(*zip(*pairs))
        delta = max(float(np.abs(a.leaf_value - b.leaf_value).max())
                    for a, b in pairs) if structure else float("nan")
        return (f"models=4 auc={[round(a, 4) for a in aucs]} "
                f"model0_text_equal_to_solo={equal} "
                f"structure_equal={structure} max_leaf_delta={delta:.3g}")

    def _mode_predict_quantized(self, mode):
        import lightgbm_tpu as lgb
        b = lgb.Booster(model_str=self.booster.model_to_string(),
                        params={"tpu_predict_quantize": mode})
        raw = b.predict(self.X_hold, raw_score=True)
        ref = self.booster.predict(self.X_hold, raw_score=True)
        err = float(np.abs(raw - ref).max())
        tol = float(b._inner.config.io.tpu_predict_quantize_tol) \
            * max(1.0, float(np.abs(ref).max()))
        check(np.isfinite(raw).all() and err <= tol,
              f"{mode} raw scores differ from f32 by {err:.3g} > {tol:.3g}")
        return f"rows={len(raw)} max|{mode}-f32|={err:.2e} (gate tol {tol:.2e})"

    def mode_predict_f16(self):
        return self._mode_predict_quantized("f16")

    def mode_predict_int8(self):
        return self._mode_predict_quantized("int8")

    def run_modes(self, names):
        """Each non-default program once. A failure is recorded with the
        compiler's message and fails the run at the end — the remaining
        modes still get their verdict from the same chip call."""
        results = {}
        for name in names:
            try:
                self.run("mode:" + name, getattr(self, "mode_" + name))
                results[name] = "pass"
            except Exception as exc:
                traceback.print_exc()
                results[name] = f"FAIL {type(exc).__name__}: {exc}"[:2000]
                print(f"[chip_smoke] mode:{name}: {results[name]}",
                      flush=True)
        self.summary["modes"] = results
        return all(v == "pass" for v in results.values())

    # -- driver ---------------------------------------------------------
    def main(self) -> int:
        import jax
        t_start = time.perf_counter()
        self.device()
        self.run("data", self.data)
        self.run("train", self.train_full_width)
        self.run("predict", self.predict)
        self.run("serve", self.serve)
        self.run("repeat_train", self.repeat_train)
        if jax.device_count() >= 4:
            self.run("data_parallel", self.data_parallel)
        else:
            self.summary["data_parallel"] = \
                f"not run: {jax.device_count()} device"
            print("[chip_smoke] data_parallel: "
                  + self.summary["data_parallel"], flush=True)
        ok = True
        if self.args.modes:
            ok = self.run_modes(self.args.modes)
        after = cache_entries(self.cache_dir)
        new = sorted(set(after) - set(self.cache_before))
        total = self.observer.snapshot()
        a = self.args
        self.summary.update(
            ok=ok, rows=a.rows, holdout_rows=a.holdout, features=FEATURES,
            max_bin=TRAIN_PARAMS["max_bin"],
            num_leaves=TRAIN_PARAMS["num_leaves"], iterations=ITERATIONS,
            smoke_phases=self.phases,
            smoke_wall_s=round(time.perf_counter() - t_start, 2),
            compiles=total["total_compiles"],
            compile_s=round(total["total_seconds"], 2),
            cache={"dir": self.cache_dir,
                   "entries_before": len(self.cache_before),
                   "entries_after": len(after), "new_entries": len(new),
                   "new_entry_programs": program_names(new),
                   "persistent_hits": self.cache_events["hits"],
                   "persistent_writes": self.cache_events["misses"]})
        print(f"[chip_smoke] cache: dir={self.cache_dir} "
              f"entries_after={len(after)} new={len(new)} "
              f"persistent_hits={self.cache_events['hits']}", flush=True)
        print("[chip_smoke] summary: " + json.dumps(self.summary), flush=True)
        # the verdict line has exactly these keys: it is what a caller parses
        print(json.dumps({"ok": ok, "device": self.summary["device"]}),
              flush=True)
        return 0 if ok else 1


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=DEFAULT_ROWS,
                    help="training rows (the only size knob)")
    ap.add_argument("--holdout", type=int, default=DEFAULT_HOLDOUT)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--expect-platform", default="tpu",
                    help="fail unless jax lands on this platform")
    ap.add_argument("--modes", default="",
                    help="'all' or a comma list of " + ",".join(MODES)
                         + ": also run the non-default programs")
    args = ap.parse_args(argv)
    names = list(MODES) if args.modes == "all" else \
        [m for m in args.modes.split(",") if m]
    unknown = sorted(set(names) - set(MODES))
    if unknown:
        ap.error(f"unknown mode(s) {unknown}; choose from {MODES}")
    args.modes = names
    return args


def main(argv=None) -> int:
    return Smoke(parse_args(argv)).main()


if __name__ == "__main__":
    sys.exit(main())
