"""Generate docs/Parameters.md from the Config dataclasses + alias table
(reference: docs/Parameters.md, the canonical flag reference — ours is
generated so it cannot drift from the whitelist).

Usage: python scripts/gen_params_doc.py
"""
from __future__ import annotations

import dataclasses
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)



# one-line description per parameter (reference: docs/Parameters.md —
# rewritten, not copied; TPU-specific flags documented from our code)
DESCRIPTIONS = {
    # core
    "task": "what to do: train, predict, or convert_model",
    "seed": "master seed fanned out to data/feature/bagging/drop seeds",
    "boosting_type": "gbdt, dart, goss, or rf",
    "objective": "loss to optimize: regression, regression_l1, huber, "
                 "fair, poisson, binary, multiclass, multiclassova, "
                 "lambdarank, xentropy, xentlambda, none",
    "tree_learner": "serial, or distributed: feature, data, voting "
                    "(mapped onto a jax device mesh)",
    # io
    "max_bin": "max number of histogram bins per feature",
    "min_data_in_bin": "minimum rows per value bin during bin finding",
    "bin_construct_sample_cnt": "rows sampled to find bin boundaries",
    "data_random_seed": "seed for the bin-finding row sample",
    "output_model": "path the trained model text is written to",
    "output_result": "path predictions are written to (task=predict)",
    "convert_model": "output path for task=convert_model (if-else C++)",
    "input_model": "model text to load (predict / continued training)",
    "verbosity": "<0 fatal only, 0 warnings, 1 info, >1 debug",
    "num_iteration_predict": "use only the first N iterations to predict",
    "is_pre_partition": "multi-machine: data files are pre-partitioned "
                        "per rank (no row sharding by the loader)",
    "is_enable_sparse": "kept for API compat (storage is dense+EFB)",
    "enable_load_from_binary_file": "reuse <data>.bin when present "
                                    "(checksummed, memory-mapped; "
                                    "skips parsing AND binning; a "
                                    "cache whose fingerprint does not "
                                    "match the data file + binning "
                                    "params is refused)",
    "use_two_round_loading": "stream the file twice instead of holding "
                             "raw values in memory (subsumed by "
                             "tpu_ingest, kept for the multi-process "
                             "loader)",
    "is_save_binary_file": "write <data>.bin after construction (v2 "
                           "ingest cache: versioned + checksummed + "
                           "source-fingerprinted)",
    "enable_bundle": "exclusive feature bundling (EFB)",
    "max_conflict_rate": "max fraction of conflicting rows per bundle",
    "has_header": "data files carry a header row",
    "label_column": "label selector: index or name:colname",
    "weight_column": "per-row weight column selector",
    "group_column": "ranking query/group column selector",
    "ignore_column": "columns dropped before binning",
    "categorical_column": "columns treated as categorical (indices or "
                          "name:c1,c2)",
    "data_filename": "training data path (CLI)",
    "valid_data_filenames": "validation data paths (CLI)",
    "snapshot_freq": "save the model every N iterations",
    "tpu_checkpoint_dir": "directory for crash-consistent full-state "
                          "checkpoints (model + RNG + DART ledger + "
                          "scores + early-stop history); training "
                          "resumes BIT-IDENTICALLY from the newest "
                          "valid snapshot on restart (empty = off)",
    "tpu_checkpoint_interval": "write a checkpoint every N iterations",
    "tpu_checkpoint_keep": "checkpoints retained per rank (older ones "
                           "are rotated out; corrupt/truncated "
                           "snapshots fall back to the previous good "
                           "one on resume)",
    "tpu_elastic_resume": "accept checkpoints taken at a DIFFERENT "
                          "world size: scores re-shard onto the new "
                          "device/process layout; across DEVICE-count "
                          "changes the resumed model is byte-identical "
                          "to an uninterrupted run (process-count "
                          "changes restore exact state but f32 "
                          "summation order differs). false = refuse "
                          "world-size changes",
    "tpu_io_retries": "retries per critical durable write (checkpoint/"
                      "artifact/cache) on transient IO errors; "
                      "exhaustion raises a structured DurableWriteError "
                      "naming path, errno and attempts",
    "tpu_io_backoff_s": "initial retry backoff for durable writes, "
                        "doubling per attempt",
    "tpu_io_deadline_s": "wall-clock budget for one durable write "
                         "including retries (0 = unbounded); a slow-IO "
                         "stall fails the write instead of wedging "
                         "training",
    "tpu_telemetry_dir": "observability directory: a structured JSONL "
                         "run log (header + one record per iteration + "
                         "events + summary; see README Observability) "
                         "plus end-of-run Prometheus text-exposition "
                         "metric dumps, one file per rank (empty = off)",
    "tpu_telemetry": "collect span timers / counters / compile events "
                     "without writing files (exit dump only — the "
                     "LGBM_TPU_TIMETAG behavior, config-exposed)",
    "tpu_telemetry_prometheus": "write metrics_r<rank>.prom (+ the "
                                "cross-rank metrics_aggregate.prom on "
                                "rank 0) into tpu_telemetry_dir at end "
                                "of run",
    "tpu_ingest": "streaming ingest (lightgbm_tpu/ingest): build "
                  "datasets by a chunked two-pass pipeline (pass 1 "
                  "sketches bin bounds from a streamed row sample, "
                  "pass 2 re-streams and bins against the frozen "
                  "bounds) — bit-identical to in-memory construction "
                  "at any chunk size; false restores the "
                  "load-everything path",
    "tpu_ingest_chunk_rows": "rows per streamed ingest chunk",
    "tpu_ingest_device_shards": "land the binned matrix directly as "
                                "per-device row shards under a "
                                "single-process data/voting-parallel "
                                "mesh (host blocks freed as they ship, "
                                "so the dataset can exceed one "
                                "device's HBM)",
    "tpu_sweep_size": "declared width of a many-model sweep "
                      "(engine.train_sweep): 0 accepts any length of "
                      "param-dict list, > 0 refuses a list of any other "
                      "length (a supervisor can pin the fleet size it "
                      "provisioned). Sweep membership never changes a "
                      "model's trees: model k of a vmapped sweep is "
                      "byte-identical to training its config alone",
    "tpu_sweep_name_prefix": "serving.ModelRegistry name prefix for "
                             "sweep models published without explicit "
                             "names: model k lands as '<prefix>/<k>' "
                             "through one shared publish_many "
                             "budget/eviction pass",
    "is_predict_raw_score": "predict raw scores instead of transformed",
    "is_predict_leaf_index": "predict leaf indices per tree",
    "is_predict_contrib": "predict TreeSHAP feature contributions",
    "pred_early_stop": "stop accumulating trees once the margin is safe",
    "pred_early_stop_freq": "check the margin every N iterations",
    "pred_early_stop_margin": "margin threshold for prediction early stop",
    "tpu_predict_cache": "device-resident compiled forest cache: trees "
                         "are stacked/padded/transferred once per model "
                         "version instead of per predict call (false = "
                         "per-call restack, for A/B timing)",
    "tpu_predict_bucket_min": "smallest row bucket of the power-of-two "
                              "predict dispatch ladder; batches pad up "
                              "the ladder so arbitrary sizes reuse a "
                              "handful of compiled programs (<= 0 "
                              "disables bucketing)",
    "tpu_predict_chunk": "rows per predict dispatch chunk (0 = auto: "
                         "512k matmul / 128k walk)",
    "tpu_predict_pipeline": "double-buffered predict chunk loop: "
                            "dispatch chunk k+1 before fetching chunk "
                            "k so transfer and compute overlap",
    "tpu_predict_quantize": "quantized serving forest layout: none = "
                            "bit-exact f32 stacks; f16 = f16 leaf "
                            "values + bf16 path/category tables "
                            "(decisions stay bit-exact); int8 = "
                            "additionally codes split thresholds "
                            "fixed-point against the per-feature bin "
                            "bounds (8-bit code space) with a single "
                            "default-precision selection einsum. "
                            "Value prediction only; pred_leaf and "
                            "prediction early stop stay exact f32",
    "tpu_predict_quantize_tol": "accuracy gate for quantized layouts: "
                                "max |raw-score delta| vs the f32 "
                                "stack on a calibration batch, "
                                "relative to the batch's score scale; "
                                "a lossier layout is refused with an "
                                "error instead of served",
    "tpu_serving_budget_mb": "serving.ModelRegistry device-memory "
                             "budget for compiled stacks across all "
                             "resident models, in MiB (0 = unlimited); "
                             "least-recently-used models' stacks are "
                             "evicted past it (host trees stay, the "
                             "next request restacks)",
    "tpu_serving_max_queue": "max queued Predictor.submit() requests; "
                             "past it new requests are refused with a "
                             "structured retriable ServingOverload "
                             "(reason queue_full) instead of queueing "
                             "late (0 = unbounded)",
    "tpu_serving_max_inflight": "max concurrent synchronous predict() "
                                "calls per Predictor; excess requests "
                                "are refused with reason inflight_full "
                                "(0 = unbounded)",
    "tpu_serving_deadline_ms": "default per-request deadline: requests "
                               "whose EWMA-estimated queue wait already "
                               "exceeds it are shed at admission, and "
                               "requests that expire while queued fail "
                               "with DeadlineExceeded before any device "
                               "work; per-call deadline_ms= overrides "
                               "(0 = no deadline)",
    "tpu_serving_model_qps": "per-model token-bucket rate in "
                             "serving.ModelRegistry (tokens/s, burst = "
                             "one second's worth; 0 = unlimited): a hot "
                             "model sheds with reason rate_limited "
                             "instead of starving other residents",
    "tpu_serving_breaker_failures": "consecutive predict failures "
                                    "before a model's circuit breaker "
                                    "opens (overload rejections never "
                                    "count; 0 disables the breaker)",
    "tpu_serving_breaker_reset_s": "seconds an open breaker waits "
                                   "before half-opening for a single "
                                   "probe; failed probes re-open with "
                                   "exponential backoff",
    "tpu_compile_cache_dir": "persistent XLA compilation cache "
                             "directory: bucket-ladder and grower "
                             "programs persist to disk so restarted "
                             "trainers / cold serving replicas warm "
                             "from a file read instead of re-tracing. "
                             "The environment variable "
                             "JAX_COMPILATION_CACHE_DIR, when set, wins "
                             "over this parameter (empty = package "
                             "default, <checkout>/.jax_cache)",
    "tpu_predict_warmup_rows": "Predictor.warmup() compiles bucket "
                               "programs up to this many rows",
    "tpu_predict_micro_batch": "max concurrent single-row requests "
                               "Predictor.submit() coalesces into one "
                               "device dispatch (0 = no micro-batching)",
    "tpu_predict_micro_batch_window_ms": "how long submit() waits for "
                                         "co-arriving rows before "
                                         "dispatching the micro-batch",
    "tpu_export_dir": "directory to write a self-contained exported-"
                      "forest artifact (StableHLO via jax.export) after "
                      "training; serving replicas load it without the "
                      "training stack (empty = no export)",
    "tpu_export_layouts": "comma-separated quantized layouts packed "
                          "alongside f32 in the artifact (e.g. "
                          "\"f16,int8\"; \"none\" = f32 only)",
    "tpu_export_buckets": "number of power-of-two row buckets exported "
                          "per layout, starting at "
                          "tpu_predict_bucket_min",
    "use_missing": "handle NaN/missing specially (false = plain values)",
    "zero_as_missing": "treat zeros as missing (sparse semantics)",
    "sparse_threshold": "column sparsity above which EFB treats the "
                        "column as sparse when bundling",
    "init_score_file": "initial scores sidecar for the training data",
    "valid_init_score_file": "initial-score sidecars for valid sets",
    # tree
    "min_data_in_leaf": "minimum rows per leaf",
    "min_sum_hessian_in_leaf": "minimum hessian sum per leaf",
    "lambda_l1": "L1 regularization on leaf values",
    "lambda_l2": "L2 regularization on leaf values",
    "min_gain_to_split": "minimum gain to accept a split",
    "num_leaves": "max leaves per tree",
    "feature_fraction": "features sampled per tree",
    "feature_fraction_seed": "seed for the per-tree feature sample",
    "max_depth": "max tree depth (<=0 = unlimited)",
    "top_k": "features each shard submits in voting-parallel elections",
    "max_cat_threshold": "max categories grouped on one side of a "
                         "categorical split",
    "histogram_pool_size": "kept for API compat (the TPU grower keeps "
                           "its histogram cache on device)",
    "linear_tree": "piecewise-linear leaves: fit a ridge regression "
                   "per leaf over the features split on along the "
                   "leaf's root path, replacing the constant output "
                   "with intercept + coeff . x (requires raw feature "
                   "values; keep_raw is armed automatically)",
    "linear_lambda": "linear_tree: L2 on the fitted slopes (the "
                     "intercept is never penalized)",
    "tpu_linear_max_features": "linear_tree: per-leaf design width cap "
                               "— the first N distinct root-path split "
                               "features, nearest the leaf first (the "
                               "static [leaves, N] shape the linear "
                               "kernels compile against)",
    "gpu_platform_id": "kept for API compat (no OpenCL here)",
    "gpu_device_id": "kept for API compat",
    "gpu_use_dp": "kept for API compat",
    "tpu_hist_chunk": "rows per histogram contraction step",
    "tpu_hist_bf16": "bf16 hi+lo MXU histogram contraction",
    "tpu_compact_threshold": "row fraction below which a pass takes the "
                             "compacted path (also sizes the gather "
                             "buffer; >= 1.0 forces compaction, <= 0 "
                             "disables it). When unset it is chosen from "
                             "the shape's pass costs: the break-even of "
                             "a full pass against an index build plus "
                             "gathers, at most 0.25, and 0 (no "
                             "compaction) on narrow tables such as 28 "
                             "features x 63 bins; an explicit value is "
                             "used as given",
    "tpu_hist_reduce": "data-parallel histogram merge collective: "
                       "scatter (default) ReduceScatters the histogram "
                       "over the stored-group axis so each device owns "
                       "groups/num_devices of the result and finds "
                       "splits only on its owned features; allreduce "
                       "restores the full-psum schedule (every device "
                       "scores every feature). Trees are bit-identical "
                       "either way; voting keeps its elected-slice "
                       "exchange and ignores this",
    "tpu_hist_quantize": "quantized-gradient training: none (default) "
                         "= bit-exact f32 histogram path; int16/int8 = "
                         "per-iteration gradients/hessians scaled and "
                         "stochastically rounded to narrow integer "
                         "codes (deterministic per-(seed, iteration, "
                         "class) keys), histograms accumulated in the "
                         "exact int32 domain — scatter/allreduce/"
                         "sibling-subtraction merges stay bitwise "
                         "schedule-invariant — and dequantized once at "
                         "the split-scoring seam. int8 also widens the "
                         "leaf batch per pass (3 channels vs 5 in the "
                         "same 128-lane tile). Refused under "
                         "multi-process training",
    "tpu_hist_quantize_tol": "train-time accuracy gate for quantized "
                             "histograms: at setup one calibration "
                             "tree is grown with the quantized "
                             "pipeline and one with f32; the config "
                             "is refused with an error when the max "
                             "per-row leaf-value delta (relative to "
                             "the f32 tree's leaf-value scale) "
                             "exceeds this tolerance",
    # boosting
    "num_iterations": "boosting rounds",
    "learning_rate": "shrinkage applied to each tree",
    "bagging_fraction": "rows sampled per bagging refresh",
    "bagging_freq": "refresh the bag every N iterations (0 = off)",
    "bagging_seed": "seed for bagging",
    "early_stopping_round": "stop when no metric improves for N rounds",
    "drop_rate": "DART: fraction of trees dropped per iteration",
    "max_drop": "DART: max trees dropped per iteration",
    "skip_drop": "DART: probability of skipping the drop",
    "uniform_drop": "DART: drop trees uniformly instead of by weight",
    "xgboost_dart_mode": "DART: xgboost-style normalization",
    "drop_seed": "DART: seed for the drop choice",
    "top_rate": "GOSS: keep fraction of largest gradients",
    "other_rate": "GOSS: sample fraction of the rest",
    "tpu_guard_nonfinite": "raise a descriptive error (objective/metric "
                           "+ iteration) when gradients, hessians or "
                           "metric values go NaN/Inf instead of "
                           "silently growing garbage trees",
    # objective
    "is_unbalance": "binary: reweight classes to balance label mass",
    "sigmoid": "sigmoid scale for binary/xentropy objectives",
    "huber_delta": "huber loss delta",
    "fair_c": "fair loss c",
    "poisson_max_delta_step": "poisson: max delta step safeguard",
    "gaussian_eta": "regression hessian eta",
    "scale_pos_weight": "binary: weight multiplier on positives",
    "boost_from_average": "start scores from the label average",
    "label_gain": "lambdarank: gain per integer relevance label",
    "max_position": "lambdarank: NDCG truncation position",
    "num_class": "number of classes (multiclass objectives)",
    # metric
    "metric_types": "metrics to evaluate (comma list)",
    "metric_freq": "evaluate every N iterations",
    "output_freq": "CLI metric print frequency",
    "is_provide_training_metric": "also evaluate on the training data",
    "ndcg_eval_at": "NDCG/MAP truncation positions",
    # network
    "num_machines": "machine count for distributed training",
    "local_listen_port": "kept for API compat (jax.distributed wires "
                         "processes via the coordinator address)",
    "time_out": "kept for API compat",
    "machine_list_filename": "host list file (rank order)",
    "machines": "inline comma-separated host list",
    "tpu_collective_timeout_s": "deadline for every host-level "
                                "collective dispatch: on expiry the "
                                "rank dumps per-thread stacks + a "
                                "rank_failure event and exits rc 113 "
                                "instead of hanging on a dead peer "
                                "(0 = off; must exceed worst-case "
                                "compile time — the first dispatch of "
                                "a new shape compiles under the guard)",
    "tpu_heartbeat_dir": "per-rank liveness directory: "
                         "heartbeat_r<rank>.json on every dispatch/"
                         "iteration, rank_failure_r<rank>.json on "
                         "watchdog expiry — what an external "
                         "supervisor reads to tell which rank died "
                         "and why",
    "tpu_heartbeat_lease_s": "heartbeat lease: a supervisor declares a "
                             "rank dead when its heartbeat is older "
                             "than this (stamped into the heartbeat "
                             "file)",
}

def main():
    from lightgbm_tpu import config as C

    aliases_by_target = {}
    for alias, target in C.ALIAS_TABLE.items():
        aliases_by_target.setdefault(target, []).append(alias)

    sections = [
        ("Core", C.Config, ("task", "objective", "boosting_type",
                            "tree_learner", "seed")),
        ("IO / Dataset", C.IOConfig, None),
        ("Tree", C.TreeConfig, None),
        ("Boosting", C.BoostingConfig, None),
        ("Objective", C.ObjectiveConfig, None),
        ("Metric", C.MetricConfig, None),
        ("Network", C.NetworkConfig, None),
    ]

    out = ["# Parameters",
           "",
           "Generated by `python scripts/gen_params_doc.py` from the "
           "config whitelist (`lightgbm_tpu/config.py`) — unknown keys "
           "are fatal, exactly like the reference "
           "(`config.h:351-483`). Aliases follow the reference alias "
           "table.",
           ""]
    for title, cls, only in sections:
        out.append(f"## {title}")
        out.append("")
        out.append("| parameter | default | aliases | description |")
        out.append("|---|---|---|---|")
        if dataclasses.is_dataclass(cls):
            fields = dataclasses.fields(cls)
        else:
            fields = []
        for f in fields:
            if only is not None and f.name not in only:
                continue
            if f.name in ("io", "tree", "boosting", "objective_config",
                          "metric", "network", "raw_params"):
                continue
            if f.default is not dataclasses.MISSING:
                default = f.default
            elif f.default_factory is not dataclasses.MISSING:  # type: ignore
                default = f.default_factory()  # type: ignore
            else:
                default = ""
            al = ", ".join(sorted(aliases_by_target.get(f.name, [])))
            d = DESCRIPTIONS.get(f.name, "")
            out.append(f"| `{f.name}` | `{default}` | {al} | {d} |")
        out.append("")
    os.makedirs(os.path.join(REPO, "docs"), exist_ok=True)
    path = os.path.join(REPO, "docs", "Parameters.md")
    with open(path, "w") as fh:
        fh.write("\n".join(out) + "\n")
    print(path, f"({len(out)} lines)")


if __name__ == "__main__":
    main()
