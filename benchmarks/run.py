"""The benchmark's entry point. One run of one cell:

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell, configuration, traffic mix, mode or
per-layer metric is a file found by its name (see README.md); this file
holds none of it. The last line of standard output is one JSON object:
`correct`, `attempted`, `failed`, `metrics`, `device`, with `--trace 1`
`breakdown`, and last `compared` (each number compared beside its
limit). Any platform but a TPU, or fewer chips than the cell asks for,
exits non-zero with no result line; `--rehearse-rows N` (never passed by
the driver) shrinks the data for a rehearsal on the CPU and says so.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def startup_seconds() -> float:
    """Seconds this process had lived before this file ran (interpreter
    start), from /proc; 0 where that cannot be read."""
    try:
        with open("/proc/self/stat") as fh:
            ticks = float(fh.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as fh:
            uptime = float(fh.read().split()[0])
        lived = uptime - ticks / os.sysconf("SC_CLK_TCK")
        return max(0.0, lived - (time.perf_counter() - T_START))
    except (OSError, ValueError, IndexError):
        return 0.0


def load_json(*parts):
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


def named(entries, name, what):
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise SystemExit(f"run.py: no {what} {name!r} in BENCHMARK.json "
                     f"(has {[e['name'] for e in entries]})")


def load_cell(workload: str) -> dict:
    """The cell's entry of BENCHMARK.json with the files it names."""
    bench = load_json(ROOT, "BENCHMARK.json")
    entry = named(bench["workloads"], workload, "workload")
    cfg_entry = named(bench["configs"], entry["config"], "config")
    return {
        "bench": bench, "entry": entry,
        "cell": load_json(HERE, "workloads", workload + ".json"),
        "config": load_json(ROOT, cfg_entry["file"]),
        "traffic": load_json(HERE, "traffic", entry["traffic"] + ".json"),
    }


def load_mode(traffic: dict):
    """The driver of this kind of traffic: modes/<mode>.py."""
    import datagen
    return datagen.load_file_module(
        os.path.join(HERE, "modes", traffic["mode"] + ".py"),
        "benchmarks_mode_" + traffic["mode"])


def keep_every_program(jax) -> None:
    """Every program, however small, goes to the persistent cache (jax's
    default keeps those under 1 s out, and every process would compile
    them again). WHERE the cache lives is the program's own rule:
    $JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_info(jax) -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-rows", type=int, default=0,
                    help="CPU rehearsal at this many rows; not a benchmark run")
    args = ap.parse_args(argv)
    startup_s = startup_seconds()

    loaded = load_cell(args.workload)
    entry, cell, config, traffic = (loaded["entry"], loaded["cell"],
                                    loaded["config"], loaded["traffic"])
    sys.path.insert(0, ROOT)
    sys.path.insert(0, HERE)
    try:
        import lightgbm_tpu  # noqa: F401  the system under test
    except ImportError as exc:
        print(f"run.py: the system under test is not here: {exc}",
              file=sys.stderr)
        return 3
    import jax
    keep_every_program(jax)

    device = device_info(jax)
    rehearsal = args.rehearse_rows > 0
    if not rehearsal and (device["platform"] != "tpu"
                          or device["count"] < int(entry["chips"])):
        print(f"run.py: cell {args.workload} needs {entry['chips']} TPU "
              f"chip(s), jax found {device}; nothing was measured",
              file=sys.stderr)
        return 2
    if rehearsal:
        print(json.dumps({"rehearsal": True, "rows": args.rehearse_rows,
                          "device": device}), flush=True)

    import datagen
    out = load_mode(traffic).run({
        "cell": cell, "config": config, "traffic": traffic,
        "seed": args.seed, "seconds": args.seconds, "trace": bool(args.trace),
        "rows": args.rehearse_rows or int(config["rows"]),
        "t_start": T_START, "startup_s": startup_s,
        "limits": cell["limits"], "rehearsal": rehearsal,
    })

    bench = loaded["bench"]
    metrics = {}
    if args.trace:
        if not rehearsal:
            import work
            out["peaks"] = work.load_peaks(device["kind"])
        for m in bench["per_layer"]:
            reader = datagen.load_file_module(
                os.path.join(HERE, "layer_metrics", m["name"] + ".py"),
                "benchmarks_metric_" + m["name"].replace(".", "_"))
            value = reader.read(out)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in bench["end_to_end"]:
            if m["name"] in out:
                metrics[m["name"]] = {"value": out[m["name"]],
                                      "unit": m["unit"]}

    device["memory_peak_bytes"] = out["memory_peak_bytes"]
    device["memory_limit_bytes"] = out["memory_limit_bytes"]
    result = {"correct": bool(out["correct"]),
              "attempted": out["iterations"], "failed": out["stopped"],
              "metrics": metrics, "device": device}
    trace = out.get("trace")
    trace_detail = None
    if trace:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
        trace_detail = {"lines": trace["lines"], "events": trace["events"],
                        "traced_host_s": out.get("traced_host_s")}
    result["detail"] = {
        "workload": args.workload, "seed": args.seed,
        "window_s": out["window_s"], "iterations": out["iterations"],
        "update_returned_s": out.get("update_returned_s"),
        "phases": out["phases"], "construct_s": out["construct_s"],
        "compile_setup": out["compile_setup"], "schedule": out["schedule"],
        "startup_s": startup_s, "trace": trace_detail,
        "passes_per_tree": [e[0] for e in out.get("pass_log_window", [])],
        "steps": out["steps"],
    }
    result["compared"] = out["compared"]
    for name, row in out["compared"].items():
        print(f"compared {name} {row['value']!r} limit {row['limit']!r}",
              file=sys.stderr)
    print(f"correct {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
