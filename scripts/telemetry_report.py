"""Pretty-print a telemetry run log (lightgbm_tpu/telemetry/runlog.py).

Usage:
    python scripts/telemetry_report.py <tpu_telemetry_dir | runlog.jsonl>
        [--json]

Renders every run recorded in the JSONL trail: header (topology,
schedule, versions), a per-iteration table (metrics, phase seconds,
compile activity, pass economics), events, and the summary totals.
`--json` emits one machine-readable digest instead (the shape the
MULTICHIP/BENCH artifacts use).
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from lightgbm_tpu.telemetry import read_records, validate_record  # noqa: E402


def _fmt_seconds(s: float) -> str:
    return f"{s * 1e3:.1f}ms" if s < 1.0 else f"{s:.2f}s"


def digest(records):
    """Machine-readable roll-up of one run-log file."""
    runs = []
    cur = None
    for rec in records:
        validate_record(rec)
        if rec["type"] == "header":
            cur = {"header": rec, "iterations": [], "events": [],
                   "summary": None}
            runs.append(cur)
            continue
        if cur is None:  # tolerate trails beginning mid-run
            cur = {"header": None, "iterations": [], "events": [],
                   "summary": None}
            runs.append(cur)
        if rec["type"] == "iteration":
            cur["iterations"].append(rec)
        elif rec["type"] == "event":
            cur["events"].append(rec)
        elif rec["type"] == "summary":
            cur["summary"] = rec
    out = []
    for run in runs:
        hdr = run["header"] or {}
        iters = run["iterations"]
        compile_s = sum(r["compile"].get("seconds", 0.0) for r in iters)
        compiles = sum(r["compile"].get("compiles", 0) for r in iters)
        retraces = sum(r["compile"].get("retraces", 0) for r in iters)
        phase_tot = {}
        for r in iters:
            for name, p in r["phases"].items():
                phase_tot[name] = phase_tot.get(name, 0.0) + p["seconds"]
        rows_contracted = sum(r.get("pass", {}).get("rows_contracted", 0.0)
                              for r in iters)
        out.append({
            "run_id": hdr.get("run_id"),
            "rank": hdr.get("rank"),
            "platform": (hdr.get("devices") or {}).get("platform"),
            "device_kind": (hdr.get("devices") or {}).get("device_kind"),
            "num_devices": (hdr.get("devices") or {}).get("num_devices"),
            "boosting": hdr.get("boosting"),
            "start_iteration": hdr.get("start_iteration"),
            "iterations": len(iters),
            "last_iteration": iters[-1]["iteration"] if iters else None,
            "compiles": compiles, "compile_seconds": round(compile_s, 3),
            "retraces": retraces,
            "phase_seconds": {k: round(v, 4)
                              for k, v in sorted(phase_tot.items())},
            "rows_contracted": rows_contracted,
            "events": [{"kind": e["kind"],
                        "iteration": e.get("iteration")}
                       for e in run["events"]],
            "final_metrics": iters[-1]["metrics"] if iters else {},
            "status": (run["summary"] or {}).get("status"),
            "wall_seconds": (run["summary"] or {}).get("wall_seconds"),
        })
    return out


def render(records) -> str:
    lines = []
    for run in digest(records):
        lines.append("=" * 72)
        lines.append(f"run {run['run_id']}  rank={run['rank']}  "
                     f"platform={run['platform']} "
                     f"x{run['num_devices']}  boosting={run['boosting']}")
        lines.append(f"  iterations: {run['iterations']} "
                     f"(start {run['start_iteration']}, "
                     f"last {run['last_iteration']})  "
                     f"status={run['status']}  "
                     f"wall={run['wall_seconds']}s")
        lines.append(f"  compiles: {run['compiles']} "
                     f"({_fmt_seconds(run['compile_seconds'])}, "
                     f"{run['retraces']} retraces)")
        if run["phase_seconds"]:
            lines.append("  phases:")
            for name, secs in sorted(run["phase_seconds"].items(),
                                     key=lambda kv: -kv[1]):
                lines.append(f"    {name:<28} {_fmt_seconds(secs):>10}")
        if run["rows_contracted"]:
            lines.append(f"  rows contracted: {run['rows_contracted']:.0f}")
        for e in run["events"]:
            lines.append(f"  event: {e['kind']} @ iter {e['iteration']}")
        if run["final_metrics"]:
            lines.append("  final metrics: " + "  ".join(
                f"{k}={v:g}" for k, v in run["final_metrics"].items()))
    return "\n".join(lines)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("target", help="tpu_telemetry_dir or a runlog .jsonl")
    ap.add_argument("--json", action="store_true",
                    help="emit a machine-readable digest")
    args = ap.parse_args()

    if os.path.isdir(args.target):
        paths = sorted(glob.glob(os.path.join(args.target,
                                              "runlog_r*.jsonl")))
    else:
        paths = [args.target]
    if not paths:
        print(f"no runlog_r*.jsonl under {args.target}", file=sys.stderr)
        return 2

    ok = True
    for path in paths:
        try:
            records = read_records(path)
            for rec in records:
                validate_record(rec)
        except (OSError, ValueError) as exc:
            print(f"{path}: {exc}", file=sys.stderr)
            ok = False
            continue
        if args.json:
            print(json.dumps({"file": path, "runs": digest(records)}))
        else:
            print(f"--- {path} ({len(records)} records)")
            print(render(records))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
