"""Lint gate artifact: run graftlint over lightgbm_tpu/ + scripts/ and
write the machine-readable result (the elastic/overload smoke-gate
discipline).

The artifact records per-rule counts, every unsuppressed finding (zero
for a green gate), every suppression WITH its written reason, and stale
baseline entries (also zero for green — the baseline must shrink, not
rot). The tier-1 pytest
(tests/test_static_analysis.py) enforces the same zero-findings
contract on every run.

Usage: python scripts/lint_report.py [--out LINT_r01.json]
Exits 0 iff the gate is green; prints one JSON summary line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from lightgbm_tpu.analysis import run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(REPO, "LINT_r01.json"))
    ap.add_argument("--baseline",
                    default=os.path.join(REPO, "graftlint_baseline.json"))
    args = ap.parse_args()

    report = run([os.path.join(REPO, "lightgbm_tpu"),
                  os.path.join(REPO, "scripts")],
                 baseline_path=args.baseline)
    doc = report.as_dict()
    # the committed artifact must be machine-portable (the OVERLOAD/
    # ELASTIC discipline): repo-relative paths, no local layout
    doc["paths"] = [os.path.relpath(p, REPO).replace(os.sep, "/")
                    for p in doc["paths"]]
    if doc["baseline"]["path"]:
        doc["baseline"]["path"] = os.path.relpath(
            doc["baseline"]["path"], REPO).replace(os.sep, "/")
    doc["gate"] = {
        "green": report.exit_code == 0 and not report.stale_baseline,
        "unsuppressed_findings": len(report.findings),
        "suppressions": len(report.suppressions),
        "stale_baseline_entries": len(report.stale_baseline),
    }
    with open(args.out, "w") as fh:
        json.dump(doc, fh, indent=2, sort_keys=True)
        fh.write("\n")
    print(json.dumps({"phase": "lint", "ok": doc["gate"]["green"],
                      "files_scanned": report.files_scanned,
                      "findings": len(report.findings),
                      "suppressed": len(report.suppressions),
                      "stale_baseline": len(report.stale_baseline),
                      "out": args.out}), flush=True)
    for f in report.findings:
        print(f.render(), file=sys.stderr)
    return 0 if doc["gate"]["green"] else 1


if __name__ == "__main__":
    sys.exit(main())
