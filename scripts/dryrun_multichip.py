"""Watchdog-wrapped multichip dryrun gate.

The 8-device gate used to die with a bare rc 124 and no artifact saying
where. This harness runs the same one-step
data-parallel dryrun (`__graft_entry__._dryrun_impl`) in a child process
with telemetry armed, and guarantees a diagnosis artifact either way:

- the child emits per-rank heartbeats (LGBM_TPU_HEARTBEAT_FILE — the
  grower dispatch seam in parallel/learners.py touches it on every
  call) and, on graceful termination, a partial telemetry snapshot
  (phase totals, counters, compile events);
- on timeout the parent SIGTERMs the child (giving its handler a grace
  window to dump the partial snapshot), then SIGKILLs, and writes
  `MULTICHIP_dryrun.json` carrying rc, per-rank last-seen heartbeat
  (iteration/phase/age), the partial snapshot, and the stderr tail —
  the "where did it die" evidence the next rc-124 needs;
- a C-level `faulthandler` handler rides the same SIGTERM (chained in
  FRONT of the Python handler): even a rank wedged inside an XLA
  compile/collective — where the Python-level handler can never run —
  leaves its per-thread Python stacks in the artifact (the stack dump
  says which frame each rank was blocked in).

Usage:
    python scripts/dryrun_multichip.py [n_devices] [--timeout SECONDS]
        [--out MULTICHIP_dryrun.json]
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# child: the dryrun body with telemetry + graceful partial-dump handler
# ---------------------------------------------------------------------------
def child_main(n_devices: int, evidence_dir: str) -> int:
    import faulthandler

    # run_watchdog launched this child with JAX_PLATFORMS=cpu and the
    # forced host-device count: a CPU dry run by construction
    sys.path.insert(0, REPO)
    from lightgbm_tpu import telemetry

    telemetry.enable(True)
    telemetry.install_observer()
    rank = int(os.environ.get("LGBM_TPU_RANK", "0") or 0)
    if not os.environ.get("LGBM_TPU_HEARTBEAT_FILE"):
        telemetry.set_heartbeat_file(
            os.path.join(evidence_dir, f"heartbeat_r{rank}.json"))


    def dump_partial(signum=None, frame=None):
        snap = {
            "rank": rank,
            "time": time.time(),
            "interrupted": signum is not None,
            "registry": telemetry.registry().snapshot(),
            "compile": telemetry.observer().snapshot(),
        }
        path = os.path.join(evidence_dir, f"partial_r{rank}.json")
        try:
            with open(path + ".tmp", "w") as fh:
                json.dump(snap, fh)
            os.replace(path + ".tmp", path)
        except OSError:
            pass
        if signum is not None:
            os._exit(124)

    signal.signal(signal.SIGTERM, dump_partial)
    # per-thread Python stacks on SIGTERM, written by faulthandler's
    # C-LEVEL handler so they land even when this rank is wedged inside
    # an XLA compile/collective where no Python bytecode (and hence no
    # Python signal handler) can run. Registered AFTER signal.signal —
    # faulthandler saves the handler installed at register time and
    # `chain=True` forwards into it, so the partial-telemetry JSON dump
    # still happens whenever Python is runnable.
    stacks_fh = open(os.path.join(evidence_dir, f"stacks_r{rank}.txt"),
                     "w")  # kept open: faulthandler dumps through the fd
    faulthandler.register(signal.SIGTERM, file=stacks_fh,
                          all_threads=True, chain=True)

    telemetry.heartbeat(0, phase="startup", rank=rank)
    import __graft_entry__ as g
    g._dryrun_impl(n_devices)
    telemetry.heartbeat(1, phase="done", rank=rank)
    dump_partial()
    return 0


# ---------------------------------------------------------------------------
# parent: watchdog + evidence collection
# ---------------------------------------------------------------------------
def collect_evidence(evidence_dir: str) -> dict:
    """Per-rank heartbeat + partial-telemetry files -> one dict."""
    now = time.time()
    ranks = {}
    for path in sorted(glob.glob(os.path.join(evidence_dir,
                                              "heartbeat_r*.json"))):
        try:
            with open(path) as fh:
                hb = json.load(fh)
        except (OSError, json.JSONDecodeError):
            continue
        ranks[str(hb.get("rank", "?"))] = {
            "last_iteration": hb.get("iteration"),
            "phase": hb.get("phase"),
            "age_seconds": round(now - float(hb.get("time", now)), 3),
        }
    stacks = {}
    for path in sorted(glob.glob(os.path.join(evidence_dir,
                                              "stacks_r*.txt"))):
        rank_id = os.path.basename(path)[len("stacks_r"):-len(".txt")]
        try:
            with open(path) as fh:
                text = fh.read().strip()
        except OSError:
            continue
        if text:
            # per-rank per-thread Python frames at SIGTERM time — the
            # "which frame was each rank blocked in" evidence; cap the
            # copy so a huge thread dump can't bloat the artifact
            stacks[rank_id] = text.splitlines()[-80:]
    partial = {}
    for path in sorted(glob.glob(os.path.join(evidence_dir,
                                              "partial_r*.json"))):
        try:
            with open(path) as fh:
                snap = json.load(fh)
        except (OSError, json.JSONDecodeError):
            continue
        phases = {p["name"]: round(p["seconds"], 4)
                  for p in snap.get("registry", {}).get("phases", [])}
        compile_info = snap.get("compile", {})
        partial[str(snap.get("rank", "?"))] = {
            "interrupted": snap.get("interrupted"),
            "phase_seconds": phases,
            "compiles": compile_info.get("total_compiles"),
            "compile_seconds": round(compile_info.get("total_seconds", 0.0),
                                     3),
            "grower_calls": next(
                (c["value"] for c in
                 snap.get("registry", {}).get("counters", [])
                 if c["name"] == "parallel/grower_calls"), 0),
        }
    return {"ranks": ranks, "partial_telemetry": partial,
            "sigterm_stacks": stacks}


def run_watchdog(n_devices: int, timeout: float, out_path: str) -> int:
    evidence_dir = tempfile.mkdtemp(prefix="dryrun_evidence_")
    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    env["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={n_devices}"
    ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    env["LGBM_TPU_HEARTBEAT_FILE"] = os.path.join(evidence_dir,
                                                  "heartbeat_r0.json")
    stderr_path = os.path.join(evidence_dir, "child.stderr")
    t0 = time.time()
    with open(stderr_path, "wb") as err_fh:
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--child",
             str(n_devices), evidence_dir],
            env=env, cwd=REPO, stderr=err_fh,
            stdout=subprocess.DEVNULL)
        timed_out = False
        try:
            rc = proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            timed_out = True
            # SIGTERM first: the child's handler dumps its partial
            # telemetry snapshot inside the grace window
            proc.terminate()
            try:
                rc = proc.wait(timeout=20)
            except subprocess.TimeoutExpired:
                proc.kill()
                rc = proc.wait()
            rc = 124

    evidence = collect_evidence(evidence_dir)
    try:
        with open(stderr_path, "rb") as fh:
            tail = fh.read()[-4096:].decode("utf-8", "replace")
        stderr_tail = tail.splitlines()[-12:]
    except OSError:
        stderr_tail = []
    # everything relevant is copied into the output JSON — don't leak a
    # dryrun_evidence_* directory per gate invocation
    import shutil
    shutil.rmtree(evidence_dir, ignore_errors=True)
    result = {
        "metric": "multichip_dryrun",
        "value": 1.0 if rc == 0 else 0.0,
        "unit": "ok",
        "rc": rc,
        "timed_out": timed_out,
        "n_devices": n_devices,
        "timeout_seconds": timeout,
        "wall_seconds": round(time.time() - t0, 2),
        "detail": dict(evidence, stderr_tail=stderr_tail),
    }
    with open(out_path, "w") as fh:
        json.dump(result, fh, indent=1)
    print(json.dumps({k: v for k, v in result.items() if k != "detail"}),
          flush=True)
    return rc


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("n_devices", nargs="?", type=int, default=8)
    ap.add_argument("--timeout", type=float,
                    default=float(os.environ.get("DRYRUN_TIMEOUT", 1800)))
    ap.add_argument("--out", default=os.path.join(REPO,
                                                  "MULTICHIP_dryrun.json"))
    ap.add_argument("--child", nargs=2, metavar=("N", "EVIDENCE_DIR"),
                    help=argparse.SUPPRESS)
    args, _ = ap.parse_known_args()
    if args.child:
        return child_main(int(args.child[0]), args.child[1])
    return run_watchdog(args.n_devices, args.timeout, args.out)


if __name__ == "__main__":
    sys.exit(main())
