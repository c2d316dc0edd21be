"""Storage chaos gate: training completes byte-identically under
injected disk faults, and every degradation is visible in telemetry.

The durable-IO story (ISSUE 18) in one headless smoke: a supervisor
runs the same training invocation twice — once fault-free (the
reference) and once with `LGBM_TPU_FAULT_PLAN` injecting the storage
shapes through `lightgbm_tpu/durable.py`'s in-layer sites:

- transient EIO on checkpoint publishes (absorbed by the retry
  policy, here raised via the `tpu_io_retries`/`tpu_io_backoff_s`
  params — which are fingerprint-EXCLUDED, so the chaos run's model
  must still be byte-identical to the reference's);
- a torn checkpoint write (half the payload reaches the tmp file, the
  publish dies pre-rename — atomicity must make it invisible);
- sustained slow-IO on the checkpoint rename (storage brown-out);
- EIO on run-log appends and heartbeat leases — best-effort streams
  that must DEGRADE (drop + count), never raise into training.

Acceptance: the chaos child exits 0, its `model_to_string` matches the
reference byte-for-byte, and its degradation report (durable.dropped()
+ the fault plan's fired audit) shows every injected fault was hit and
counted. A third stage trains under ENOSPC on checkpoint publishes
(absorbed by the retry budget, byte-identical again); a fourth proves
the ENOSPC escape hatch end-to-end in a child: with zero retries and a
full "disk", the checkpoint manager evicts its oldest snapshot (never
the newest) and the save lands.

Writes a machine-readable artifact (CHAOS_r01.json).

Usage:
    python scripts/storage_chaos_smoke.py [--rounds 8]
        [--out CHAOS_r01.json] [--timeout 240]

CPU gate: this script and every child it starts run on the CPU platform
(JAX_PLATFORMS=cpu); it checks behaviour, not device speed, and needs no
chip — no parent here holds a chip that a child then needs.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CHILD = r"""
import json, os, sys
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, {repo!r})
import lightgbm_tpu as lgb
from lightgbm_tpu import durable
from lightgbm_tpu.testing import faults

spec = json.loads(os.environ["CHAOS_CHILD_SPEC"])
raw = np.load(spec["data"])
X, y = raw[:, 1:], raw[:, 0]
ds = lgb.Dataset(X, y)
booster = lgb.train(spec["params"], ds, num_boost_round=spec["rounds"],
                    verbose_eval=False)
with open(spec["out"], "w") as fh:
    fh.write(booster.model_to_string())
plan = faults._plan
print("CHAOS_REPORT " + json.dumps({{
    "dropped": durable.dropped(),
    "policy": durable.policy(),
    "fired": list(plan.fired) if plan is not None else [],
}}), flush=True)
"""

HATCH_CHILD = r"""
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, {repo!r})
from lightgbm_tpu import durable
from lightgbm_tpu.checkpoint import CheckpointManager
from lightgbm_tpu.testing import faults

directory = sys.argv[1]
mgr = CheckpointManager(directory, keep_last=5, rank=0)
mgr.save({{"iteration": 1}}, 1)
mgr.save({{"iteration": 2}}, 2)
durable.configure(retries=0, backoff_s=0.0)
faults.enospc(1, site="checkpoint.write")
mgr.save({{"iteration": 3}}, 3)   # hatch: evict iter 1, retry, land
assert mgr.available_iterations() == [2, 3], mgr.available_iterations()
payload, _ = mgr.load_latest()
assert payload["iteration"] == 3, payload
print("HATCH_REPORT " + json.dumps({{
    "fired": list(faults._plan.fired),
    "kept": mgr.available_iterations(),
}}), flush=True)
"""


def _run_child(code: str, spec: dict, timeout: float, fault_plan=None,
               argv=()):
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["CHAOS_CHILD_SPEC"] = json.dumps(spec or {})
    env.pop("LGBM_TPU_FAULT_PLAN", None)
    if fault_plan:
        env["LGBM_TPU_FAULT_PLAN"] = json.dumps(fault_plan)
    t0 = time.time()
    try:
        proc = subprocess.run(
            [sys.executable, "-c", code.format(repo=REPO)] + list(argv),
            env=env, cwd=REPO, capture_output=True, text=True,
            timeout=timeout)
        rc, out = proc.returncode, proc.stdout + proc.stderr
    except subprocess.TimeoutExpired as exc:
        rc, out = 124, "timeout: " + str(exc)
    return rc, round(time.time() - t0, 2), out


def _report(out: str, tag: str):
    for line in out.splitlines():
        if line.startswith(tag + " "):
            return json.loads(line[len(tag) + 1:])
    return None


def run(args) -> dict:
    workdir = tempfile.mkdtemp(prefix="storage_chaos_")

    import numpy as np
    rng = np.random.RandomState(0)
    n, f = 600, 6
    X = rng.randn(n, f)
    y = (X[:, 0] + X[:, 1] * X[:, 2] + 0.3 * rng.randn(n) > 0).astype(
        np.float64)
    data_path = os.path.join(workdir, "data.npy")
    np.save(data_path, np.column_stack([y, X]))

    def params(tag):
        return {
            "objective": "binary", "verbose": -1, "num_leaves": 7,
            "bagging_fraction": 0.7, "bagging_freq": 1, "seed": 11,
            "tpu_checkpoint_dir": os.path.join(workdir, tag, "ckpts"),
            "tpu_checkpoint_interval": 1, "tpu_checkpoint_keep": 50,
            "tpu_telemetry_dir": os.path.join(workdir, tag, "telemetry"),
            "tpu_heartbeat_dir": os.path.join(workdir, tag, "heartbeats"),
            "tpu_heartbeat_lease_s": 5.0,
        }

    stages = []
    result = {"metric": "storage_chaos", "unit": "ok",
              "rounds": args.rounds, "stages": stages}

    def fail(msg):
        result["value"] = 0.0
        result["error"] = msg
        return result

    # stage 1: fault-free reference
    ref_spec = {"data": data_path, "params": params("ref"),
                "rounds": args.rounds,
                "out": os.path.join(workdir, "m_ref.txt")}
    rc, wall, out = _run_child(CHILD, ref_spec, args.timeout)
    stages.append({"stage": "reference", "rc": rc, "wall_seconds": wall})
    if rc != 0:
        return fail("reference run failed: " + out[-1500:])

    # stage 2: the chaos run. tpu_io_retries/tpu_io_backoff_s are raised
    # so the stacked first-publish gauntlet (EIO, EIO, torn, slow
    # rename) fits one write's budget — and being fingerprint-EXCLUDED,
    # the different IO policy must NOT change the model.
    chaos_params = dict(params("chaos"),
                        tpu_io_retries=3, tpu_io_backoff_s=0.01)
    chaos_plan = {
        "io_fail": {"checkpoint.write": ["EIO", 2],
                    "runlog.write": ["EIO", 2],
                    "watchdog.heartbeat.write": ["EIO", 3]},
        "torn": {"checkpoint": 1},
        "slow": {"checkpoint.rename": 0.05},
    }
    chaos_spec = {"data": data_path, "params": chaos_params,
                  "rounds": args.rounds,
                  "out": os.path.join(workdir, "m_chaos.txt")}
    rc, wall, out = _run_child(CHILD, chaos_spec, args.timeout,
                               fault_plan=chaos_plan)
    report = _report(out, "CHAOS_REPORT")
    stages.append({"stage": "chaos", "rc": rc, "wall_seconds": wall,
                   "report": report})
    if rc != 0:
        return fail("chaos run did not complete (best-effort fault "
                    "leaked or critical retry exhausted): " + out[-1500:])
    if report is None:
        return fail("chaos child produced no degradation report")
    result["degradations"] = report

    # every injected fault must have actually fired ...
    fired = report["fired"]
    for want in ("eio@checkpoint.write", "torn@checkpoint",
                 "slow@checkpoint.rename", "eio@runlog.write",
                 "eio@watchdog.heartbeat.write"):
        if want not in fired:
            return fail(f"injected fault never fired: {want} "
                        f"(fired: {fired})")
    # ... and every best-effort drop must be COUNTED, not silent
    dropped = report["dropped"]
    if dropped.get("telemetry.runlog") != 2:
        return fail(f"runlog drops miscounted: {dropped}")
    if dropped.get("watchdog.heartbeat") != 3:
        return fail(f"heartbeat drops miscounted: {dropped}")

    # stage 3: training under ENOSPC — the full-disk blips are absorbed
    # by the retry budget (the eviction hatch correctly declines while
    # there is no older snapshot to free) and the model still matches
    enospc_spec = {"data": data_path, "params": params("enospc"),
                   "rounds": args.rounds,
                   "out": os.path.join(workdir, "m_enospc.txt")}
    rc, wall, out = _run_child(
        CHILD, enospc_spec, args.timeout,
        fault_plan={"io_fail": {"checkpoint.write": ["ENOSPC", 2]}})
    report = _report(out, "CHAOS_REPORT")
    stages.append({"stage": "chaos_enospc", "rc": rc,
                   "wall_seconds": wall, "report": report})
    if rc != 0:
        return fail("training under ENOSPC did not complete: "
                    + out[-1500:])
    if report is None or "enospc@checkpoint.write" not in report["fired"]:
        return fail(f"ENOSPC never fired in training: {report}")

    # stage 4: ENOSPC escape hatch end-to-end in a child
    hatch_dir = os.path.join(workdir, "hatch_ckpts")
    rc, wall, out = _run_child(HATCH_CHILD, None, args.timeout,
                               argv=[hatch_dir])
    hatch = _report(out, "HATCH_REPORT")
    stages.append({"stage": "enospc_hatch", "rc": rc,
                   "wall_seconds": wall, "report": hatch})
    if rc != 0 or hatch is None:
        return fail("ENOSPC hatch stage failed: " + out[-1500:])
    if "enospc@checkpoint.write" not in hatch["fired"]:
        return fail(f"ENOSPC never fired in hatch stage: {hatch}")
    result["enospc_hatch"] = hatch

    # the verdict: same bytes, with and without the disk misbehaving
    ref = open(os.path.join(workdir, "m_ref.txt")).read()
    chaos = open(os.path.join(workdir, "m_chaos.txt")).read()
    enospc = open(os.path.join(workdir, "m_enospc.txt")).read()
    result["byte_identical"] = chaos == ref and enospc == ref
    result["value"] = 1.0 if result["byte_identical"] else 0.0
    if not result["byte_identical"]:
        result["error"] = ("chaos-run model differs from the fault-free "
                           "reference (eio/torn/slow: %s, enospc: %s)"
                           % (chaos == ref, enospc == ref))
    shutil.rmtree(workdir, ignore_errors=True)
    return result


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--rounds", type=int, default=8)
    ap.add_argument("--timeout", type=float,
                    default=float(os.environ.get("CHAOS_TIMEOUT", 240)))
    ap.add_argument("--out", default=os.path.join(REPO, "CHAOS_r01.json"))
    args = ap.parse_args()
    t0 = time.time()
    result = run(args)
    result["wall_seconds"] = round(time.time() - t0, 2)
    with open(args.out, "w") as fh:
        json.dump(result, fh, indent=1)
    print(json.dumps({k: v for k, v in result.items()
                      if k != "stages"}), flush=True)
    return 0 if result.get("value") == 1.0 else 1


if __name__ == "__main__":
    sys.exit(main())
