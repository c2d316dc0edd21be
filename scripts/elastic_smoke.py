"""Elastic train supervisor: kill -> detect -> shrink -> resume, end to end.

The preemptible-pod training story (ISSUE 11) in one headless gate:
a supervisor launches a training cohort at world size W, injects real
failures (a preempted rank, a wedged collective), watches the per-rank
heartbeat lease to tell WHICH rank died and why, and relaunches the
surviving cohort at a SHRUNKEN world size from the last checkpoint —
with bounded retry/backoff — until training completes. The final model
must be byte-identical to an uninterrupted reference run.

Two modes:

- `devices` (default; runs everywhere): world size = forced host device
  count inside one process per stage
  (`--xla_force_host_platform_device_count`, the multichip-gate
  pattern). The cycle is kill@W=4 -> wedge@4 (collective watchdog must
  exit RC_RANK_FAILURE, not hang) -> elastic resume @W'=2 -> kill ->
  elastic resume @W'=1 -> finish; final model compared byte-for-byte
  against an uninterrupted 1-device reference. PR 9's cross-device-count
  bit-identity is what makes the comparison exact.
- `processes`: a real multi-rank cohort under jax.distributed (2 ranks
  x 1 CPU device), `faults.kill_rank` killing rank 1 mid-run, rank 0's
  collective watchdog detecting the dead peer, then a single-process
  relaunch elastically re-sharding BOTH rank series
  (`checkpoint.elastic_local_state`) into one. Gated on the same
  capability probe as tests/test_multihost.py — jax CPU builds without
  multi-process collectives report `mode_unavailable` instead of
  failing. The gate is detection + successful elastic resume; bitwise
  equality against the uninterrupted original-world-size cohort is
  recorded but informational (cross-process row assembly permutes the
  f32 summation order, so it is not an invariant — devices mode
  carries the byte-identity acceptance).

Writes a machine-readable artifact (ELASTIC_r01.json): stages run,
ranks killed, detection latency, watchdog rc, resume outcomes,
byte-identity verdict.

Usage:
    python scripts/elastic_smoke.py [--rounds 12] [--mode devices]
        [--out ELASTIC_r01.json] [--timeout 240] [--max-retries 2]

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

# rc contract: 77 = the injected preemption fired (expected death);
# 113 = watchdog.RC_RANK_FAILURE (detected wedge/dead peer); 0 = done
RC_PREEMPTED = 77
RC_RANK_FAILURE = 113

CHILD = r"""
import json, os, sys
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, {repo!r})
import lightgbm_tpu as lgb
from lightgbm_tpu.testing import faults

spec = json.loads(os.environ["ELASTIC_CHILD_SPEC"])
raw = np.load(spec["data"])
X, y = raw[:, 1:], raw[:, 0]
ds = lgb.Dataset(X, y)
try:
    booster = lgb.train(spec["params"], ds,
                        num_boost_round=spec["rounds"],
                        verbose_eval=False)
except faults.SimulatedPreemption as exc:
    print("CHILD_PREEMPTED", exc.iteration, flush=True)
    sys.exit({rc_preempted})
with open(spec["out"], "w") as fh:
    fh.write(booster.model_to_string())
print("CHILD_OK", flush=True)
"""


def _run_child(ndev: int, spec: dict, timeout: float,
               fault_plan: dict = None, extra_env: dict = None):
    """One training attempt at `ndev` forced host devices. Returns
    (rc, wall_seconds, output_tail)."""
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count={ndev}"
                        ).strip()
    env["ELASTIC_CHILD_SPEC"] = json.dumps(spec)
    env.pop("LGBM_TPU_FAULT_PLAN", None)
    if fault_plan:
        env["LGBM_TPU_FAULT_PLAN"] = json.dumps(fault_plan)
    env.update(extra_env or {})
    t0 = time.time()
    try:
        proc = subprocess.run(
            [sys.executable, "-c",
             CHILD.format(repo=REPO, rc_preempted=RC_PREEMPTED)],
            env=env, cwd=REPO, capture_output=True, text=True,
            timeout=timeout)
        rc, out = proc.returncode, proc.stdout + proc.stderr
    except subprocess.TimeoutExpired as exc:
        rc, out = 124, "timeout: " + str(exc)
    return rc, round(time.time() - t0, 2), out[-2000:]


def _heartbeat_ages(hb_dir: str):
    sys.path.insert(0, REPO)
    from lightgbm_tpu.parallel import watchdog
    return watchdog.read_cohort(hb_dir, lease_s=5.0)


def run_devices_mode(args) -> dict:
    workdir = tempfile.mkdtemp(prefix="elastic_smoke_")
    ckpt_dir = os.path.join(workdir, "ckpts")
    hb_dir = os.path.join(workdir, "heartbeats")
    rounds = args.rounds
    rng_seed = 0

    import numpy as np
    rng = np.random.RandomState(rng_seed)
    n, f = 600, 6
    X = rng.randn(n, f)
    y = (X[:, 0] + X[:, 1] * X[:, 2] + 0.3 * rng.randn(n) > 0).astype(
        np.float64)
    data_path = os.path.join(workdir, "data.npy")
    np.save(data_path, np.column_stack([y, X]))

    base_params = {
        "objective": "binary", "verbose": -1, "num_leaves": 7,
        "tree_learner": "data", "tpu_hist_chunk": 64,
        "bagging_fraction": 0.7, "bagging_freq": 1, "seed": 11,
    }
    ckpt_params = dict(base_params,
                       tpu_checkpoint_dir=ckpt_dir,
                       tpu_checkpoint_interval=1,
                       tpu_checkpoint_keep=50,
                       tpu_heartbeat_dir=hb_dir,
                       tpu_heartbeat_lease_s=5.0)

    def spec(params, out_name):
        return {"data": data_path, "params": params, "rounds": rounds,
                "out": os.path.join(workdir, out_name)}

    stages = []
    result = {"metric": "elastic_smoke", "unit": "ok", "mode": "devices",
              "rounds": rounds, "world_sizes": [4, 4, 2, 1],
              "ranks_killed": [], "stages": stages}

    def run_stage(name, ndev, fault_plan, params, out_name, expect_rcs,
                  retries):
        """Launch (with bounded retry/backoff) until the child exits
        with one of the EXPECTED rcs; anything else is retried, then
        recorded as a failure."""
        last = None
        for attempt in range(retries + 1):
            if attempt:
                time.sleep(args.backoff * attempt)
            rc, wall, out = _run_child(ndev, spec(params, out_name),
                                       args.timeout,
                                       fault_plan=fault_plan)
            last = {"stage": name, "n_devices": ndev, "rc": rc,
                    "wall_seconds": wall, "attempt": attempt + 1}
            if rc in expect_rcs:
                break
            last["unexpected_output_tail"] = out.splitlines()[-6:]
        stages.append(last)
        return last

    # stage 1: cohort at W=4, rank preempted at iteration 5
    st = run_stage("kill_at_w4", 4, {"kill_at_iteration": 5},
                   ckpt_params, "m_w4.txt", {RC_PREEMPTED},
                   args.max_retries)
    if st["rc"] != RC_PREEMPTED:
        result["value"] = 0.0
        result["error"] = "stage kill_at_w4 did not preempt"
        return result
    result["ranks_killed"].append({"stage": "kill_at_w4", "rank": 0,
                                   "iteration": 5})
    cohort = _heartbeat_ages(hb_dir)
    st["cohort_after"] = {str(r): i["status"] for r, i in cohort.items()}

    # stage 2: wedge the next grower dispatch; the collective watchdog
    # must convert the hang into RC_RANK_FAILURE within timeout + grace
    wedge_params = dict(ckpt_params, tpu_collective_timeout_s=3.0)
    t_wedge = time.time()
    st = run_stage("wedge_at_w4", 4,
                   {"wedge": {"collective.call": 120}},
                   wedge_params, "m_wedge.txt", {RC_RANK_FAILURE},
                   args.max_retries)
    if st["rc"] != RC_RANK_FAILURE:
        result["value"] = 0.0
        result["error"] = ("wedged collective did not exit with "
                           f"RC_RANK_FAILURE ({st})")
        return result
    # detection latency: watchdog expiry stamp minus the rank's LAST
    # heartbeat (the supervisor-visible "how long was the rank silently
    # stuck before it was declared dead"); falls back to stage launch
    # when no heartbeat landed
    detect = None
    fail_path = os.path.join(hb_dir, "rank_failure_r0.json")
    if os.path.exists(fail_path):
        with open(fail_path) as fh:
            rec = json.load(fh)
        st["failure_site"] = rec.get("site")
        since = t_wedge
        hb_path = os.path.join(hb_dir, "heartbeat_r0.json")
        if os.path.exists(hb_path):
            try:
                with open(hb_path) as fh:
                    since = max(since, float(json.load(fh)["time"]))
            except (OSError, ValueError, KeyError):
                pass
        detect = round(rec["time"] - since, 2)
    result["detection_latency_s"] = detect
    result["watchdog_rc"] = RC_RANK_FAILURE
    result["ranks_killed"].append({"stage": "wedge_at_w4", "rank": 0,
                                   "site": st.get("failure_site")})
    for p in (fail_path, fail_path.replace(".json", ".stacks.txt")):
        if os.path.exists(p):
            os.unlink(p)  # consumed; later stages must not re-see it

    # stage 3: elastic resume at W'=2, preempted again at iteration 9
    st = run_stage("kill_at_w2", 2, {"kill_at_iteration": 9},
                   ckpt_params, "m_w2.txt", {RC_PREEMPTED},
                   args.max_retries)
    if st["rc"] != RC_PREEMPTED:
        result["value"] = 0.0
        result["error"] = "stage kill_at_w2 did not preempt"
        return result
    result["ranks_killed"].append({"stage": "kill_at_w2", "rank": 0,
                                   "iteration": 9})

    # stage 4: elastic resume at W'=1, run to completion
    st = run_stage("finish_at_w1", 1, None, ckpt_params, "m_final.txt",
                   {0}, args.max_retries)
    if st["rc"] != 0:
        result["value"] = 0.0
        result["error"] = f"final resume failed ({st})"
        return result

    # reference: uninterrupted 1-device run of the same invocation
    st = run_stage("serial_reference", 1, None, base_params, "m_ref.txt",
                   {0}, args.max_retries)
    if st["rc"] != 0:
        result["value"] = 0.0
        result["error"] = "serial reference run failed"
        return result

    final = open(os.path.join(workdir, "m_final.txt")).read()
    ref = open(os.path.join(workdir, "m_ref.txt")).read()
    result["byte_identical"] = final == ref
    result["resume_outcome"] = "completed"
    result["value"] = 1.0 if result["byte_identical"] else 0.0
    if not result["byte_identical"]:
        result["error"] = ("elastically-resumed model differs from the "
                           "uninterrupted serial reference")
    shutil.rmtree(workdir, ignore_errors=True)
    return result


# ---------------------------------------------------------------------------
# processes mode: a real multi-rank cohort (gated on backend capability)
# ---------------------------------------------------------------------------
def _free_port() -> int:
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


PROC_CHILD = r"""
import json, os, sys
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, {repo!r})
import lightgbm_tpu as lgb
from lightgbm_tpu.basic import Dataset
from lightgbm_tpu.parallel.multihost import init_distributed
from lightgbm_tpu.parallel.loader import two_round_load
from lightgbm_tpu.testing import faults

spec = json.loads(os.environ["ELASTIC_CHILD_SPEC"])
nproc = spec["nproc"]
if nproc > 1:
    assert init_distributed()
    rank = jax.process_index()
else:
    rank = 0
inner = two_round_load(spec["data"], max_bin=31, rank=rank,
                       num_machines=nproc, enable_bundle=False)
ds = Dataset._from_inner(inner)
try:
    booster = lgb.train(spec["params"], ds,
                        num_boost_round=spec["rounds"],
                        verbose_eval=False)
except faults.SimulatedPreemption as exc:
    print("CHILD_PREEMPTED", exc.iteration, flush=True)
    sys.exit({rc_preempted})
if rank == 0:
    with open(spec["out"], "w") as fh:
        fh.write(booster.model_to_string())
print("CHILD_OK", rank, flush=True)
"""


def _probe_multiprocess(timeout: float = 180) -> bool:
    probe = (
        "import sys; sys.path.insert(0, %r)\n"
        "import jax; jax.config.update('jax_platforms', 'cpu')\n"
        "from lightgbm_tpu.parallel.multihost import init_distributed\n"
        "assert init_distributed()\n"
        "import jax.numpy as jnp, numpy as np\n"
        "from jax.experimental import multihost_utils\n"
        "out = multihost_utils.process_allgather("
        "jnp.asarray(np.int64(jax.process_index())))\n"
        "assert sorted(np.asarray(out).tolist()) == [0, 1]\n" % REPO)
    port = _free_port()
    procs = []
    for rank in range(2):
        env = dict(os.environ)
        env.pop("PYTHONPATH", None)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
        env["LGBM_TPU_COORDINATOR"] = f"127.0.0.1:{port}"
        env["LGBM_TPU_NUM_MACHINES"] = "2"
        env["LGBM_TPU_RANK"] = str(rank)
        procs.append(subprocess.Popen([sys.executable, "-c", probe],
                                      env=env, stdout=subprocess.DEVNULL,
                                      stderr=subprocess.DEVNULL))
    ok = True
    for p in procs:
        try:
            ok = ok and p.wait(timeout=timeout) == 0
        except subprocess.TimeoutExpired:
            p.kill()
            ok = False
    return ok


def _launch_cohort(nproc: int, spec_for, timeout: float,
                   fault_plans: dict):
    """Launch an nproc-rank jax.distributed cohort; returns
    {rank: (rc, output_tail)}."""
    port = _free_port()
    procs = {}
    for rank in range(nproc):
        env = dict(os.environ)
        env.pop("PYTHONPATH", None)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
        env["LGBM_TPU_COORDINATOR"] = f"127.0.0.1:{port}"
        env["LGBM_TPU_NUM_MACHINES"] = str(nproc)
        env["LGBM_TPU_RANK"] = str(rank)
        env["ELASTIC_CHILD_SPEC"] = json.dumps(spec_for(rank))
        env.pop("LGBM_TPU_FAULT_PLAN", None)
        if fault_plans.get(rank):
            env["LGBM_TPU_FAULT_PLAN"] = json.dumps(fault_plans[rank])
        procs[rank] = subprocess.Popen(
            [sys.executable, "-c",
             PROC_CHILD.format(repo=REPO, rc_preempted=RC_PREEMPTED)],
            env=env, cwd=REPO, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)
    out = {}
    for rank, p in procs.items():
        try:
            text, _ = p.communicate(timeout=timeout)
            out[rank] = (p.returncode, text[-1500:])
        except subprocess.TimeoutExpired:
            p.kill()
            out[rank] = (124, "<timeout>")
    return out


def run_processes_mode(args) -> dict:
    result = {"metric": "elastic_smoke", "unit": "ok",
              "mode": "processes", "rounds": args.rounds}
    if not _probe_multiprocess():
        # a backend limitation, not a failure of the elasticity layer —
        # report it honestly and leave the gate green
        result.update(value=1.0, mode_unavailable=True,
                      reason="multi-process collectives unavailable on "
                             "this jax CPU build (capability probe "
                             "failed); devices mode covers the cycle")
        return result

    import numpy as np
    workdir = tempfile.mkdtemp(prefix="elastic_smoke_proc_")
    ckpt_dir = os.path.join(workdir, "ckpts")
    hb_dir = os.path.join(workdir, "heartbeats")
    rng = np.random.RandomState(0)
    n, f = 800, 5
    X = rng.randn(n, f)
    y = X[:, 0] * 2 + X[:, 1] + 0.1 * rng.randn(n)
    data_path = os.path.join(workdir, "mh.tsv")
    np.savetxt(data_path, np.column_stack([y, X]), delimiter="\t",
               fmt="%.8g")
    params = {"objective": "regression", "tree_learner": "data",
              "num_leaves": 15, "min_data_in_leaf": 3, "verbose": -1,
              "tpu_hist_chunk": 64}
    ckpt_params = dict(params, tpu_checkpoint_dir=ckpt_dir,
                       tpu_checkpoint_interval=1, tpu_checkpoint_keep=50,
                       tpu_heartbeat_dir=hb_dir,
                       tpu_heartbeat_lease_s=5.0,
                       tpu_collective_timeout_s=60.0)

    def spec_for(out_name, p, nproc):
        return lambda rank: {"data": data_path, "params": p,
                             "rounds": args.rounds, "nproc": nproc,
                             "out": os.path.join(workdir, out_name)}

    stages = []
    result["stages"] = stages
    # uninterrupted 2-rank reference (the bitwise baseline: a W-rank
    # cohort's model; cross-process row assembly permutes f32 sums, so
    # serial is not the reference here)
    outs = _launch_cohort(2, spec_for("m_ref.txt", params, 2),
                          args.timeout, {})
    stages.append({"stage": "cohort_reference", "nproc": 2,
                   "rcs": {str(r): rc for r, (rc, _) in outs.items()}})
    if any(rc != 0 for rc, _ in outs.values()):
        result.update(value=0.0, error="reference cohort failed",
                      detail={str(r): t for r, (_, t) in outs.items()})
        return result

    # kill rank 1 at iteration 4; rank 0's watchdog must detect the
    # dead peer inside its next collective and exit RC_RANK_FAILURE
    outs = _launch_cohort(
        2, spec_for("m_killed.txt", ckpt_params, 2), args.timeout,
        {1: {"kill_rank": [1, 4]}})
    stages.append({"stage": "kill_rank1", "nproc": 2,
                   "rcs": {str(r): rc for r, (rc, _) in outs.items()}})
    result["ranks_killed"] = [{"stage": "kill_rank1", "rank": 1,
                               "iteration": 4}]
    if outs[1][0] != RC_PREEMPTED:
        result.update(value=0.0, error="rank 1 did not preempt",
                      detail=outs[1][1])
        return result
    if outs[0][0] != RC_RANK_FAILURE:
        result.update(value=0.0,
                      error="rank 0 did not detect the dead peer "
                            f"(rc {outs[0][0]})", detail=outs[0][1])
        return result
    result["watchdog_rc"] = RC_RANK_FAILURE

    # elastic resume at W'=1: both rank series re-shard into one
    # process. Same PROC_CHILD/two_round_load construction as the
    # cohort (num_machines=1 keeps every row local) so the dataset —
    # bin bounds included — is identical.
    outs = _launch_cohort(
        1, spec_for("m_final.txt", ckpt_params, 1), args.timeout, {})
    stages.append({"stage": "finish_at_1proc",
                   "rcs": {str(r): rc for r, (rc, _) in outs.items()}})
    if outs[0][0] != 0:
        result.update(value=0.0, error="single-process elastic resume "
                                       "failed", detail=outs[0][1])
        return result
    final = open(os.path.join(workdir, "m_final.txt")).read()
    ref = open(os.path.join(workdir, "m_ref.txt")).read()
    # informational, not gating: cross-process row assembly permutes
    # the f32 summation order, so cohort-vs-resumed bitwise equality is
    # not an invariant this layer can promise (the DEVICES-mode cycle
    # carries the byte-identity acceptance)
    result["byte_identical_to_cohort"] = final == ref
    result["resume_outcome"] = "completed"
    result["value"] = 1.0
    shutil.rmtree(workdir, ignore_errors=True)
    return result


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--mode", choices=("devices", "processes"),
                    default="devices")
    ap.add_argument("--rounds", type=int, default=12)
    ap.add_argument("--timeout", type=float,
                    default=float(os.environ.get("ELASTIC_TIMEOUT", 240)))
    ap.add_argument("--max-retries", type=int, default=2)
    ap.add_argument("--backoff", type=float, default=1.0,
                    help="seconds of backoff per retry attempt")
    ap.add_argument("--out", default=os.path.join(REPO,
                                                  "ELASTIC_r01.json"))
    args = ap.parse_args()
    t0 = time.time()
    result = (run_devices_mode(args) if args.mode == "devices"
              else run_processes_mode(args))
    result["wall_seconds"] = round(time.time() - t0, 2)
    with open(args.out, "w") as fh:
        json.dump(result, fh, indent=1)
    print(json.dumps({k: v for k, v in result.items()
                      if k != "stages"}), flush=True)
    return 0 if result.get("value") == 1.0 else 1


if __name__ == "__main__":
    sys.exit(main())
