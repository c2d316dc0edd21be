"""End-to-end kill-and-resume smoke test over the real CLI.

Unlike tests/test_checkpoint.py (which simulates preemption with an
in-process exception), this drives `python -m lightgbm_tpu` in a
subprocess and delivers an actual SIGKILL mid-training — no atexit, no
finally-blocks, exactly what a preempted pod looks like — then reruns
the identical command and asserts the resumed run's model is
byte-identical to an uninterrupted one.

CPU gate: every CLI child runs with JAX_PLATFORMS=cpu; it checks
byte-identity, not device speed, and needs no chip.

Usage: python scripts/checkpoint_smoke.py
Exits 0 on success, 1 on any mismatch.
"""
from __future__ import annotations

import os
import signal
import subprocess
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ROUNDS = 60
KILL_AFTER_SNAPSHOTS = 3   # wait until a few checkpoints exist, then kill


def cli_cmd(train_path: str, model_path: str, ckpt_dir: str = ""):
    cmd = [sys.executable, "-m", "lightgbm_tpu", "task=train",
           f"data={train_path}", "objective=binary", "boosting_type=dart",
           "bagging_fraction=0.7", "bagging_freq=1", "num_leaves=15",
           f"num_trees={ROUNDS}", "seed=7", "verbose=-1",
           f"output_model={model_path}"]
    if ckpt_dir:
        cmd += [f"tpu_checkpoint_dir={ckpt_dir}", "tpu_checkpoint_interval=1"]
    return cmd


def main() -> int:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    with tempfile.TemporaryDirectory() as tmp:
        rng = np.random.RandomState(0)
        X = rng.randn(1500, 10)
        y = (X[:, 0] + X[:, 1] * X[:, 2] + rng.randn(1500) * 0.3 > 0)
        train_path = os.path.join(tmp, "train.tsv")
        np.savetxt(train_path, np.column_stack([y.astype(int), X]),
                   delimiter="\t", fmt="%.6f")

        base_model = os.path.join(tmp, "model_base.txt")
        print("[smoke] uninterrupted run ...")
        subprocess.run(cli_cmd(train_path, base_model),
                       env=env, cwd=REPO, check=True)

        ckpt_dir = os.path.join(tmp, "ckpts")
        model = os.path.join(tmp, "model.txt")
        print("[smoke] preemptible run (will be SIGKILLed) ...")
        proc = subprocess.Popen(cli_cmd(train_path, model, ckpt_dir),
                                env=env, cwd=REPO)
        deadline = time.time() + 600
        killed = False
        while time.time() < deadline:
            if proc.poll() is not None:
                break  # finished before we could kill it — still a valid run
            snaps = [f for f in os.listdir(ckpt_dir)
                     if f.startswith("ckpt_")] if os.path.isdir(ckpt_dir) \
                else []
            if len(snaps) >= KILL_AFTER_SNAPSHOTS:
                os.kill(proc.pid, signal.SIGKILL)
                proc.wait()
                killed = True
                break
            time.sleep(0.05)
        print(f"[smoke] killed mid-run: {killed} "
              f"(snapshots: {sorted(os.listdir(ckpt_dir))})")

        print("[smoke] resume run (same command) ...")
        subprocess.run(cli_cmd(train_path, model, ckpt_dir),
                       env=env, cwd=REPO, check=True)

        with open(base_model, "rb") as fh:
            base = fh.read()
        with open(model, "rb") as fh:
            resumed = fh.read()
        if base != resumed:
            print("[smoke] FAIL: resumed model differs from uninterrupted "
                  "run")
            return 1
        print(f"[smoke] OK: byte-identical final model "
              f"({len(base)} bytes, {ROUNDS} rounds, killed={killed})")
        return 0


if __name__ == "__main__":
    sys.exit(main())
