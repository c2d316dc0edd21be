"""Exported-forest artifact gates (ISSUE 16).

Three phases, one machine-readable artifact (`--out`):

1. **round_trip** — train, pack an artifact carrying the f32 + f16 +
   int8 layouts over the full bucket ladder, reload it in-process, and
   gate on byte-for-byte bit-identity against the live booster for
   every layout, fused probabilities AND raw margins.
2. **refusal** — a loader must never serve a wrong forest: flipped
   payload bytes are refused with the CRC-failing section named,
   a future format version is refused before any section is touched,
   a fingerprint mismatch (model re-trained since packing) is refused,
   and a plain text model file is recognised as not-an-artifact.
3. **cold_serve** — the headline gate. A child process arms a
   meta-path import blocker over the ENTIRE training stack
   (boosting/, learner/, ingest/, parallel/ and their front doors),
   loads the artifact cold through `lightgbm_tpu.export.runtime`,
   warms the exported ladder, then serves every pre-exported bucket
   while a `jax.monitoring` listener counts compile/trace traffic:
   gates are trainer-stack-absent, ZERO retraces in steady state, and
   bit-identical predictions vs the parent's live booster.

Usage: python scripts/export_smoke.py [--out EXPORT_r01.json]
Exits nonzero on any gate failure; prints one machine-readable JSON
line per phase plus a final summary line.

CPU gate: this script and every child it starts run on the CPU platform
(JAX_PLATFORMS=cpu); it checks behaviour, not device speed, and needs no
chip — no parent here holds a chip that a child then needs.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402

N_FEATURES = 12
LAYOUTS = ["none", "f16", "int8"]

# the serving replica's forbidden surface: the trainer packages the
# export-import-hygiene lint rule bans, plus their front doors
BLOCKED = (
    "lightgbm_tpu.boosting", "lightgbm_tpu.learner",
    "lightgbm_tpu.ingest", "lightgbm_tpu.parallel",
    "lightgbm_tpu.basic", "lightgbm_tpu.engine",
    "lightgbm_tpu.dataset", "lightgbm_tpu.cli",
    "lightgbm_tpu.sklearn", "lightgbm_tpu.objectives",
)


def _train():
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(0)
    X = rng.rand(3000, N_FEATURES).astype(np.float32)
    y = (X[:, 0] + X[:, 1] * X[:, 2] > 0.7).astype(np.float32)
    params = {"objective": "binary", "verbose": -1, "num_leaves": 31,
              "min_data_in_leaf": 5, "seed": 3}
    ds = lgb.Dataset(X, y, params=dict(params))
    booster = lgb.train(dict(params), ds, num_boost_round=25,
                        verbose_eval=False)
    return X, booster


def _export(booster, X, out_dir):
    path = os.path.join(out_dir, "forest.artifact")
    info = booster.export_forest(path, layouts=list(LAYOUTS),
                                 calibration=X[:512])
    return path, info


def phase_round_trip(tmpdir: str) -> dict:
    from lightgbm_tpu.export import load_artifact

    X, booster = _train()
    path, info = _export(booster, X, tmpdir)
    rng = np.random.RandomState(11)
    Xt = rng.rand(200, N_FEATURES).astype(np.float32)
    Xt[:7, 3] = np.nan                      # missing-value routing too

    inner = booster._inner
    gates, deltas = {}, {}
    for mode in LAYOUTS:
        model = load_artifact(path, params={"tpu_predict_quantize": mode})
        inner.config.io.tpu_predict_quantize = mode
        ref = inner.predict(Xt)
        got = model.predict(Xt)
        ref_raw = inner.predict(Xt, raw_score=True)
        got_raw = model.predict(Xt, raw_score=True)
        gates["bit_identical_%s" % mode] = bool(
            np.array_equal(ref, got) and np.array_equal(ref_raw, got_raw))
        deltas[mode] = float(np.max(np.abs(ref - got)))
    inner.config.io.tpu_predict_quantize = "none"
    return {"phase": "round_trip", "ok": all(gates.values()),
            "gates": gates, "max_abs_delta": deltas,
            "artifact": {k: info[k] for k in
                         ("bytes", "sections", "layouts", "buckets")}}


def phase_refusal(tmpdir: str) -> dict:
    from lightgbm_tpu.export import (ArtifactError, is_artifact,
                                     load_artifact)

    X, booster = _train()
    path, _ = _export(booster, X, tmpdir)
    blob = open(path, "rb").read()
    gates, messages = {}, {}

    # 1. corrupted payload: flip a byte inside the LAST section and the
    # CRC check must name it when that section is first deserialized
    bad = os.path.join(tmpdir, "corrupt.artifact")
    with open(bad, "wb") as fh:
        fh.write(blob[:-3] + bytes([blob[-3] ^ 0xFF]) + blob[-2:])
    try:
        load_artifact(bad).predict(X[:16])
        gates["corruption_refused"] = False
    except ArtifactError as exc:
        msg = str(exc)
        messages["corruption"] = msg
        gates["corruption_refused"] = (
            ("checksum" in msg or "CRC" in msg)
            and ("fn/" in msg or "conv/" in msg or "leaves/" in msg
                 or "model_text" in msg))

    # 2. version skew: a future format number (byte-patched in place,
    # same width) must be refused at load, before any section is read
    skew = os.path.join(tmpdir, "skew.artifact")
    patched = blob.replace(b'"format": 1,', b'"format": 9,', 1)
    with open(skew, "wb") as fh:
        fh.write(patched)
    try:
        load_artifact(skew)
        gates["version_skew_refused"] = False
    except ArtifactError as exc:
        messages["version_skew"] = str(exc)
        gates["version_skew_refused"] = "format" in str(exc)

    # 3. stale artifact: the deployed config fingerprint moved on
    try:
        load_artifact(path, expect_fingerprint="0" * 16)
        gates["fingerprint_refused"] = False
    except ArtifactError as exc:
        messages["fingerprint"] = str(exc)
        gates["fingerprint_refused"] = "fingerprint" in str(exc)

    # 4. a plain text model is not an artifact
    model_txt = os.path.join(tmpdir, "model.txt")
    booster.save_model(model_txt)
    not_artifact = not is_artifact(model_txt)
    try:
        load_artifact(model_txt)
        gates["text_model_refused"] = False
    except ArtifactError as exc:
        messages["text_model"] = str(exc)
        gates["text_model_refused"] = not_artifact

    return {"phase": "refusal", "ok": all(gates.values()),
            "gates": gates, "messages": messages}


def _cold_child(artifact: str, ref_npz: str) -> None:
    """The 'serving replica': arm the trainer import blocker BEFORE any
    lightgbm_tpu import, load the artifact cold, warm the exported
    ladder, then serve every bucket counting compile traffic."""
    class _TrainerImportBlocker:
        def find_spec(self, name, path=None, target=None):
            for b in BLOCKED:
                if name == b or name.startswith(b + "."):
                    raise ImportError(
                        "training stack blocked in serving replica: "
                        + name)
            return None

    sys.meta_path.insert(0, _TrainerImportBlocker())
    blocker_armed = False
    try:
        import lightgbm_tpu.boosting  # noqa: F401
    except ImportError:
        blocker_armed = True

    import jax.monitoring
    events = []
    jax.monitoring.register_event_listener(
        lambda name, **kw: events.append(name))

    from lightgbm_tpu.export.runtime import ArtifactServer

    t0 = time.perf_counter()
    server = ArtifactServer(artifact)     # load + warm the full ladder
    warm_s = time.perf_counter() - t0
    warm_events = list(events)

    ref = np.load(ref_npz)
    X, prob_ref, raw_ref = ref["X"], ref["prob"], ref["raw"]
    buckets = list(server.model._buckets)

    # absorb per-program first-call compile-cache chatter, then demand
    # TOTAL silence in steady state
    for b in buckets:
        server.model.predict(X[:b])
        server.model.predict(X[:b], raw_score=True)
        server.predict(X[:b])
    events.clear()

    bit_identical = True
    for _ in range(2):                    # steady-state rounds
        for b in buckets:
            got = server.model.predict(X[:b])
            got_raw = server.model.predict(X[:b], raw_score=True)
            via_pred = server.predict(X[:b])
            one = server.predict_one(X[0])
            bit_identical = bit_identical and bool(
                np.array_equal(got, prob_ref[:b])
                and np.array_equal(got_raw, raw_ref[:b])
                and np.array_equal(via_pred, prob_ref[:b])
                and float(one) == float(prob_ref[0]))
    steady_events = list(events)

    trainer_loaded = sorted(
        m for m in sys.modules
        if any(m == b or m.startswith(b + ".") for b in BLOCKED))
    print(json.dumps({
        "blocker_armed": blocker_armed,
        "trainer_modules_loaded": trainer_loaded,
        "warmup_seconds": round(warm_s, 3),
        "warmup_events": len(warm_events),
        "steady_events": steady_events,
        "buckets": buckets,
        "bit_identical": bit_identical,
        "stats": server.stats(),
    }), flush=True)
    server.close()


def phase_cold_serve(tmpdir: str) -> dict:
    X, booster = _train()
    path, _ = _export(booster, X, tmpdir)
    top = max(booster._inner.config.io.tpu_predict_bucket_min << 3, 128)
    rng = np.random.RandomState(29)
    Xt = rng.rand(top, N_FEATURES).astype(np.float32)
    Xt[:5, 2] = np.nan
    ref_npz = os.path.join(tmpdir, "refs.npz")
    np.savez(ref_npz, X=Xt, prob=booster._inner.predict(Xt),
             raw=booster._inner.predict(Xt, raw_score=True))

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["LIGHTGBM_TPU_COMPILE_CACHE"] = "0"
    res = subprocess.run(
        [sys.executable, os.path.abspath(__file__),
         "--cold-child", path, "--ref", ref_npz],
        env=env, capture_output=True, text=True, timeout=600)
    line = next((ln for ln in reversed(res.stdout.splitlines())
                 if ln.startswith("{")), None)
    if res.returncode != 0 or line is None:
        return {"phase": "cold_serve", "ok": False,
                "error": (res.stdout + res.stderr)[-800:]}
    child = json.loads(line)
    retrace = [e for e in child["steady_events"]
               if "compil" in e or "trace" in e or "lower" in e]
    gates = {
        "blocker_armed": child["blocker_armed"],
        "trainer_stack_absent": child["trainer_modules_loaded"] == [],
        # the listener demonstrably sees compile traffic during warmup,
        # so the steady-state zero below is not vacuous
        "warmup_compiled": child["warmup_events"] > 0,
        "zero_retrace_steady_state": retrace == []
        and child["steady_events"] == [],
        "bit_identical": child["bit_identical"],
    }
    return {"phase": "cold_serve", "ok": all(gates.values()),
            "gates": gates, "child": child}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(REPO, "EXPORT_r01.json"))
    ap.add_argument("--cold-child", default=None)
    ap.add_argument("--ref", default=None)
    args = ap.parse_args()
    if args.cold_child:
        _cold_child(args.cold_child, args.ref)
        return 0

    import tempfile
    t0 = time.time()
    phases = {}
    with tempfile.TemporaryDirectory(prefix="lgbm_tpu_export_") as tmp:
        for fn in (phase_round_trip, phase_refusal, phase_cold_serve):
            rec = fn(tmp)
            phases[rec["phase"]] = rec
            print(json.dumps(rec), flush=True)

    ok = all(p.get("ok") for p in phases.values())
    summary = {"shape": "export", "ok": ok,
               "wall_seconds": round(time.time() - t0, 1),
               "phases": phases}
    with open(args.out, "w") as fh:
        json.dump(summary, fh, indent=1)
    print(json.dumps({"shape": "export", "ok": ok, "out": args.out}),
          flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
