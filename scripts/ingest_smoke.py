"""Ingest smoke: stream a file whose RAW matrix exceeds an rlimit-enforced
memory budget, train, and match the unconstrained in-memory run.

Three child processes (fresh address spaces, so rlimits and peak-memory
accounting don't bleed):

1. `streamed`  — dataset CONSTRUCTION under a soft RLIMIT_AS of
   (pre-construction baseline + budget) with budget < the raw float64
   matrix size: the old load-everything path CANNOT fit, the chunked
   two-pass ingest (lightgbm_tpu/ingest) must. The cap is lifted for
   training (XLA's runtime handles mid-computation allocation failure
   badly) — corruption would fail the byte-compare below anyway.
2. `inmem`     — same construction cap, `tpu_ingest=false`: expected to
   die at the cap (proves the budget bites and the streamed path is
   doing real work, not that the budget was secretly roomy).
3. `reference` — no cap, `tpu_ingest=false` in-memory construction:
   the bit-identity oracle.

PASS = streamed child constructed under the cap AND its trained model
text is byte-identical to the reference's AND the in-memory child hit
the cap.

Usage: python scripts/ingest_smoke.py
Env: SMOKE_ROWS (default 600000), SMOKE_FEATURES (40), SMOKE_ITERS (3).

CPU gate: this script and every child it starts run on the CPU platform
(JAX_PLATFORMS=cpu); it checks behaviour, not device speed, and needs no
chip — no parent here holds a chip that a child then needs.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

ROWS = int(os.environ.get("SMOKE_ROWS", 2_000_000))
FEATURES = int(os.environ.get("SMOKE_FEATURES", 40))
ITERS = int(os.environ.get("SMOKE_ITERS", 2))
RAW_BYTES = ROWS * FEATURES * 8
# Half the raw matrix, with a floor: the floor covers the REAL fixed
# costs every capped run pays over the pre-cap baseline (the gathered
# bin/EFB samples, the 1-byte binned output, chunk buffers, grower
# compile arenas) — and, just as important, keeps the allocator out of
# the pathological near-zero-headroom regime (glibc/obmalloc retry
# storms were observed with <120MB of headroom on a 7GB-virtual jax
# process). The smoke therefore needs a raw matrix comfortably ABOVE
# the floor to prove anything: ~2M x 40 float64 = 640MB vs a 320MB cap.
_BUDGET_FLOOR = 256 << 20
# 0.6: the CPU backend's training footprint is ~2-3x the 1-byte binned
# matrix (host copy + padded copy + "device" copy — the CPU backend's
# device memory IS host RAM) plus labels/scores; at F=40 that is
# ~0.35x raw, and 0.6x leaves real headroom while staying far below raw
BUDGET = max(int(RAW_BYTES * 0.6), _BUDGET_FLOOR)

PARAMS = {
    # the smoke's claim is about CONSTRUCTION memory, so training is
    # kept cheap (the CPU backend pays the histogram flops for real):
    # few leaves, narrow bins, 2 iterations
    "objective": "binary", "verbose": -1, "max_bin": 31,
    "num_leaves": 7, "min_data_in_leaf": 20, "learning_rate": 0.1,
    # small streaming chunks: the text parser's per-chunk buffer must
    # fit the budget too
    "tpu_ingest_chunk_rows": 8192,
    # ... and so must the grower's per-pass working set: at the default
    # 65536-row histogram chunk the one-hot transient is
    # chunk * G*B * 4B = 335MB at F=40/max_bin=31 — row-count
    # INDEPENDENT, so it would dominate any budget; 8192 rows makes it
    # 42MB (training under a memory budget means sizing the chunk to it)
    "tpu_hist_chunk": 8192,
    # land the binned matrix straight into the device buffer, freeing
    # host blocks as they ship — without this the matrix exists three
    # times around trainer init (host + padded host + device), which on
    # the CPU backend (device memory IS host RAM) triples the footprint
    "tree_learner": "data",
    "tpu_ingest_device_shards": True,
    # pass 1's gathered row samples are a REAL fixed cost — the default
    # 200k-row bin sample is 200k*F*8B (64MB at F=40), most of the
    # budget. Streaming under a memory budget means sizing the sample
    # to it; bit-identity holds at any sample count (both construction
    # paths share the sampling code)
    "bin_construct_sample_cnt": 50_000,
}


def _vmsize() -> int:
    for line in open("/proc/self/status"):
        if line.startswith("VmSize"):
            return int(line.split()[1]) * 1024
    return 0


def _child(role: str, path: str, model_out: str) -> None:
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import jax.numpy as jnp
    import lightgbm_tpu as lgb

    # pre-rlimit warmup: everything a training run allocates that is NOT
    # data-proportional must land in the baseline the cap is measured
    # against — the XLA/Eigen thread pool (24 x 8MB stacks; without it
    # the capped run silently degrades to one thread), compiler arenas,
    # numpy/python allocator pools. A tiny end-to-end train touches all
    # of it.
    (jnp.ones((4096, 4096)) @ jnp.ones((4096, 4096))).block_until_ready()
    rng = np.random.RandomState(0)
    Xw = rng.randn(512, 8)
    lgb.train({"objective": "binary", "verbose": -1, "num_leaves": 4},
              lgb.Dataset(Xw, label=(Xw[:, 0] > 0).astype(float)),
              num_boost_round=1, verbose_eval=False)
    # ... including the ingest binning thread pool: its worker threads'
    # first mallocs each map a fresh glibc arena (64MB of ADDRESS SPACE
    # apiece — ~300MB observed for 8 workers), so warm them pre-cap on a
    # matrix big enough to take the pooled path (the arenas persist and
    # are reused after the pool is torn down). MALLOC_ARENA_MAX in
    # _spawn bounds whatever still leaks through.
    from lightgbm_tpu.dataset import Dataset as _Inner
    Xp = rng.randn(120_001, 6)
    _Inner.from_numpy(Xp, None, max_bin=15, chunk_rows=120_001)
    del Xp

    params = dict(PARAMS)
    if role in ("inmem", "reference"):
        params["tpu_ingest"] = False
    capped = role in ("streamed", "inmem")
    try:
        ds = lgb.Dataset(path, params=dict(params))
        if capped:
            # the budget covers CONSTRUCTION — the thing the streaming
            # subsystem claims needs no raw matrix. The soft RLIMIT_AS
            # is restored before training: XLA's runtime does not fail
            # allocations cleanly mid-computation (garbage results were
            # observed), and the trained model is byte-compared against
            # the uncapped reference anyway, which would expose any
            # corruption.
            import resource
            _, hard = resource.getrlimit(resource.RLIMIT_AS)
            limit = _vmsize() + BUDGET
            resource.setrlimit(resource.RLIMIT_AS, (limit, hard))
            try:
                ds.construct()
            finally:
                resource.setrlimit(resource.RLIMIT_AS,
                                   (resource.RLIM_INFINITY, hard))
        booster = lgb.train(dict(params), ds, num_boost_round=ITERS,
                            verbose_eval=False)
        booster.save_model(model_out)
        status = {"role": role, "ok": True,
                  "iterations": booster.current_iteration()}
    except MemoryError:
        import traceback
        status = {"role": role, "ok": False, "oom": True,
                  "at": traceback.format_exc(limit=6).splitlines()[-8:]}
    print("SMOKE_RESULT " + json.dumps(status), flush=True)


def _spawn(role: str, path: str, model_out: str) -> dict:
    env = dict(os.environ)
    env["SMOKE_ROLE"] = role
    env["SMOKE_PATH"] = path
    env["SMOKE_MODEL"] = model_out
    env["JAX_PLATFORMS"] = "cpu"
    # XLA:CPU's parallel codegen spawns ~32 fresh threads per compile
    # (8MB stack each — a ~256MB TRANSIENT spike that has nothing to do
    # with the data); serialize codegen in every child so capped and
    # uncapped runs compile the same way
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_cpu_parallel_codegen_split_count=1"
                        ).strip()
    # bound glibc's per-thread arena reservations (64MB of address space
    # each — poison under an RLIMIT_AS budget)
    env["MALLOC_ARENA_MAX"] = "4"
    res = subprocess.run([sys.executable, os.path.abspath(__file__)],
                         env=env, capture_output=True, text=True,
                         timeout=3600)
    for line in res.stdout.splitlines():
        if line.startswith("SMOKE_RESULT "):
            return json.loads(line[len("SMOKE_RESULT "):])
    return {"role": role, "ok": False, "rc": res.returncode,
            "tail": (res.stdout + res.stderr)[-600:]}


def main() -> int:
    role = os.environ.get("SMOKE_ROLE")
    if role:
        _child(role, os.environ["SMOKE_PATH"], os.environ["SMOKE_MODEL"])
        return 0

    import numpy as np

    print(f"[smoke] rows={ROWS} features={FEATURES} "
          f"raw={RAW_BYTES / 1e6:.0f}MB budget={BUDGET / 1e6:.0f}MB",
          file=sys.stderr)
    assert BUDGET < RAW_BYTES, (
        f"budget ({BUDGET / 1e6:.0f}MB) must be smaller than the raw "
        f"matrix ({RAW_BYTES / 1e6:.0f}MB) — raise SMOKE_ROWS/"
        f"SMOKE_FEATURES so the raw matrix exceeds the "
        f"{_BUDGET_FLOOR / 1e6:.0f}MB fixed-cost floor")
    tmp = tempfile.mkdtemp(prefix="ingest_smoke_")
    path = os.path.join(tmp, "smoke.tsv")
    rng = np.random.RandomState(7)
    # write in slabs so the PARENT does not hold the matrix either
    slab = 100_000
    with open(path, "w") as fh:
        for lo in range(0, ROWS, slab):
            m = min(slab, ROWS - lo)
            X = rng.randn(m, FEATURES)
            X[rng.rand(m, FEATURES) < 0.2] = 0.0
            y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float64)
            np.savetxt(fh, np.column_stack([y, X]), delimiter="\t",
                       fmt="%.7g")
            del X, y

    streamed = _spawn("streamed", path, os.path.join(tmp, "streamed.txt"))
    print(f"[smoke] streamed: {streamed}", file=sys.stderr)
    inmem = _spawn("inmem", path, os.path.join(tmp, "inmem.txt"))
    print(f"[smoke] inmem-under-cap: {inmem}", file=sys.stderr)
    reference = _spawn("reference", path, os.path.join(tmp, "ref.txt"))
    print(f"[smoke] reference: {reference}", file=sys.stderr)

    failures = []
    if not streamed.get("ok"):
        failures.append(f"streamed run failed (construction under the "
                        f"{BUDGET / 1e6:.0f}MB budget): {streamed}")
    if inmem.get("ok"):
        failures.append("in-memory construction SUCCEEDED under the "
                        "budget — the cap is not binding, the smoke "
                        "proves nothing")
    if not reference.get("ok"):
        failures.append(f"uncapped reference run failed: {reference}")
    if streamed.get("ok") and reference.get("ok"):
        a = open(os.path.join(tmp, "streamed.txt")).read()
        b = open(os.path.join(tmp, "ref.txt")).read()
        if a != b:
            failures.append("streamed-under-budget model differs from "
                            "the in-memory reference model")
        else:
            print("[smoke] models byte-identical", file=sys.stderr)

    print(json.dumps({
        "smoke": "ingest", "ok": not failures,
        "rows": ROWS, "features": FEATURES,
        "raw_mb": round(RAW_BYTES / 1e6, 1),
        "budget_mb": round(BUDGET / 1e6, 1),
        "streamed": streamed, "inmem_under_cap": inmem,
        "failures": failures,
    }), flush=True)
    return 0 if not failures else 1


if __name__ == "__main__":
    sys.exit(main())
