"""Compile one configuration's grow+update program at FULL size for a
described TPU v5e, WITHOUT the chip, and list what XLA:TPU made of the
histogram's chunk loop: every chunk-sized operation (fusions, copies,
reshapes, with the memory space `S(1)` each buffer landed in) and the
matmul fusions with their operands.

    JAX_PLATFORMS=cpu python scripts/compile_grow.py \
        --config benchmarks/configs/higgs-10m5x28.json

A small booster on generated rows (`--small`, default 600,000) gives the
feature tables and the rest of `GrowerConfig`; the schedule is picked
again for the configuration's real rows and the chip's memory
(`schedule.pick_schedule`, as `GBDT.init` does on the chip), and every
row dimension of the program's arguments is replaced by the real padded
rows. Nothing runs: the compiler's choices (what it fuses, what it
copies, which layout a loop-carried buffer gets) are there to read
before a chip call is spent, in `<out>/<name>.hlo.txt` too; times are
not. PR 37 found three faults this way that a kernel timed alone on the
chip had not shown (`PERF.md` section 6). Serial learner only; about a
minute for the HIGGS configuration, two for Epsilon's (`--small 131072`).
A configuration with `num_class` > 1 compiles the program of a
multiclass iteration (`_grow_and_update_multi_impl`: the class trees one
after another, each on the one-tree schedule) instead. The program's
bytes on the chip (`memory_analysis()`: arguments, outputs, temporaries)
are printed last.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

V5E_BYTES = 16_909_336_064      # `bytes_limit` of one TPU v5e chip


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default=os.path.join(
        REPO, "benchmarks", "configs", "higgs-10m5x28.json"))
    ap.add_argument("--small", type=int, default=600_000,
                    help="rows of the booster that lends its tables")
    ap.add_argument("--out", default=os.path.join(REPO, "chiprun_out"))
    ap.add_argument("--name", default="compile_grow")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    import datagen
    import lightgbm_tpu as lgb
    from lightgbm_tpu.boosting import gbdt as gbdt_mod
    from lightgbm_tpu.learner.grow import FMETA_KEYS
    from lightgbm_tpu.learner.schedule import (pick_schedule,
                                               plan_row_layout, relabel_rows)

    with open(args.config) as fh:
        config = json.load(fh)
    params = dict(config["params"], verbose=-1)
    features, rows = int(config["features"]), int(config["rows"])
    X, y, *_ = datagen.generator(config["generator"])(
        args.small, features, 7)
    inner = lgb.Booster(dict(params), lgb.Dataset(
        X, y, params=dict(params)).construct())._inner
    cfg = inner._grower_cfg
    groups, n_small = len(cfg.group_widths), inner._binned.shape[0]
    layout = plan_row_layout(rows, groups, cfg.max_bins)
    classes = int(params.get("num_class", 1))
    picked = pick_schedule(groups, cfg.max_bins, rows, layout.n_pad,
                           layout.chunk, num_leaves=cfg.num_leaves,
                           device_bytes=V5E_BYTES, cache_groups=groups)
    cfg = cfg._replace(
        **picked.grower_fields(layout.chunk),
        relabel_rows=relabel_rows(groups, cfg.max_bins, picked.batch_k,
                                  layout.n_pad))
    print(f"{rows} rows padded to {layout.n_pad}; {cfg}", flush=True)

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def full(a):
        a = jnp.asarray(a)
        return jax.ShapeDtypeStruct(
            tuple(layout.n_pad if d == n_small else d for d in a.shape),
            a.dtype, sharding=one)

    grad, hess = inner._compute_gradients(inner._score)
    # a compile for a described chip cannot be read back from the cache
    jax.config.update("jax_enable_compilation_cache", False)
    fmeta = tuple(full(inner._fmeta[k]) for k in FMETA_KEYS)
    if classes > 1:
        compiled = jax.jit(gbdt_mod._grow_and_update_multi_impl,
                           static_argnames=("cfg",)).lower(
            full(inner._score), full(inner._binned),
            full(grad.reshape(classes, -1)), full(hess.reshape(classes, -1)),
            full(inner._base_weight),
            full(jnp.ones((classes, inner._num_features_padded), bool)),
            full(jnp.float32(0.1)), full(jnp.int32(rows)), fmeta,
            cfg=cfg).compile()
    else:
        compiled = jax.jit(gbdt_mod._grow_and_update_impl,
                           static_argnames=("cls", "cfg")).lower(
            full(inner._score), full(inner._binned), full(grad), full(hess),
            full(inner._base_weight),
            full(jnp.ones(inner._num_features_padded, bool)),
            full(jnp.float32(0.1)), full(jnp.int32(rows)), fmeta,
            cls=0, cfg=cfg).compile()
    text = compiled.as_text()
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, args.name + ".hlo.txt")
    with open(path, "w") as fh:
        fh.write(text)
    print(f"{len(text)} bytes of optimized HLO in {path}")

    shown = ("fusion", "copy", "copy-start", "copy-done", "reshape")
    op = re.compile(r"\s*(?:ROOT )?%?([\w.\-]+) = (\(?\w+\["
                    + str(cfg.chunk) + r",[^ ]*) ([\w\-]+)\(")
    for line in text.splitlines():
        m = op.match(line)
        if m and m.group(3) in shown:
            kind = re.search(r"kind=(\w+)", line)
            print(f"  {m.group(1)} = {m.group(2)[:72]} {m.group(3)} "
                  f"{kind.group(1) if kind else ''}")
        elif "dot_general" in line and re.search(
                r"= f32\[\d+,\d+,\d+\]\S* fusion\(", line):
            print("  matmul:", re.sub(r", kind=.*", "", line.strip())[:240])
    mem = compiled.memory_analysis()
    print(f"bytes on the chip: arguments {mem.argument_size_in_bytes}, "
          f"outputs {mem.output_size_in_bytes}, temporaries "
          f"{mem.temp_size_in_bytes}, aliased {mem.alias_size_in_bytes}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
