"""The execution schedule of one tree's program, as a function of the shape.

What `GBDT.init` hands the grower that is not a hyperparameter: the
histogram row chunk and the row padding (`plan_row_layout`), whether the
sibling-subtraction cache is kept and how large the node table is,
whether small-node passes are gather-compacted and under what row
fraction, and how many nodes one histogram pass expands
(`pick_schedule`). Trees are bit-identical for any `batch_k` and
`table_mult`; subtraction and compaction only change float32 summation
order. Pure Python and numpy, no jax: the ingest side plans a landing
with it (`ingest.landing.ShardedLanding`) without loading the grower.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import numpy as np


class RowLayout(NamedTuple):
    chunk: int          # histogram row-chunk the grower will use
    row_multiple: int   # chunk x the devices this process shards rows over
    n_pad: int          # padded row count (this process)
    ndev: int           # device count the plan assumed
    local_dev: int      # local devices per process


def plan_row_layout(n: int, num_groups: int, max_num_bin: int, *,
                    tpu_hist_chunk: int = 65536,
                    tree_learner: str = "serial",
                    ndev: int = 1, nproc: int = 1) -> RowLayout:
    """The padded-row plan of GBDT.init (boosting/gbdt.py): histogram
    chunk capped by the group-block budget, rows padded to a chunk (x
    shard) multiple. A layout on one shard (serial, feature-parallel, or
    one device) is then bucketed into coarse power-of-two granules so
    nearby row counts share one compiled signature. A row-sharded layout
    (`data` or `voting` over more than one device) is not: its padding is
    one suffix of a process's rows, so a granule's padding would all sit
    on the last shards while every shard is stepped at the pace of a full
    one. It pads to one row multiple (chunk x the process's devices), so
    the shards hold real rows to within that many chunks of each other,
    and each such row count compiles a program of its own. A landing
    padded by this plan is byte-compatible with what the trainer would
    have padded itself. Multi-process callers must still allgather-max
    the result across ranks."""
    kind = tree_learner if tree_learner in ("data", "feature", "voting") \
        else "serial"
    if kind == "serial":
        ndev = 1
    local_dev = max(1, ndev // max(1, nproc))
    chunk = min(int(tpu_hist_chunk), 1 << 20)
    gb = max(1, int(num_groups) * int(max_num_bin))
    target = max(1, (16 << 26) // gb)
    chunk = min(chunk, max(8192, 1 << int(np.floor(np.log2(target)))))
    chunk = int(min(chunk, max(256, 1 << int(np.ceil(np.log2(max(n, 1)))))))
    row_sharded = kind in ("data", "voting") and ndev > 1
    row_multiple = chunk * (local_dev if nproc > 1 else ndev) \
        if kind in ("data", "voting") else chunk
    m_count = (n + row_multiple - 1) // row_multiple
    if m_count > 1 and not row_sharded:
        p2 = 1 << (m_count - 1).bit_length()
        g = max(1, p2 // 8)
        m_count = ((m_count + g - 1) // g) * g
    return RowLayout(chunk=chunk, row_multiple=row_multiple,
                     n_pad=m_count * row_multiple, ndev=ndev,
                     local_dev=local_dev)


# The sibling-subtraction histogram cache ([M, G, B, 3] float32 per class
# tree) is kept where it fits the device, judged from what the code can
# observe: the device's memory (`bytes_limit` of its `memory_stats()`,
# handed in by the caller: this module imports no jax) less the binned
# matrix the shape already holds there. The cache may take a third of
# what is left; past that the grower builds both children directly.
# Read on a TPU v5e (PR 31, `scripts/profile_train.py` on
# `epsilon-400kx2000`, 1,048,576 x 2000 x 63 bins, PERF.md section 6): a
# cache of 2.39 GB costs the program one buffer of its size
# (`peak_bytes_reserved` 4.28 -> 6.72 GB; 9.55 GB with one of 4.7 GB) and,
# read and written a slot at a time, 0.005 s of a 2.49 s pair of trees;
# no stall. A third leaves room for a second buffer of it, which XLA held
# while `grow.py` read the parents by a gather.
SUBTRACT_CACHE_SHARE = 1.0 / 3.0
# A backend that reports no memory (the CPU) keeps a fixed budget
_SUBTRACT_CACHE_BUDGET = 256 << 20


def subtract_cache_bytes(groups: int, max_bins: int, num_leaves: int,
                         table_mult: int, *, classes: int = 1,
                         copies: int = 1) -> int:
    """Bytes of `copies` subtraction caches of this shape. A cache holds
    one [G, B, 3] float32 histogram per class tree for every node-table
    slot: `table_mult` per configured leaf plus the widest batch's
    speculative children (grow.py: M = table_mult * L + 2K + 2, K <= 25)."""
    slots = table_mult * num_leaves + 52
    return slots * classes * groups * max_bins * 3 * 4 * copies


def subtract_cache_budget(groups: int, max_bins: int, rows_padded: int,
                          device_bytes: int) -> int:
    """Bytes the subtraction caches of a shape may take on a device of
    `device_bytes` (0: the backend reports none) that already holds the
    shape's binned matrix, `rows_padded` x `groups` bin indices."""
    if device_bytes <= 0:
        return _SUBTRACT_CACHE_BUDGET
    held = rows_padded * groups * (1 if max_bins <= 256 else 2)
    return max(0, int((device_bytes - held) * SUBTRACT_CACHE_SHARE))


def subtract_cache_fits(groups: int, max_bins: int, num_leaves: int,
                        table_mult: int, *, classes: int = 1,
                        copies: int = 1, rows_padded: int = 0,
                        device_bytes: int = 0) -> bool:
    """Whether `copies` subtraction caches of this shape stay inside the
    budget of a device of `device_bytes` holding `rows_padded` rows of it
    (`subtract_cache_budget`)."""
    return subtract_cache_bytes(
        groups, max_bins, num_leaves, table_mult, classes=classes,
        copies=copies) <= subtract_cache_budget(
            groups, max_bins, rows_padded, device_bytes)


def _compact_rows(n: int, chunk: int, fraction: float,
                  feature_sharded: bool) -> int:
    """`compact_capacity` on the bare quantities. Single-chunk (per-
    shard) inputs have no chunks to skip: the capacity would round up to
    n and force EVERY pass through the slower gather, so they keep the
    contiguous full-pass kernel; so does the feature-parallel learner
    (routing reads the replicated matrix through a traced shard offset).
    A non-positive fraction disables compaction, >= 1.0 forces it on."""
    if (feature_sharded or float(fraction) <= 0.0
            or n % chunk != 0 or n < 2 * chunk):
        return 0
    cap = max(1, int(n * min(float(fraction), 1.0)))
    return min(n, ((cap + chunk - 1) // chunk) * chunk)


def compact_capacity(cfg, n: int) -> int:
    """Rows the gather-compaction buffer holds for `n` (per-shard) rows
    under `cfg` (a `grow.GrowerConfig`): `compact_fraction` of them,
    rounded UP to a chunk multiple and clamped to n, so every shape in
    the grower's while_loop stays compile-stable; 0 where grow_tree keeps
    every pass on the full kernel. The capacity doubles as the switch
    threshold: a pass is compacted iff its selected nodes' in-bag member
    rows fit the buffer. Also read on the host, to tell full from
    compacted passes in `pass_rows` (telemetry.layers.split_passes)."""
    if not cfg.hist_compact:
        return 0
    return _compact_rows(n, cfg.chunk, cfg.compact_fraction,
                         cfg.feature_axis is not None)


class CompactChoice(NamedTuple):
    """`compact_threshold`'s answer and the three pass costs behind it,
    each in ns a REAL row so they compare (the Schedule log prints them)."""
    fraction: float   # of the padded rows, as `compact_capacity` takes it
    full_ns: float    # a full pass, per row contracted
    index_ns: float   # the index build, spread over the real rows
    gather_ns: float  # a compacted pass's extra cost per gathered row


# The model's constants: TPU v5 lite, one chip, `scripts/profile_train.py`
# on `synth_higgs` at max_bin 63 / 255 leaves, compaction off against on
# at 0.25, two traced trees a run, at 28, 137, 700 and 2000 features
# (PERF.md section 6, PR 27, has the readings). Stored groups x bins -> ns a
# row of `lgbm/hist/contract` with every pass full. Stored groups -> ns a
# gathered row: `lgbm/hist/gather` (bins, the three channels, leaf ids;
# 43-47 ns at every width) plus what the contraction of gathered chunks
# costs over a full pass's (0 to 22 ns). The index build reads leaf ids
# and weights only, so one number a PADDED row at every width: the
# reading at 28 features, whose builds are the longest (25.2M rows each:
# `lgbm/grow/compact_index` 9.0 ns, less the relabel's select that XLA
# fuses into it, plus the cumsum's unscoped reduce-window); at 2.6M, 0.5M
# and 0.46M rows a build the others read 5.1, 6.6 and 7.8.
_WIDTHS, _FULL_NS = (1764, 8631, 44100, 126000), (3.4, 8.5, 64.0, 181.4)
_GROUPS, _GATHER_NS = (28, 137, 700, 2000), (46.9, 49.9, 54.6, 64.5)
_INDEX_NS_PER_PADDED_ROW = 7.5
COMPACT_FRACTION_MAX = 0.25
# Under this the few passes that qualify cannot repay what the compacted
# branch costs a program merely by being in it: at 137 features that
# width's own three readings put the break-even at 0.05, and thresholds
# 0.03-0.1 ran 8% to 14% slower than no compaction (XLA then copies the
# whole binned matrix into the gather's layout once a tree, 0.058 s of
# 0.75).
COMPACT_FRACTION_MIN = 0.05


def compact_threshold(num_groups: int, max_bins: int, rows: int,
                      rows_padded: int) -> CompactChoice:
    """The row fraction under which a gather-compacted pass is cheaper
    than a full one, for a (per-shard) shape. A full pass costs
    `rows * full`; a compacted one `rows_padded * index + cnt * (gather +
    full)`: an index over every padded row whatever it finds, then the
    gathers and the same contraction over the `cnt` member rows. The
    break-even `cnt` is given as a fraction of the PADDED rows, which is
    what `compact_capacity` multiplies, clipped to COMPACT_FRACTION_MAX so
    the buffer never outgrows the one a fixed 0.25 gave, and 0 under
    COMPACT_FRACTION_MIN; 0 means a full pass always wins and the grower
    compiles without the compacted branch. Between the measured widths
    the costs are piecewise-linear; outside them `np.interp` holds them
    flat: no reading is trusted past the nearest measured width."""
    rows = max(rows, 1)
    full = float(np.interp(num_groups * max_bins, _WIDTHS, _FULL_NS))
    gather = float(np.interp(num_groups, _GROUPS, _GATHER_NS))
    index = _INDEX_NS_PER_PADDED_ROW * rows_padded / rows
    cnt = rows * (full - index) / (gather + full)
    fraction = min(cnt / rows_padded, COMPACT_FRACTION_MAX)
    if fraction < COMPACT_FRACTION_MIN:
        fraction = 0.0
    return CompactChoice(fraction, full, index, gather)


# The relabel pass (`grow.route`) has two forms that give the same labels
# to the bit. The column form slices each selected node's bin column out
# of the transposed matrix and threads the labels through K selects: its
# cost a row follows the nodes a pass and not the width, and between 12
# and 24 nodes XLA:TPU stops fusing the slices into the select (24
# `s32[1, N]` columns written and read back). The blocked form loops over
# blocks of rows, reads the whole [G, block] uint8 bin block and picks
# each node's row by a one-hot product: G bytes a row, little more for
# more nodes. Both were read ALONE on a TPU v5e, 8 passes a call (PR 35's
# sweeps, PERF.md section 6), ms a pass, column / blocked at 262,144:
#   25,165,824 x 28:  8 nodes 4.79 / 3.59, 12: 7.15 / 4.82, 24: 26.05 / 5.98
#   12,582,912 x 137: 8 nodes 2.35 / 4.68, 12: 3.62 / 4.40, 24: 12.87 / 4.57
#   1,048,576 x 2000: 8 nodes 0.29 / not run (2.1 GB a pass)
# In the cells (`higgs-train-1chip`, `higgs-train-dp4`: 18 passes of 24
# nodes a tree) the scope read 24.4 -> 6.0 ms a pass. As ns a row:
_RELABEL_NODES = (8, 12, 24)
_RELABEL_COLUMN_NS = (0.190, 0.286, 1.030)
_RELABEL_GROUPS = (28, 137)
_RELABEL_BLOCKED_NS = ((0.142, 0.192, 0.237), (0.372, 0.349, 0.363))
# Rows a step of the blocked form routes. At 28 groups and 24 nodes
# blocks of 65,536 / 131,072 / 262,144 / 524,288 / 1,048,576 rows read
# 8.50 / 6.10 / 5.98 / 5.77 / 5.74 ms a pass; at 137 groups 4.82 / 4.65 /
# 4.57 / 4.46 / 4.73. One-row slices in place of the whole block (16,384
# to 262,144 rows) read 23.2-28.9 ms: a row of the packed uint8 tile
# costs what the tile does.
RELABEL_BLOCK = 262144


def relabel_rows(groups: int, max_bins: int, batch_k: int,
                 rows_padded: int) -> int:
    """Rows a step of the blocked relabel routes on a shard of
    `rows_padded` rows of `groups` stored groups at `batch_k` nodes a
    pass; 0 means the column form (`grow.GrowerConfig.relabel_rows`).
    Blocked where the readings above say it is the cheaper: linear
    between the measured widths and node counts, flat past the node
    counts. No reading is trusted outside the widths or under 8 nodes,
    nor for bins past uint8 (the one-hot product is exact to 255): those
    keep the column form."""
    if (max_bins > 256 or batch_k < _RELABEL_NODES[0]
            or groups > _RELABEL_GROUPS[-1]):
        return 0
    column = np.interp(batch_k, _RELABEL_NODES, _RELABEL_COLUMN_NS)
    blocked = np.interp(
        groups, _RELABEL_GROUPS,
        [np.interp(batch_k, _RELABEL_NODES, ns) for ns in _RELABEL_BLOCKED_NS])
    return int(min(RELABEL_BLOCK, rows_padded)) if blocked < column else 0


class Schedule(NamedTuple):
    """`pick_schedule`'s answer: what `GBDT.init` hands the grower."""
    wide: bool                 # groups x bins > 8192: channel-cost-bound
    subtract: bool             # the sibling-subtraction histogram cache
    table_mult: int            # node-table slots per configured leaf
    compact: bool              # gather-compacted small-node passes
    compact_fraction: float    # of the padded rows (compact_capacity)
    compact_model: CompactChoice   # the pass-cost model's own answer
    batch_k: int               # nodes expanded per histogram pass

    def grower_fields(self, chunk: int) -> dict:
        """The schedule fields of `grow.GrowerConfig`, by its names."""
        return {"chunk": chunk, "batch_k": self.batch_k,
                "hist_subtract": self.subtract,
                "hist_compact": self.compact,
                "compact_fraction": self.compact_fraction,
                "table_mult": self.table_mult}


def pick_schedule(groups: int, max_bins: int, rows: int, rows_padded: int,
                  chunk: int, *, num_leaves: int,
                  learner: str = "serial", bundled: bool = False,
                  quantize: str = "none",
                  compact_fraction: Optional[float] = None,
                  device_bytes: int = 0,
                  cache_groups: Optional[int] = None) -> Schedule:
    """The execution schedule of ONE tree's program as a function of the
    shape: stored groups, bins of the widest group, rows and padded rows
    of ONE shard, and the histogram chunk (`plan_row_layout` gives the
    last two); of the learner kind; and of the device: `device_bytes` is
    its memory, 0 where the backend reports none (`subtract_cache_budget`).
    A multiclass iteration runs it once a class tree, one after another
    (`gbdt._grow_and_update_multi_impl`).
    `cache_groups` is the width the subtraction cache has on one device
    where that is not the shape's: the data-parallel learner under the
    scatter merge keeps its owned slice, ceil(groups / shards) of the
    stored groups (grow.py); None means all of them. The contraction's
    cost model (`wide`, `compact_threshold`) keeps `groups`, which is
    what a pass contracts. `compact_fraction` is the one thing a user
    can set (`tpu_compact_threshold`); None leaves it to the shape.

    "Wide" shapes (large groups x bins) are channel-cost-bound in the
    histogram contraction (the [G*B, chunk] x [chunk, S] matmul's FLOPs
    scale with S), narrow ones MXU-tile-bound: Bosch-shape (~22k)
    measured fastest at narrow batches, HIGGS/Expo (~2k) at full-tile
    ones."""
    wide = groups * max_bins > 8192
    # sibling subtraction: the per-node [M, G, B, 3] histogram cache must
    # fit the budget. Node-table size rides on it: generous tables keep
    # late-boosting speculation wide (grow.py table notes), so where slots
    # are cheap take the largest table_mult in [6, 12] whose cache stays
    # inside the fixed 256 MB; a cache past that, which only a device that
    # reports room allows, takes 6: on the run above trees 3-4 filled
    # 509-527 of its 1,548 slots, and 12 read the same passes for 2.8 GB
    # more. Without the cache the table is [M]-scalar cheap: take the max.
    if cache_groups is None:
        cache_groups = groups

    def cache_fits(mult: int, device: int) -> bool:
        return subtract_cache_fits(cache_groups, max_bins, num_leaves, mult,
                                   rows_padded=rows_padded,
                                   device_bytes=device)

    mult_fit = next((m for m in range(12, 5, -1)
                     if cache_fits(m, 0) and cache_fits(m, device_bytes)),
                    6 if cache_fits(6, device_bytes) else 0)
    # The serial and the data-parallel learner keep the cache: under the
    # data axis only the K smaller children travel through the merge and
    # each shard subtracts in what it keeps of it (grow.py). On four v5e
    # chips: 30 -> 18 passes a tree, 29.86 -> 41.04 Mrow-iters/s, subtract
    # and table 0.9 ms a tree (PR 33, `higgs-train-dp4`, PERF.md section
    # 6). Voting keeps local histograms and drops the cache itself
    # (grow.py), and the feature-parallel learner has never run with it:
    # both keep the direct path, and consent here would only widen
    # `batch_k` for a cache that is not built.
    subtract = learner in ("serial", "data") and mult_fit > 0
    table_mult = mult_fit if subtract else 12
    # gather-compacted small-node contraction: wherever the grower would
    # hold a buffer for it (`compact_capacity`'s guards: rows locally
    # resident, more than one chunk of them) and the shape's pass costs
    # say it can pay. The threshold is a pure scheduling choice — for any
    # value the grown trees match the full-pass grower on order-invariant
    # sums (grow.py notes) — so unless the user gave one it is the
    # break-even of a full pass against an index build plus gathers
    # (`compact_threshold`). A narrow table never reaches it: at 28
    # groups x 63 bins the index build alone costs 2.7 full passes, and
    # the fraction comes out 0.
    model = compact_threshold(groups, max_bins, rows, rows_padded)
    if compact_fraction is None:
        compact_fraction = model.fraction
    compact = _compact_rows(rows_padded, chunk, compact_fraction,
                            learner == "feature") > 0
    if subtract:
        # one smaller-child channel set per node: 24 x (3 hi + 2 lo)
        # fills the 128-lane tile. Under the data axis as on one chip:
        # `higgs-train-dp4` (28 groups, 25.2M rows a shard, four v5e
        # chips, PR 33, PERF.md section 6) read 41.05 Mrow-iters/s at 24
        # (18 passes a tree) and 32.27 at 12 (30 passes; 29.88 with no
        # cache). A wide shape's pass is not tile-bound:
        # on the run above a contracted row costs 121-122 ns at 30-40
        # matmul columns and 166-176 at 50-120, and fewer nodes a pass
        # leave more passes under the compaction threshold; two trees at
        # batch_k 4 / 6 / 8 / 10 / 16 took 2.88 / 3.09 / 2.49 / 3.39 /
        # 3.56 s of device time (12 and 24, before the parents' gather
        # was cured: 3.88 and 3.58, of which 0.40 and 0.25 the gather).
        # 137-224 groups keep 8 on ONE reading: `msltr-rank-1chip` (137
        # groups, 12.58M rows, v5e, PR 34, PERF.md section 5) took 35 full
        # passes a tree at 105.6 ms, 8.39 ns a row a pass, at 8; 4, 12 and
        # 24 were not run there (ROADMAP Queue 3 item 11)
        batch_k = 8 if wide else 24
    else:
        # Bosch-class data (wide AND heavily EFB-bundled — sparse
        # one-hot blocks) measured fastest at K=4: deep depth-bound
        # trees, channel-cost-bound passes. Unbundled wide shapes
        # keep the full-tile default.
        batch_k = 4 if (wide and bundled) else 12
    if quantize == "int8":
        # int8 contracts 3 channels per node id instead of the bf16
        # hi+lo path's 5, so the same 128-lane MXU output tile (and,
        # on CPU, the same one-hot operand materialization) covers
        # 5/3 more leaves per pass. Widening the batch is free on
        # correctness: quantized histograms live in the exact int32
        # domain, where trees are bit-identical for ANY batch_k.
        batch_k = (batch_k * 5) // 3
    return Schedule(wide, subtract, table_mult, compact,
                    float(compact_fraction), model, batch_k)


def schedule_info(picked: Schedule, layout: RowLayout, cfg, *, rows: int,
                  groups: int, tree_learner: str, num_processes: int,
                  hist_reduce: Optional[str], owned_groups: int,
                  device_bytes: int = 0) -> dict:
    """JSON-safe record of the schedule a run took (`GBDT._schedule_info`,
    the telemetry run-log header, the benchmark's `schedule`): the knobs
    that explain its pass economics, host-readable without re-deriving
    the choice. `cfg` is the grower's final static config; under
    `grower` stands what else is baked into the compiled program (group
    widths summarized, not dumped: wide shapes carry thousands)."""
    widths = cfg.group_widths or ()
    return {
        "tree_learner": tree_learner,
        "num_shards": int(layout.ndev), "num_processes": int(num_processes),
        # data-parallel histogram-merge collective + per-device owned
        # histogram slice (scatter: groups/ndev after padding; other
        # schedules score the full group set everywhere)
        "hist_reduce": hist_reduce, "owned_groups": int(owned_groups),
        "groups": int(groups), "max_bin": int(cfg.max_bins),
        "wide": bool(picked.wide), "subtract": bool(picked.subtract),
        # the cache's bytes on one device (one class tree: `pick_schedule`
        # subtracts for no more; at the owned slice's width, which is
        # every group except under the scatter merge) and the device
        # memory it was judged against (0: the backend reports none,
        # `subtract_cache_budget`)
        "subtract_cache_bytes": subtract_cache_bytes(
            owned_groups, cfg.max_bins, cfg.num_leaves, picked.table_mult)
        if picked.subtract else 0,
        "device_bytes": int(device_bytes),
        "compact": bool(picked.compact),
        "compact_fraction": picked.compact_fraction,
        # the pass-cost model's answer for this (per-shard) shape,
        # beside what was used: they differ when the user set one
        "compact_model": picked.compact_model._asdict(),
        "batch_k": int(picked.batch_k), "table_mult": int(picked.table_mult),
        "chunk": int(layout.chunk), "rows": int(rows),
        "rows_padded": int(layout.n_pad),
        # the relabel pass (`grow.route`, `relabel_rows`): its form and,
        # blocked, the rows a step of its loop routes (0: the column
        # form, whole columns)
        "relabel": {"form": "blocked" if cfg.relabel_rows else "columns",
                    "block_rows": int(cfg.relabel_rows)},
        "hist_quantize": cfg.hist_quantize, "hist_qmax": int(cfg.hist_qmax),
        "hist_hess_const": bool(cfg.hist_hess_const),
        "grower": dict(
            {k: v for k, v in cfg._asdict().items() if k != "group_widths"},
            num_groups=len(widths),
            group_width_max=int(max(widths)) if widths else int(cfg.max_bins)),
    }
