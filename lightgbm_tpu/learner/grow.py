"""Leaf-wise tree growth as a single jitted program.

TPU-native re-design of the reference SerialTreeLearner
(`src/treelearner/serial_tree_learner.cpp:152-583`). The reference grows a
tree with per-leaf dynamic row partitions (DataPartition), a histogram LRU
pool, and host loops. Here the entire `num_leaves-1` split loop is ONE
`lax.while_loop` under jit with fixed shapes:

- the row partition is a `leaf_id[N]` vector (no index shuffling; split
  application is a vectorized where — replaces data_partition.hpp:94-170);
- best-split finding is the vectorized [F, B] scan (ops/split.py) followed
  by an argmax over features, replacing per-feature OMP loops
  (serial_tree_learner.cpp:451-516).

Speculative expansion (the round-3 redesign). A full-N histogram pass has
a HARD per-pass cost floor on TPU: the MXU's 128-lane output tile means a
one-hot-over-bins contraction costs the same for 1 live channel as for
128, and the measured floor (~4-7 ms at 2M rows x 28 features x 64 bins)
is ~70% of the bf16 roofline — per-pass optimization is exhausted. What
is NOT fixed is the NUMBER of passes. Round 2 ran one pass per "round"
of the strict best-first commit loop (~91 passes per 255-leaf tree,
~2.8 commits each) because child histograms were only built for leaves
about to commit. The key fact this version exploits: building a leaf's
children histograms needs only the leaf's CACHED best split — not its
commit. So the grower speculatively expands the gain-priority frontier
down the tree, decoupled from the commit order:

- a NODE TABLE of M = 6L + 2K + 2 slots holds every speculative node
  (a grown tree consumes ~2L slots for commits plus ~2L for the
  speculatively-expanded end frontier; 6L leaves mis-speculation
  headroom — at 4L the table exhausted mid-tree once late-boosting
  gains flattened and passes degraded to one forced expansion each):
  parent link, depth, aggregate (g, h, count), its cached best split,
  and lifecycle bits (created/expanded/committed/frontier);
- `leaf_id[N]` labels rows with the DEEPEST speculative node that owns
  them; each expansion pass routes the rows of up to `batch_k` selected
  nodes under their cached splits and relabels them to fresh child ids —
  children histograms are then direct `leaf_id == child` masked
  reductions (ops/histogram.batched_leaves_histogram);
- selection is top-K by cached gain among unexpanded nodes — throttled
  to the nodes whose gain ranks within the remaining commit budget (see
  expand()), since slots spent on never-committed expansions exhaust
  the table when late-boosting gains flatten — with the commit-blocking
  frontier argmax force-included, so the strict order can always make
  progress;
- COMMITS touch only [M]/[L]-sized state: pop the frontier argmax,
  write the tree node, promote the (already created) children to the
  frontier. No data pass, no row updates. Trees are therefore
  BIT-IDENTICAL to the sequential best-first grower for every batch_k —
  speculation only precomputes work earlier (the same guarantee the
  reference's HistogramPool gives: a pure cache never changes the tree,
  feature_histogram.hpp:380-548).

Sibling subtraction (round 5, `hist_subtract`): a [M, G, B, 3] cache
retains every created node's histogram (the HistogramPool,
feature_histogram.hpp:380-548); each expansion contracts only the
SMALLER child per node and derives the larger as parent - smaller
(FeatureHistogram::Subtract, feature_histogram.hpp:64-70). Channels per
node halve, so batch_k doubles inside the same 128-lane MXU output tile
(K*(3+2) <= 128 -> K <= 25). Under a data axis the cache is kept too
(`schedule.pick_schedule` consents for the serial and the data-parallel
learner): each shard contracts the smaller child's rows it holds, only
those K histograms travel through the merge, and a shard subtracts in
what it keeps of the merged tensor: its owned slice of the stored groups
under hist_scatter, every group under the full psum. Which child is the
smaller is read from the merged counts, so all shards agree. Voting
keeps local histograms and drops the cache.

Pass count drops from ~(commits / 2.8) to ~max(tree depth, commits / K):
measured 91 -> ~30 per 255-leaf tree (batch_k=12, round 3), ~20 with
subtraction's batch_k=24.

Gather-compacted small-node contraction (round 6, `hist_compact`): pass
COUNT optimization leaves a per-pass O(N) floor — late in a tree the
selected nodes hold ~1% of the rows yet the full-pass kernel still
contracts every chunk, so an amortized 500-iteration run spends most of
its histogram time on rows that land in no live channel. The reference
never pays this: its DataPartition keeps per-leaf index lists and
histogram cost tracks the leaf (serial_tree_learner.cpp:349-363,
data_partition.hpp:94-170). Here, when a pass's selected nodes jointly
hold at most compact_fraction*N in-bag rows (they are exactly the rows
relabeled this pass, so membership is ONE compare against the
allocation pointer; under subtraction only the smaller children's rows
count and are gathered, `rows_of_nodes`), their indices are compacted
by a stable cumsum scatter into a fixed-capacity chunk-multiple buffer
and the SAME contraction runs over the gathered subset with a dynamic
trip count (ops/histogram.gathered_leaves_histogram) — shapes stay
compile-stable, and per-pass cost drops to O(rows-in-selected-nodes).
Selection, routing, and split scans are unchanged, so trees keep the
bit-identical-to-sequential guarantee on order-invariant sums (the
gather only reorders f32 partial sums, like subtraction). The
`rows_contracted` / `pass_rows` counters record the realized economics
next to `num_passes`.

`num_leaves-1` commits, one compile per (N, F, B, L, hyperparam)
signature, reused across trees and boosting iterations.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..binning import MISSING_NAN, MISSING_ZERO
from ..ops import histogram as hist_ops
from ..ops import split as split_ops
from ..ops.lookup import row_lookup
from ..ops.split import leaf_output
from ..telemetry.layers import scope
from .schedule import compact_capacity


class GrowerConfig(NamedTuple):
    """Static hyperparameters baked into the compiled grower.

    Distributed axes (SURVEY.md §2.5, §3.5 — the reference's tree_learner
    matrix mapped onto a jax Mesh):
    - data_axis: mesh axis name over which ROWS are sharded. Histograms are
      reduced over it — the collective replacing Network::ReduceScatter +
      Allgather of HistogramBinEntry buffers (data_parallel_tree_learner
      .cpp:148-163). With hist_scatter the reduction IS a ReduceScatter
      (jax.lax.psum_scatter over the stored-group axis): each shard owns
      groups/num_data_shards of the reduced histogram, scans splits only
      for the features living in its owned slice, and the global best
      travels through the same allreduce-argmax the feature-parallel path
      uses — per-device collective bytes AND split-scan FLOPs both drop
      ~num_data_shards x vs the full-psum schedule. Without hist_scatter
      the full histogram is psum'd and every shard scores every feature
      redundantly.
    - feature_axis: mesh axis name over which FEATURES are sharded (data
      replicated). Each shard builds histograms/splits only for its feature
      block; the global best split is an allreduce-argmax on (gain, payload)
      — replacing SyncUpGlobalBestSplit (parallel_tree_learner.h:184-207).
    - num_feature_shards: size of feature_axis (features must be padded to
      a multiple of it host-side).
    - batch_k: number of nodes speculatively expanded per data pass
      (1 = the one-pass-per-split sequential behavior). 2*batch_k*(3+2)
      output channels ride one 128-lane MXU tile for batch_k <= 12.
    - hist_bf16: compute the histogram contraction with bf16 one-hot and
      hi+lo-split bf16 weights (~f32-quality sums at bf16 MXU rates).
    - max_bins is the STORED-GROUP histogram width (after EFB bundling);
      feature_bins is the per-feature scan width for split finding
      (<= max_bins; 0 means use max_bins). With bundling disabled the two
      coincide and features == groups.
    """
    num_leaves: int
    max_bins: int
    chunk: int
    lambda_l1: float
    lambda_l2: float
    min_gain_to_split: float
    min_data_in_leaf: int
    min_sum_hessian_in_leaf: float
    max_depth: int
    data_axis: Optional[str] = None
    feature_axis: Optional[str] = None
    num_feature_shards: int = 1
    # K <= 12 keeps the 2K*(3hi+2lo)-channel contraction in one 128-lane
    # MXU output tile (ops/histogram.py)
    batch_k: int = 12
    hist_bf16: bool = True
    feature_bins: int = 0
    # voting-parallel (PV-tree, voting_parallel_tree_learner.cpp): with
    # data_axis set, exchange only the globally-elected top_k features'
    # histogram slices instead of the full histogram tensor
    voting: bool = False
    top_k: int = 20
    num_data_shards: int = 1
    # ReduceScatter histogram merge (the reference data-parallel design,
    # data_parallel_tree_learner.cpp:148-163): reduce histograms with
    # psum_scatter over the stored-group axis so each data shard owns
    # groups/num_data_shards of the result and scans splits only for the
    # features in its owned slice (owned_feats table, built host-side by
    # parallel.learners.DataParallelGrower — requires the group count to
    # be padded to a shard multiple). The sibling-subtraction cache and
    # all cached per-node histograms then live at owned-slice width too.
    # Ignored for voting (which exchanges elected slices instead) and
    # under feature parallelism.
    hist_scatter: bool = False
    # static per-STORED-GROUP bin counts; the histogram kernels tile the
    # group axis into constant-row-chunk blocks scanned at each block's
    # own width (ops/histogram.plan_group_blocks). () = uniform max_bins.
    # Ignored under feature parallelism (each shard sees a traced feature
    # offset, so a static per-shard plan is impossible there).
    group_widths: tuple = ()
    # sibling subtraction (reference: FeatureHistogram::Subtract,
    # feature_histogram.hpp:64-70, retained by the HistogramPool,
    # feature_histogram.hpp:380-548): keep every speculative node's group
    # histogram in a [M, G, B, 3] cache, build only the SMALLER child's
    # histogram per expanded node and derive the larger as
    # parent - smaller. Halves the contraction channels per node, so
    # batch_k can double inside the same 128-lane MXU output tile.
    # `schedule.pick_schedule` gates this on the learner kind and on the
    # cache, at the width one device keeps of it, fitting its budget.
    hist_subtract: bool = False
    # node-table slots per num_leaves (M = table_mult*L + 2K + 2). The
    # GBDT layer raises this as far as the subtraction cache's memory
    # budget allows: generous tables keep late-boosting (flat-gain)
    # speculation wide — see the table-exhaustion notes in expand().
    table_mult: int = 6
    # gather-compacted small-node contraction (reference economics:
    # serial_tree_learner.cpp:349-363 + data_partition.hpp:94-170 —
    # per-node histogram cost tracks the LEAF's row count, not N): when
    # the nodes selected for one expansion pass jointly hold at most
    # compact_fraction*N in-bag rows, their row indices are compacted
    # device-side (stable cumsum scatter) into a fixed-capacity padded
    # buffer and the pass contracts only the gathered subset
    # (ops/histogram.gathered_leaves_histogram). Off by default at this
    # layer so raw grow_tree calls keep their exact summation order;
    # the GBDT layer turns it on for the serial/data-parallel learners
    # (f32 gather-order differences are the same class of reordering
    # subtraction already introduces — trees stay bit-identical on
    # order-invariant sums, see tests/test_grower_batching.py).
    # Disabled under feature parallelism: routing there reads the
    # replicated matrix through a traced per-shard feature offset, so a
    # compacted gather cannot keep a static group-width plan.
    hist_compact: bool = False
    # switch threshold AND buffer capacity, as a fraction of N (rounded
    # up to a chunk multiple; >= 1.0 forces every pass through the
    # compacted path — useful for tests; <= 0 disables compaction)
    compact_fraction: float = 0.25
    # rows a step of the blocked relabel routes; 0 keeps the column form
    # (`route`; `schedule.relabel_rows` picks it from stored groups and
    # batch_k). Either gives the same labels to the bit.
    relabel_rows: int = 0
    # quantized-gradient training (tpu_hist_quantize, ISSUE 20):
    # "none" | "int16" | "int8". Quantized modes expect grad/hess already
    # scaled + stochastically rounded to integer-valued f32 in
    # [-hist_qmax, hist_qmax] (ops.histogram.quantize_gradients) with
    # row_weight collapsed to the 0/1 in-bag indicator, and a [3] qscale
    # passed to grow_tree; histograms then accumulate/reduce/subtract in
    # int32 (order-invariant — scatter == serial bitwise) and dequantize
    # to real units only at the split-scoring seam.
    hist_quantize: str = "none"
    # the quantizer's clip magnitude (ops.histogram.train_qmax) — static
    # so the constant-hessian collective rebuild below can bake it in
    hist_qmax: int = 0
    # constant-hessian channel elision: when the quantizer's hess_const
    # branch is active (q_h == hist_qmax * in_bag exactly), the hess
    # channel of every data-axis histogram reduction is DERIVABLE from
    # the count channel — reduce only (g, cnt) and rebuild h = qmax*cnt
    # after the collective: 2/3 the psum/psum_scatter bytes per pass.
    hist_hess_const: bool = False


class GrowParams(NamedTuple):
    """TRACED regularization/constraint knobs, as a pytree argument.

    The shape-affecting schedule (num_leaves, max_bins, chunk, batch_k,
    ...) stays static in GrowerConfig — it decides array shapes and loop
    structure. These five knobs only enter the f32 gain/output arithmetic,
    so they can ride as runtime values: `jax.vmap` then maps a [K] array
    of them over a MODEL axis and K boosters with different
    regularization train inside ONE compiled program (learner/sweep.py),
    where the static form would retrace per distinct value. Passing
    `gp=None` to grow_tree rebuilds them from the static config — the
    compiled result is bit-identical either way (constants vs runtime
    scalars feed the same instructions; asserted per-model in
    tests/test_sweep.py)."""
    lambda_l1: jnp.ndarray
    lambda_l2: jnp.ndarray
    min_gain_to_split: jnp.ndarray
    min_data_in_leaf: jnp.ndarray
    min_sum_hessian_in_leaf: jnp.ndarray

    @classmethod
    def from_config(cls, cfg: "GrowerConfig") -> "GrowParams":
        return cls(cfg.lambda_l1, cfg.lambda_l2, cfg.min_gain_to_split,
                   cfg.min_data_in_leaf, cfg.min_sum_hessian_in_leaf)


class TreeGrowerState(NamedTuple):
    """Public result of one tree growth (what GBDT / Tree export read)."""
    leaf_id: jnp.ndarray          # [N] i32 committed LEAF SLOT per row
    # per-leaf-slot aggregates [L]; every row count the grower stores or
    # hands on (count, node_count, the table's) is int32 and exact
    sum_g: jnp.ndarray
    sum_h: jnp.ndarray
    count: jnp.ndarray
    leaf_value: jnp.ndarray
    leaf_depth: jnp.ndarray
    leaf_parent: jnp.ndarray
    num_passes: jnp.ndarray       # scalar i32: data passes this tree
    next_free: jnp.ndarray        # scalar i32: node-table high-water mark
                                  # (speculation-waste observability)
    comm_elems: jnp.ndarray       # scalar f32: elements moved through
                                  # cross-shard collectives this tree
    rows_contracted: jnp.ndarray  # scalar f32: rows fed to histogram
                                  # contractions this tree (global under
                                  # data_axis), rounded past 2^24; the
                                  # exact figure is pass_rows' sum
    pass_rows: jnp.ndarray        # [4L+64] i32 rows contracted per pass
                                  # (index = pass number; compaction
                                  # observability)
    # tree node arrays [L-1]
    node_feature: jnp.ndarray
    node_threshold: jnp.ndarray
    node_default_left: jnp.ndarray
    node_is_cat: jnp.ndarray
    node_left: jnp.ndarray
    node_right: jnp.ndarray
    node_gain: jnp.ndarray
    node_value: jnp.ndarray
    node_count: jnp.ndarray
    num_leaves_used: jnp.ndarray  # scalar i32
    # the root histogram's fullest (group, bin) count cell and its flat
    # index group * B + bin: a cell of 2^24 rows or more is past what the
    # float32 count channel holds exactly (GBDT warns, naming the feature)
    root_cell_max: jnp.ndarray    # scalar f32
    root_cell_at: jnp.ndarray     # scalar i32


class _NodeTable(NamedTuple):
    """Speculative node table, all arrays [M] (M = 6L + 2K + 2; slot M-1
    is never allocated — out-of-range scatter indices use mode='drop')."""
    parent: jnp.ndarray           # i32
    depth: jnp.ndarray            # i32
    sum_g: jnp.ndarray            # f32 node aggregates
    sum_h: jnp.ndarray
    count: jnp.ndarray            # i32, exact (as left_c)
    gain: jnp.ndarray             # cached best split of the node
    feature: jnp.ndarray
    threshold: jnp.ndarray
    default_left: jnp.ndarray
    is_cat: jnp.ndarray
    left_g: jnp.ndarray
    left_h: jnp.ndarray
    left_c: jnp.ndarray
    created: jnp.ndarray          # bool lifecycle
    expanded: jnp.ndarray
    frontier: jnp.ndarray         # leaf of the COMMITTED tree
    child_l: jnp.ndarray          # i32 spec children (valid iff expanded)
    child_r: jnp.ndarray
    leaf_slot: jnp.ndarray        # i32 committed leaf slot (frontier only)

    @classmethod
    def zeros(cls, m):
        neg_inf = jnp.float32(-jnp.inf)
        return cls(
            parent=jnp.zeros(m, jnp.int32),
            depth=jnp.zeros(m, jnp.int32),
            sum_g=jnp.zeros(m, jnp.float32),
            sum_h=jnp.zeros(m, jnp.float32),
            count=jnp.zeros(m, jnp.int32),
            gain=jnp.full(m, neg_inf),
            feature=jnp.zeros(m, jnp.int32),
            threshold=jnp.zeros(m, jnp.int32),
            default_left=jnp.zeros(m, bool),
            is_cat=jnp.zeros(m, bool),
            left_g=jnp.zeros(m, jnp.float32),
            left_h=jnp.zeros(m, jnp.float32),
            left_c=jnp.zeros(m, jnp.int32),
            created=jnp.zeros(m, bool),
            expanded=jnp.zeros(m, bool),
            frontier=jnp.zeros(m, bool),
            child_l=jnp.zeros(m, jnp.int32),
            child_r=jnp.zeros(m, jnp.int32),
            leaf_slot=jnp.zeros(m, jnp.int32),
        )


@scope("lgbm/split/extract")
def _extract_feature_hist(group_hist, sum_g, sum_h, count, fmeta, cfg):
    """Per-feature histograms [F, Bf, 3] out of the stored-group histogram
    [G, Bg, 3] (EFB layout, efb.py): feature f's bins live at
    group_hist[group[f], offset[f] : offset[f] + num_bin[f]]. For bundled
    features the default-bin slot holds no rows — its mass is leaf totals
    minus the rest (the reference's FixHistogram, dataset.cpp:747-767)."""
    g_, bg, _ = group_hist.shape
    bf = cfg.feature_bins or cfg.max_bins
    flat = group_hist.reshape(g_ * bg, 3)
    bins = jnp.arange(bf, dtype=jnp.int32)[None, :]              # [1,Bf]
    idx = fmeta["group"][:, None] * bg + fmeta["offset"][:, None] + bins
    valid = bins < fmeta["num_bin"][:, None]
    fh = flat[jnp.clip(idx, 0, g_ * bg - 1)]                     # [F,Bf,3]
    fh = jnp.where(valid[:, :, None], fh, 0.0)
    # FixHistogram for bundled features
    at_default = (bins == fmeta["default_bin"][:, None]) & \
        fmeta["is_bundled"][:, None]
    totals = jnp.stack([jnp.broadcast_to(sum_g, at_default.shape[:1]),
                        jnp.broadcast_to(sum_h, at_default.shape[:1]),
                        jnp.broadcast_to(count.astype(jnp.float32),
                                         at_default.shape[:1])], -1)
    rest = totals[:, None, :] - fh.sum(axis=1, keepdims=True)
    return jnp.where(at_default[:, :, None], rest, fh)


def _fullest_count_cell(hist, meta, first_group, num_groups):
    """(rows, flat index group * B + bin) of the fullest count cell of a
    root histogram [Gl, B, 3] that a stored count can be summed from;
    `hist` holds groups [first_group, first_group + Gl) of `num_groups`.
    Left out: groups no splittable feature maps to (the scatter
    schedule's padding columns, one-bin features: every row sits in their
    bin 0) and bin 0 of a bundle, which holds the rows at every bundled
    feature's default and is never read (a bundled feature's default bin
    is the node's count less the rest)."""
    gl, b, _ = hist.shape
    at = lambda flags: jnp.zeros(num_groups, bool).at[meta["group"]].max(
        flags)
    own = lambda mask: jax.lax.dynamic_slice_in_dim(mask, first_group, gl)
    read = own(at(meta["num_bin"] > 1))[:, None] & ~(
        own(at(meta["is_bundled"]))[:, None]
        & (jnp.arange(b, dtype=jnp.int32) == 0)[None, :])
    cells = jnp.where(read, hist[..., 2].astype(jnp.float32), 0.0)
    flat = jnp.argmax(cells.reshape(-1)).astype(jnp.int32)
    return cells.reshape(-1)[flat], flat + first_group * b


def _chosen_left_count(fh, best, count, res, meta):
    """The exact int32 left count of the split chosen at position `best`
    of the per-feature histograms `fh` [F, Bf, 3] (ops/split.py
    exact_left_count: the float32 running count of the scan stays the
    min_data_in_leaf gate and is not stored)."""
    pick = lambda arr: arr[best]
    return split_ops.exact_left_count(
        fh[best, :, 2], count, pick(res.threshold), pick(res.default_left),
        pick(res.is_categorical), pick(meta["num_bin"]),
        pick(meta["missing_type"]), pick(meta["default_bin"]),
        pick(meta["is_bundled"]))


@scope("lgbm/split/scan")
def _leaf_best_split(hist, sum_g, sum_h, count, depth, feature_mask, fmeta,
                     cfg, gp):
    """Best (gain, feature, ...) for one leaf from its (local) histogram.

    Mirrors FindBestSplitsFromHistograms (serial_tree_learner.cpp:451-516):
    per-feature best via the vectorized scan, then argmax over features with
    the per-tree feature_fraction mask and max_depth guard applied. Under
    feature parallelism the argmax covers only this shard's features and is
    then combined across shards by an allreduce-argmax (the reference's
    SyncUpGlobalBestSplit, parallel_tree_learner.h:184-207)."""
    hist = _extract_feature_hist(hist, sum_g, sum_h, count, fmeta, cfg)
    res = split_ops.find_best_splits(
        hist, sum_g, sum_h, count,
        fmeta["num_bin"], fmeta["missing_type"], fmeta["default_bin"],
        fmeta["is_categorical"],
        lambda_l1=gp.lambda_l1, lambda_l2=gp.lambda_l2,
        min_gain_to_split=gp.min_gain_to_split,
        min_data_in_leaf=gp.min_data_in_leaf,
        min_sum_hessian_in_leaf=gp.min_sum_hessian_in_leaf)
    gains = jnp.where(feature_mask, res.gain, -jnp.inf)
    if cfg.max_depth > 0:
        gains = jnp.where(depth + 1 > cfg.max_depth, -jnp.inf, gains)
    # clamp to finite: degenerate configs (min_sum_hessian=0, lambda_l2=0)
    # can yield +inf gains, and the speculative selection needs +inf free
    # as its force-include sentinel (grow_tree.expand)
    gains = jnp.minimum(gains, _GAIN_CLAMP)
    best_f = jnp.argmax(gains).astype(jnp.int32)
    pick = lambda arr: arr[best_f]
    vals = (pick(gains), best_f, pick(res.threshold), pick(res.default_left),
            pick(res.is_categorical), pick(res.left_sum_g), pick(res.left_sum_h),
            _chosen_left_count(hist, best_f, count, res, fmeta))
    if cfg.feature_axis is None:
        return vals
    # allreduce-argmax across feature shards: winner shard's payload wins,
    # ties broken toward the lowest shard index (the reference's reducer
    # compares gains then keeps the first, parallel_tree_learner.h:190-205)
    ax = cfg.feature_axis
    fl = hist.shape[0]
    fidx = jax.lax.axis_index(ax)
    gain, feat, thr, dl, cat, lg, lh, lc = vals
    feat_global = feat + fidx * fl
    gmax = jax.lax.pmax(gain, ax)
    win = (gain == gmax) & jnp.isfinite(gmax)
    wrank = jax.lax.pmin(jnp.where(win, fidx, jnp.int32(1 << 30)), ax)
    sel = win & (fidx == wrank)

    def bcast(x):
        xi = x.astype(jnp.int32) if x.dtype == jnp.bool_ else x
        z = jnp.where(sel, xi, jnp.zeros_like(xi))
        out = jax.lax.psum(z, ax)
        return out > 0 if x.dtype == jnp.bool_ else out

    return (gmax, bcast(feat_global), bcast(thr), bcast(dl), bcast(cat),
            bcast(lg), bcast(lh), bcast(lc))


@scope("lgbm/split/scan")
def _scattered_best_split(hist, sum_g, sum_h, count, depth, feature_mask,
                          fmeta, owned, gs, cfg, gp):
    """Owned-slice split finding for the ReduceScatter histogram schedule.

    `hist` is this shard's REDUCED [Gl, B, 3] stored-group slice (groups
    [gs, gs+Gl) of the global histogram, already summed over data shards
    by psum_scatter); `owned` is the [Fl] table of global feature ids
    whose stored group lives inside the slice (-1 padding — Fl is the max
    owned-feature count over shards so every shard scans one static
    shape). Each shard scans ONLY its owned features — the per-device
    split-finding FLOPs drop ~num_data_shards x vs scoring all features
    redundantly — and the winners merge through an allreduce-argmax with
    ties broken toward the LOWEST global feature id, which is exactly the
    serial argmax-over-[F] tie-break: scatter trees stay bit-identical to
    the allreduce/serial schedules even on tied gains (the reference's
    SyncUpGlobalBestSplit contract, parallel_tree_learner.h:184-207)."""
    ok = owned >= 0
    fidx = jnp.where(ok, owned, 0)
    sub = {k: v[fidx] for k, v in fmeta.items()}
    # rebase group ids into the owned slice; padded slots become 1-bin
    # trivial features that can never split
    sub["group"] = jnp.clip(sub["group"] - gs, 0, hist.shape[0] - 1)
    sub["num_bin"] = jnp.where(ok, sub["num_bin"], 1)
    fh = _extract_feature_hist(hist, sum_g, sum_h, count, sub, cfg)
    res = split_ops.find_best_splits(
        fh, sum_g, sum_h, count,
        sub["num_bin"], sub["missing_type"], sub["default_bin"],
        sub["is_categorical"],
        lambda_l1=gp.lambda_l1, lambda_l2=gp.lambda_l2,
        min_gain_to_split=gp.min_gain_to_split,
        min_data_in_leaf=gp.min_data_in_leaf,
        min_sum_hessian_in_leaf=gp.min_sum_hessian_in_leaf)
    gains = jnp.where(ok & feature_mask[fidx], res.gain, -jnp.inf)
    if cfg.max_depth > 0:
        gains = jnp.where(depth + 1 > cfg.max_depth, -jnp.inf, gains)
    gains = jnp.minimum(gains, _GAIN_CLAMP)
    # `owned` is ascending in global feature id, so argmax (first maximal
    # position) is the shard's lowest-id winner
    best = jnp.argmax(gains).astype(jnp.int32)
    pick = lambda arr: arr[best]
    gain = pick(gains)
    feat_global = owned[best]

    ax = cfg.data_axis
    gmax = jax.lax.pmax(gain, ax)
    win = (gain == gmax) & jnp.isfinite(gmax)
    wfeat = jax.lax.pmin(jnp.where(win, feat_global, jnp.int32(1 << 30)),
                         ax)
    sel = win & (feat_global == wfeat)

    def bcast(x):
        xi = x.astype(jnp.int32) if x.dtype == jnp.bool_ else x
        z = jnp.where(sel, xi, jnp.zeros_like(xi))
        out = jax.lax.psum(z, ax)
        return out > 0 if x.dtype == jnp.bool_ else out

    # the left count travels as int32: an int32 psum of one non-zero term
    return (gmax, bcast(jnp.maximum(feat_global, 0)),
            bcast(pick(res.threshold)), bcast(pick(res.default_left)),
            bcast(pick(res.is_categorical)), bcast(pick(res.left_sum_g)),
            bcast(pick(res.left_sum_h)),
            bcast(_chosen_left_count(fh, best, count, res, sub)))


@scope("lgbm/split/scan")
def _voting_children_best(hists_local, sum_g, sum_h, count, depth,
                          feature_mask, fmeta, cfg, gp):
    """Voting-parallel best splits for a batch of C children
    (reference: VotingParallelTreeLearner::FindBestSplitsFromHistograms +
    GlobalVoting + CopyLocalHistogram, voting_parallel_tree_learner
    .cpp:260-430). hists_local are LOCAL (un-reduced) group histograms
    [C, G, B, 3]; sum_g/h/count are GLOBAL child aggregates [C].

    Per child: (1) scan LOCAL histograms with constraints relaxed by
    1/num_machines (cpp:55-56), (2) submit the local top_k features'
    count-weighted gains, (3) elect the global top_k features by pmax'd
    weighted gain — replicated, no tie ambiguity, (4) psum ONLY the
    elected features' group-histogram slices, (5) full-precision scan of
    the elected features with global sums. Communication per child is
    O(top_k * B) instead of O(G * B)."""
    ax = cfg.data_axis
    m = cfg.num_data_shards
    c = hists_local.shape[0]
    bf = cfg.feature_bins or cfg.max_bins
    bg = hists_local.shape[2]

    # (1) local scans, relaxed constraints
    ltot = hists_local[:, 0].sum(axis=1)                     # [C, 3]

    def local_scan(h, lt):
        fh = _extract_feature_hist(h, lt[0], lt[1], lt[2], fmeta, cfg)
        res = split_ops.find_best_splits(
            fh, lt[0], lt[1] + 2e-15, lt[2],
            fmeta["num_bin"], fmeta["missing_type"], fmeta["default_bin"],
            fmeta["is_categorical"],
            lambda_l1=gp.lambda_l1, lambda_l2=gp.lambda_l2,
            min_gain_to_split=gp.min_gain_to_split,
            min_data_in_leaf=jnp.maximum(1, gp.min_data_in_leaf // m),
            min_sum_hessian_in_leaf=gp.min_sum_hessian_in_leaf / m)
        return res.gain

    gains_local = jax.vmap(local_scan)(hists_local, ltot)    # [C, F]
    gains_local = jnp.where(feature_mask[None, :], gains_local, -jnp.inf)

    # (2) local vote: only the local top_k features are submitted, with
    # gains weighted by the local/mean data share (GlobalVoting weighting,
    # cpp:171-180)
    kth = jax.lax.top_k(gains_local, min(cfg.top_k, gains_local.shape[1]))[0][:, -1]
    mean_cnt = jnp.maximum(count.astype(jnp.float32) / m, 1.0)  # [C] global/m
    weight = ltot[:, 2] / mean_cnt
    submitted = jnp.where(gains_local >= kth[:, None],
                          gains_local * weight[:, None], -jnp.inf)

    # (3) global election (allgather of LightSplitInfos -> pmax here)
    global_gain = jax.lax.pmax(submitted, ax)                # [C, F]
    k_sel = min(cfg.top_k, global_gain.shape[1])
    _, elected = jax.lax.top_k(global_gain, k_sel)           # [C, k]

    # (4) exchange only elected features' group slices
    egrp = fmeta["group"][elected]                            # [C, k]
    slices = jax.vmap(lambda h, g: h[g])(hists_local, egrp)   # [C, k, B, 3]
    with scope("lgbm/hist/merge"):
        slices = jax.lax.psum(slices, ax)
    comm = jnp.float32(c * k_sel * bg * 3 + c * gains_local.shape[1])

    # (5) global scan of elected features with global sums
    eoff = fmeta["offset"][elected]
    enb = fmeta["num_bin"][elected]
    bins = jnp.arange(bf, dtype=jnp.int32)[None, None, :]
    valid = bins < enb[:, :, None]
    gidx = jnp.clip(eoff[:, :, None] + bins, 0, bg - 1)
    efh = jnp.take_along_axis(
        slices, gidx[:, :, :, None], axis=2)                  # [C, k, Bf, 3]
    efh = jnp.where(valid[:, :, :, None], efh, 0.0)
    at_default = (bins == fmeta["default_bin"][elected][:, :, None]) & \
        fmeta["is_bundled"][elected][:, :, None]
    totals = jnp.stack([sum_g, sum_h, count.astype(jnp.float32)], -1)  # [C, 3]
    rest = totals[:, None, None, :] - efh.sum(axis=2, keepdims=True)
    efh = jnp.where(at_default[:, :, :, None], rest, efh)

    def global_scan(fh_c, eidx, g, h, cnt, d):
        res = split_ops.find_best_splits(
            fh_c, g, h, cnt,
            fmeta["num_bin"][eidx], fmeta["missing_type"][eidx],
            fmeta["default_bin"][eidx], fmeta["is_categorical"][eidx],
            lambda_l1=gp.lambda_l1, lambda_l2=gp.lambda_l2,
            min_gain_to_split=gp.min_gain_to_split,
            min_data_in_leaf=gp.min_data_in_leaf,
            min_sum_hessian_in_leaf=gp.min_sum_hessian_in_leaf)
        gains = jnp.where(feature_mask[eidx], res.gain, -jnp.inf)
        if cfg.max_depth > 0:
            gains = jnp.where(d + 1 > cfg.max_depth, -jnp.inf, gains)
        gains = jnp.minimum(gains, _GAIN_CLAMP)
        best = jnp.argmax(gains).astype(jnp.int32)
        pick = lambda a: a[best]
        emeta = {k: fmeta[k][eidx] for k in
                 ("num_bin", "missing_type", "default_bin", "is_bundled")}
        return (pick(gains), eidx[best], pick(res.threshold),
                pick(res.default_left), pick(res.is_categorical),
                pick(res.left_sum_g), pick(res.left_sum_h),
                _chosen_left_count(fh_c, best, cnt, res, emeta))

    vals = jax.vmap(global_scan)(efh, elected, sum_g, sum_h, count, depth)
    return vals, comm


# split gains are clamped to this finite ceiling (degenerate configs can
# produce +inf); the expansion selection then uses +inf as its
# force-include sentinel, so the commit-blocking node is ALWAYS rank 0 of
# top_k — which both guarantees progress and keeps the slot-allocation
# capacity masks monotone in rank (no allocation gaps). Plain float:
# module import must not touch the XLA backend — multihost workers call
# jax.distributed.initialize() after importing this package.
_GAIN_CLAMP = 1e30
# added to eligible frontier nodes' selection scores (expand()): gains are
# clamped to _GAIN_CLAMP, so + 2e30 strictly dominates any spec node while
# staying far below the +inf forced-include sentinel
_FRONTIER_BOOST = 2e30


class _Carry(NamedTuple):
    leaf_id: jnp.ndarray          # [N] i32: deepest SPEC node per row
    table: _NodeTable
    next_free: jnp.ndarray        # scalar i32 allocation pointer
    num_passes: jnp.ndarray
    comm_elems: jnp.ndarray
    rows_contracted: jnp.ndarray  # scalar f32 (local to this shard)
    pass_rows: jnp.ndarray        # [4L+64] i32 per-pass contracted rows
    # [M, G, B, 3] per-node group histograms (hist_subtract only; [0]
    # placeholder otherwise) — the HistogramPool analogue
    hist_cache: jnp.ndarray
    # committed-tree output state (slot-indexed), as TreeGrowerState
    sum_g: jnp.ndarray
    sum_h: jnp.ndarray
    count: jnp.ndarray
    leaf_value: jnp.ndarray
    leaf_depth: jnp.ndarray
    leaf_parent: jnp.ndarray
    node_feature: jnp.ndarray
    node_threshold: jnp.ndarray
    node_default_left: jnp.ndarray
    node_is_cat: jnp.ndarray
    node_left: jnp.ndarray
    node_right: jnp.ndarray
    node_gain: jnp.ndarray
    node_value: jnp.ndarray
    node_count: jnp.ndarray
    num_leaves_used: jnp.ndarray


@functools.partial(jax.jit, static_argnames=("cfg",))
def grow_tree(binned: jnp.ndarray, grad: jnp.ndarray, hess: jnp.ndarray,
              row_weight: jnp.ndarray, feature_mask: jnp.ndarray,
              fmeta_num_bin: jnp.ndarray, fmeta_missing: jnp.ndarray,
              fmeta_default_bin: jnp.ndarray, fmeta_is_cat: jnp.ndarray,
              fmeta_group: jnp.ndarray, fmeta_offset: jnp.ndarray,
              fmeta_is_bundled: jnp.ndarray,
              cfg: GrowerConfig, n_valid=None, owned_feats=None, gp=None,
              qscale=None):
    """Grow one leaf-wise tree.

    Args:
      binned: [N, G] integer STORED-GROUP bin indices (uint8 for <=256
        bins; G <= F after EFB bundling, efb.py), rows padded to a
        multiple of cfg.chunk (padded rows must have row_weight 0).
      grad/hess: [N] f32 gradients/hessians (GOSS amplification pre-applied
        via row_weight).
      row_weight: [N] f32 bagging weight (0 = excluded, GOSS weights > 0).
      feature_mask: [F] bool per-tree feature_fraction sample.
      fmeta_*: per-LOGICAL-feature metadata (Dataset.feature_meta_arrays).
      n_valid: optional traced GLOBAL count of real (non-padding) rows.
        Padding must be a row-suffix; histogram passes then skip the
        all-padding chunks with a dynamic trip count, which lets the GBDT
        layer bucket row counts into shared compiled signatures at ~zero
        padding cost. Under data_axis the per-shard count is derived from
        the shard's position (padding lives in the last shards).
      owned_feats: [num_data_shards, Fl] i32 owned-feature table for the
        hist_scatter schedule (-1 padding; each row ascending in global
        feature id) — required when cfg.hist_scatter is active, ignored
        otherwise. Built by parallel.learners.DataParallelGrower.
      gp: optional GrowParams pytree of TRACED regularization/constraint
        scalars; None rebuilds them from the static cfg (identical
        numerics). The vmapped sweep grower maps a [K] model axis over
        this argument (learner/sweep.py).
      qscale: [3] f32 dequantization scale (g_scale, h_scale, 1.0) —
        REQUIRED when cfg.hist_quantize != "none" (grad/hess/row_weight
        must then be the quantizer's outputs, see GrowerConfig notes);
        ignored in the f32 path so the "none" graph is unchanged.
    Returns: TreeGrowerState — the host wraps the node arrays and converts
      bin thresholds to raw-space values.
    """
    if gp is None:
        gp = GrowParams.from_config(cfg)
    quant = cfg.hist_quantize != "none"
    if quant and qscale is None:
        raise ValueError(
            "hist_quantize=%r needs the quantizer's qscale (pass the "
            "[3] scale from ops.histogram.quantize_gradients)"
            % cfg.hist_quantize)
    if not quant:
        qscale = None   # f32 path: keep the traced graph byte-identical
    dequant = functools.partial(split_ops.dequantize_hist, qscale=qscale)
    n, g_cols = binned.shape
    L = cfg.num_leaves
    B = cfg.max_bins
    K = max(1, min(cfg.batch_k, L))
    M = max(4, cfg.table_mult) * L + 2 * K + 2
    fmeta = {"num_bin": fmeta_num_bin, "missing_type": fmeta_missing,
             "default_bin": fmeta_default_bin, "is_categorical": fmeta_is_cat,
             "group": fmeta_group, "offset": fmeta_offset,
             "is_bundled": fmeta_is_bundled}
    f = fmeta_num_bin.shape[0]

    # feature parallelism: this shard builds histograms/splits only for its
    # contiguous feature block; routing still uses the full (replicated)
    # matrix (feature_parallel_tree_learner.cpp:31-69 — data replicated,
    # features partitioned per machine). Requires features == groups (the
    # GBDT layer disables EFB bundling for the feature-parallel learner).
    if cfg.feature_axis is not None:
        fl = f // cfg.num_feature_shards
        fstart = jax.lax.axis_index(cfg.feature_axis) * fl
        local_binned = jax.lax.dynamic_slice_in_dim(binned, fstart, fl, axis=1)
        local_fmeta = {k: jax.lax.dynamic_slice_in_dim(v, fstart, fl)
                       for k, v in fmeta.items()}
        # rebase group indices into the local block
        local_fmeta["group"] = local_fmeta["group"] - fstart
        local_fmask = jax.lax.dynamic_slice_in_dim(feature_mask, fstart, fl)
    else:
        fl = g_cols
        local_binned, local_fmeta, local_fmask = binned, fmeta, feature_mask

    voting = cfg.voting and cfg.data_axis is not None

    # ReduceScatter histogram schedule: reductions scatter over the
    # stored-group axis, each shard keeping its owned [Gl, B, 3] slice
    scatter = (cfg.hist_scatter and cfg.data_axis is not None
               and not voting and cfg.feature_axis is None
               and cfg.num_data_shards > 1)
    if scatter:
        if g_cols % cfg.num_data_shards != 0:
            raise ValueError(
                f"hist_scatter needs stored groups ({g_cols}) padded to a "
                f"multiple of num_data_shards ({cfg.num_data_shards})")
        if owned_feats is None:
            raise ValueError("hist_scatter requires the owned_feats table")
        gl = g_cols // cfg.num_data_shards
        gs = jax.lax.axis_index(cfg.data_axis) * gl
        # this shard's owned-feature row (the table rides replicated so
        # the same call works single- and multi-process)
        owned = jax.lax.dynamic_index_in_dim(
            jnp.asarray(owned_feats, jnp.int32),
            jax.lax.axis_index(cfg.data_axis), 0, keepdims=False)
    else:
        gl = fl
    # width of the histogram slices this shard retains after reduction
    # (the subtraction cache and all split scans live at this width)
    own_g = gl if scatter else fl

    if n_valid is None:
        nv_local = None
    elif cfg.data_axis is not None:
        # rows are sharded in contiguous blocks of n; global padding is a
        # suffix, so this shard's real-row count clamps into [0, n]
        nv_local = jnp.clip(
            n_valid - jax.lax.axis_index(cfg.data_axis) * n, 0, n)
    else:
        nv_local = jnp.minimum(n_valid, n)

    # constant-hessian channel elision (quantized modes): q_h is exactly
    # hist_qmax * in_bag per row, so the hess channel of every reduced
    # histogram equals hist_qmax * count — ship only (g, cnt) through the
    # collective and rebuild h afterwards. int32 makes the rebuild exact.
    elide_hess = (quant and cfg.hist_hess_const
                  and cfg.data_axis is not None and not voting)
    # live channels per bin crossing the data-axis collective
    red_ch = 2 if elide_hess else 3

    @scope("lgbm/hist/merge")
    def reduce_hist(h, group_dim=0):
        """Data-axis reduction seam (the ReduceScatter of
        data_parallel_tree_learner.cpp:148-163). hist_scatter reduces
        with an ACTUAL ReduceScatter over the stored-group axis (each
        shard keeps only its owned slice — ~num_data_shards x fewer
        collective bytes per device than the full psum, whose allgather
        half replicates the whole tensor everywhere); otherwise a full
        psum. Voting mode keeps histograms LOCAL; only elected slices
        travel."""
        if cfg.data_axis is not None and not voting:
            if elide_hess:
                h = h[..., 0::2]                      # (g, cnt)
            if scatter:
                h = jax.lax.psum_scatter(h, cfg.data_axis,
                                         scatter_dimension=group_dim,
                                         tiled=True)
            else:
                h = jax.lax.psum(h, cfg.data_axis)
            if elide_hess:
                cnt = h[..., 1]
                h = jnp.stack([h[..., 0], cfg.hist_qmax * cnt, cnt],
                              axis=-1)
        return h

    with scope("lgbm/grow/root_hist"):
        w3 = jnp.stack([grad * row_weight, hess * row_weight,
                        (row_weight > 0).astype(jnp.float32)], axis=-1)

    # transposed bin matrix for the routing step: row g is the contiguous
    # bin column of stored group g. Made once a tree, before the round
    # loop; XLA:TPU keeps the [N, G] uint8 matrix rows-minor already
    # (`u8[25165824,28]{0,1:T(8,128)(4,1)}`; so at 137 and 2000 groups),
    # and the transpose is a bitcast: no operation runs for it (TPU v5e,
    # PR 35's profiles). A pass reads from it the K rows its selected
    # nodes split on, whole, or every row a block at a time (`route`)
    with scope("lgbm/grow/relabel"):
        binned_T = binned.T

    if (cfg.feature_axis is None
            and len(cfg.group_widths) == local_binned.shape[1]):
        gw = cfg.group_widths
    elif (cfg.feature_axis is not None
          and len(cfg.group_widths) == g_cols
          and g_cols % cfg.num_feature_shards == 0):
        # feature parallelism: each shard's feature block starts at a
        # TRACED offset, so a per-shard exact plan is impossible — but a
        # single static plan at the PER-POSITION MAX width across shards
        # is valid for every shard (a one-hot wider than the shard's
        # actual bin count just never matches the extra lanes). On
        # homogeneous-width data (the Epsilon 15-bin regime this
        # discount exists for) the max equals the true width and the
        # full narrow-block discount survives sharding.
        gw = shard_group_widths(cfg.group_widths, cfg.num_feature_shards)
    else:
        gw = None
    # sibling subtraction: voting keeps LOCAL histograms (the cache would
    # have to be local too and the elected-slice exchange breaks the
    # parent-minus-child identity) so it keeps the direct 2K-children path
    subtract = cfg.hist_subtract and not voting

    # gather-compacted small-node contraction: a static buffer capacity,
    # 0 where every pass stays on the full kernel (schedule.py has the
    # arithmetic and the guards)
    cap = compact_capacity(cfg, n)
    compact = cap > 0
    pass_cap = 4 * L + 64   # == the round_cond hard pass cap

    # --- root (BeforeTrain: serial_tree_learner.cpp:234-323) ------------
    with scope("lgbm/grow/root_hist"):
        local_root = hist_ops.leaf_histogram(
            local_binned, w3, B, cfg.chunk, bf16=cfg.hist_bf16,
            n_valid=nv_local, group_widths=gw, quantize=cfg.hist_quantize)
        root_hist = reduce_hist(local_root)
        # global leaf sums: the reference Allreduces (cnt, sum_g, sum_h)
        # (data_parallel_tree_learner.cpp:117-145); summing any group's bins
        # gives the same totals. Voting keeps local histograms so it psums
        # the LOCAL group-0 bin sums. Scatter reads the REDUCED group-0
        # slice on its owning shard (shard 0, local index 0 — psum_scatter
        # slices are bitwise equal to the full psum) and broadcasts, so the
        # bin-sum ORDER matches the allreduce path exactly and totals stay
        # bit-identical between the two schedules.
        # The root's COUNT is the int32 sum of those count cells: a
        # float32 sum over bins goes wrong past 2^24 rows.
        if voting:
            root_tot = jax.lax.psum(local_root[0].sum(axis=0), cfg.data_axis)
            root_c = jax.lax.psum(
                split_ops.exact_count(local_root[0, :, 2]), cfg.data_axis)
        elif scatter:
            owner0 = jax.lax.axis_index(cfg.data_axis) == 0
            rt = root_hist[0].sum(axis=0)
            root_tot = jax.lax.psum(
                jnp.where(owner0, rt, jnp.zeros_like(rt)), cfg.data_axis)
            root_c = jax.lax.psum(
                jnp.where(owner0, split_ops.exact_count(root_hist[0, :, 2]),
                          0), cfg.data_axis)
        else:
            root_tot = root_hist[0].sum(axis=0)
            root_c = split_ops.exact_count(root_hist[0, :, 2])
        # quantized modes: totals leave the exact integer domain HERE; every
        # table aggregate / gain / leaf value downstream is real-unit f32
        root_tot = dequant(root_tot)
        root_g, root_h = root_tot[0], root_tot[1]
        # the fullest count cell of the (merged) root histogram, for the
        # host's warning; a scatter shard sees its owned groups and voting
        # its local rows, so those take the largest over shards
        root_cell_max, root_cell_at = _fullest_count_cell(
            root_hist, fmeta if scatter else local_fmeta,
            gs if scatter else 0, g_cols if scatter else fl)
        if scatter or voting:
            top = jax.lax.pmax(root_cell_max, cfg.data_axis)
            root_cell_at = jax.lax.pmin(
                jnp.where(root_cell_max == top, root_cell_at,
                          jnp.int32(1 << 30)), cfg.data_axis)
            root_cell_max = top
    root_comm = jnp.float32(0.0)
    if cfg.data_axis is not None:
        # per-device elements moved: voting ships 3 totals, scatter keeps
        # one owned slice, the full psum replicates every group (the
        # constant-hessian elision drops the hess channel from the
        # histogram tensor's transit: red_ch = 2)
        root_comm = jnp.float32(3.0 if voting
                                else (gl * B * red_ch + 3 if scatter
                                      else fl * B * red_ch))

    root_hist_f = dequant(root_hist)
    if voting:
        root_vals, comm1 = _voting_children_best(
            root_hist_f[None], root_g[None], root_h[None], root_c[None],
            jnp.zeros(1, jnp.int32), local_fmask, local_fmeta, cfg, gp)
        root_vals = tuple(v[0] for v in root_vals)
        root_comm = root_comm + comm1
    elif scatter:
        root_vals = _scattered_best_split(
            root_hist_f, root_g, root_h, root_c, jnp.int32(0), local_fmask,
            local_fmeta, owned, gs, cfg, gp)
    else:
        root_vals = _leaf_best_split(
            root_hist_f, root_g, root_h, root_c, jnp.int32(0), local_fmask,
            local_fmeta, cfg, gp)

    with scope("lgbm/grow/table"):
        table = _NodeTable.zeros(M)
        table = table._replace(
            parent=table.parent.at[0].set(0),
            sum_g=table.sum_g.at[0].set(root_g),
            sum_h=table.sum_h.at[0].set(root_h),
            count=table.count.at[0].set(root_c),
            gain=table.gain.at[0].set(root_vals[0]),
            feature=table.feature.at[0].set(root_vals[1]),
            threshold=table.threshold.at[0].set(root_vals[2]),
            default_left=table.default_left.at[0].set(root_vals[3]),
            is_cat=table.is_cat.at[0].set(root_vals[4]),
            left_g=table.left_g.at[0].set(root_vals[5]),
            left_h=table.left_h.at[0].set(root_vals[6]),
            left_c=table.left_c.at[0].set(root_vals[7]),
            created=table.created.at[0].set(True),
            frontier=table.frontier.at[0].set(True),
            leaf_slot=table.leaf_slot.at[0].set(0),
        )

        if subtract:
            # under hist_scatter the cache holds owned-slice histograms — the
            # parent-minus-smaller identity is linear, so it holds slice-wise.
            # Quantized modes cache the INT32 histograms: parent - child is
            # then exact, so sum(left) + sum(right) == parent holds bitwise
            # in the quantized domain (the ISSUE 20 parent-sum contract).
            hist_cache = jnp.zeros((M, own_g, B, 3),
                                   root_hist.dtype).at[0].set(root_hist)
        else:
            hist_cache = jnp.zeros((1,), jnp.float32)

        neg_inf = jnp.float32(-jnp.inf)
        # rows the root pass contracted (the full-pass kernels skip whole
        # all-padding chunks via n_valid, so count only the real rows)
        full_rows = jnp.float32(n) if nv_local is None \
            else nv_local.astype(jnp.float32)
        carry = _Carry(
            leaf_id=jnp.zeros(n, jnp.int32),
            table=table,
            next_free=jnp.int32(1),
            num_passes=jnp.int32(1),
            comm_elems=root_comm,
            rows_contracted=full_rows,
            pass_rows=jnp.zeros(pass_cap, jnp.int32).at[0].set(
                full_rows.astype(jnp.int32)),
            hist_cache=hist_cache,
            sum_g=jnp.zeros(L, jnp.float32).at[0].set(root_g),
            sum_h=jnp.zeros(L, jnp.float32).at[0].set(root_h),
            count=jnp.zeros(L, jnp.int32).at[0].set(root_c),
            leaf_value=jnp.zeros(L, jnp.float32).at[0].set(
                leaf_output(root_g, root_h, gp.lambda_l1, gp.lambda_l2)),
            leaf_depth=jnp.zeros(L, jnp.int32),
            leaf_parent=jnp.full(L, -1, jnp.int32),
            node_feature=jnp.zeros(L - 1, jnp.int32),
            node_threshold=jnp.zeros(L - 1, jnp.int32),
            node_default_left=jnp.zeros(L - 1, bool),
            node_is_cat=jnp.zeros(L - 1, bool),
            node_left=jnp.zeros(L - 1, jnp.int32),
            node_right=jnp.zeros(L - 1, jnp.int32),
            node_gain=jnp.zeros(L - 1, jnp.float32),
            node_value=jnp.zeros(L - 1, jnp.float32),
            node_count=jnp.zeros(L - 1, jnp.int32),
            num_leaves_used=jnp.int32(1),
        )

    def expand(carry: _Carry) -> _Carry:
        """One speculative expansion pass: select up to K unexpanded nodes
        (commit-blocking argmax force-included), route+relabel their rows
        under their cached splits, build both children's histograms in one
        contraction, scan the children's best splits into the table."""
        with scope("lgbm/grow/select"):
            t = carry.table
            eligible = t.created & ~t.expanded & (t.gain > 0.0)
            # budget-aware speculation throttle: the tree has R = L - used
            # commits left, so only nodes whose gain ranks within the top R
            # of the current commit-candidate pool (frontier nodes + created
            # unexpanded spec nodes) are worth slots. Without this, every
            # eventual LEAF with positive gain attracts one speculative
            # expansion that never commits (~2L wasted slots late in
            # boosting, when gains flatten), the table hits its capacity
            # reserve, and passes degrade to one forced expansion per commit
            # (measured: 18 -> 145 passes/tree by iteration 100 at 2M rows).
            # Like any selection policy this only changes WHICH precompute
            # happens early — commits stay bit-identical.
            # rank-count formulation: a node passes iff fewer than R pool
            # gains strictly beat it (ties all pass — harmless slack) — an
            # [M, M] compare, ~1M bool ops.
            R = L - carry.num_leaves_used
            pool = t.created & (t.gain > 0.0) & (t.frontier | ~t.expanded)
            pg = jnp.where(pool, t.gain, neg_inf)
            rank = jnp.sum((pg[None, :] > t.gain[:, None]).astype(jnp.int32),
                           axis=1)                                # [M]
            f_gain = jnp.where(t.frontier, t.gain, neg_inf)
            f_arg = jnp.argmax(f_gain).astype(jnp.int32)
            # the commit-blocking frontier argmax is EXEMPT from the
            # throttle: deep spec nodes elsewhere can out-rank every
            # frontier gain, and throttling the argmax would deadlock the
            # commit chain — the expansion loop then spins without progress
            # until the device watchdog kills the worker (observed as a
            # mid-run "TPU worker crashed" at 2M rows, iteration ~50+).
            eligible = eligible & ((rank < R)
                                   | (jnp.arange(M, dtype=jnp.int32) == f_arg))
            # frontier-first selection: unexpanded FRONTIER nodes are the
            # commit chain's immediate blockers — every one expanded this
            # pass is a commit the next drain can pop — so they outrank
            # deeper speculative nodes regardless of raw gain (late-boosting
            # flat gains otherwise spend the batch on spec descendants while
            # the drain stalls one forced expansion per round). Selection
            # policy only: commits stay bit-identical.
            score = jnp.where(eligible, t.gain, neg_inf)
            if K >= 12:
                # wide batches only: narrow batches (wide-shape configs,
                # K<=8) serve depth-bound trees where the deep chain — not
                # frontier breadth — is the scarce resource (Bosch-shape
                # measured slower with the boost)
                score = jnp.where(eligible & t.frontier,
                                  score + _FRONTIER_BOOST, score)
            score = score.at[f_arg].set(
                jnp.where(eligible[f_arg], jnp.inf, score[f_arg]))
            top_gain, sel = jax.lax.top_k(score, K)
            valid = top_gain > neg_inf                           # [K]

            # allocate child slots (rank-compacted so padding slots don't
            # leak table space). Capacity invariant: every future commit may
            # need one forced expansion of the frontier argmax (2 slots), so
            # SPECULATIVE allocations must leave 2*(L - num_leaves_used)
            # slots in reserve — the forced expansion itself may dip into
            # the reserve. This keeps the commit chain unblockable and the
            # bit-identical-to-sequential guarantee unconditional, for any
            # table fill pattern.
            rank = jnp.cumsum(valid.astype(jnp.int32)) \
                - valid.astype(jnp.int32)
            cl = carry.next_free + 2 * rank
            cr = cl + 1
            reserve = 2 * (L - carry.num_leaves_used)
            is_forced = eligible[f_arg] & (sel == f_arg)
            # (measured dead end, kept as a note: tying cumulative slot
            # spend to commit progress — e.g. 4 slots per committed leaf —
            # bounds the table mathematically but chokes the broad
            # speculation that flat-gain trees NEED to keep commits batched:
            # passes got WORSE, 105 -> 147 at iterations 100+. Generous
            # tables beat tight budgets here.)
            valid = valid & jnp.where(is_forced, cr < M, cr + reserve < M)
            cl_eff = jnp.where(valid, cl, M)
            cr_eff = jnp.where(valid, cr, M)
            sel_eff = jnp.where(valid, sel, M)
            next_free = carry.next_free + 2 * jnp.sum(valid.astype(jnp.int32))

            # histogram ids: direct mode builds BOTH children; subtraction
            # mode builds only each node's SMALLER child (the larger comes
            # from parent - smaller below, feature_histogram.hpp:64-70)
            sel_c = jnp.clip(sel, 0, M - 1)
            if subtract:
                small_left = t.left_c[sel_c] * 2 <= t.count[sel_c]    # [K]
                hist_ids = jnp.where(valid,
                                     jnp.where(small_left, cl, cr), -1)
            else:
                hist_ids = jnp.concatenate([jnp.where(valid, cl, -1),
                                            jnp.where(valid, cr, -1)])

        with scope("lgbm/grow/relabel"):
            leaf_id = route(carry.leaf_id, binned_T, fmeta, t, sel, valid,
                            cl, cr, block_rows=cfg.relabel_rows)

        if compact:
            # member rows of THIS pass's selected nodes are exactly the
            # rows just relabeled to fresh child ids — every id >=
            # next_free is new this pass (the allocation pointer is
            # monotone), so membership is one compare, no K-loop.
            # Under subtraction only the SMALLER child of a node lands
            # in a channel (the larger is parent - smaller), so only
            # its rows are members: the K ids of `hist_ids`.
            # Zero-weight (out-of-bag / padding) rows contribute zero to
            # every channel either way; excluding them keeps small
            # bagged nodes inside the buffer.
            with scope("lgbm/grow/compact_index"):
                if subtract:
                    in_pass = rows_of_nodes(leaf_id, hist_ids)
                else:
                    in_pass = leaf_id >= carry.next_free
                member = in_pass & (w3[:, 2] > 0.0)
                cnt = jnp.sum(member.astype(jnp.int32))
                use_compact = cnt <= cap

            def gathered(_):
                # stable compaction: cumsum ranks keep row order, so the
                # gathered chunks sum rows in their original relative
                # order. Built INSIDE the branch: cond executes only the
                # taken side, so full passes skip the cumsum + scatter.
                with scope("lgbm/grow/compact_index"):
                    pos = jnp.cumsum(member.astype(jnp.int32)) - 1
                    rows_buf = jnp.zeros(cap, jnp.int32).at[
                        jnp.where(member, pos, cap)].set(
                            jnp.arange(n, dtype=jnp.int32), mode="drop")
                return hist_ops.gathered_leaves_histogram(
                    local_binned, w3, leaf_id, rows_buf, hist_ids, B,
                    cfg.chunk, bf16=cfg.hist_bf16, n_valid=cnt,
                    group_widths=gw, quantize=cfg.hist_quantize)

            with scope("lgbm/hist/contract"):
                hists = jax.lax.cond(
                    use_compact,
                    gathered,
                    lambda _: hist_ops.batched_leaves_histogram(
                        local_binned, w3, leaf_id, hist_ids, B, cfg.chunk,
                        bf16=cfg.hist_bf16, n_valid=nv_local,
                        group_widths=gw, quantize=cfg.hist_quantize),
                    None)
                rows_pass = jnp.where(use_compact, cnt.astype(jnp.float32),
                                      full_rows)
        else:
            hists = hist_ops.batched_leaves_histogram(
                local_binned, w3, leaf_id, hist_ids, B, cfg.chunk,
                bf16=cfg.hist_bf16, n_valid=nv_local,
                group_widths=gw, quantize=cfg.hist_quantize)
            rows_pass = full_rows
        # [C, G, B, 3]: the stored-group axis is dim 1
        hists = reduce_hist(hists, group_dim=1)
        # per-device elements kept from this reduction (C = K under
        # subtraction — only the smaller children travel — else 2K)
        red_c = hists.shape[0]

        if subtract:
            # larger child = parent - smaller (the cache holds every
            # created node's histogram; parents are always present)
            with scope("lgbm/grow/subtract"):
                # K reads of one slot each, not `hist_cache[sel_c]`: that
                # gather XLA:TPU serves by slicing the WHOLE cache in two
                # (`mini-gather-slice`), a second buffer of the cache's
                # size copied every pass (7.4 ms a pass at 2.39 GB, 14.2
                # at 4.7: TPU v5e, PR 31, PERF.md section 6)
                parent_h = jnp.stack([
                    jax.lax.dynamic_index_in_dim(
                        carry.hist_cache, sel_c[k], axis=0, keepdims=False)
                    for k in range(K)])                      # [K, fl, B, 3]
                other = parent_h - hists
                sl4 = small_left[:, None, None, None]
                hists = jnp.concatenate([jnp.where(sl4, hists, other),
                                         jnp.where(sl4, other, hists)])
            # [2K, fl, B, 3] — same (left-block, right-block) layout as
            # the direct path from here on

        # children aggregates from the parents' cached split stats
        with scope("lgbm/grow/table"):
            pg, ph, pc = t.sum_g[sel_c], t.sum_h[sel_c], t.count[sel_c]
            lg, lh = t.left_g[sel_c], t.left_h[sel_c]
            lcc = t.left_c[sel_c]
            cdepth = t.depth[sel_c] + 1
            all_g = jnp.concatenate([lg, pg - lg])
            all_h = jnp.concatenate([lh, ph - lh])
            all_c = jnp.concatenate([lcc, pc - lcc])
            all_d = jnp.concatenate([cdepth, cdepth])

        # split scoring reads real-unit f32; the int32 histograms stay
        # exact for the cache/subtraction identity above
        hists_f = dequant(hists)
        comm = jnp.float32(0.0)
        if voting:
            vals2, comm = _voting_children_best(
                hists_f, all_g, all_h, all_c, all_d,
                local_fmask, local_fmeta, cfg, gp)
        else:
            if cfg.data_axis is not None:
                comm = jnp.float32(red_c * own_g * B * red_ch)
            if scatter:
                split_fn = jax.vmap(
                    lambda h, g, hh, c, d: _scattered_best_split(
                        h, g, hh, c, d, local_fmask, local_fmeta,
                        owned, gs, cfg, gp))
            else:
                split_fn = jax.vmap(
                    lambda h, g, hh, c, d: _leaf_best_split(
                        h, g, hh, c, d, local_fmask, local_fmeta, cfg,
                        gp))
            vals2 = split_fn(hists_f, all_g, all_h, all_c, all_d)
        gain2, feat2, thr2, dl2, cat2, lg2, lh2, lc2 = vals2

        with scope("lgbm/grow/table"):
            idx = jnp.concatenate([cl_eff, cr_eff])          # [2K], M = drop
            par2 = jnp.concatenate([sel_eff, sel_eff])
            hist_cache = carry.hist_cache
            if subtract:
                # children become candidate parents: retain their histograms
                hist_cache = hist_cache.at[idx].set(hists, mode="drop")
            t = t._replace(
                parent=t.parent.at[idx].set(par2, mode="drop"),
                depth=t.depth.at[idx].set(all_d, mode="drop"),
                sum_g=t.sum_g.at[idx].set(all_g, mode="drop"),
                sum_h=t.sum_h.at[idx].set(all_h, mode="drop"),
                count=t.count.at[idx].set(all_c, mode="drop"),
                gain=t.gain.at[idx].set(gain2, mode="drop"),
                feature=t.feature.at[idx].set(feat2, mode="drop"),
                threshold=t.threshold.at[idx].set(thr2, mode="drop"),
                default_left=t.default_left.at[idx].set(dl2, mode="drop"),
                is_cat=t.is_cat.at[idx].set(cat2, mode="drop"),
                left_g=t.left_g.at[idx].set(lg2, mode="drop"),
                left_h=t.left_h.at[idx].set(lh2, mode="drop"),
                left_c=t.left_c.at[idx].set(lc2, mode="drop"),
                created=t.created.at[idx].set(True, mode="drop"),
                expanded=t.expanded.at[sel_eff].set(True, mode="drop"),
                child_l=t.child_l.at[sel_eff].set(cl, mode="drop"),
                child_r=t.child_r.at[sel_eff].set(cr, mode="drop"),
            )
            return carry._replace(
                leaf_id=leaf_id, table=t, next_free=next_free,
                num_passes=carry.num_passes + 1,
                comm_elems=carry.comm_elems + comm,
                rows_contracted=carry.rows_contracted + rows_pass,
                pass_rows=carry.pass_rows.at[carry.num_passes].set(
                    rows_pass.astype(jnp.int32), mode="drop"),
                hist_cache=hist_cache)

    # --- commit (Train: serial_tree_learner.cpp:152-205) ----------------
    # strict best-first: pop the frontier argmax, write the tree node,
    # promote the (speculatively created) children to the frontier.
    # Touches only [M]/[L]-sized state — zero data passes.
    C = max(4, 2 * K)  # commits drained per round

    def commit_one(carry: _Carry):
        t = carry.table
        f_gain = jnp.where(t.frontier, t.gain, neg_inf)
        l = jnp.argmax(f_gain).astype(jnp.int32)
        feat = t.feature[l]
        thr = t.threshold[l]
        dl = t.default_left[l]
        cat = t.is_cat[l]
        lg, lh, lc = t.left_g[l], t.left_h[l], t.left_c[l]
        pg, ph, pc = t.sum_g[l], t.sum_h[l], t.count[l]
        rg, rh, rc = pg - lg, ph - lh, pc - lc
        slot_l = t.leaf_slot[l]
        new_slot = carry.num_leaves_used
        node = carry.num_leaves_used - 1

        # tree bookkeeping (Tree::Split, tree.cpp:50-69)
        parent_node = carry.leaf_parent[slot_l]
        has_parent = parent_node >= 0
        pn = jnp.maximum(parent_node, 0)
        fix_left = carry.node_left[pn] == ~slot_l
        node_left = carry.node_left.at[pn].set(
            jnp.where(has_parent & fix_left, node, carry.node_left[pn]))
        node_right = carry.node_right.at[pn].set(
            jnp.where(has_parent & ~fix_left, node, carry.node_right[pn]))
        node_left = node_left.at[node].set(~slot_l)
        node_right = node_right.at[node].set(~new_slot)

        depth_l = carry.leaf_depth[slot_l]
        lv = leaf_output(lg, lh, gp.lambda_l1, gp.lambda_l2)
        rv = leaf_output(rg, rh, gp.lambda_l1, gp.lambda_l2)

        cl, cr = t.child_l[l], t.child_r[l]
        t = t._replace(
            frontier=t.frontier.at[l].set(False)
                               .at[cl].set(True).at[cr].set(True),
            leaf_slot=t.leaf_slot.at[cl].set(slot_l).at[cr].set(new_slot),
        )
        return carry._replace(
            table=t,
            sum_g=carry.sum_g.at[slot_l].set(lg).at[new_slot].set(rg),
            sum_h=carry.sum_h.at[slot_l].set(lh).at[new_slot].set(rh),
            count=carry.count.at[slot_l].set(lc).at[new_slot].set(rc),
            leaf_value=carry.leaf_value.at[slot_l].set(lv)
                                       .at[new_slot].set(rv),
            leaf_depth=carry.leaf_depth.at[slot_l].set(depth_l + 1)
                                       .at[new_slot].set(depth_l + 1),
            leaf_parent=carry.leaf_parent.at[slot_l].set(node)
                                         .at[new_slot].set(node),
            node_feature=carry.node_feature.at[node].set(feat),
            node_threshold=carry.node_threshold.at[node].set(thr),
            node_default_left=carry.node_default_left.at[node].set(dl),
            node_is_cat=carry.node_is_cat.at[node].set(cat),
            node_left=node_left,
            node_right=node_right,
            node_gain=carry.node_gain.at[node].set(t.gain[l]),
            node_value=carry.node_value.at[node].set(
                leaf_output(pg, ph, gp.lambda_l1, gp.lambda_l2)),
            node_count=carry.node_count.at[node].set(pc),
            num_leaves_used=carry.num_leaves_used + 1,
        )

    def _can_commit(carry: _Carry):
        t = carry.table
        f_gain = jnp.where(t.frontier, t.gain, neg_inf)
        l = jnp.argmax(f_gain).astype(jnp.int32)
        return ((f_gain[l] > 0.0) & t.expanded[l]
                & (carry.num_leaves_used < L))

    def round_body(carry: _Carry) -> _Carry:
        carry = expand(carry)

        # drain: commit in strict argmax order until the argmax is an
        # unexpanded node (next round's forced expansion) or the round's
        # commit budget is spent. A while_loop (not fori+cond) so empty
        # drain steps cost nothing and committed state never round-trips
        # through cond branches.
        start = carry.num_leaves_used

        def drain_cond(carry):
            return (carry.num_leaves_used - start < C) & _can_commit(carry)

        with scope("lgbm/grow/commit"):
            return jax.lax.while_loop(drain_cond, commit_one, carry)

    def round_cond(carry: _Carry):
        with scope("lgbm/grow/commit"):
            t = carry.table
            f_gain = jnp.where(t.frontier, t.gain, neg_inf)
            growing = (carry.num_leaves_used < L) & (jnp.max(f_gain) > 0.0)
            # safety nets only: the reservation rule in expand() guarantees
            # the blocking argmax always has room (progress), and a tree can
            # never need more rounds than commits (each round commits >= 1
            # via the forced expansion) — the hard cap turns any future
            # no-progress bug into a truncated tree instead of an infinite
            # device loop that gets the TPU worker killed.
            f_arg = jnp.argmax(f_gain)
            progress = t.expanded[f_arg] | (carry.next_free + 1 < M)
            return growing & progress & (carry.num_passes < 4 * L + 64)

    carry = jax.lax.while_loop(round_cond, round_body, carry)

    # --- map rows to committed leaf slots -------------------------------
    # rows are labeled with UNEXPANDED spec node ids; each maps to its
    # nearest frontier ancestor's leaf slot. Saturating pointer-doubling
    # (ancestors stop at resolved nodes so a jump can never skip the
    # frontier into the committed region); spec depth is bounded by the
    # number of allocations (M/2), so ceil(log2(M))+1 hops always resolve.
    with scope("lgbm/grow/finalize"):
        t = carry.table
        slot_map = jnp.where(t.frontier, t.leaf_slot, -1)
        anc = jnp.where(t.frontier, jnp.arange(M, dtype=jnp.int32), t.parent)
        hops = int(M).bit_length() + 1

        def hop(_, sm_anc):
            sm, a = sm_anc
            sm = jnp.where(sm >= 0, sm, sm[a])
            a = jnp.where(sm >= 0, jnp.arange(M, dtype=jnp.int32),
                          jnp.where(sm[a] >= 0, a, a[a]))
            return sm, a

        slot_map, _ = jax.lax.fori_loop(0, hops, hop, (slot_map, anc))
        slot_map = jnp.clip(slot_map, 0, L - 1)
        leaf_slot_of_row = row_lookup(
            slot_map, jnp.clip(carry.leaf_id, 0, M - 1))

    # the contraction counters are per-shard (each shard compacts its own
    # rows and may even take a different path per pass); sum them once so
    # the returned observability state is GLOBAL and truly replicated —
    # the distributed learners' out_specs mark all non-leaf_id state
    # replicated (parallel/learners.py)
    rows_contracted = carry.rows_contracted
    pass_rows = carry.pass_rows
    if cfg.data_axis is not None:
        rows_contracted = jax.lax.psum(rows_contracted, cfg.data_axis)
        pass_rows = jax.lax.psum(pass_rows, cfg.data_axis)

    return TreeGrowerState(
        leaf_id=leaf_slot_of_row,
        sum_g=carry.sum_g, sum_h=carry.sum_h, count=carry.count,
        leaf_value=carry.leaf_value, leaf_depth=carry.leaf_depth,
        leaf_parent=carry.leaf_parent,
        num_passes=carry.num_passes, next_free=carry.next_free,
        comm_elems=carry.comm_elems,
        rows_contracted=rows_contracted, pass_rows=pass_rows,
        node_feature=carry.node_feature,
        node_threshold=carry.node_threshold,
        node_default_left=carry.node_default_left,
        node_is_cat=carry.node_is_cat,
        node_left=carry.node_left, node_right=carry.node_right,
        node_gain=carry.node_gain, node_value=carry.node_value,
        node_count=carry.node_count,
        num_leaves_used=carry.num_leaves_used,
        root_cell_max=root_cell_max, root_cell_at=root_cell_at,
    )


def leaf_path_features(leaf_parent, node_feature, node_left, node_right,
                       num_leaves_used, k: int):
    """Per-leaf candidate features for linear leaves: the first `k`
    DISTINCT split features on the leaf's root path, nearest-the-leaf
    first ("top-k by path proximity" — the splits closest to the leaf
    are the ones that shaped its region most recently).

    Inputs are TreeGrowerState arrays: `leaf_parent[l]` is the internal
    node whose split created leaf slot l (-1 for unused slots and the
    single-leaf tree), node_left/node_right encode leaves as `~slot`.
    Features are in used-feature (inner) space, like node_feature.
    Returns [L, k] i32, -1-padded. Traceable; `k` static.
    """
    m = node_left.shape[0]                           # L - 1 node slots
    nodes = jnp.arange(m, dtype=jnp.int32)
    # parent of each internal node, scattered from the child links;
    # only committed nodes may write (stale slots hold zeros, which
    # would otherwise claim node 0 as their child)
    valid = nodes < num_leaves_used - 1
    idx_l = jnp.where(valid & (node_left >= 0), node_left, m)
    idx_r = jnp.where(valid & (node_right >= 0), node_right, m)
    node_parent = jnp.full(m, -1, jnp.int32)
    node_parent = node_parent.at[idx_l].set(nodes, mode="drop")
    node_parent = node_parent.at[idx_r].set(nodes, mode="drop")

    def one_leaf(start):
        def body(_, carry):
            feats, cnt, node = carry
            live = node >= 0
            f = node_feature[jnp.maximum(node, 0)]
            take = live & ~jnp.any(feats == f) & (cnt < k)
            feats = feats.at[jnp.where(take, cnt, k)].set(f, mode="drop")
            cnt = cnt + take.astype(jnp.int32)
            node = jnp.where(live, node_parent[jnp.maximum(node, 0)], -1)
            return feats, cnt, node
        feats0 = jnp.full((k,), -1, jnp.int32)
        feats, _, _ = jax.lax.fori_loop(
            0, m, body, (feats0, jnp.int32(0), start))
        return feats

    return jax.vmap(one_leaf)(leaf_parent.astype(jnp.int32))


def _goes_left(bins, s):
    """The split rule on stored-group bins (int32, any shape): `s` holds
    each split's scalars, broadcastable against `bins`."""
    # EFB decode (efb.py): inside the feature's bundle slice the group
    # bin is offset+bin; anywhere else the row sits at the default bin
    in_slice = (bins >= s["off"]) & (bins < s["off"] + s["nb"])
    bins = jnp.where(s["bundled"],
                     jnp.where(in_slice, bins - s["off"], s["dbin"]), bins)
    is_missing = (((s["missing"] == MISSING_NAN) & (bins == s["nb"] - 1))
                  | ((s["missing"] == MISSING_ZERO) & (bins == s["dbin"])))
    return jnp.where(s["cat"], bins == s["thr"],
                     jnp.where(is_missing, s["dl"], bins <= s["thr"]))


def route(leaf_id, binned_T, fmeta, splits, sel, valid, cl, cr,
          block_rows: int = 0):
    """Apply the K selected splits to a leaf-label vector (replaces
    DataPartition::Split, data_partition.hpp:94-170): a row labelled
    `sel[k]` with `valid[k]` moves to `cl[k]` or `cr[k]` by node
    `sel[k]`'s cached split in `splits` (`.feature`, `.threshold`,
    `.default_left`, `.is_cat`, each [M]); every other row keeps its
    label. `binned_T` is the [G, N] transposed bin matrix, `fmeta` the
    per-feature tables; a slot with `valid` false may hold any `sel`.
    Each split is a handful of scalars and the arithmetic is integer
    logic, so the two forms give the same labels to the bit; which one a
    run takes is `schedule.relabel_rows`'s.

    `block_rows` 0, the column form: each node's bin column is ONE row
    of `binned_T`, a contiguous dynamic slice, and the labels thread
    through K selects. XLA:TPU fuses the slices into the select up to
    about 12 nodes; at 24 it writes each out as `s32[1, N]` first and
    reads them back (`schedule.RELABEL_BLOCK` has the readings).

    `block_rows` > 0, the blocked form: one loop over blocks of that
    many rows, rows on the minor axis as in `ops/lookup.row_lookup`,
    `leaf_id` updated in place. A block of b rows reads its labels and
    the WHOLE `[G, b]` uint8 bin block, picks each node's group row by a
    `[K, G] x [G, b]` one-hot product in bfloat16 (bins are at most 255
    and one term is non-zero: exact), compares `[K, b]` at once and
    writes its labels once. Nothing of length N exists inside a pass but
    `leaf_id` itself; it reads G bytes a row, so it is the narrow side's.
    The last block is pulled back inside the rows, so rows it shares
    with the block before are routed twice. That is routing them once:
    the children `cl`, `cr` are fresh ids, never a selected node's (the
    allocation pointer is past every created node)."""
    (n,), K = leaf_id.shape, sel.shape[0]
    G = binned_T.shape[0]
    m = jnp.clip(sel, 0, splits.feature.shape[0] - 1)
    feat = splits.feature[m]
    grp = fmeta["group"][feat]
    s = {"off": fmeta["offset"][feat], "nb": fmeta["num_bin"][feat],
         "dbin": fmeta["default_bin"][feat],
         "missing": fmeta["missing_type"][feat],
         "bundled": fmeta["is_bundled"][feat],
         "thr": splits.threshold[m], "dl": splits.default_left[m],
         "cat": splits.is_cat[m]}                               # each [K]

    if not block_rows:
        for k in range(K):
            bins = jax.lax.dynamic_slice(
                binned_T, (grp[k], 0), (1, n))[0].astype(jnp.int32)
            go_left = _goes_left(bins, {a: v[k] for a, v in s.items()})
            in_k = valid[k] & (leaf_id == sel[k])
            leaf_id = jnp.where(in_k, jnp.where(go_left, cl[k], cr[k]),
                                leaf_id)
        return leaf_id

    if binned_T.dtype != jnp.uint8:
        raise TypeError("the blocked relabel moves bins through bfloat16, "
                        f"exact to 255, not {binned_T.dtype}")
    b = min(block_rows, n)
    pick = (grp[:, None] == jnp.arange(G, dtype=grp.dtype)[None, :]
            ).astype(jnp.bfloat16)                              # [K, G]
    s = {a: v[:, None] for a, v in s.items()}
    sel, valid, cl, cr = (v[:, None] for v in (sel, valid, cl, cr))

    def one_block(i, lid):
        start = jnp.minimum(i * b, n - b)
        lb = jax.lax.dynamic_slice(lid, (start,), (b,))
        bins = jnp.dot(
            pick, jax.lax.dynamic_slice(binned_T, (0, start), (G, b)
                                        ).astype(jnp.bfloat16),
            preferred_element_type=jnp.float32).astype(jnp.int32)   # [K, b]
        in_k = valid & (lb[None, :] == sel)
        # a row is in at most one selected node; child ids are positive
        child = jnp.max(jnp.where(
            in_k, jnp.where(_goes_left(bins, s), cl, cr), 0), axis=0)
        return jax.lax.dynamic_update_slice(
            lid, jnp.where(child > 0, child, lb), (start,))

    return jax.lax.fori_loop(0, -(-n // b), one_block, leaf_id)


def rows_of_nodes(leaf_id, node_ids):
    """[N] bool: the rows whose label is one of the K `node_ids` (-1 for
    an empty slot; labels are never negative). K compares a row with the
    rows on the minor axis, no gather: K is a pass's batch."""
    return jnp.any(leaf_id[None, :] == node_ids[:, None], axis=0)


def shard_group_widths(group_widths, num_shards: int):
    """Per-position max of the per-shard feature-block widths: the one
    static block plan that is correct for every feature shard (see the
    feature_axis branch in grow_tree)."""
    fl = len(group_widths) // num_shards
    return tuple(max(int(group_widths[s * fl + j])
                     for s in range(num_shards))
                 for j in range(fl))


FMETA_KEYS = ("num_bin", "missing_type", "default_bin", "is_categorical",
              "group", "offset", "is_bundled")


def make_grower(cfg: GrowerConfig):
    """Convenience closure binding the static config."""
    def run(binned, grad, hess, row_weight, feature_mask, fmeta):
        return grow_tree(binned, grad, hess, row_weight, feature_mask,
                         *[fmeta[k] for k in FMETA_KEYS], cfg)
    return run
