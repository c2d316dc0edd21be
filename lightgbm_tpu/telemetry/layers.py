"""The names this program gives its layers, in one place.

Four vocabularies, each read by `telemetry/devtrace.py` and by the
benchmark's per-layer readers:

- `SCOPES` — `jax.named_scope` names inside the jitted programs
  (`scope(name)`). A scope is trace-time metadata only: it lands in the
  HLO's `op_name` and from there in the profiler's device events, so a
  device operation can be charged to the layer that emitted it whatever
  number XLA gives its fusion. It adds no operation and changes no
  fusion; jax's persistent compile cache keys on the program WITHOUT this
  metadata, so an executable cached before the scopes existed is loaded
  as it was, names missing.
- `ITER_SPANS` — host spans of one boosting iteration
  (`telemetry.span(name, iteration=i)`), written into the profiler's own
  trace so they share a clock with the device. They tell HOST time: none
  of them waits for the device except `lgbm/iter/fetch`, which IS the
  wait.
- `DATASET_SPANS` — host spans of one `Dataset` construction
  (`ingest/build.build_inner`), by phase. Their seconds are also kept,
  always, as the `ConstructRecord` on the dataset they built.
- `INIT_SPANS` — host spans of one `GBDT.init`, by phase. Their seconds
  are also kept, always, as the `InitRecord` on the booster
  (`GBDT.init_record`).
- `TreeRecord` — the per-tree entry of `GBDT.pass_log`.

`Phases` is how the two record-keeping layers take their seconds.
"""
from __future__ import annotations

import functools
import time
from typing import NamedTuple, Tuple

from .metrics import span

SCOPES = (
    "lgbm/gradients",         # objective gradients (boosting/gbdt.py)
    # lambdarank's share of it, by stage (objectives.py); each is opened
    # INSIDE lgbm/gradients, and devtrace charges an event to the longest
    # name at the innermost place
    "lgbm/gradients/rank_sort",     # scores gathered into the padded query
                                    # batches, the two argsorts
    "lgbm/gradients/rank_pairs",    # the [Qb, D, D] pair arithmetic and
                                    # its two reductions
    "lgbm/gradients/rank_scatter",  # lambdas and hessians back to rows
    "lgbm/grow/root_hist",    # the root's full pass and its totals
    "lgbm/grow/select",       # gain ranking, top_k, child slot allocation
    "lgbm/grow/relabel",      # grow.route(): rows of the selected nodes ->
                              # children; blocked (a block's labels, its
                              # [G, b] uint8 bins, a one-hot product, one
                              # in-place write) or by whole bin columns
                              # (schedule.relabel_rows); and binned.T
    "lgbm/grow/compact_index",  # member mask, cumsum, scatter of row indices
    "lgbm/hist/gather",       # bins and (g, h, w) gathered through the index
    "lgbm/hist/contract",     # the one-hot contraction, full or gathered
    "lgbm/hist/operand",      # its [chunk, S] channel operand: the rows'
                              # channels in their node's columns, 0 elsewhere
    "lgbm/hist/merge",        # data-axis psum / psum_scatter of histograms
    "lgbm/grow/subtract",     # larger child = parent - smaller
    "lgbm/split/scan",        # ops/split.find_best_splits and its vmaps
    "lgbm/split/extract",     # opened INSIDE lgbm/split/scan: per-feature
                              # histograms gathered out of the stored
                              # groups', a bundled feature's default bin
                              # repaired (grow._extract_feature_hist)
    "lgbm/grow/table",        # node-table and histogram-cache writes
    "lgbm/grow/commit",       # the drain: frontier argmax -> tree node
    "lgbm/grow/finalize",     # slot-map hops, then rows -> committed leaf
                              # slots by one-hot lookup (ops/lookup.py)
    "lgbm/score/update",      # leaf values onto the training score, by
                              # one-hot lookup
)

ITER_SPANS = (
    "lgbm/iter/gradients",
    "lgbm/iter/bagging",
    "lgbm/iter/dispatch",     # the enqueue of the grow(+update) program
    "lgbm/iter/fetch",        # device_get of a tree's small state: the wait
    "lgbm/iter/build_tree",   # Tree.from_grower_state, shrinkage, bookkeeping
)

INIT_SPANS = (
    "lgbm/init/objective",    # objective.init (label statistics; lambdarank's
                              # pair layout), its padding, the metrics' init
    "lgbm/init/schedule",     # row layout, the padded host matrix,
                              # pick_schedule, grower configuration, the
                              # distributed grower and its mesh
    "lgbm/init/state",        # score, weights, per-row objective arrays to
                              # their sharding, _fmeta, boost-from-average
    "lgbm/init/land",         # the binned matrix to the device: waited for
                              # where the rows are sharded over this
                              # process's devices, the host seconds of the
                              # enqueue on one device (no wait is added)
    "lgbm/init/gate",         # _hist_quant_gate (tpu_hist_quantize only)
)

DATASET_SPANS = (
    "lgbm/dataset/sketch",    # pass 1 over the rows, bin finding per column
    "lgbm/dataset/groups",    # the sample binned, efb.find_groups_sampled
    "lgbm/dataset/bin",       # pass 2: value-to-bin, bundling, the landing
)

PREFIX = "lgbm/"
UNSCOPED = "unscoped"


class Phases:
    """Host seconds of one piece of set-up by span name: a `perf_counter`
    pair around each phase, always taken, inside the host span of the
    same name (a no-op unless telemetry or a profiler session is on).
    Nothing here waits for the device. A name may be entered more than
    once: its seconds add up.

    `phase(name)` closes the phase that is open and opens `name`; leaving
    a `with` block of the object closes the open one. So `with
    phase(name):` times a block, and a long function told in consecutive
    stretches calls `phase(name)` at the head of each, inside one `with
    Phases(names) as phase:`."""

    def __init__(self, names: Tuple[str, ...]):
        self.seconds = dict.fromkeys(names, 0.0)
        self._open = None           # (name, span, perf_counter at entry)

    def __call__(self, name: str) -> "Phases":
        self.__exit__(None, None, None)
        if name not in self.seconds:
            raise KeyError(name)
        cm = span(name)
        cm.__enter__()
        self._open = (name, cm, time.perf_counter())
        return self

    def __enter__(self) -> "Phases":
        return self

    def __exit__(self, *exc) -> None:
        if self._open is not None:
            name, cm, t = self._open
            self._open = None
            cm.__exit__(*exc)
            self.seconds[name] += time.perf_counter() - t


class scope:
    """`jax.named_scope(name)` for a name of `SCOPES`, and no other; a
    context manager, or a decorator that opens it around every call."""

    def __init__(self, name: str):
        if name not in SCOPES:
            raise KeyError(f"{name!r} is not in telemetry.layers.SCOPES")
        self.name = name
        self._cm = None

    def __enter__(self):
        import jax
        self._cm = jax.named_scope(self.name)
        return self._cm.__enter__()

    def __exit__(self, *exc):
        return self._cm.__exit__(*exc)

    def __call__(self, fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with scope(self.name):
                return fn(*args, **kwargs)
        return scoped


class TreeRecord(NamedTuple):
    """One tree's schedule economics and host timing. The first five
    fields are the historical `pass_log` tuple, in its order; readers
    that index `e[0]`, `e[2]` or take `list(e)` keep working.

    Counts come from state the tree fetch already carries (`pass_rows`,
    the rows each pass contracted) and the static schedule; the three
    times are `perf_counter` differences on the host. Under a data axis
    `pass_rows` is summed over shards that each choose their own path, so
    full/compact is then told by the summed capacity and is exact only
    when the shards agree."""
    num_passes: int
    table_high_water: int
    rows_contracted: float
    comm_elems: float
    comm_bytes: float
    full_passes: int = 0        # contraction ran over all valid rows
    compact_passes: int = 0     # contraction ran over a gathered subset
    rows_indexed: int = 0       # padded rows scanned by index builds
    rows_gathered: int = 0      # rows the compacted passes contracted
    dispatch_s: float = 0.0     # train_one_iter entry -> grow enqueue returned
    fetch_wait_s: float = 0.0   # the device_get of this tree's small state
    build_tree_s: float = 0.0   # end of the fetch -> tree appended
    # jax's compile path inside the interval `dispatch_s` times, from
    # `observer().totals()`: 0 where no observer is installed and in
    # every tree after the programs exist
    trace_lower_s: float = 0.0  # Python trace to jaxpr + lowering to MLIR
    backend_s: float = 0.0      # XLA compile, or the persistent cache's load
    cache_misses: int = 0       # programs compiled and written to the cache


class ConstructRecord(NamedTuple):
    """Host seconds of one `ingest/build.build_inner` by phase, one field
    per name of `DATASET_SPANS`: `perf_counter` differences that are
    always taken (three pairs a dataset) and never wait for the device.
    It is the `construct_record` of the dataset it tells of. The upload
    of a host-landed matrix is the trainer's (`GBDT.init`) and is in none
    of them."""
    sketch_s: float
    groups_s: float
    bin_s: float
    values: int                 # what the source held for pass 2: rows x
                                # used columns of a dense source, the
                                # stored entries of a sparse one
    nonzeros: int = 0           # what pass 2 visited of them: every value
                                # of a dense source, the entries in used
                                # columns of a sparse one


class EfbCounters(NamedTuple):
    """What one data set's bundle layout holds, counted once in
    `ingest/build.build_inner` (the dataset's `efb_counters`;
    `schedule_info["efb"]`)."""
    features: int               # used (non-trivial) features
    groups: int                 # stored columns
    bundles: int                # groups of more than one feature
    widest_group_bins: int
    sample_conflicts: int       # conflicting rows of the EFB sample that
                                # the grouping accepted; 0 at
                                # max_conflict_rate 0 and for a layout
                                # taken from a reference or a cache

    def as_dict(self) -> dict:
        return dict(self._asdict())


class InitRecord(NamedTuple):
    """Host seconds of one `GBDT.init` by phase, one field per name of
    `INIT_SPANS` in its order: `perf_counter` differences that are always
    taken and never wait for the device, but for `land_s` where the rows
    are sharded (that upload is waited for, record or no record). With
    them what `init` decided about the rows and what jax's compile path
    did meanwhile (`observer().totals()` over the interval of `init`; 0
    where no observer is installed). It is the `init_record` of the
    booster it tells of."""
    objective_s: float
    schedule_s: float
    state_s: float
    land_s: float
    gate_s: float
    total_s: float              # entry to return of init
    rows: int                   # this process's real rows
    binned_bytes: int           # the binned matrix as it sits on the device(s)
    shard_rows: Tuple[int, ...]  # real rows a device's shard holds
    trace_lower_s: float = 0.0
    backend_s: float = 0.0
    cache_hits: int = 0
    cache_misses: int = 0


def shard_rows(rows: int, rows_padded: int, shards: int) -> Tuple[int, ...]:
    """Real rows in each of `shards` equal blocks of a row axis whose
    padding is one suffix (what `GBDT.init`'s `_pad_to` makes)."""
    block = rows_padded // max(1, shards)
    return tuple(max(0, min(block, rows - i * block))
                 for i in range(max(1, shards)))


def split_passes(pass_rows, num_passes: int, cap: int):
    """(full_passes, compact_passes, rows_gathered) from the per-pass row
    counts. `cap` is the compaction buffer's capacity in rows, 0 where
    the schedule has compaction off. Pass 0 is the root's and always
    full; a later pass was compacted iff it contracted at most `cap`
    rows (the grower takes the gathered path iff the members fit the
    buffer, and records the valid-row count otherwise)."""
    later = [int(r) for r in pass_rows[1:num_passes]]
    gathered = [r for r in later if cap and r <= cap]
    return (min(num_passes, 1) + len(later) - len(gathered),
            len(gathered), sum(gathered))
