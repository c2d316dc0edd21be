"""Distributed tree learners over a jax.sharding.Mesh.

TPU-native replacement for the reference's distributed learner hierarchy
(`src/treelearner/parallel_tree_learner.h` + data/feature/voting .cpp) and
the whole socket/MPI collective backend (`src/network/`): the Bruck
allgather / recursive-halving reduce-scatter schedules (network.cpp:99-163)
are obsolete — XLA chooses collective schedules over ICI/DCN; what remains
of the reference design are the three SPMD seams (SURVEY.md §3.5):

  1. leaf sums       -> psum            (was Allreduce of 12-byte tuples)
  2. histograms      -> psum_scatter over the stored-group axis
                                        (hist_reduce=scatter, the default:
                                         the reference's ReduceScatter +
                                         owned-feature merge — each device
                                         owns groups/D of the reduced
                                         histogram and scans only its own
                                         features) or full psum
                                        (hist_reduce=allreduce: every
                                         device scores every feature
                                         redundantly)
  3. best split      -> pmax + masked psum broadcast (was allreduce with a
                                         custom argmax reducer)

These collectives live INSIDE the jitted tree grower (learner/grow.py) and
are activated by GrowerConfig.data_axis / feature_axis; this module wraps
the grower in shard_map with the right partitioning and host-side padding.

Multi-host: the same code runs under jax.distributed initialization — the
mesh spans hosts, psum rides ICI within a slice and DCN across slices.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import log
from .. import telemetry
from ..learner.grow import GrowerConfig, grow_tree
from ..testing import faults
from . import watchdog


def _sharded_grower(mesh, cfg, row_axis, state_spec, scatter, quantized,
                    update_cls=None):
    """`jax.jit(jax.shard_map(grow_tree))` for one operand layout. After
    the six leading operands come, in order: the scatter schedule's
    replicated owned-feature table (each shard dynamic-indexes its own
    row — multihost-safe), the quantized path's [3] dequant scale, the 7
    fmeta arrays; the f32 dispatch thus keeps its own signature and
    program. Callers build this ONCE per (cfg, layout) and keep it: an
    eager shard_map rebuilt from a fresh closure re-traces and
    re-compiles the whole grow program on every dispatch.

    With `update_cls` the program is the serial learner's fused one
    (`gbdt._grow_and_update_impl`: grow, then class `update_cls` of the
    training score updated from each shard's own leaf ids): two more
    leading operands, the [k, N] score with its rows sharded and the
    shrinkage; it returns the score and the small tree state, and
    `state_spec` is not read."""
    def split(rest):
        rest = list(rest)
        of = rest.pop(0) if scatter else None
        qs = rest.pop(0) if quantized else None
        return rest, of, qs

    def body(b, g, h, w, fm, nv, *rest):
        rest, of, qs = split(rest)
        return grow_tree(b, g, h, w, fm, *rest, cfg, n_valid=nv,
                         owned_feats=of, qscale=qs)

    def fused(score, shrink, b, g, h, w, fm, nv, *rest):
        from ..boosting.gbdt import _grow_and_update_impl
        rest, of, qs = split(rest)
        return _grow_and_update_impl(score, b, g, h, w, fm, shrink, nv,
                                     rest, update_cls, cfg, qscale=qs,
                                     owned_feats=of)

    extra = ((P(None, None),) if scatter else ()) \
        + ((P(None),) if quantized else ())
    in_specs = (P(row_axis, None), P(row_axis), P(row_axis), P(row_axis),
                P(None), P()) + extra + (P(None),) * 7
    if update_cls is None:
        return jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=in_specs, out_specs=state_spec,
            check_vma=False))
    # P() as the small state's spec is a prefix of its dict: replicated
    return jax.jit(jax.shard_map(
        fused, mesh=mesh, in_specs=(P(None, row_axis), P()) + in_specs,
        out_specs=(P(None, row_axis), P()), check_vma=False))


def make_mesh(num_devices: Optional[int] = None, axis_name: str = "data",
              devices=None) -> Mesh:
    """1-D mesh over the available devices (reference analogue: the machine
    list / rank assignment in Network::Init, network.cpp:18-38)."""
    devs = devices if devices is not None else jax.devices()
    if num_devices is not None:
        devs = devs[:num_devices]
    return Mesh(np.asarray(devs), (axis_name,))


def _pad_rows(n: int, multiple: int) -> int:
    return ((n + multiple - 1) // multiple) * multiple


class DataParallelGrower:
    """Rows sharded over the mesh; histograms merged by ReduceScatter
    (hist_reduce="scatter", the default — each device owns a stored-group
    slice of the reduced histogram and finds splits only on its owned
    features, the reference DataParallelTreeLearner design,
    data_parallel_tree_learner.cpp:148-163) or by full Allreduce
    (hist_reduce="allreduce" — every device scores every feature
    redundantly, num_devices x more collective bytes per pass)."""

    def __init__(self, mesh: Mesh, cfg: GrowerConfig, axis: str = "data",
                 hist_reduce: str = "scatter"):
        if hist_reduce not in ("scatter", "allreduce"):
            log.fatal("hist_reduce must be 'scatter' or 'allreduce' "
                      "(got %r)" % (hist_reduce,))
        self.mesh = mesh
        self.axis = axis
        self.nshards = mesh.shape[axis]
        # a 1-shard mesh has nothing to scatter
        self.hist_reduce = hist_reduce if self.nshards > 1 else "allreduce"
        self.cfg = cfg._replace(
            data_axis=axis, num_data_shards=self.nshards,
            hist_scatter=self.hist_reduce == "scatter")
        self._global_binned = None
        self._global_binned_id = None
        self._calls = 0
        # (cfg, scatter, quantized, score class of the fused program or
        # None) -> jitted shard_map (_sharded_grower)
        self._programs: Dict = {}
        # scatter prep cache: (id(binned) -> padded binned), owned table
        self._scatter_binned = None
        self._scatter_binned_id = None
        self._owned_feats = None
        self._owned_counted = False

    # ------------------------------------------------------------------
    # ReduceScatter host-side prep
    # ------------------------------------------------------------------
    def owned_feature_table(self, fmeta: Dict, num_groups: int):
        """[nshards, Fl] table of global feature ids per owned group
        slice (-1 padding, rows ascending in feature id — the scattered
        argmax tie-break relies on the ordering, grow._scattered_best_
        split). Shard s owns stored groups [s*Gl, (s+1)*Gl)."""
        d = self.nshards
        gp = -(-num_groups // d) * d
        gl = gp // d
        groups = np.asarray(fmeta["group"], np.int64)
        owned = [np.nonzero((groups >= s * gl) & (groups < (s + 1) * gl))[0]
                 for s in range(d)]
        fl_max = max(1, max(len(o) for o in owned))
        table = np.full((d, fl_max), -1, np.int32)
        for s, o in enumerate(owned):
            table[s, :len(o)] = o
        return table, gp, gl

    def _scatter_prep(self, binned, fmeta: Dict):
        """Pad the stored-group axis to a shard multiple (appended groups
        are all-bin-0 columns no feature maps to) and build the owned-
        feature table; both cached — the padded matrix by input id, the
        table for the grower's lifetime (feature->group layout is fixed
        at dataset construction)."""
        g = binned.shape[1]
        if self._owned_feats is None:
            table, gp, gl = self.owned_feature_table(fmeta, g)
            self._owned_feats = jnp.asarray(table)
            self._owned_groups = gl
            widths = self.cfg.group_widths
            if widths and len(widths) == g and gp != g:
                self.cfg = self.cfg._replace(
                    group_widths=widths + (1,) * (gp - g))
            if not self._owned_counted:
                telemetry.counter_add("parallel/owned_groups", gl)
                telemetry.counter_add("parallel/owned_features",
                                      int((table >= 0).sum(axis=1).max()))
                self._owned_counted = True
        d = self.nshards
        gp = -(-g // d) * d
        if gp == g:
            return binned, self._owned_feats
        if self._scatter_binned_id != id(binned):
            arr = np.asarray(binned)
            pad = np.zeros((arr.shape[0], gp - g), arr.dtype)
            padded = np.concatenate([arr, pad], axis=1)
            # keep the cached copy device-resident in single-process
            # runs so repeat dispatches don't re-upload the matrix
            # (multi-process shards stay host-side for the
            # global_row_array assembly below)
            self._scatter_binned = padded if jax.process_count() > 1 \
                else jnp.asarray(padded)
            self._scatter_binned_id = id(binned)
        return self._scatter_binned, self._owned_feats

    def grow_and_update(self, score, shrinkage, cls, binned, grad, hess,
                        row_weight, feature_mask, fmeta: Dict, n_valid=None,
                        qscale=None):
        """One tree and the training score's update as ONE sharded
        program (single-process runs: the score is one array whose rows
        live where their leaf ids do). Returns what the serial
        `gbdt._grow_and_update` returns, the score and the small state."""
        self._calls += 1
        with watchdog.deadline("collective.dispatch",
                               iteration=self._calls):
            return self._dispatch(binned, grad, hess, row_weight,
                                  feature_mask, fmeta, n_valid, qscale,
                                  update=(score, shrinkage, cls))

    def __call__(self, binned, grad, hess, row_weight, feature_mask,
                 fmeta: Dict, n_valid=None, qscale=None):
        # the per-pass dispatch is a host-level collective seam: under
        # multi-process training the global-row-array assembly below
        # blocks on every peer, and a dead/wedged rank would park this
        # one here forever — the deadline guard converts that into a
        # diagnosable RC_RANK_FAILURE exit (parallel/watchdog.py). Note
        # the first dispatch of a new shape compiles under the guard,
        # so tpu_collective_timeout_s must exceed worst-case compile.
        self._calls += 1
        with watchdog.deadline("collective.dispatch",
                               iteration=self._calls):
            return self._dispatch(binned, grad, hess, row_weight,
                                  feature_mask, fmeta, n_valid, qscale)

    def _dispatch(self, binned, grad, hess, row_weight, feature_mask,
                  fmeta: Dict, n_valid=None, qscale=None, update=None):
        # injection point: a severed/restarting worker surfaces here as
        # a failed collective dispatch; a WEDGED worker surfaces as an
        # injected sleep the deadline guard above must catch
        # (testing/faults.py wedge_collective)
        faults.inject("collective.call")
        # liveness evidence for watchdogs (scripts/dryrun_multichip.py,
        # scripts/elastic_smoke.py): an rc-124 timeout inside a
        # collective leaves the last grower dispatch this rank reached,
        # not just a dead process
        telemetry.heartbeat(self._calls, phase="grower_dispatch")
        telemetry.counter_add("parallel/grower_calls", 1)
        owned_feats = None
        if self.cfg.hist_scatter:
            binned, owned_feats = self._scatter_prep(binned, fmeta)
        ax = self.axis
        # multi-host: inputs arrive as THIS PROCESS's row shard — assemble
        # the global row axis (each host contributes its loader partition,
        # parallel/multihost.py); binned is assembled once and cached
        if jax.process_count() > 1:
            from .multihost import global_row_array

            def needs_assembly(a):
                return not (isinstance(a, jax.Array)
                            and not a.is_fully_addressable)

            if needs_assembly(binned):
                if self._global_binned_id != id(binned):
                    self._global_binned = global_row_array(
                        np.asarray(binned), self.mesh, ax)
                    self._global_binned_id = id(binned)
                binned = self._global_binned
            if needs_assembly(grad):
                grad = global_row_array(np.asarray(grad), self.mesh, ax)
            if needs_assembly(hess):
                hess = global_row_array(np.asarray(hess), self.mesh, ax)
            if needs_assembly(row_weight):
                row_weight = global_row_array(np.asarray(row_weight),
                                              self.mesh, ax)
        else:
            # hand the row vectors over in the sharding the program
            # declares (a no-op once they are): the first tree's inputs
            # are unplaced, later ones derive from the sharded leaf ids,
            # and jit would compile the grower once for each layout
            grad, hess, row_weight = jax.device_put(
                (grad, hess, row_weight), NamedSharding(self.mesh, P(ax)))
        from ..learner.grow import FMETA_KEYS
        # n_valid=None means "all rows real" — identical to the padded
        # row count, so one program signature serves both
        if n_valid is None:
            n_valid = binned.shape[0]
        scatter, quantized = owned_feats is not None, qscale is not None
        cls = None if update is None else update[2]
        key = (self.cfg, scatter, quantized, cls)
        run = self._programs.get(key)
        if run is None:
            # out_specs: leaf_id stays sharded by rows; everything else
            # is replicated (identical on all shards by construction)
            run = self._programs[key] = _sharded_grower(
                self.mesh, self.cfg, self.axis, self._state_specs(),
                scatter, quantized, update_cls=cls)
        extra = [a for a in (owned_feats, qscale) if a is not None]
        lead = () if update is None else (
            jax.device_put(update[0], NamedSharding(self.mesh, P(None, ax))),
            jnp.float32(update[1]))
        return run(*lead, binned, grad, hess, row_weight, feature_mask,
                   jnp.int32(n_valid), *extra,
                   *[fmeta[k] for k in FMETA_KEYS])

    def _state_specs(self):
        from ..learner.grow import TreeGrowerState
        ax = self.axis
        fields = {name: P() for name in TreeGrowerState._fields}
        fields["leaf_id"] = P(ax)
        return TreeGrowerState(**fields)


class FeatureParallelGrower:
    """Features sharded, data replicated; global split via allreduce-argmax
    (reference: FeatureParallelTreeLearner,
    feature_parallel_tree_learner.cpp:31-69)."""

    def __init__(self, mesh: Mesh, cfg: GrowerConfig, axis: str = "feature"):
        self.mesh = mesh
        self.axis = axis
        self.nshards = mesh.shape[axis]
        self.cfg = cfg._replace(feature_axis=axis,
                                num_feature_shards=self.nshards)
        self._calls = 0
        # (cfg, quantized) -> jitted shard_map (_sharded_grower)
        self._programs: Dict = {}

    def pad_features(self, binned: np.ndarray, fmeta: Dict):
        """Pad the feature dimension to a multiple of the shard count with
        trivial (1-bin) features that can never split."""
        f = binned.shape[1]
        fpad = _pad_rows(f, self.nshards)
        if fpad == f:
            return binned, fmeta
        extra = fpad - f
        binned = np.concatenate(
            [binned, np.zeros((binned.shape[0], extra), binned.dtype)], axis=1)
        fmeta = dict(fmeta)
        fmeta["num_bin"] = np.concatenate([fmeta["num_bin"], np.ones(extra, np.int32)])
        fmeta["missing_type"] = np.concatenate([fmeta["missing_type"], np.zeros(extra, np.int32)])
        fmeta["default_bin"] = np.concatenate([fmeta["default_bin"], np.zeros(extra, np.int32)])
        fmeta["is_categorical"] = np.concatenate([fmeta["is_categorical"], np.zeros(extra, bool)])
        fmeta["group"] = np.concatenate(
            [fmeta["group"], np.arange(f, fpad, dtype=np.int32)])
        fmeta["offset"] = np.concatenate([fmeta["offset"], np.zeros(extra, np.int32)])
        fmeta["is_bundled"] = np.concatenate([fmeta["is_bundled"], np.zeros(extra, bool)])
        return binned, fmeta

    def __call__(self, binned, grad, hess, row_weight, feature_mask, fmeta,
                 n_valid=None, qscale=None):
        self._calls += 1
        with watchdog.deadline("collective.dispatch",
                               iteration=self._calls):
            return self._dispatch(binned, grad, hess, row_weight,
                                  feature_mask, fmeta, n_valid, qscale)

    def _dispatch(self, binned, grad, hess, row_weight, feature_mask, fmeta,
                  n_valid=None, qscale=None):
        faults.inject("collective.call")
        telemetry.heartbeat(self._calls, phase="grower_dispatch")
        telemetry.counter_add("parallel/grower_calls", 1)
        from ..learner.grow import FMETA_KEYS, TreeGrowerState
        if n_valid is None:
            n_valid = binned.shape[0]
        quantized = qscale is not None
        key = (self.cfg, quantized)
        run = self._programs.get(key)
        if run is None:
            state_spec = TreeGrowerState(
                **{name: P() for name in TreeGrowerState._fields})
            run = self._programs[key] = _sharded_grower(
                self.mesh, self.cfg, None, state_spec,
                scatter=False, quantized=quantized)
        extra = [] if qscale is None else [qscale]
        return run(binned, grad, hess, row_weight, feature_mask,
                   jnp.int32(n_valid), *extra,
                   *[fmeta[k] for k in FMETA_KEYS])


class VotingParallelGrower(DataParallelGrower):
    """PV-tree voting-parallel (reference: VotingParallelTreeLearner,
    voting_parallel_tree_learner.cpp:1-482): rows sharded like
    data-parallel, but histograms stay shard-local; each shard submits its
    top_k features by (relaxed-constraint) local gain, a pmax elects the
    global top_k by count-weighted gain (GlobalVoting, cpp:165-194), and
    only the elected features' histogram slices are psum'd
    (CopyLocalHistogram + ReduceScatter, cpp:196-258). Cross-shard traffic
    per batched pass is O(children * top_k * bins) instead of
    O(groups * bins * children); `state.comm_elems` records the measured
    volume. Split choice equals data-parallel when top_k >= num_features
    (every feature elected -> full-precision scan of everything)."""

    def __init__(self, mesh: Mesh, cfg: GrowerConfig, axis: str = "data",
                 top_k: int = 20):
        # voting's elected-slice exchange already moves O(top_k * B) per
        # child — it keeps LOCAL histograms, so there is nothing for a
        # ReduceScatter to merge (grow.py forces hist_scatter off under
        # voting either way)
        super().__init__(mesh, cfg, axis, hist_reduce="allreduce")
        self.cfg = self.cfg._replace(
            voting=True, top_k=max(1, top_k),
            num_data_shards=self.nshards)
