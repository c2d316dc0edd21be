"""GBDT: the gradient-boosting training loop.

TPU-native re-implementation of the reference GBDT engine
(`src/boosting/gbdt.{h,cpp}` — TrainOneIter at gbdt.cpp:380-474): owns the
tree learner, per-class scores, gradients, bagging, early stopping, model
(de)serialization and prediction. The training set lives on device as a
padded binned matrix; one `TrainOneIter` runs gradients (objective kernel)
and bagging weight sampling, dispatches the iteration's tree growths with
the training score's update on the device, and flushes them into host
trees and the validation scores, pipelined one iteration behind where it
can be.
"""
from __future__ import annotations

import contextlib
import copy
import functools
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import checkpoint as ckpt
from .. import log
from .. import telemetry
from ..testing import faults
from ..config import Config
from ..dataset import Dataset, Metadata
from ..learner.grow import GrowerConfig, grow_tree
from ..learner.schedule import (compact_capacity, pick_schedule,
                                plan_row_layout, relabel_rows,
                                schedule_info)
from ..metrics import Metric, create_metric, default_metric_for_objective
from ..objectives import ObjectiveFunction
from ..ops.histogram import contraction_counters
from ..ops.lookup import row_lookup
from ..ops.predict import predict_leaf_binned, predict_value_binned
from ..tree import Tree

_K_EPSILON = 1e-15

_forest_jit_cache: Dict[str, object] = {}


def _forest_jit(fn_name: str, static=()):
    """Memoized module-level jax.jit of ops.predict.<fn_name>: one
    jitted dispatch over the stacked ensemble instead of one per tree
    (compiled once per (num_trees, max_nodes, num_rows) shape). The
    cache is module-global so traces survive across calls and boosters
    (a fresh jax.jit per call would retrace every time)."""
    f = _forest_jit_cache.get(fn_name)
    if f is None:
        import jax

        from ..ops import predict as predict_ops
        f = jax.jit(getattr(predict_ops, fn_name),
                    static_argnames=tuple(static) or None)
        _forest_jit_cache[fn_name] = f
    return f


def _jit_forest_raw(stacked, data):
    return _forest_jit("predict_forest_raw")(stacked, data)


def _jit_forest_binned(stacked, binned):
    return _forest_jit("predict_forest_binned")(stacked, binned)


def _jit_forest_raw_matmul(mf, data):
    return _forest_jit("predict_forest_raw_matmul")(mf, data)


def _jit_forest_leaf_matmul(mf, data):
    return _forest_jit("predict_forest_leaf_matmul")(mf, data)


def _jit_forest_leaf_raw(stacked, data):
    return _forest_jit("predict_forest_leaf_raw")(stacked, data)


def _jit_forest_f16(mf, data):
    return _forest_jit("predict_forest_f16")(mf, data)


def _jit_forest_quant(qf, data):
    return _forest_jit("predict_forest_quant")(qf, data)


def _jit_forest_es(stacked_kt, data, margin, freq):
    """Margin-based early-stop forest walk (freq is static: it feeds a
    `t % freq` under the iteration while_loop; margin stays a traced
    scalar so sweeping it does not retrace)."""
    import jax.numpy as jnp
    return _forest_jit("predict_forest_raw_early_stop", static=("freq",))(
        stacked_kt, data, jnp.float32(margin), freq=freq)


def objective_array_keys(obj) -> Tuple[str, ...]:
    """Names of the objective's row-array attributes. These are passed
    into gradient jits as ARGUMENTS, never closure captures: a captured
    [N] array gets inlined into the lowered module as a giant literal
    (measured 16 MB of HLO text at 2M rows) and defeats the persistent
    compile cache. Shared by the serial gradient jit below and the
    sweep grower (learner/sweep.py) so the discovery rule cannot
    drift."""
    import jax
    return tuple(sorted(k for k, v in vars(obj).items()
                        if isinstance(v, (np.ndarray, jax.Array))))


@contextlib.contextmanager
def objective_arrays_swapped(obj, arr_keys, arrs):
    """Temporarily rebind the objective's row arrays to the traced
    argument values for the duration of a trace (the companion of
    objective_array_keys)."""
    saved = {k: getattr(obj, k) for k in arr_keys}
    try:
        for k, v in arrs.items():
            setattr(obj, k, v)
        yield
    finally:
        for k, v in saved.items():
            setattr(obj, k, v)


def _is_plain(v) -> bool:
    return isinstance(v, (bool, int, float, str, type(None))) or (
        isinstance(v, tuple) and all(_is_plain(x) for x in v))


def _jit_gradients(obj, arr_keys):
    import jax

    @telemetry.scope("lgbm/gradients")
    def f(s, arrs):
        with objective_arrays_swapped(obj, arr_keys, arrs):
            return obj.get_gradients(s.reshape(-1))

    return jax.jit(f)


@functools.lru_cache(maxsize=16)
def _shared_gradient_jit(cls, arr_keys, scalars):
    """One jitted gradient program per (objective class, row-array
    names, scalar state), built on a stand-in instance that carries the
    scalars and no arrays."""
    proto = object.__new__(cls)
    vars(proto).update(scalars, **dict.fromkeys(arr_keys))
    return _jit_gradients(proto, arr_keys)


def _gradient_jit(obj):
    """(jitted `f(score, arrs) -> (grad, hess)`, arr_keys) for `obj`.

    The row arrays are arguments, so objectives that agree on class and
    scalar state SHARE one program: a second booster on the same data (a
    warm-up train, then the timed one) reuses the compiled gradients
    instead of re-tracing a fresh closure. Lambdarank's pair layout is
    held that way too (flat arrays beside a tuple of bucket shapes). An
    objective holding anything besides arrays and plain scalars bakes
    that into its trace and gets a private jit."""
    arr_keys = objective_array_keys(obj)
    rest = {k: v for k, v in vars(obj).items() if k not in arr_keys}
    if all(_is_plain(v) for v in rest.values()):
        return _shared_gradient_jit(type(obj), arr_keys,
                                    tuple(sorted(rest.items()))), arr_keys
    return _jit_gradients(obj, arr_keys), arr_keys


def feature_fraction_mask(rng, frac: float, num_features: int,
                          num_features_padded: int) -> np.ndarray:
    """One per-tree feature_fraction sample
    (serial_tree_learner.cpp:239-257). Module-level because the sweep
    trainer (boosting/sweep.py) draws each model's masks from ITS own
    RandomState with the exact serial expression — sharing the code is
    what keeps the sweep's byte-identity-to-serial contract from
    drifting."""
    f = num_features
    if frac >= 1.0:
        mask = np.ones(f, bool)
    else:
        used = max(1, int(f * frac))
        idx = rng.choice(f, size=used, replace=False)
        mask = np.zeros(f, bool)
        mask[idx] = True
    if num_features_padded > f:
        mask = np.pad(mask, (0, num_features_padded - f))
    return mask


def _device_memory_bytes(device) -> int:
    """The device's memory as its backend reports it (`bytes_limit`), 0
    where it reports none (the CPU): `schedule.subtract_cache_budget`."""
    return int((device.memory_stats() or {}).get("bytes_limit", 0))


def _dispatched(t_enter: float, compiled):
    """What one iteration's enqueue took, for its `telemetry.TreeRecord`:
    (`dispatch_s`, `trace_lower_s`, `backend_s`, `cache_misses`) since
    `t_enter`, a `perf_counter`, and `compiled`, the observer's `totals()`
    read at that moment. The gradient program, bagging and the grow
    program are all traced, lowered and loaded inside that interval, on
    the first tree(s) alone."""
    trace_lower_s, backend_s, _, misses = telemetry.compile_path_since(
        compiled)
    return time.perf_counter() - t_enter, trace_lower_s, backend_s, misses


def _pad_to(arr: np.ndarray, n: int, value=0):
    pad = n - arr.shape[0]
    if pad <= 0:
        return arr
    width = [(0, pad)] + [(0, 0)] * (arr.ndim - 1)
    return np.pad(arr, width, constant_values=value)


# small-state fields fetched host-side to build the Tree (everything
# from_grower_state reads — NOT the [N]-sized leaf_id, which stays
# on device)
_SMALL_STATE_KEYS = (
    "num_leaves_used", "leaf_value", "count", "node_feature",
    "node_threshold", "node_default_left", "node_is_cat", "node_left",
    "node_right", "node_gain", "node_value", "node_count", "num_passes",
    "next_free", "comm_elems", "rows_contracted", "pass_rows",
    "root_cell_max", "root_cell_at")


class _HostState:
    """Host-numpy view of the grower small state (duck-typed for
    Tree.from_grower_state)."""

    def __init__(self, d):
        self.__dict__.update(d)


def _class_state(host_state, cls: int) -> _HostState:
    """Class tree `cls` of the multiclass program's host state."""
    return _HostState({key: v[cls] for key, v in vars(host_state).items()})


def _score_update(score, leaf_value, leaf_id, num_leaves_used, shrinkage,
                  cls, num_leaves):
    """Class `cls` of the training score plus one tree's shrunk leaf
    values, looked up by each row's leaf id; nothing where the tree did
    not split. The one score update of every learner: traced into the
    fused grow programs, and run alone (`_score_update_device`) where
    the learner grows without one."""
    import jax.numpy as jnp
    with telemetry.scope("lgbm/score/update"):
        grew = num_leaves_used > 1
        leaf_vals = leaf_value * shrinkage
        delta = jnp.where(
            grew,
            row_lookup(leaf_vals, jnp.clip(leaf_id, 0, num_leaves - 1)), 0.0)
        return score.at[cls].add(delta)


_score_update_jit = None


def _score_update_device(score, leaf_value, leaf_id, num_leaves_used,
                         shrinkage, cls, num_leaves):
    """`_score_update` as a device program of its own, the class index
    traced: the score update of a learner that grows without a fused
    program."""
    import jax
    import jax.numpy as jnp
    global _score_update_jit
    if _score_update_jit is None:
        _score_update_jit = jax.jit(_score_update,
                                    static_argnames=("num_leaves",))
    return _score_update_jit(score, leaf_value, leaf_id, num_leaves_used,
                             jnp.float32(shrinkage), jnp.int32(cls),
                             num_leaves=num_leaves)


def _grow_and_update_impl(score, binned, grad, hess, row_weight, fmask,
                          shrinkage, n_valid, fmeta_args, cls, cfg,
                          qscale=None, owned_feats=None):
    """grow one tree + train-score update, fused into ONE device program.

    The per-tree path (grow -> leaf lookup -> score add) is one dispatch
    that returns only the small tree arrays, so the host pays one
    dispatch + one device_get per tree instead of an eager op chain.
    Also the body of the data-parallel learners' sharded program
    (parallel/learners.py): there the rows are one shard's."""
    state = grow_tree(binned, grad, hess, row_weight, fmask, *fmeta_args,
                      cfg, n_valid=n_valid, qscale=qscale,
                      owned_feats=owned_feats)
    score = _score_update(score, state.leaf_value, state.leaf_id,
                          state.num_leaves_used, shrinkage, cls,
                          cfg.num_leaves)
    small = {k: getattr(state, k) for k in _SMALL_STATE_KEYS}
    return score, small


def _grow_and_update(score, binned, grad, hess, row_weight, fmask,
                     shrinkage, n_valid, fmeta_args, cls, cfg, qscale=None,
                     grower=None):
    """The one device program of a training iteration. `grower` is the
    data-parallel learner's, which runs the same body sharded over its
    mesh under its own config (`cfg` is then not read)."""
    import jax
    import jax.numpy as jnp
    if grower is not None:
        from ..learner.grow import FMETA_KEYS
        return grower.grow_and_update(
            score, shrinkage, cls, binned, grad, hess, row_weight, fmask,
            dict(zip(FMETA_KEYS, fmeta_args)), n_valid=n_valid,
            qscale=qscale)
    global _grow_and_update_jit
    if _grow_and_update_jit is None:
        _grow_and_update_jit = jax.jit(
            _grow_and_update_impl, static_argnames=("cls", "cfg"))
    return _grow_and_update_jit(score, binned, grad, hess, row_weight,
                                fmask, jnp.float32(shrinkage),
                                jnp.int32(n_valid), tuple(fmeta_args),
                                qscale=qscale, cls=cls, cfg=cfg)


_grow_and_update_jit = None


def _fit_linear_post(raw, grad, hess, row_weight, state, linear_lambda,
                     cfg, k_feats):
    """Post-growth piecewise-linear leaf fit + train-score values, ONE
    device program shared by the serial and distributed paths.

    The fit is deliberately OUTSIDE the grower: it consumes only
    schedule-invariant inputs (final leaf assignment, the leaf->root
    split-feature paths, raw feature values, grad/hess, row weights), so
    a serial grow and a scatter-reduce data-parallel grow that assign
    rows to the same leaves produce BIT-IDENTICAL coefficients — it is
    literally the same compiled program on identical operands (the
    serial-vs-scatter identity test pins this)."""
    import jax
    import jax.numpy as jnp
    global _fit_linear_jit
    if _fit_linear_jit is None:
        def impl(raw, grad, hess, row_weight, leaf_id, leaf_parent,
                 node_feature, node_left, node_right, num_leaves_used,
                 leaf_const, lam, cfg, k_feats):
            from ..learner.grow import leaf_path_features
            from ..linear.solver import fit_leaves, linear_row_values
            lid = jnp.clip(leaf_id, 0, cfg.num_leaves - 1)
            feats = leaf_path_features(leaf_parent, node_feature,
                                       node_left, node_right,
                                       num_leaves_used, k_feats)
            leaf_value, leaf_coeff, _ = fit_leaves(
                raw, grad, hess, row_weight, lid, feats, leaf_const,
                lam, cfg.num_leaves)
            vals = linear_row_values(raw, lid, leaf_value, leaf_coeff,
                                     feats)
            return leaf_value, leaf_coeff, feats, vals

        _fit_linear_jit = jax.jit(impl, static_argnames=("cfg", "k_feats"))
    return _fit_linear_jit(raw, grad, hess, row_weight, state.leaf_id,
                           state.leaf_parent, state.node_feature,
                           state.node_left, state.node_right,
                           state.num_leaves_used, state.leaf_value,
                           jnp.float32(linear_lambda), cfg=cfg,
                           k_feats=k_feats)


_fit_linear_jit = None


def _grow_and_update_multi_impl(score, binned, grads, hesses, row_weight,
                                fmasks, shrinkage, n_valid, fmeta_args, cfg,
                                qscales=None):
    """Grow ALL num_class trees of one boosting iteration in ONE device
    program, one after another, and update every score row.

    The class trees of an iteration are independent given the gradients,
    and the reference grows them in sequence (gbdt.cpp:410-462, one
    `tree_learner_->Train` per class). So does this program: a `lax.scan`
    over the class axis whose body is the one-class grow+update
    (`_grow_and_update_impl`, its class index traced), so each class tree
    runs the program of one tree on the schedule `pick_schedule` gives
    one tree, and the body's buffers serve every class in turn. One
    dispatch and one fetch an iteration; the small state comes back with
    a leading class axis."""
    import jax
    import jax.numpy as jnp

    def one(score, xs):
        cls, g, h, m, qs = xs
        return _grow_and_update_impl(score, binned, g, h, row_weight, m,
                                     shrinkage, n_valid, fmeta_args, cls,
                                     cfg, qscale=qs)

    classes = jnp.arange(grads.shape[0], dtype=jnp.int32)
    return jax.lax.scan(one, score,
                        (classes, grads, hesses, fmasks, qscales))


def _grow_and_update_multi(score, binned, grads, hesses, row_weight, fmasks,
                           shrinkage, n_valid, fmeta_args, cfg, qscales=None):
    import jax
    import jax.numpy as jnp
    global _grow_and_update_multi_jit
    if _grow_and_update_multi_jit is None:
        _grow_and_update_multi_jit = jax.jit(
            _grow_and_update_multi_impl, static_argnames=("cfg",))
    return _grow_and_update_multi_jit(score, binned, grads, hesses,
                                      row_weight, fmasks,
                                      jnp.float32(shrinkage),
                                      jnp.int32(n_valid),
                                      tuple(fmeta_args), qscales=qscales,
                                      cfg=cfg)


_grow_and_update_multi_jit = None


def _bagging_mask_impl(ridx, *, seed, n, n_pad, fraction):
    import jax
    import jax.numpy as jnp
    key = jax.random.fold_in(jax.random.PRNGKey(seed), ridx)
    # draw over the REAL rows only, then pad: threefry is not
    # prefix-stable across output shapes, so a (n_pad,) draw would make
    # the in-bag mask a function of the padded row count — which varies
    # with the device count, breaking the bit-identity of training
    # across world sizes that elastic resume relies on
    # (scripts/elastic_smoke.py). Over (n,) the mask is a pure function
    # of (seed, iteration, n) at ANY world size.
    u = jax.random.uniform(key, (n,))
    mask = (u < fraction).astype(jnp.float32)
    return jnp.pad(mask, (0, n_pad - n))


_bagging_mask_jit = None


_nonfinite_probe_jit = None


def _nonfinite_probe_device(grad, hess):
    """Device bool scalar: any non-finite gradient/hessian. Returned
    UNFETCHED so the pipelined path can overlap the reduction with tree
    growth and read it at the next flush instead of syncing here."""
    import jax
    import jax.numpy as jnp
    global _nonfinite_probe_jit
    if _nonfinite_probe_jit is None:
        _nonfinite_probe_jit = jax.jit(
            lambda g, h: ~(jnp.isfinite(g).all() & jnp.isfinite(h).all()))
    return _nonfinite_probe_jit(grad, hess)


def _bagging_mask_device(seed: int, refresh_idx, n: int, n_pad: int,
                         fraction: float):
    """[n_pad] f32 in-bag mask on device (no host RNG / H2D transfer)."""
    import jax
    import jax.numpy as jnp
    global _bagging_mask_jit
    if _bagging_mask_jit is None:
        _bagging_mask_jit = jax.jit(
            _bagging_mask_impl,
            static_argnames=("n", "n_pad", "fraction", "seed"))
    return _bagging_mask_jit(jnp.int32(refresh_idx), seed=seed, n=n,
                             n_pad=n_pad, fraction=float(fraction))


_quantize_iter_jit = None


def _quantize_iter_device(grad, hess, row_weight, it, *, seed, n, qmax,
                          hess_const):
    """Quantize one iteration's [k, n_pad] gradient/hessian stack for the
    low-precision histogram path (tpu_hist_quantize, ISSUE 20): one
    device program per iteration, vmapped over the class axis.

    Returns (q_grad, q_hess, w01, qscales): integer-valued [k, n_pad]
    gradient/hessian codes in [-qmax, qmax], the 0/1 row weight (any
    bagging/GOSS weighting is FOLDED INTO the codes — the grower's
    grad*row_weight product then stays integer), and the [k, 3]
    per-class dequantization scales. The rounding keys chain
    fold_in(fold_in(fold_in(PRNGKey(seed), iteration), class), 0|1) —
    structurally distinct from the bagging stream's
    fold_in(PRNGKey(seed), refresh) draw, so sharing the base seed
    cannot collide — and the uniform draw itself rides the serial (n,)
    shape inside quantize_gradients (world-size invariance, same
    rationale as _bagging_mask_impl)."""
    import jax
    import jax.numpy as jnp
    global _quantize_iter_jit
    if _quantize_iter_jit is None:
        def impl(grad, hess, row_weight, it, *, seed, n, qmax, hess_const):
            from ..ops.histogram import quantize_gradients
            base = jax.random.fold_in(jax.random.PRNGKey(seed), it)

            def one(g, h, cls_idx):
                kc = jax.random.fold_in(base, cls_idx)
                return quantize_gradients(
                    g, h, row_weight, n=n, qmax=qmax,
                    key_g=jax.random.fold_in(kc, 0),
                    key_h=jax.random.fold_in(kc, 1),
                    hess_const=hess_const)

            k = grad.shape[0]
            qg, qh, w01, qs = jax.vmap(one)(grad, hess,
                                            jnp.arange(k, dtype=jnp.int32))
            # w01 is class-independent (it only reads row_weight)
            return qg, qh, w01[0], qs

        _quantize_iter_jit = jax.jit(
            impl, static_argnames=("seed", "n", "qmax", "hess_const"))
    return _quantize_iter_jit(grad, hess, row_weight, jnp.int32(it),
                              seed=seed, n=n, qmax=qmax,
                              hess_const=hess_const)


_gate_grow_jit = None


def _gate_grow(binned, g, h, w, mask, fmeta, cfg, n_cal, qscale=None):
    """One calibration tree for the train-time quantize gate: grow under
    `cfg` and return (per-row leaf values, leaf-value table). Jitted with
    the static cfg so the quantized and f32 variants each compile once."""
    import jax
    import jax.numpy as jnp
    global _gate_grow_jit
    if _gate_grow_jit is None:
        def impl(binned, g, h, w, mask, n_valid, fmeta, cfg, qscale=None):
            state = grow_tree(binned, g, h, w, mask, *fmeta, cfg,
                              n_valid=n_valid, qscale=qscale)
            lid = jnp.clip(state.leaf_id, 0, cfg.num_leaves - 1)
            return state.leaf_value[lid], state.leaf_value

        _gate_grow_jit = jax.jit(impl, static_argnames=("cfg",))
    return _gate_grow_jit(binned, g, h, w, mask, jnp.int32(n_cal),
                          tuple(fmeta), cfg=cfg, qscale=qscale)


class GBDT:
    """Reference: class GBDT, gbdt.h:25-441."""

    def __init__(self, config: Config):
        self.config = config
        self.iter_ = 0
        self.models: List[Tree] = []          # flat: iter-major, class-minor
        self.num_class = max(config.objective_config.num_class, 1)
        self.num_tree_per_iteration = 1
        self.objective: Optional[ObjectiveFunction] = None
        self.train_data: Optional[Dataset] = None
        self.metrics: List[Metric] = []
        self.valid_sets: List[Dataset] = []
        self.valid_names: List[str] = []
        self.valid_metrics: List[List[Metric]] = []
        self.best_iter: Dict[str, int] = {}
        self.best_score: Dict[str, Dict[str, float]] = {}
        self.init_score_bias = 0.0
        self.average_output = False  # RF mode
        self.shrinkage_rate = config.boosting.learning_rate
        self._early_stop_counter: Dict = {}
        self.max_feature_idx = 0
        self.feature_names: List[str] = []
        self._eval_history: List[dict] = []
        self._stopped = False
        # the dispatched iteration not yet flushed (train_one_iter): a
        # pipelined call leaves its small tree arrays on the device until
        # the NEXT iteration has been dispatched, so the blocking fetch +
        # host Tree build overlap device compute instead of serializing
        # with it
        self._pending_small = None
        # device-resident stacked-forest cache (serving/forest.py):
        # every ensemble mutation must go through _bump_model_version()
        # so a cached stack can never outlive the model it was built from
        from ..serving.forest import CompiledForest
        self._compiled_forest = CompiledForest()
        # publish hook (serving/registry.py): callbacks fired on every
        # model-version bump, so a registry front end can track stack
        # budgets / swap visibility without polling
        self._version_listeners: List = []
        # persistent XLA program cache (ISSUE 12): every program this
        # booster traces — the grower passes AND the serving bucket
        # ladder — persists to disk, so a restarted trainer or a cold
        # serving replica warms from a file read instead of a re-trace
        if getattr(config.io, "tpu_compile_cache_dir", ""):
            from ..serving.forest import enable_compile_cache
            enable_compile_cache(config.io.tpu_compile_cache_dir)

    # ------------------------------------------------------------------
    def init(self, train_data: Dataset, objective: Optional[ObjectiveFunction],
             metric_names: Sequence[str] = ()) -> None:
        """Reference: GBDT::Init, gbdt.cpp:65-193. What it took and what
        it decided about the rows is kept, always, as `init_record` (a
        `telemetry.InitRecord`: host seconds by `telemetry.INIT_SPANS`
        phase, the real rows a device's shard holds, jax's compile path
        over the call), and this booster becomes the process's
        `telemetry.last_run()`."""
        compiled = telemetry.observer().totals()
        t_enter = time.perf_counter()
        with telemetry.Phases(telemetry.INIT_SPANS) as phase:
            shards = self._init(phase, train_data, objective, metric_names)
        self.init_record = telemetry.InitRecord(
            *(phase.seconds[name] for name in telemetry.INIT_SPANS),
            time.perf_counter() - t_enter,
            self._n, int(self._binned.nbytes),
            telemetry.layers.shard_rows(self._n, self._n_pad, shards),
            *telemetry.compile_path_since(compiled))
        # one `telemetry.TreeRecord` a tree (_log_pass_economics)
        self.pass_log: List[telemetry.TreeRecord] = []
        telemetry.record_run(getattr(train_data, "construct_record", None),
                             self.init_record, self.pass_log)

    def _init(self, phase, train_data: Dataset,
              objective: Optional[ObjectiveFunction],
              metric_names: Sequence[str]) -> int:
        """`init` in consecutive stretches, each told to `phase` by its
        `telemetry.INIT_SPANS` name. Returns the row shards this process
        holds (1 where the rows are not sharded)."""
        import jax
        import jax.numpy as jnp

        phase("lgbm/init/schedule")
        self.train_data = train_data
        self.objective = objective
        if objective is not None:
            self.num_tree_per_iteration = objective.num_model_per_iteration()
        else:
            self.num_tree_per_iteration = self.num_class
        self.max_feature_idx = train_data.num_total_features - 1
        self.feature_names = list(train_data.feature_names)
        self.feature_infos_ = train_data.feature_infos()

        n = train_data.num_data
        f = train_data.num_features
        # distributed learner selection (reference: CreateTreeLearner's
        # {serial,feature,data,voting} axis, tree_learner.cpp:9-33)
        tl = self.config.tree_learner
        self._tree_learner_kind = tl if tl in ("data", "feature", "voting") \
            else "serial"
        # `device` in the config selects nothing: training runs on
        # whatever backend jax initialized, so every log says which
        devs = jax.devices()
        log.info("Training on platform=%s device_kind=%s devices=%d",
                 devs[0].platform, devs[0].device_kind, len(devs))
        ndev = len(devs) if self._tree_learner_kind != "serial" else 1
        self._num_shards = ndev
        # multi-host: jax.devices() is GLOBAL; this process holds a row
        # SHARD of the training data (parallel/loader.py partitioning) and
        # pads it to local-device granularity — the data-parallel grower
        # assembles the global row axis (multihost.global_row_array)
        nproc = jax.process_count()
        self._num_processes = nproc
        if nproc > 1 and self._tree_learner_kind not in ("data", "voting"):
            log.fatal("Multi-host training requires tree_learner=data or "
                      "voting (got %s)" % self._tree_learner_kind)
        # arm the collective watchdog + heartbeat lease for this run
        # (parallel/watchdog.py): every host-level collective from here
        # on — including this init's own allgathers below — runs under
        # the deadline guard when tpu_collective_timeout_s is set
        import os as _os

        from ..parallel import watchdog
        net = self.config.network
        rank = jax.process_index()
        self._process_rank = rank
        hb_dir = net.tpu_heartbeat_dir
        # durable-IO retry policy for every storage write this run makes
        # (checkpoint snapshots, caches, artifacts, telemetry sinks)
        from .. import durable
        durable.configure(retries=self.config.io.tpu_io_retries,
                          backoff_s=self.config.io.tpu_io_backoff_s,
                          deadline_s=self.config.io.tpu_io_deadline_s)
        watchdog.configure(
            timeout_s=net.tpu_collective_timeout_s,
            failure_dir=hb_dir or None,
            lease_s=net.tpu_heartbeat_lease_s if hb_dir else None,
            rank=rank)
        if hb_dir:
            _os.makedirs(hb_dir, exist_ok=True)
            telemetry.set_heartbeat_file(
                _os.path.join(hb_dir, f"heartbeat_r{rank}.json"))
            telemetry.heartbeat(0, phase="init", rank=rank)

        # row-padding plan (learner/schedule.py, which the streaming ingest
        # shares so its per-device shards land byte-compatible with this)
        layout = plan_row_layout(
            n, train_data.num_groups, train_data.max_num_bin(),
            tpu_hist_chunk=self.config.tree.tpu_hist_chunk,
            tree_learner=self._tree_learner_kind, ndev=ndev, nproc=nproc)
        self._chunk = layout.chunk
        n_pad = layout.n_pad
        if nproc > 1:
            # every process must contribute an equal-sized row block to
            # the global array: pad all shards to the largest. Deadline-
            # guarded like every other host collective: a peer that died
            # before init must fail this rank with rc 113, not hang it
            from jax.experimental import multihost_utils
            with watchdog.deadline("gbdt.init.pad_sync"):
                n_pad = int(multihost_utils.process_allgather(
                    jnp.asarray(np.int64(n_pad))).max())
            layout = layout._replace(n_pad=n_pad)
        self._n = n
        self._n_pad = n_pad

        # ingest may have landed the binned matrix as per-device row
        # shards already (ingest.ShardedLanding); reuse it when its
        # padding matches this plan, otherwise gather and re-pad
        # the scatter-reduce data-parallel schedule pads the stored-group
        # axis to a device multiple host-side (appended groups are empty
        # columns no feature maps to) — decide it here so the device-landed
        # reuse check and the grower see one consistent layout
        hist_reduce = self.config.tree.tpu_hist_reduce
        use_scatter = (self._tree_learner_kind == "data" and ndev > 1
                       and hist_reduce == "scatter")
        g_pad = (-(-int(train_data.num_groups) // ndev) * ndev
                 if use_scatter else int(train_data.num_groups))
        device_binned = getattr(train_data, "device_binned", None)
        if device_binned is not None:
            usable = (int(device_binned.shape[0]) == n_pad and nproc == 1
                      and self._tree_learner_kind in ("data", "voting"))
            if usable and g_pad > int(device_binned.shape[1]):
                # scatter needs the stored-group axis padded to a device
                # multiple; pad ON DEVICE (zero columns, row sharding
                # preserved) instead of bouncing the landed shards
                # through the host
                device_binned = jnp.pad(
                    device_binned,
                    ((0, 0), (0, g_pad - int(device_binned.shape[1]))))
            if usable:
                binned_host = None
            else:
                log.warning(
                    "Device-landed dataset does not match the training "
                    "layout (rows %d vs %d, learner %s, processes %d); "
                    "gathering to host and re-padding",
                    int(device_binned.shape[0]), n_pad,
                    self._tree_learner_kind, nproc)
                binned_host = _pad_to(
                    np.asarray(device_binned)[:n], n_pad)
                device_binned = None
        else:
            binned_host = _pad_to(train_data.binned, n_pad)
        fm = train_data.feature_meta_arrays()
        self._max_bins = int(train_data.max_num_bin())

        phase("lgbm/init/objective")
        # the objective captures its statistics (bias, class counts, query
        # DCGs) from the REAL data, then pads its row arrays so the gradient
        # kernels line up with the padded scores (padded rows are masked by
        # row_weight 0 in the grower)
        if objective is not None:
            if train_data.metadata.label is None:
                log.fatal("Training data must have a label")
            objective.init(train_data.metadata, n)
            if nproc > 1:
                # label statistics (bias, class counts) were computed on
                # this shard only — sum them across processes (the
                # reference's distributed boost-from-average Allreduce,
                # gbdt.cpp:298-335)
                from jax.experimental import multihost_utils

                def _allreduce_sum(arr):
                    # f64 end-to-end: the reference Allreduces doubles
                    # (gbdt.cpp BoostFromAverage) and a 10M-row label sum
                    # loses real precision in f32. jax defaults to x32,
                    # so ship each double as (hi=f32, lo=residual-f32).
                    a = np.asarray(arr, np.float64)
                    hi = a.astype(np.float32)
                    lo = (a - hi.astype(np.float64)).astype(np.float32)
                    with watchdog.deadline("gbdt.boost_from_average"):
                        g = multihost_utils.process_allgather(
                            jnp.stack([jnp.asarray(hi), jnp.asarray(lo)]))
                    g = np.asarray(g, np.float64)  # [P, 2, ...]
                    return (g[:, 0] + g[:, 1]).sum(axis=0)

                objective.sync_distributed(_allreduce_sum)
            objective.pad_to(n_pad)

        phase("lgbm/init/state")
        self._base_weight = jnp.asarray(
            _pad_to(np.ones(n, np.float32), n_pad))

        # piecewise-linear leaves (linear_tree): the post-growth leaf
        # regression needs RAW feature values on device. Landed in the
        # USED-feature (inner) space so leaf_path_features' inner-space
        # indices address it directly; padding rows are ZEROS so the
        # padded score tail stays finite (the non-finite gradient probe
        # reduces over the whole padded array).
        self._linear = bool(self.config.tree.linear_tree)
        self._linear_k = int(self.config.tree.tpu_linear_max_features)
        self._raw = None
        if self._linear:
            if self.config.boosting_type not in ("gbdt", "goss"):
                raise log.LightGBMError(
                    "linear_tree supports boosting=gbdt/goss only (got "
                    "%s): dart re-normalization and RF averaging replay "
                    "trees through the binned-only path"
                    % self.config.boosting_type)
            if self.num_tree_per_iteration > 1:
                raise log.LightGBMError(
                    "linear_tree does not support multiclass training "
                    "(num_tree_per_iteration=%d); train one-vs-all "
                    "boosters or set linear_tree=false"
                    % self.num_tree_per_iteration)
            if nproc > 1:
                raise log.LightGBMError(
                    "linear_tree does not support multi-host training "
                    "(the leaf regression needs the global raw matrix "
                    "resident on every process); set linear_tree=false")
            if train_data.raw is None:
                raise log.LightGBMError(
                    "linear_tree requires raw feature values: construct "
                    "the training Dataset with keep_raw=true (params "
                    "routed through engine.train/sklearn arm this "
                    "automatically)")
            raw_inner = np.asarray(train_data.raw, np.float32)[
                :, train_data.used_features]
            self._raw = jnp.asarray(_pad_to(raw_inner, n_pad))
            # every iteration drained at once (train_one_iter), as under
            # a validation set or explicit gradients
            self._supports_pipeline = False

        # scores: [num_tree_per_iteration, n_pad]
        k = self.num_tree_per_iteration
        self._score = jnp.zeros((k, n_pad), jnp.float32)
        init_score = train_data.metadata.init_score
        if init_score is not None:
            isc = np.asarray(init_score, np.float32)
            if isc.size == n * k:
                self._score = jnp.asarray(
                    _pad_to(isc.reshape(k, n).T, n_pad).T.reshape(k, n_pad))
            else:
                self._score = self._score + jnp.asarray(_pad_to(isc, n_pad))[None, :]

        # metrics
        phase("lgbm/init/objective")
        self.metrics = []
        for mname in metric_names:
            m = create_metric(mname, self.config)
            if m is not None:
                m.init(train_data.metadata, n)
                self.metrics.append(m)

        # --- quantized-gradient training (tpu_hist_quantize, ISSUE 20) ---
        phase("lgbm/init/schedule")
        from ..ops.histogram import TRAIN_QUANTIZE_MODES, train_qmax
        quant_mode = str(self.config.tree.tpu_hist_quantize or "none").lower()
        if quant_mode not in TRAIN_QUANTIZE_MODES:  # config validates; belt
            raise log.LightGBMError(
                "tpu_hist_quantize must be one of %s (got %r)"
                % (TRAIN_QUANTIZE_MODES, quant_mode))
        if quant_mode != "none" and nproc > 1:
            raise log.LightGBMError(
                "tpu_hist_quantize=%s does not support multi-host "
                "training: the rounding-key stream and the calibration "
                "gate are defined over the global row axis resident on "
                "one process; train with tpu_hist_quantize=none"
                % quant_mode)
        # the integer range adapts to the row count so a full-column bin
        # sum can never overflow the exact int32 accumulator domain
        # (ops/histogram.train_qmax); precision degrades gracefully at
        # extreme n and the gate below judges the result
        quant_qmax = train_qmax(quant_mode, n) if quant_mode != "none" else 0
        # constant-hessian detection enables the hessian-channel comm
        # elision AND exact hessian codes (q_h == qmax * in_bag). GOSS is
        # excluded: its amplification weights fold into the quantized
        # codes, so in-bag hessians are not all equal
        quant_hess_const = bool(
            quant_mode != "none" and objective is not None
            and objective.is_constant_hessian()
            and self.config.boosting_type == "gbdt")
        self._quant_mode = quant_mode
        self._quant_qmax = quant_qmax
        self._quant_hess_const = quant_hess_const
        # the rounding-key base seed: data_random_seed is NOT sweep-
        # variable (boosting/sweep.SWEEP_VARIABLE_PARAMS), so a vmapped
        # sweep and a solo train of the same config derive identical
        # key chains — the sweep==solo byte-identity contract holds
        # under quantization too
        self._quant_seed = int(self.config.io.data_random_seed)

        # --- the execution schedule (learner/schedule.py) ----------------
        # each data/voting shard compacts its own block, so the schedule
        # is asked about ONE shard's rows: what it models is what the
        # grower's own guard sees; and about ONE tree, since every path
        # grows the class trees of an iteration one after another
        g_cnt = max(1, int(train_data.num_groups))
        shards = layout.row_multiple // layout.chunk  # this process's
        # the memory the subtraction cache is judged against (a device
        # of THIS process: `devs` is global under multi-host)
        self._device_bytes = _device_memory_bytes(jax.local_devices()[0])
        # the groups one device keeps of a merged histogram, which is the
        # width of its subtraction cache: its owned slice under the
        # scatter merge (grow.py), every group otherwise
        owned_groups = g_pad // ndev if use_scatter else g_cnt
        picked = pick_schedule(
            g_cnt, self._max_bins, n // shards, n_pad // shards,
            layout.chunk, num_leaves=self.config.tree.num_leaves,
            learner=self._tree_learner_kind,
            bundled=g_cnt < 0.8 * max(1, train_data.num_features),
            quantize=quant_mode,
            compact_fraction=(
                float(self.config.tree.tpu_compact_threshold)
                if "tpu_compact_threshold" in self.config.raw_params
                else None),
            device_bytes=self._device_bytes, cache_groups=owned_groups)
        costs = picked.compact_model
        relabel = relabel_rows(g_cnt, self._max_bins, picked.batch_k,
                               n_pad // shards)
        log.info("Schedule: groups=%d max_bin=%d wide=%s subtract=%s "
                 "compact=%s@%.3f (ns a row: full=%.1f index=%.1f "
                 "gather=%.1f) batch_k=%d table_mult=%d chunk=%d "
                 "relabel=%s@%d quantize=%s qmax=%d",
                 g_cnt, self._max_bins, picked.wide, picked.subtract,
                 picked.compact, picked.compact_fraction, costs.full_ns,
                 costs.index_ns, costs.gather_ns, picked.batch_k,
                 picked.table_mult, layout.chunk,
                 "blocked" if relabel else "columns", relabel, quant_mode,
                 quant_qmax)
        self._grower_cfg = GrowerConfig(
            num_leaves=self.config.tree.num_leaves,
            max_bins=self._max_bins,
            feature_bins=int(train_data.num_bins_per_feature().max(initial=1)),
            **picked.grower_fields(layout.chunk),
            relabel_rows=relabel,
            hist_bf16=self.config.tree.tpu_hist_bf16,
            lambda_l1=self.config.tree.lambda_l1,
            lambda_l2=self.config.tree.lambda_l2,
            min_gain_to_split=self.config.tree.min_gain_to_split,
            min_data_in_leaf=self.config.tree.min_data_in_leaf,
            min_sum_hessian_in_leaf=self.config.tree.min_sum_hessian_in_leaf,
            max_depth=self.config.tree.max_depth,
            hist_quantize=quant_mode,
            hist_qmax=quant_qmax,
            hist_hess_const=quant_hess_const,
            # the scatter schedule pads the stored-group axis to a
            # device multiple; the appended empty groups get 1-bin
            # width-plan entries HERE (the single source — the binned
            # matrices are padded to match below / in the grower prep)
            group_widths=tuple(
                int(b) for b in (train_data.groups.group_num_bin
                                 if train_data.groups is not None
                                 and train_data.groups.num_groups
                                 else train_data.num_bins_per_feature()))
            + (1,) * (g_pad - g_cnt if use_scatter else 0),
        )

        # build the distributed grower + finalize the (possibly feature-
        # padded) device-resident binned matrix
        self._dist_grower = None
        if self._tree_learner_kind != "serial" and ndev >= 1:
            from ..parallel import (DataParallelGrower, FeatureParallelGrower,
                                    VotingParallelGrower, make_mesh)
            if self._tree_learner_kind == "feature":
                mesh = make_mesh(axis_name="feature")
                self._dist_grower = FeatureParallelGrower(
                    mesh, self._grower_cfg, axis="feature")
                binned_host, fm = self._dist_grower.pad_features(binned_host, fm)
                # rebuild the static width plan over the PADDED feature
                # axis so the narrow-block bin-width discount survives
                # feature sharding (grow.py shard_group_widths)
                self._grower_cfg = self._grower_cfg._replace(
                    group_widths=tuple(int(b) for b in fm["num_bin"]))
                # the grower reads the DIST cfg (captured at construction,
                # before padding) — keep it in sync or the width plan
                # silently drops (round-5 review finding)
                self._dist_grower.cfg = self._dist_grower.cfg._replace(
                    group_widths=self._grower_cfg.group_widths)
            elif self._tree_learner_kind == "voting":
                mesh = make_mesh(axis_name="data")
                self._dist_grower = VotingParallelGrower(
                    mesh, self._grower_cfg, axis="data",
                    top_k=self.config.tree.top_k)
            else:
                mesh = make_mesh(axis_name="data")
                self._dist_grower = DataParallelGrower(
                    mesh, self._grower_cfg, axis="data",
                    hist_reduce=hist_reduce)
                if self._dist_grower.cfg.hist_scatter \
                        and binned_host is not None \
                        and g_pad > binned_host.shape[1]:
                    # pre-pad the stored-group axis ONCE here so the
                    # grower's per-call prep sees an already-aligned
                    # device-resident matrix (no host copy per
                    # dispatch); the matching 1-bin width-plan entries
                    # were appended at _grower_cfg construction above
                    extra = g_pad - binned_host.shape[1]
                    binned_host = np.concatenate(
                        [binned_host,
                         np.zeros((binned_host.shape[0], extra),
                                  binned_host.dtype)], axis=1)
            log.info("Using %s-parallel tree learner over %d devices",
                     self._tree_learner_kind, ndev)
        if (self._tree_learner_kind == "feature"
                and train_data.groups is not None
                and train_data.num_groups != train_data.num_features):
            log.fatal("feature-parallel requires unbundled features; "
                      "construct the Dataset with enable_bundle=false")
        # one process, rows over its devices: the learner's fused grow +
        # score-update program serves (train_one_iter), and every per-row
        # array lands ONCE in the sharding that program declares; left
        # unplaced it sits whole on the first device and is re-split at
        # every tree
        phase("lgbm/init/state")
        self._row_sharded = (self._tree_learner_kind in ("data", "voting")
                             and ndev > 1 and nproc == 1)
        if self._row_sharded:
            from jax.sharding import NamedSharding, PartitionSpec
            mesh = self._dist_grower.mesh
            rows_1d = NamedSharding(mesh, PartitionSpec("data"))
            self._score = jax.device_put(self._score, NamedSharding(
                mesh, PartitionSpec(None, "data")))
            self._base_weight = jax.device_put(self._base_weight, rows_1d)
            for key in objective_array_keys(objective) if objective else ():
                arr = getattr(objective, key)
                if arr.ndim == 1 and arr.shape[0] == n_pad:
                    setattr(objective, key, jax.device_put(arr, rows_1d))
        phase("lgbm/init/land")
        if device_binned is not None:
            # already sharded the way the data/voting shard_map wants
            self._binned = device_binned
        elif self._row_sharded:
            # the upload itself, waited for: four shards from one process
            # are this deployment's own share of the set-up
            self._binned = jax.block_until_ready(jax.device_put(
                binned_host, NamedSharding(
                    mesh, PartitionSpec("data", None))))
        else:
            # the host seconds of the enqueue: nothing waits here
            self._binned = jnp.asarray(binned_host)
        phase("lgbm/init/state")
        # logical (possibly shard-padded) feature count for feature_fraction
        # masks; the stored binned width is the GROUP count (EFB)
        self._num_features_padded = int(fm["num_bin"].shape[0])
        self._fmeta = {k: jnp.asarray(v) for k, v in fm.items()}

        self._feature_rng = np.random.RandomState(self.config.tree.feature_fraction_seed)

        # the run-log header's record of the schedule, made last: the
        # feature-parallel padding above may have re-planned group widths
        self._schedule_info = schedule_info(
            picked, layout, self._grower_cfg, rows=n, groups=g_cnt,
            tree_learner=self._tree_learner_kind, num_processes=nproc,
            hist_reduce=((hist_reduce if use_scatter else "allreduce")
                         if self._tree_learner_kind == "data" else None),
            owned_groups=owned_groups,
            device_bytes=self._device_bytes)
        if self.num_tree_per_iteration > 1:
            # the class trees of an iteration run the schedule above one
            # after another (`_grow_and_update_multi_impl`)
            self._schedule_info["class_trees"] = "sequential"
        rank = getattr(objective, "rank_counters", None)
        if rank is not None:
            # lambdarank's pair layout, counted once in its init()
            self._schedule_info["rank"] = rank.as_dict()
            log.info("Schedule: rank queries=%d docs=%d max_docs=%d "
                     "buckets=%s slots=%d pair_slots=%d valid_pairs=%d",
                     rank.queries, rank.docs, rank.max_docs,
                     ",".join("%d:%dx%d" % b for b in rank.buckets),
                     rank.slots, rank.pair_slots, rank.valid_pairs)

        efb = getattr(train_data, "efb_counters", None)
        if efb is not None and efb.groups < efb.features:
            # the bundle layout, counted once in ingest/build.build_inner
            self._schedule_info["efb"] = efb.as_dict()
            log.info("Schedule: efb features=%d groups=%d bundles=%d "
                     "widest_group_bins=%d sample_conflicts=%d",
                     efb.features, efb.groups, efb.bundles,
                     efb.widest_group_bins, efb.sample_conflicts)

        # the contraction's block plan as the kernels take it (the serial
        # and data learners' widths; feature shards re-plan per position)
        hist = contraction_counters(self._grower_cfg.group_widths,
                                    layout.chunk, self._max_bins)
        self._schedule_info["hist"] = hist
        if hist["split_groups"]:
            log.info("Schedule: hist split_groups=%d sub_width=%d "
                     "onehot_columns=%d", hist["split_groups"],
                     hist["sub_width"], hist["onehot_columns"])

        # boost from average (gbdt.cpp:358-378): the score bump happens at
        # init; the bias itself is folded into the first trained tree via
        # AddBias (gbdt.cpp:446) so the saved model is self-contained
        if (objective is not None and objective.boost_from_average()
                and self.config.objective_config.boost_from_average
                and self.num_tree_per_iteration == 1):
            self.init_score_bias = objective.bias()
            if self.init_score_bias != 0.0:
                self._score = self._score + self.init_score_bias
                log.info("Start training from score %f", self.init_score_bias)
        self._pending_bias = self.init_score_bias

        # train-time accuracy gate (tpu_hist_quantize_tol): judge the
        # quantized config on a calibration slice BEFORE any tree is
        # grown — refuse a lossy setup instead of silently training with
        # it. Runs after boost-from-average so the calibration gradients
        # match the real iteration-0 score.
        if quant_mode != "none":
            phase("lgbm/init/gate")
            self._hist_quant_gate()
        return shards

    def _hist_quant_gate(self) -> None:
        """Setup-time gate for tpu_hist_quantize (the serving
        `_quant_gate` pattern applied to TRAINING): grow one calibration
        tree with the quantized pipeline and one with the f32 pipeline on
        the leading row chunk, both serial/full-pass (schedule knobs off
        so the comparison isolates quantization), and refuse the config
        when the worst per-row leaf-value delta — relative to the f32
        tree's leaf-value scale, floored at 1 — exceeds
        `tpu_hist_quantize_tol`."""
        import jax
        import jax.numpy as jnp

        from ..learner.grow import FMETA_KEYS
        from ..ops.histogram import quantize_gradients, train_qmax

        mode = self._quant_mode
        if self.objective is None:
            log.debug("tpu_hist_quantize=%s: custom-objective training "
                      "(explicit gradients) has no setup-time gradient "
                      "source — skipping the calibration gate", mode)
            return
        c = min(self._n_pad, self._chunk)
        n_cal = min(self._n, c)
        binned_cal = self._binned[:c]
        grad, hess = self._compute_gradients(self._score)
        k = self.num_tree_per_iteration
        g = grad.reshape(k, self._n_pad)[0, :c]
        h = hess.reshape(k, self._n_pad)[0, :c]
        w = (jnp.arange(c) < n_cal).astype(jnp.float32)
        mask = jnp.asarray(np.ones(self._num_features_padded, bool))
        fmeta = [self._fmeta[key] for key in FMETA_KEYS]
        # serial full-pass schedule, small tree: the gate isolates the
        # quantization delta (subtract/compact/scatter are separately
        # pinned bit-transparent by the schedule tests)
        cfg = self._grower_cfg._replace(
            data_axis=None, feature_axis=None, voting=False,
            hist_subtract=False, hist_compact=False,
            num_leaves=min(31, self.config.tree.num_leaves))
        qmax = train_qmax(mode, n_cal)
        kc = jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(self._quant_seed), 0), 0)
        q_g, q_h, w01, qscale = quantize_gradients(
            g, h, w, n=n_cal, qmax=qmax,
            key_g=jax.random.fold_in(kc, 0),
            key_h=jax.random.fold_in(kc, 1),
            hess_const=self._quant_hess_const)
        vq, _ = _gate_grow(binned_cal, q_g, q_h, w01, mask, fmeta,
                           cfg._replace(hist_quantize=mode, hist_qmax=qmax),
                           n_cal, qscale=qscale)
        vf, lv_f = _gate_grow(binned_cal, g, h, w, mask, fmeta,
                              cfg._replace(hist_quantize="none", hist_qmax=0,
                                           hist_hess_const=False), n_cal)
        scale = max(float(jnp.max(jnp.abs(lv_f))), 1.0)
        delta = float(jnp.max(jnp.abs(vq[:n_cal] - vf[:n_cal]))) / scale
        telemetry.gauge_set("train/hist_quantize_gate_delta", delta)
        telemetry.counter_add("train/hist_quantize_gate_runs", 1)
        log.debug("Hist-quantize gate (%s, qmax=%d): relative leaf-value "
                  "delta %.3g on %d calibration rows", mode, qmax, delta,
                  n_cal)
        tol = float(self.config.tree.tpu_hist_quantize_tol)
        if delta > tol:
            raise log.LightGBMError(
                "tpu_hist_quantize=%s refused: max calibration leaf-value "
                "delta %.3g vs the f32 grower exceeds "
                "tpu_hist_quantize_tol=%.3g (relative to the f32 tree's "
                "leaf-value scale, %d calibration rows). Raise the "
                "tolerance or train with tpu_hist_quantize=none."
                % (mode, delta, tol, n_cal))

    def add_valid(self, valid_data: Dataset, name: str,
                  metric_names: Sequence[str] = ()) -> None:
        """Reference: GBDT::AddValidDataset, gbdt.cpp:204-224."""
        import jax.numpy as jnp
        self.finalize_training()
        self.valid_sets.append(valid_data)
        self.valid_names.append(name)
        ms = []
        for mname in metric_names:
            m = create_metric(mname, self.config)
            if m is not None:
                m.init(valid_data.metadata, valid_data.num_data)
                ms.append(m)
        self.valid_metrics.append(ms)
        if not hasattr(self, "_valid_binned"):
            self._valid_binned = []
            self._valid_score = []
        if not hasattr(self, "_valid_raw"):
            self._valid_raw = []
        vb = jnp.asarray(valid_data.binned)
        self._valid_binned.append(vb)
        # linear trees evaluate coeff . x on raw values: land the valid
        # set's raw matrix (inner space, unpadded like vb) alongside
        vraw = None
        if getattr(self, "_linear", False) \
                or any(getattr(t, "is_linear", False) for t in self.models):
            if valid_data.raw is None:
                raise log.LightGBMError(
                    "linear_tree validation needs raw feature values: "
                    "construct the valid Dataset with keep_raw=true")
            vraw = jnp.asarray(np.asarray(valid_data.raw, np.float32)[
                :, self.train_data.used_features])
        self._valid_raw.append(vraw)
        k = self.num_tree_per_iteration
        vs = jnp.zeros((k, valid_data.num_data), jnp.float32)
        init_score = valid_data.metadata.init_score
        if init_score is not None:
            isc = np.asarray(init_score, np.float32)
            nv = valid_data.num_data
            if isc.size == nv * k:
                vs = jnp.asarray(isc.reshape(k, nv))
            else:
                vs = vs + jnp.asarray(isc)[None, :]
        if self.init_score_bias != 0.0:
            vs = vs + self.init_score_bias
        # replay existing trees (continued training on new valid set);
        # RF keeps scores as the running AVERAGE of contributions
        acc = jnp.zeros_like(vs)
        for it in range(self.iter_):
            for cls in range(k):
                tree = self.models[it * k + cls]
                acc = acc.at[cls].add(self._tree_values_device(
                    tree.to_device(), vb, vraw))
        if self.average_output and self.iter_ > 0:
            acc = acc / float(self.iter_)
        self._valid_score.append(vs + acc)

    # ------------------------------------------------------------------
    def _bagging_weights(self, iter_idx: int, grad=None, hess=None):
        """0/1 in-bag weights (reference: GBDT::Bagging, gbdt.cpp:225-286),
        built ON DEVICE: per-row Bernoulli(bagging_fraction) from the jax
        PRNG keyed by (bagging_seed, refresh index). DEVIATION from the
        reference: its BaggingHelper adapts probabilities within each
        block to guarantee an exact in-bag count (CHECK(cur_left_cnt ==
        bag_data_cnt)); plain Bernoulli sampling makes the in-bag count
        binomially distributed around n*fraction instead.
        GOSS overrides this using the gradient magnitudes
        (goss.hpp:87-131). Returns a [n_pad] device array (padding
        suffix zeroed) or None for no bagging."""
        bf = self.config.boosting.bagging_fraction
        freq = self.config.boosting.bagging_freq
        if bf >= 1.0 or freq <= 0:
            return None
        if iter_idx % freq == 0 or not hasattr(self, "_bag_cache"):
            self._bag_cache = _bagging_mask_device(
                self.config.boosting.bagging_seed, iter_idx // freq,
                self._n, self._n_pad, bf)
            telemetry.counter_add("boosting/bagging_refresh", 1)
        return self._bag_cache

    def _row_weight_from_bag(self, bag):
        """Normalize a bagging result (None / host [n] / device [n_pad])
        to the [n_pad] device row-weight the grower consumes."""
        import jax.numpy as jnp
        if bag is None:
            return self._base_weight
        if isinstance(bag, np.ndarray):
            return jnp.asarray(_pad_to(bag, self._n_pad))
        return bag

    def _feature_mask(self) -> np.ndarray:
        """Per-tree feature_fraction sample (serial_tree_learner.cpp:239-257)."""
        return feature_fraction_mask(
            self._feature_rng, self.config.tree.feature_fraction,
            self.train_data.num_features, self._num_features_padded)

    def _grow(self, grad, hess, row_weight, feature_mask, qscale=None):
        """Dispatch one tree growth to the serial or distributed grower."""
        import jax.numpy as jnp
        # padding is a row-suffix only in single-process runs (multi-host
        # assembles per-process blocks, each with its own padding tail)
        nv = jnp.int32(self._n) if self._num_processes == 1 else None
        if self._dist_grower is not None:
            return self._dist_grower(self._binned, grad, hess, row_weight,
                                     jnp.asarray(feature_mask), self._fmeta,
                                     n_valid=nv, qscale=qscale)
        from ..learner.grow import FMETA_KEYS
        return grow_tree(
            self._binned, grad, hess, row_weight, jnp.asarray(feature_mask),
            *[self._fmeta[k] for k in FMETA_KEYS], self._grower_cfg,
            n_valid=nv, qscale=qscale)

    # ------------------------------------------------------------------
    def _compute_gradients(self, score) -> Tuple:
        # one jitted program per iteration instead of an eager op chain.
        # The objective's row arrays (label, weights, pair tensors, ...)
        # are passed as ARGUMENTS, not closure captures: a captured [N]
        # array gets inlined into the lowered module as a giant literal
        # (measured 16 MB of HLO text and ~12s of lowering at 2M rows)
        # and defeats the persistent compile cache, since the constant
        # bytes differ per dataset.
        if getattr(self, "_jit_grads", None) is None:
            self._jit_grads, self._jit_grads_keys = _gradient_jit(
                self.objective)
        arrs = {k: getattr(self.objective, k) for k in self._jit_grads_keys}
        return self._jit_grads(score, arrs)

    def train_one_iter(self, gradients: Optional[np.ndarray] = None,
                       hessians: Optional[np.ndarray] = None) -> bool:
        """One boosting iteration (reference: GBDT::TrainOneIter,
        gbdt.cpp:380-474): prepare the gradients and row weights,
        `_dispatch` the iteration's device work, `_flush_pending` it into
        host trees. A pipelined call flushes the PREVIOUS iteration while
        its own runs on the device (__init__); any other call flushes its
        own at once. Returns True when no further splits are possible
        (training should stop)."""
        # injection point: a dying TPU worker surfaces as a failed grow
        # dispatch (testing/faults.py)
        faults.inject("backend.grow")
        import jax.numpy as jnp

        t_enter = time.perf_counter()
        compiled = telemetry.observer().totals()
        it = self.iter_
        k = self.num_tree_per_iteration
        n_pad = self._n_pad
        if gradients is None or hessians is None:
            if self.objective is None:
                log.fatal("Custom objective training requires explicit "
                          "gradients and hessians")
            with telemetry.span("lgbm/iter/gradients", iteration=it):
                grad, hess = self._compute_gradients(self._score)
        else:
            grad = jnp.asarray(np.asarray(gradients, np.float32).reshape(k, -1))
            hess = jnp.asarray(np.asarray(hessians, np.float32).reshape(k, -1))
            if grad.shape[1] != n_pad:
                grad = jnp.asarray(_pad_to(np.asarray(grad).T, n_pad).T)
                hess = jnp.asarray(_pad_to(np.asarray(hess).T, n_pad).T)
            grad = grad.reshape(-1)
            hess = hess.reshape(-1)
        grad = grad.reshape(k, n_pad)
        hess = hess.reshape(k, n_pad)
        probe = self._nonfinite_probe(grad, hess)

        with telemetry.span("lgbm/iter/bagging", iteration=it):
            bag = self._bagging_weights(self.iter_, grad, hess)
            row_weight = self._row_weight_from_bag(bag)

        # quantized-gradient training: replace the f32 moments with
        # integer codes + the 0/1 row weight for the grower; the RAW f32
        # moments are kept for consumers whose math stays full-precision
        # (the piecewise-linear leaf fit)
        moments_f32 = (grad, hess, row_weight)
        qscales = None
        if getattr(self, "_quant_mode", "none") != "none":
            with telemetry.span("boosting/quantize"):
                grad, hess, row_weight, qscales = _quantize_iter_device(
                    grad, hess, row_weight, self.iter_,
                    seed=self._quant_seed, n=self._n,
                    qmax=self._quant_qmax,
                    hess_const=self._quant_hess_const)

        # pipelined: the serial learner, or the one-class iteration of a
        # learner whose rows live on this process's devices; every other
        # call is the same path drained at once
        pipelined = (not self.valid_sets and gradients is None
                     and getattr(self, "_supports_pipeline", True)
                     and (self._dist_grower is None
                          or (self._row_sharded and k == 1)))
        if pipelined and self._stopped:
            # report the stop a drain latched ONCE, then drop the latch:
            # the reference retries every TrainOneIter call, and a fresh
            # bag can open splits the previous one closed
            self._stopped = False
            return True
        small = self._dispatch(grad, hess, row_weight, qscales, moments_f32)
        dispatch = _dispatched(t_enter, compiled)
        # the PREVIOUS iteration's trees, built while this one runs
        grew_prev = self._flush_pending()
        # stash the DISPATCH-TIME shrinkage (a learning-rate schedule
        # changes self.shrinkage_rate before a pipelined flush happens one
        # iteration later), the dispatch-time non-finite probe and
        # iteration index, and what this call took to enqueue the
        # iteration (_dispatched)
        self._pending_small = (small, self.shrinkage_rate, probe, self.iter_,
                               dispatch)
        self.iter_ += 1
        if pipelined and grew_prev:
            return False
        # drained: this iteration's trees now. Pipelined behind a stop: the
        # speculative iteration just dispatched is discarded
        grew = self._flush_pending(discard=pipelined)
        # this return reports the stop: nothing is left to latch
        self._stopped = False
        return not grew

    def _dispatch(self, grad, hess, row_weight, qscales, moments_f32):
        """Enqueue the device work of one iteration, the training score's
        update included, and return its small tree state; nothing here
        waits for the device or builds a host tree. The serial learner's
        K > 1 class trees are one program (`_grow_and_update_multi`, a
        leading class axis on the state). Otherwise each class is
        dispatched in turn and the state is a list of K dicts: the fused
        grow+update program where the learner has one in this process
        (serial, data, voting), else `_grow_then_update`. The fused
        programs are looked up in this module at every call."""
        import jax.numpy as jnp

        from ..learner.grow import FMETA_KEYS

        k = self.num_tree_per_iteration
        fmeta = [self._fmeta[key] for key in FMETA_KEYS]
        masks = [self._feature_mask() for _ in range(k)]
        fused = not self._linear and (
            self._dist_grower is None or (self._num_processes == 1
                                          and self._tree_learner_kind
                                          in ("data", "voting")))
        with telemetry.span("lgbm/iter/dispatch", iteration=self.iter_):
            if k > 1 and self._dist_grower is None:
                self._score, small = _grow_and_update_multi(
                    self._score, self._binned, grad, hess, row_weight,
                    jnp.asarray(np.stack(masks)), self.shrinkage_rate,
                    self._n, fmeta, self._grower_cfg, qscales=qscales)
                return small
            smalls = []
            for cls, mask in enumerate(masks):
                qs = None if qscales is None else qscales[cls]
                if fused:
                    self._score, small = _grow_and_update(
                        self._score, self._binned, grad[cls], hess[cls],
                        row_weight, jnp.asarray(mask), self.shrinkage_rate,
                        self._n, fmeta, cls, self._grower_cfg, qscale=qs,
                        grower=self._dist_grower)
                else:
                    small = self._grow_then_update(
                        cls, grad[cls], hess[cls], row_weight, mask, qs,
                        moments_f32)
                smalls.append(small)
        return smalls

    def _grow_then_update(self, cls, grad, hess, row_weight, mask, qscale,
                          moments_f32):
        """One class tree where the learner has no fused program in this
        process, or the leaves are linear: `_grow`, then the training
        score's update on the device. Returns the small tree state."""
        import jax.numpy as jnp

        state = self._grow(grad, hess, row_weight, mask, qscale=qscale)
        small = {key: getattr(state, key) for key in _SMALL_STATE_KEYS}
        if self._linear:
            # the leaf regression consumes the RAW f32 moments:
            # quantization narrows the HISTOGRAM path only, the fitted
            # intercept/slope normal equations stay exact
            grad_f32, hess_f32, row_weight_f32 = moments_f32
            small["leaf_value"], small["leaf_coeff"], \
                small["leaf_features_inner"], vals = _fit_linear_post(
                    self._raw, grad_f32[cls], hess_f32[cls], row_weight_f32,
                    state, self.config.tree.linear_lambda, self._grower_cfg,
                    self._linear_k)
            self._score = self._score.at[cls].add(jnp.where(
                state.num_leaves_used > 1,
                jnp.float32(self.shrinkage_rate) * vals, 0.0))
            return small
        lid, used, values = (state.leaf_id, state.num_leaves_used,
                             state.leaf_value)
        if self._num_processes > 1:
            # scores are per-process row shards: this process's block of
            # the global leaf ids
            from ..parallel.multihost import local_rows
            lid, used, values = (local_rows(lid), np.asarray(used),
                                 np.asarray(values))
        self._score = _score_update_device(self._score, values, lid, used,
                                           self.shrinkage_rate, cls,
                                           self._grower_cfg.num_leaves)
        return small

    def _fetch(self, small, iteration, shrink):
        """ONE `device_get` of a dispatched iteration's small state (the
        host's wait for the device, a span of its own), then its K host
        trees, each that grew shrunk by the dispatch-time rate. Returns
        the trees, the host state (a leading class axis where K > 1, the
        per-class dispatches' states stacked so) and the wait and its end
        for `_log_pass_economics`."""
        import jax

        k = self.num_tree_per_iteration
        t0 = time.perf_counter()
        with telemetry.span("lgbm/iter/fetch", iteration=iteration):
            host = jax.device_get(small)
        t1 = time.perf_counter()
        with telemetry.span("lgbm/iter/build_tree", iteration=iteration):
            if isinstance(host, list):
                host = host[0] if k == 1 else {
                    key: np.stack([h[key] for h in host]) for key in host[0]}
            host_state = _HostState(host)
            trees = [Tree.from_grower_state(
                host_state if k == 1 else _class_state(host_state, cls),
                self.train_data) for cls in range(k)]
            for tree in trees:
                if tree.num_leaves > 1:
                    tree.apply_shrinkage(shrink)
        return trees, host_state, t1 - t0, t1

    def _log_pass_economics(self, host_state, dispatch=(0.0, 0.0, 0.0, 0),
                            fetch_wait_s=0.0, t_fetched=None) -> None:
        """Append this iteration's `telemetry.TreeRecord` to `pass_log` (read
        by the run log and by benchmarks/layer_metrics/) and feed the
        `tree/*` counters from it. rows_contracted is the compaction
        economics headline (full passes report ~passes * N), comm_elems
        the histogram-merge volume the scatter schedule exists to shrink;
        full against compacted passes are told from `pass_rows`, which
        the fetch already carried. Called once the trees are appended:
        `build_tree_s` runs from the end of the fetch to here."""
        # the multiclass program's state has a leading class axis, one
        # class tree after another: a count of the record is the mean over
        # its class trees, so `classes` x it is what the program ran
        class_passes = tuple(int(p) for p in np.ravel(host_state.num_passes))
        trees = [host_state] if np.ndim(host_state.num_passes) == 0 else [
            _class_state(host_state, c) for c in range(len(class_passes))]

        def per_tree(values):
            return values[0] if len(values) == 1 else sum(values) / len(values)

        comm_elems = float(np.sum(getattr(host_state, "comm_elems", 0.0)))
        cfg = self._grower_cfg if self._dist_grower is None \
            else self._dist_grower.cfg
        shards = max(1, cfg.num_data_shards)
        cap = shards * compact_capacity(cfg, self._n_pad // shards)
        counts = []
        for tree in trees:
            n, rows = int(tree.num_passes), getattr(tree, "pass_rows", ())
            # the per-pass int32 counts summed here: the device's own
            # float32 total rounds past 2^24 and an int32 one would pass
            # 2^31 (84M rows x 28 passes)
            counts.append((float(np.sum(rows[:n], dtype=np.int64)),
                           *telemetry.layers.split_passes(rows, n, cap)))
        contracted, full, compacted, gathered = zip(*counts)
        # every class tree's root counts the same rows
        self._warn_count_cell(trees[0])
        rec = telemetry.TreeRecord(
            num_passes=per_tree(class_passes),
            table_high_water=int(np.max(host_state.next_free)),
            rows_contracted=per_tree(contracted),
            comm_elems=comm_elems,
            # element count -> wire bytes: every exchanged histogram
            # element is 4 bytes (f32, or the exact int32 domain under
            # tpu_hist_quantize — where the constant-hessian channel
            # elision already shrank comm_elems itself by red_ch/3)
            comm_bytes=comm_elems * 4.0,
            full_passes=per_tree(full), compact_passes=per_tree(compacted),
            rows_indexed=per_tree(compacted) * self._n_pad,
            rows_gathered=per_tree(gathered),
            dispatch_s=dispatch[0], fetch_wait_s=fetch_wait_s,
            build_tree_s=0.0 if t_fetched is None
            else time.perf_counter() - t_fetched,
            trace_lower_s=dispatch[1], backend_s=dispatch[2],
            cache_misses=dispatch[3],
            classes=len(class_passes), class_passes=class_passes)
        self.pass_log.append(rec)
        telemetry.counter_add("tree/num_passes", rec.num_passes)
        telemetry.counter_add("tree/rows_contracted", rec.rows_contracted)
        telemetry.counter_add("tree/comm_elems", rec.comm_elems)
        telemetry.counter_add("tree/comm_bytes", rec.comm_bytes)
        telemetry.counter_add("tree/compact_passes", rec.compact_passes)
        telemetry.counter_add("tree/rows_gathered", rec.rows_gathered)

    def _warn_count_cell(self, host_state) -> None:
        """Say once, naming the feature, that a (group, bin) cell of the
        root histogram holds 2^24 rows or more: the float32 count channel
        is exact below that, and the int32 node counts are sums of its
        cells (ops/split.exact_count)."""
        top = float(getattr(host_state, "root_cell_max", 0.0))
        if top < 2.0 ** 24 or getattr(self, "_count_cell_warned", False):
            return
        self._count_cell_warned = True
        group, bin_ = divmod(int(host_state.root_cell_at), self._max_bins)
        fm = self.train_data.feature_meta_arrays()
        inside = ((fm["group"] == group) & (fm["offset"] <= bin_)
                  & (bin_ < fm["offset"] + fm["num_bin"]))
        names = [self.feature_names[self.train_data.real_feature_index(int(j))]
                 for j in np.nonzero(inside)[0]] or ["group %d" % group]
        log.warning(
            "Feature %s: bin %d holds %.0f rows, 2^24 or more, which the "
            "float32 count channel of a histogram cannot hold exactly; "
            "node row counts (min_data_in_leaf, leaf_count, "
            "internal_count) may be off by a few rows"
            % (", ".join(names), bin_, top))

    def _flush_pending(self, discard: bool = False) -> bool:
        """The one place a dispatched iteration becomes host trees, if one
        is pending: `_fetch` them, add each tree that grew to the
        validation scores, THEN fold the boost-from-average bias into the
        first (gbdt.cpp:445-447; the validation scores already start from
        it, add_valid), append the trees and write the iteration's
        `TreeRecord`. Returns False when the iteration is rolled back
        (gbdt.cpp:466-472): no class tree grew, a stop latched for the
        next pipelined call; or `discard`, the speculative iteration
        dispatched behind a stop."""
        if self._pending_small is None:
            return True
        small, shrink, probe, it, dispatch = self._pending_small
        self._pending_small = None
        self._raise_if_nonfinite(probe, it)
        trees, host_state, fetch_wait_s, t_fetched = self._fetch(
            small, it, shrink)
        grew = [cls for cls, tree in enumerate(trees) if tree.num_leaves > 1]
        for cls in grew:
            if discard:
                # a fresh bag can split where the previous one stopped, and
                # the leaf values are already in the device score: subtract
                # them as rollback_one_iter does
                neg = copy.deepcopy(trees[cls])
                neg.leaf_value = -neg.leaf_value
                self._score = self._score.at[cls].add(
                    predict_value_binned(neg.to_device(), self._binned))
            else:
                self._update_valid_scores(cls, trees[cls])
        kept = bool(grew) and not discard
        if kept:
            if abs(getattr(self, "_pending_bias", 0.0)) > _K_EPSILON:
                trees[grew[0]].add_bias(self._pending_bias)
                self._pending_bias = 0.0
                self.init_score_bias = 0.0
            self.models.extend(trees)
            self._bump_model_version()
        self._log_pass_economics(host_state, dispatch, fetch_wait_s,
                                 t_fetched)
        # a latched stop is cleared by a tree that grows: the latch carries
        # a stop across a drain (finalize_training, e.g. a training-metric
        # eval mid-loop), it must not poison later iterations
        self._stopped = not (kept or discard)
        if not kept:
            self.iter_ -= 1
            if not discard:
                log.warning("Stopped training because there are no more "
                            "leaves that meet the split requirements")
        return kept

    def finalize_training(self) -> None:
        """Drain the async pipeline (engine.train calls this after the
        boosting loop; model/prediction readers call it defensively)."""
        self._flush_pending()

    # ------------------------------------------------------------------
    # model-version bookkeeping (serving/forest.py): EVERY ensemble
    # mutation — tree append, rollback, model load, checkpoint restore,
    # continued training, DART re-normalization — must route through
    # here so device-resident stacked forests can never serve a stale
    # model. The version only ever increases.
    def _bump_model_version(self) -> None:
        self._compiled_forest.invalidate()
        for listener in list(getattr(self, "_version_listeners", ())):
            try:
                listener(self._compiled_forest.version)
            except Exception:  # a broken observer must not poison training
                log.warning("model-version listener raised (ignored)")

    def add_version_listener(self, fn) -> None:
        """Publish hook: `fn(version)` fires after every ensemble
        mutation (the registry uses it to refresh budget accounting and
        swap-visibility gauges)."""
        self._version_listeners.append(fn)

    def remove_version_listener(self, fn) -> None:
        try:
            self._version_listeners.remove(fn)
        except ValueError:
            pass

    def compiled_stack_bytes(self) -> int:
        """Device bytes currently held by this booster's compiled
        forest stacks (the registry's budget unit)."""
        return self._compiled_forest.device_bytes()

    def model_version(self) -> int:
        """Monotonic counter identifying the current ensemble contents
        (drains the async tree pipeline first, like num_trees(): a
        pending tree is part of the model the next predict serves)."""
        self.finalize_training()
        return self._compiled_forest.version

    # ------------------------------------------------------------------
    # NaN/Inf gradient guard
    def _nonfinite_probe(self, grad, hess):
        """Lazily-fetched device flag; None when the guard is disabled
        (tpu_guard_nonfinite=false)."""
        if not self.config.boosting.tpu_guard_nonfinite:
            return None
        return _nonfinite_probe_device(grad, hess)

    def _raise_if_nonfinite(self, probe, iteration: int) -> None:
        """A NaN/Inf gradient would not crash anything downstream — the
        histogram sums just absorb it and every later tree fits garbage
        residuals — so fail loudly, naming the objective and iteration,
        instead of silently degrading the whole remaining run."""
        if probe is None or not bool(probe):
            return
        name = self.objective.name if self.objective is not None \
            else "custom (fobj)"
        raise log.LightGBMError(
            "Objective '%s' produced non-finite gradients/hessians at "
            "iteration %d. This usually means the labels/init_score "
            "contain NaN/Inf, the learning rate diverged the scores, or "
            "a custom objective overflowed; set tpu_guard_nonfinite="
            "false to disable this check." % (name, iteration))

    def _tree_values_device(self, dtree, binned, raw):
        """Per-row values of one device tree over a binned matrix.
        Constant-leaf trees gather leaf_value from the binned traversal;
        linear trees additionally need the RAW (inner-space) matrix for
        the leaf-gathered coeff . x term — predict_value_binned refuses
        them by design (ops/predict.py)."""
        import jax.numpy as jnp

        from ..ops.predict import linear_leaf_addend
        if dtree.leaf_coeff is None or dtree.leaf_coeff.shape[-1] == 0:
            return predict_value_binned(dtree, binned)
        if raw is None:
            raise log.LightGBMError(
                "linear_tree score replay needs raw feature values for "
                "this dataset: construct it with keep_raw=true")
        lid = predict_leaf_binned(dtree, binned)
        return dtree.leaf_value[lid].astype(jnp.float32) \
            + linear_leaf_addend(dtree.leaf_coeff, dtree.leaf_feat, lid,
                                 raw)

    def _update_valid_scores(self, cls: int, tree) -> None:
        with telemetry.span("boosting/update_valid_score"):
            dtree = tree.to_device() if self.valid_sets else None
            vraws = getattr(self, "_valid_raw", None)
            for vi in range(len(self.valid_sets)):
                self._valid_score[vi] = \
                    self._valid_score[vi].at[cls].add(
                        self._tree_values_device(
                            dtree, self._valid_binned[vi],
                            vraws[vi] if vraws else None))

    def rollback_one_iter(self) -> None:
        """Reference: GBDT::RollbackOneIter, gbdt.cpp:476-492."""
        import jax.numpy as jnp
        self.finalize_training()
        self._stopped = False
        if self.iter_ <= 0:
            return
        k = self.num_tree_per_iteration
        for cls in reversed(range(k)):
            tree = self.models.pop()
            if tree.num_leaves > 1:
                neg = copy.deepcopy(tree)
                neg.leaf_value = -neg.leaf_value
                neg.leaf_coeff = -neg.leaf_coeff
                dtree = neg.to_device()
                vraws = getattr(self, "_valid_raw", None)
                self._score = self._score.at[cls].add(
                    self._tree_values_device(dtree, self._binned,
                                             getattr(self, "_raw", None)))
                for vi in range(len(self.valid_sets)):
                    self._valid_score[vi] = self._valid_score[vi].at[cls].add(
                        self._tree_values_device(
                            dtree, self._valid_binned[vi],
                            vraws[vi] if vraws else None))
        self.iter_ -= 1
        self._bump_model_version()

    def _train_score_unpadded(self) -> np.ndarray:
        s = np.asarray(self._score, np.float64)
        return s[:, :self._n].reshape(-1)

    # ------------------------------------------------------------------
    def num_trees(self) -> int:
        self.finalize_training()
        return len(self.models)

    def current_iteration(self) -> int:
        # drain the async pipeline like num_trees(): mid-pipeline the
        # counter could name an iteration whose tree later fails to
        # split and is rolled back, so it would go down again and
        # disagree with num_trees()
        self.finalize_training()
        return self.iter_

    # ------------------------------------------------------------------
    # prediction (reference: gbdt_prediction.cpp + Predictor)

    # rows per device dispatch: 2^17 for the WALK path (over-budget
    # forests), 2^19 for the matmul path, whose per-chunk upload +
    # dispatch overhead is amortized over the larger batch. Both sizes
    # predate the present machine and await a ledger measurement.
    _PREDICT_ROW_CHUNK = 1 << 17
    _PREDICT_ROW_CHUNK_MATMUL = 1 << 19

    def _capped_total(self, num_iteration: int) -> int:
        """Trees used under a num_iteration cap (shared by the value,
        leaf, and early-stop prediction routes — they used to slice
        `self.models` independently)."""
        total = len(self.models)
        if num_iteration > 0:
            total = min(total, num_iteration * self.num_tree_per_iteration)
        return total

    def _forest_cache(self):
        """The CompiledForest cache with its enable bit refreshed from
        config (tpu_predict_cache=false reproduces the per-call-restack
        seed behavior for A/B timing)."""
        self._compiled_forest.enabled = bool(self.config.io.tpu_predict_cache)
        return self._compiled_forest

    def _predict_chunk_rows(self, default: int) -> int:
        c = int(self.config.io.tpu_predict_chunk)
        return c if c > 0 else default

    # ------------------------------------------------------------------
    # quantized serving layouts (tpu_predict_quantize, serving/forest.py)
    # calibration rows for the accuracy-delta gate: enough to exercise
    # every split region of a realistic forest without making the first
    # quantized predict pay a second full-batch evaluation
    _QUANT_CALIB_ROWS = 256

    def _quantize_mode(self) -> str:
        mode = str(self.config.io.tpu_predict_quantize or "none").lower()
        from ..serving.forest import QUANTIZE_MODES
        if mode not in QUANTIZE_MODES:  # config validates; double belt
            raise log.LightGBMError(
                "tpu_predict_quantize must be one of %s (got %r)"
                % (QUANTIZE_MODES, mode))
        return mode

    def _class_stack_dev(self, entry, dj, mode):
        """Dispatch one class's stacked forest on a padded chunk."""
        if mode == "int8":
            qf, st = entry
            if qf is not None:
                return _jit_forest_quant(qf, dj)
            return _jit_forest_raw(st, dj) if st is not None else None
        mf, st = entry
        if mf is not None:
            return _jit_forest_f16(mf, dj) if mode == "f16" \
                else _jit_forest_raw_matmul(mf, dj)
        return _jit_forest_raw(st, dj) if st is not None else None

    def _quant_gate(self, cache, mode, k, total, q_stacks, data) -> None:
        """Build-time accuracy gate: on the first predict of a freshly
        stacked quantized layout, evaluate it AND the f32 stack on a
        calibration batch (the head of the incoming data) and refuse to
        serve if the worst raw-score delta exceeds
        `tpu_predict_quantize_tol` (relative to the batch's raw-score
        scale, floored at 1). The measured delta is cached per
        (layout, model version), so steady-state requests only compare
        a float against the tolerance — and a later call with a
        tightened tolerance re-judges the same measurement instead of
        re-running the comparison."""
        import jax.numpy as jnp

        from ..serving.forest import pad_rows
        key = ("value", total, k, mode)
        delta = cache.gate_delta(key)
        if delta is None and getattr(self, "_quant_gate_defer", False):
            # warmup traffic (synthetic all-zeros rows) must not become
            # the cached calibration measurement — defer to the first
            # real batch (serving/predictor.warmup sets the flag)
            return
        if delta is None:
            n_cal = min(data.shape[0], self._QUANT_CALIB_ROWS)
            calib = np.asarray(data[:n_cal], np.float32)
            bucket = self._bucket_size(n_cal, self._PREDICT_ROW_CHUNK)
            dj = jnp.asarray(pad_rows(calib, bucket))
            f32_stacks = cache.value_stacks(self.models, k, total)
            delta = 0.0
            scale = 1.0
            for cls in range(k):
                fr = self._class_stack_dev(f32_stacks[cls], dj, "none")
                qr = self._class_stack_dev(q_stacks[cls], dj, mode)
                if fr is None or qr is None:
                    continue
                fr = np.asarray(fr, np.float64)[:n_cal]
                qr = np.asarray(qr, np.float64)[:n_cal]
                delta = max(delta, float(np.max(np.abs(fr - qr)))
                            if n_cal else 0.0)
                scale = max(scale, float(np.max(np.abs(fr)))
                            if n_cal else 1.0)
            delta = delta / scale
            cache.record_gate(key, delta)
            telemetry.gauge_set("serving/quantize_gate_delta", delta)
            telemetry.counter_add("predict/quant_gate_runs", 1)
            log.debug("Quantize gate (%s, %d trees): relative raw-score "
                      "delta %.3g on %d calibration rows", mode, total,
                      delta, n_cal)
        tol = float(self.config.io.tpu_predict_quantize_tol)
        if delta > tol:
            raise log.LightGBMError(
                "tpu_predict_quantize=%s refused: max raw-score delta "
                "%.3g vs the f32 stack exceeds tpu_predict_quantize_tol"
                "=%.3g (relative to the calibration batch's score "
                "scale). Raise the tolerance or serve with "
                "tpu_predict_quantize=none." % (mode, delta, tol))

    def _bucket_size(self, nrows: int, cap: int) -> int:
        from ..serving.forest import bucket_rows
        return bucket_rows(nrows, int(self.config.io.tpu_predict_bucket_min),
                           cap)

    def _pipelined_chunks(self, data: np.ndarray, chunk: int,
                          dispatch, fetch) -> None:
        """Double-buffered row-chunk loop: dispatch chunk k+1 BEFORE
        fetching chunk k, so chunk k's D2H fetch overlaps chunk k+1's
        H2D/compute instead of serializing with it (jax dispatch is
        async; the blocking call is the fetch). Each chunk's row count
        is padded up the bucket ladder so the remainder chunk reuses a
        compiled program instead of retracing — every prediction kernel
        is row-independent, so the padding is sliced off at fetch with
        bit-identical results. `dispatch(dj)` returns unfetched device
        value(s); `fetch(sl, nrows, dev)` materializes them."""
        import jax.numpy as jnp

        from ..serving.forest import pad_rows
        n = data.shape[0]
        pipeline = bool(self.config.io.tpu_predict_pipeline)
        pending = None
        for i in range(0, n, chunk):
            nrows = min(chunk, n - i)
            bucket = self._bucket_size(nrows, chunk)
            dj = jnp.asarray(pad_rows(data[i:i + nrows], bucket))
            telemetry.counter_add("predict/chunks", 1)
            dev = dispatch(dj)
            if pending is not None:
                fetch(*pending)
            pending = (slice(i, i + nrows), nrows, dev)
            if not pipeline:
                fetch(*pending)
                pending = None
        if pending is not None:
            fetch(*pending)

    def _predict_raw_matrix(self, data: np.ndarray,
                            num_iteration: int = -1,
                            pred_early_stop: bool = False,
                            pred_early_stop_freq: int = 10,
                            pred_early_stop_margin: float = 10.0,
                            transform=None) -> np.ndarray:
        """Raw scores [num_data, num_tree_per_iteration] from raw features.

        Steady-state serving shape: the stacked forest comes from the
        device-resident CompiledForest cache (stacked/transferred once
        per model version, not per call), rows dispatch through the
        bucket ladder, and the chunk loop is pipelined — see
        _pipelined_chunks. Only the row axis is chunked."""
        data = np.asarray(data, np.float32)
        self.finalize_training()
        n = data.shape[0]
        k = self.num_tree_per_iteration
        total = self._capped_total(num_iteration)
        out = np.zeros((k, n), np.float64)
        # margin-based prediction early stop (predictor.hpp:34-60: binary
        # and multiclass objectives only)
        use_es = (pred_early_stop and total > 0
                  and (k > 1 or (self.objective is not None
                                 and self.objective.name == "binary")))
        cache = self._forest_cache()
        # quantized serving layouts (serving/forest.py): raw-score value
        # prediction only — pred_leaf stays exact by contract and the
        # early-stop route keeps its f32 [K, T] walk
        mode = self._quantize_mode() if not use_es else "none"
        stacked_kt = None
        class_stacks = []
        if use_es:
            stacked_kt = cache.early_stop_stacks(self.models, k, total // k)
        elif total > 0:
            # gather-free MXU path (ops/predict.MatmulForest), including
            # categorical models via the one-hot category expansion;
            # only over-budget forests take the walk
            from ..ops.predict import QuantRefused
            try:
                class_stacks = cache.value_stacks(self.models, k, total,
                                                  quantize=mode)
            except QuantRefused as exc:
                raise log.LightGBMError(
                    "tpu_predict_quantize=%s refused for this model: %s"
                    % (mode, exc)) from exc
            if mode != "none" and n > 0:
                self._quant_gate(cache, mode, k, total, class_stacks, data)

        c = self._predict_chunk_rows(
            self._PREDICT_ROW_CHUNK_MATMUL
            if (not use_es and class_stacks
                and all(mf is not None for mf, _ in class_stacks))
            else self._PREDICT_ROW_CHUNK)

        def dispatch(dj):
            if use_es:
                # [K, bucket] device array, fetched as ONE D2H transfer
                # instead of k blocking per-class fetches per chunk
                return _jit_forest_es(stacked_kt, dj,
                                      float(pred_early_stop_margin),
                                      int(pred_early_stop_freq))
            devs = []
            for entry in class_stacks:
                raw = self._class_stack_dev(entry, dj, mode)
                if raw is not None and transform is not None:
                    # output transform fused on device: ONE f32 fetch
                    # instead of fetch-raw + re-upload + fetch-converted
                    raw = transform(raw)
                devs.append(raw)
            return devs

        def fetch(sl, nrows, devs):
            if not isinstance(devs, list):       # early-stop [K, bucket]
                out[:, sl] = np.asarray(devs, np.float64)[:, :nrows]
                return
            for cls, dev in enumerate(devs):
                if dev is not None:
                    out[cls, sl] = np.asarray(dev, np.float64)[:nrows]

        if use_es or class_stacks:
            self._pipelined_chunks(data, c, dispatch, fetch)
        if transform is None:
            if self.average_output and total > 0:
                out /= max(total // k, 1)
            out += self.init_score_bias
        return out.T

    def predict(self, data: np.ndarray, num_iteration: int = -1,
                raw_score: bool = False, pred_leaf: bool = False,
                pred_contrib: bool = False,
                pred_early_stop: bool = False,
                pred_early_stop_freq: int = 10,
                pred_early_stop_margin: float = 10.0) -> np.ndarray:
        import jax.numpy as jnp
        self.finalize_training()
        if pred_leaf:
            data = np.asarray(data, np.float32)
            n = data.shape[0]
            total = self._capped_total(num_iteration)
            if total == 0:
                return np.zeros((n, 0), np.int32)
            # same cache/cap/layout route as the value path (the two
            # used to slice self.models and pick matmul-vs-walk
            # independently)
            mf, st = self._forest_cache().leaf_stacks(self.models, total)
            c = self._predict_chunk_rows(
                self._PREDICT_ROW_CHUNK_MATMUL if mf is not None
                else self._PREDICT_ROW_CHUNK)
            out = np.zeros((n, total), np.int32)

            def dispatch(dj):
                return _jit_forest_leaf_matmul(mf, dj) if mf is not None \
                    else _jit_forest_leaf_raw(st, dj)

            def fetch(sl, nrows, dev):
                out[sl] = np.asarray(dev)[:nrows]

            self._pipelined_chunks(data, c, dispatch, fetch)
            return out
        if pred_contrib:
            from ..shap import predict_contrib
            return predict_contrib(self, np.asarray(data, np.float64), num_iteration)
        k = self.num_tree_per_iteration
        total_cap = self._capped_total(num_iteration)
        if (not raw_score and self.objective is not None and k == 1
                and not pred_early_stop and total_cap > 0):
            # single-class fast path: bias/averaging + the objective's
            # output transform run on device before the single fetch.
            # Zero-tree models fall through to the slow path, which
            # returns the transformed bias prior; the averaging
            # denominator honors the num_iteration cap.
            obj = self.objective
            denom = float(max(total_cap // k, 1)) \
                if self.average_output else 1.0
            bias = float(self.init_score_bias)
            if getattr(self, "_fused_convert", None) is None:
                import jax

                def _conv(r, d, b):
                    return obj.convert_output(r / d + b)

                self._fused_convert = jax.jit(_conv)
            tr = lambda r: self._fused_convert(
                r, jnp.float32(denom), jnp.float32(bias))
            raw = self._predict_raw_matrix(data, num_iteration, transform=tr)
            return raw[:, 0]
        raw = self._predict_raw_matrix(
            data, num_iteration, pred_early_stop=pred_early_stop,
            pred_early_stop_freq=pred_early_stop_freq,
            pred_early_stop_margin=pred_early_stop_margin)
        if raw_score or self.objective is None:
            return raw[:, 0] if raw.shape[1] == 1 else raw
        conv = np.asarray(self.objective.convert_output(
            jnp.asarray(raw.T.reshape(-1), jnp.float32)), np.float64)
        if k == 1:
            return conv
        return conv.reshape(k, -1).T

    # ------------------------------------------------------------------
    # model text IO (reference: gbdt_model.cpp:170-370)
    def model_name(self) -> str:
        return "tree"

    def save_model_to_string(self, num_iteration: int = -1) -> str:
        self.finalize_training()
        out = [self.model_name()]
        out.append("version=v2_tpu")
        out.append(f"num_class={self.num_class}")
        out.append(f"num_tree_per_iteration={self.num_tree_per_iteration}")
        out.append("label_index=0")
        out.append(f"max_feature_idx={self.max_feature_idx}")
        if self.objective is not None:
            out.append(f"objective={self.objective.to_string()}")
        if self.average_output:
            out.append("average_output")
        out.append("feature_names=" + " ".join(self.feature_names))
        out.append("feature_infos=" + " ".join(
            getattr(self, "feature_infos_", None)
            or ["none"] * (self.max_feature_idx + 1)))
        if self.init_score_bias != 0.0:
            # only reachable for models loaded from old-format files; new
            # models carry the bias inside the first tree (AddBias)
            out.append(f"init_score_bias={self.init_score_bias}")
        out.extend(self._extra_model_header(num_iteration))
        out.append("")
        total = len(self.models)
        if num_iteration > 0:
            total = min(total, num_iteration * self.num_tree_per_iteration)
        for i in range(total):
            out.append(f"Tree={i}")
            out.append(self.models[i].to_string())
        out.append("end of trees")
        out.append("")
        imp = self.feature_importance("split")
        pairs = sorted(((v, self.feature_names[i]) for i, v in enumerate(imp) if v > 0),
                       reverse=True)
        out.append("feature importances:")
        for v, name in pairs:
            out.append(f"{name}={int(v)}")
        return "\n".join(out) + "\n"

    def _extra_model_header(self, num_iteration: int = -1) -> List[str]:
        """Subclass hook for extra `key=value` header lines (DART's drop
        ledger); emitted before the tree blocks, ignored by loaders that
        don't know them."""
        return []

    def save_model(self, filename: str, num_iteration: int = -1) -> None:
        # atomic (tmp + fsync + rename): a preemption mid-save must never
        # leave a truncated file that still parses as a shorter model
        ckpt.atomic_write_text(filename,
                               self.save_model_to_string(num_iteration))
        log.info("Saved model to %s", filename)

    def load_model_from_string(self, text: str) -> None:
        """Reference: GBDT::LoadModelFromString, gbdt_model.cpp:247-330."""
        lines = text.splitlines()
        kv = {}
        tree_blocks: List[List[str]] = []
        cur: Optional[List[str]] = None
        for line in lines:
            ls = line.strip()
            if ls.startswith("Tree="):
                if cur is not None:
                    tree_blocks.append(cur)
                cur = []
                continue
            if ls == "end of trees":
                if cur is not None:
                    tree_blocks.append(cur)
                cur = None
                continue
            if cur is not None:
                if ls:
                    cur.append(ls)
            elif "=" in ls:
                k, v = ls.split("=", 1)
                kv[k] = v
            elif ls == "average_output":
                kv["average_output"] = "1"
        if cur:
            tree_blocks.append(cur)
        self.num_class = int(kv.get("num_class", 1))
        self.num_tree_per_iteration = int(kv.get("num_tree_per_iteration", self.num_class))
        self.max_feature_idx = int(kv.get("max_feature_idx", 0))
        self.feature_names = kv.get("feature_names", "").split()
        self.feature_infos_ = kv.get("feature_infos", "").split()
        self.init_score_bias = float(kv.get("init_score_bias", 0.0))
        self.average_output = "average_output" in kv
        self.models = [Tree.from_string("\n".join(b)) for b in tree_blocks]
        self.iter_ = len(self.models) // max(self.num_tree_per_iteration, 1)
        self._bump_model_version()

    # ------------------------------------------------------------------
    # checkpoint/resume (lightgbm_tpu/checkpoint.py drives this through
    # engine.train; the contract is bit-identical restart: everything the
    # next train_one_iter reads must round-trip EXACTLY)
    def _checkpoint_extra(self) -> dict:
        """Subclass hook for boosting-variant state (DART's drop ledger +
        drop RNG). GOSS and bagging need nothing here: their row masks
        are pure functions of (seed, iteration) via jax fold_in."""
        return {}

    def _restore_extra(self, extra: dict) -> None:
        return None

    def checkpoint_state(self) -> dict:
        """Full JSON-serializable training state EXCLUDING the model
        string (the snapshot payload carries that separately so tooling
        can extract a plain model from any checkpoint). Scores are the
        exact f32 device arrays: replaying trees would re-sum their
        contributions in a different order and break bit-identity."""
        self.finalize_training()
        state = {
            "iter": int(self.iter_),
            "shrinkage_rate": float(self.shrinkage_rate),
            "init_score_bias": float(self.init_score_bias),
            "pending_bias": float(getattr(self, "_pending_bias", 0.0)),
            "stopped": bool(self._stopped),
            "score": ckpt.encode_array(np.asarray(self._score)),
            "valid_scores": [ckpt.encode_array(np.asarray(v))
                             for v in getattr(self, "_valid_score", [])],
            "feature_rng": ckpt.encode_rng(self._feature_rng),
            "best_iter": {k: int(v) for k, v in self.best_iter.items()},
            "best_score": {k: dict(v) for k, v in self.best_score.items()},
            "eval_history": list(self._eval_history),
            "extra": self._checkpoint_extra(),
            # world-size metadata (elastic resume, checkpoint.py): how
            # many real rows the score block covers, which global rows
            # they are, and the world this snapshot was taken under —
            # what a different-sized cohort needs to re-shard it
            "num_data": int(self._n),
            "world": {
                "processes": int(self._num_processes),
                "rank": int(self._process_rank),
                "devices": int(self._num_shards),
                "n_pad": int(self._n_pad),
            },
        }
        n_global = getattr(self.train_data, "num_global_rows", None)
        if n_global:
            state["num_data_global"] = int(n_global)
        if self._num_processes > 1:
            # the partition is identical across a run's snapshots, but
            # each snapshot must stay SELF-CONTAINED: resume falls back
            # past corrupt/rotated files to any older snapshot, and a
            # sidecar partition file would re-introduce a second thing
            # that can be lost/corrupt independently. The cost is
            # ~10.7 B64-bytes/row per snapshot, bounded by keep-last-K
            row_index = getattr(self.train_data, "used_row_indices", None)
            if row_index is not None and len(row_index) == self._n:
                state["row_index"] = ckpt.encode_array(
                    np.asarray(row_index, np.int64))
        return state

    def restore_state(self, state: dict, model_str: str) -> None:
        """Inverse of checkpoint_state, applied to a freshly-init()'d
        booster (same dataset, same config — the engine verifies the
        config fingerprint before calling this)."""
        import jax.numpy as jnp
        self.finalize_training()
        self.load_model_from_string(model_str)
        for tree in self.models:
            # our text carries complete bin/group metadata, so loaded
            # trees are device-ready as-is; only legacy/reference text
            # needs re-derivation (which is NOT bit-exactness-critical:
            # such models never came from a checkpoint of this build)
            if tree.num_leaves > 1 and not tree.has_bin_metadata:
                tree.attach_bin_metadata(self.train_data)
        # metadata attach mutates the trees after the load's bump
        self._bump_model_version()
        self.iter_ = int(state["iter"])
        self.shrinkage_rate = float(state["shrinkage_rate"])
        self.init_score_bias = float(state["init_score_bias"])
        self._pending_bias = float(state["pending_bias"])
        self._stopped = bool(state["stopped"])
        score = ckpt.decode_array(state["score"])
        have_shape = tuple(np.asarray(self._score).shape)
        if tuple(score.shape) == have_shape:
            self._score = jnp.asarray(score)
        else:
            # world-size-elastic resume: a snapshot taken at a different
            # device/process count pads (or shards) its score block
            # differently. The REAL rows' exact f32 values carry over
            # unchanged; the padding region keeps this init's values —
            # padded rows are weight-0 in every histogram and never read
            # by eval, so trees stay byte-identical (the same argument
            # that makes trees bit-identical across device counts,
            # tests/test_scatter_reduce.py)
            elastic_ok = bool(getattr(self.config.io, "tpu_elastic_resume",
                                      True))
            old_n = state.get("num_data")
            if (elastic_ok and old_n is not None
                    and int(old_n) == int(self._n)
                    and score.shape[0] == have_shape[0]
                    and score.shape[1] >= int(self._n)):
                log.info(
                    "Elastic resume: re-padding checkpoint scores from "
                    "%s to %s (%d real rows; snapshot world %s, now %d "
                    "device(s) x %d process(es))",
                    tuple(score.shape), have_shape, int(self._n),
                    state.get("world"), self._num_shards,
                    self._num_processes)
                fresh = np.asarray(self._score).copy()
                fresh[:, :int(self._n)] = score[:, :int(self._n)]
                self._score = jnp.asarray(fresh)
            else:
                raise log.LightGBMError(
                    "Checkpoint score shape %s does not match this "
                    "training setup %s — the dataset differs from the "
                    "checkpointed run%s"
                    % (score.shape, have_shape,
                       "" if elastic_ok else
                       " (tpu_elastic_resume=false refuses world-size "
                       "changes)"))
        valid_encs = state.get("valid_scores", [])
        have = getattr(self, "_valid_score", [])
        if len(valid_encs) != len(have):
            raise log.LightGBMError(
                "Checkpoint carries %d validation-score arrays but %d "
                "validation sets are attached; resume with the same "
                "valid_sets as the original run"
                % (len(valid_encs), len(have)))
        for vi, enc in enumerate(valid_encs):
            vs = ckpt.decode_array(enc)
            if tuple(vs.shape) != tuple(np.asarray(have[vi]).shape):
                raise log.LightGBMError(
                    "Checkpoint valid set %d score shape %s != %s — "
                    "validation data differs from the checkpointed run"
                    % (vi, vs.shape, np.asarray(have[vi]).shape))
            self._valid_score[vi] = jnp.asarray(vs)
        self._feature_rng = ckpt.decode_rng(state["feature_rng"])
        self.best_iter = {k: int(v)
                          for k, v in state.get("best_iter", {}).items()}
        self.best_score = {k: dict(v)
                           for k, v in state.get("best_score", {}).items()}
        self._eval_history = list(state.get("eval_history", []))
        # derived per-iteration caches must not leak across the restore
        self._pending_small = None
        if hasattr(self, "_bag_cache"):
            del self._bag_cache
        self._restore_extra(state.get("extra", {}))

    # ------------------------------------------------------------------
    def feature_importance(self, importance_type: str = "split",
                           num_iteration: int = -1) -> np.ndarray:
        """Reference: GBDT::FeatureImportance (gbdt_model.cpp:335-370)."""
        self.finalize_training()
        nf = self.max_feature_idx + 1
        imp = np.zeros(nf, np.float64)
        total = len(self.models)
        if num_iteration > 0:
            total = min(total, num_iteration * self.num_tree_per_iteration)
        for i in range(total):
            t = self.models[i]
            m = t.num_leaves - 1
            for j in range(m):
                if importance_type == "split":
                    imp[t.split_feature[j]] += 1
                else:
                    imp[t.split_feature[j]] += max(t.split_gain[j], 0.0)
        return imp

    def dump_model(self, num_iteration: int = -1) -> dict:
        self.finalize_training()
        total = len(self.models)
        if num_iteration > 0:
            total = min(total, num_iteration * self.num_tree_per_iteration)
        return {
            "name": "tree",
            "version": "v2_tpu",
            "num_class": self.num_class,
            "num_tree_per_iteration": self.num_tree_per_iteration,
            "label_index": 0,
            "max_feature_idx": self.max_feature_idx,
            "feature_names": self.feature_names,
            "tree_info": [t.to_json() for t in self.models[:total]],
        }
