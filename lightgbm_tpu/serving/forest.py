"""Device-resident compiled forest cache + shape-bucketed dispatch plan.

The reference builds its prediction closures once per booster
(`Predictor::Predictor`, predictor.hpp:24-78: the `predict_fun_`
lambdas capture the iterated-over trees) and GBDT inference
accelerators keep the packed forest resident across requests
(arXiv:2011.02022). The TPU analogue: stacking/padding/transferring
the host `Tree` objects into a `MatmulForest`/`DeviceTree` is O(forest)
host work and an H2D transfer of the whole ensemble — paying it per
`predict` call makes steady-state serving host-bound. `CompiledForest`
caches every stacked layout keyed by `(layout, trees-used, model
version)`; the monotonically increasing model version is bumped by the
owning `GBDT` on EVERY ensemble mutation (tree append, rollback,
continued training, checkpoint restore, model load, DART
re-normalization), so a stale stack is structurally impossible: old
versions can never be looked up again.

Quantized layouts (`tpu_predict_quantize={f16,int8}`) are additional
cache entries keyed by the quantize mode, so the f32 stack and its
quantized siblings coexist per model version — the accuracy gate
compares them on a calibration batch and the registry budgets them
together. `f16` keeps the MatmulForest/DeviceTree algorithm with f16
leaf values (+ bf16 path/category tables); `int8` is the fixed-point
bin-code layout (`ops/predict.QuantForest`). Split decisions stay
bit-exact in both; only the leaf-value storage is lossy, and
`gate_delta` records the measured worst-case raw-score delta so
`boosting/gbdt.py` can refuse a layout exceeding
`tpu_predict_quantize_tol` instead of silently serving it.

Shape buckets: `jax` compiles one program per input shape. Serving
traffic has arbitrary batch sizes, so the row axis is padded up a
power-of-two ladder (`bucket_rows`) — arbitrary sizes then hit a
handful of compiled programs instead of retracing per shape. Every
prediction kernel in ops/predict.py is row-independent (per-row
gathers / per-row matmul contractions; the traversal while_loops only
extend their trip count), so padded rows change nothing for the real
rows: predictions stay bit-identical and the padding is sliced off
after the fetch.
"""
from __future__ import annotations

import os
import threading
import time
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

# stacked layouts kept per model version: one per distinct
# (num_iteration cap, layout kind) seen — enough for a serving process
# that predicts at a couple of caps without letting an iteration sweep
# (e.g. a learning-curve plot) pin every prefix of the forest on device
_MAX_ENTRIES = 8

# default power-of-two ladder floor: a single row pads to 16, which
# costs nothing on a 128-lane machine and keeps the ladder short
DEFAULT_BUCKET_MIN = 16

QUANTIZE_MODES = ("none", "f16", "int8")


def bucket_rows(n: int, bucket_min: int = DEFAULT_BUCKET_MIN,
                cap: int = 1 << 19) -> int:
    """Smallest ladder size >= n: power-of-two steps from bucket_min up
    to cap (chunking splits anything larger). bucket_min <= 0 disables
    bucketing (every size compiles its own program — the seed
    behavior)."""
    if bucket_min <= 0 or n >= cap:
        return min(n, cap) if n > 0 else n
    b = max(1, int(bucket_min))
    while b < n:
        b <<= 1
    return min(b, cap)


def bucket_ladder(bucket_min: int, cap: int) -> List[int]:
    """All bucket sizes warmup() should compile, smallest first. The
    top entry rounds cap UP to the next ladder step — real requests
    dispatch through bucket_rows, which only ever produces power-of-two
    multiples of bucket_min, so a raw non-power-of-two cap would warm a
    program no request ever uses."""
    if bucket_min <= 0:
        return []
    out = []
    b = max(1, int(bucket_min))
    while b < cap:
        out.append(b)
        b <<= 1
    out.append(b)
    return out


def pad_rows(arr: np.ndarray, size: int) -> np.ndarray:
    """Zero-pad the row axis to `size` (no-op when already there)."""
    if arr.shape[0] >= size:
        return arr
    pad = np.zeros((size - arr.shape[0],) + arr.shape[1:], arr.dtype)
    return np.concatenate([arr, pad], axis=0)


def _tree_bytes(value) -> int:
    """Device bytes held by a cache entry (stacked NamedTuples, lists,
    tuples — anything jax.tree can walk)."""
    import jax
    total = 0
    for leaf in jax.tree.leaves(value):
        nbytes = getattr(leaf, "nbytes", None)
        if nbytes is not None:
            total += int(nbytes)
    return total


class CompiledForest:
    """Per-booster cache of device-resident stacked forests.

    Owned by `GBDT`; every ensemble mutation calls `invalidate()`,
    which bumps the model version and drops all entries. Lookups key on
    the CURRENT version, so even an entry that somehow survived a clear
    could never be returned for a newer model. `enabled=False` (the
    `tpu_predict_cache=false` escape hatch) makes every lookup rebuild,
    reproducing the per-call-restack seed behavior for A/B timing.

    `evict_entries()` drops the cached stacks WITHOUT bumping the model
    version — the registry's device-memory budget reclaims idle models'
    stacks this way; the next predict restacks from the host trees and
    versioned lookups stay correct throughout."""

    def __init__(self):
        self._version = 0
        self._cache: "OrderedDict[Tuple, Any]" = OrderedDict()
        self._entry_bytes: Dict[Tuple, int] = {}
        # accuracy-gate ledger: (layout key) -> measured max raw-score
        # delta vs the f32 stack on the calibration batch
        self._gate_delta: Dict[Tuple, float] = {}
        self.enabled = True
        # the Predictor serves concurrent requests (micro-batcher thread
        # + caller threads); the lock covers lookup AND build so two
        # simultaneous misses cannot stack/transfer the forest twice
        # (which would break the one-restack-per-version invariant)
        self._lock = threading.RLock()
        self.stats: Dict[str, int] = {
            "restacks": 0, "hits": 0, "invalidations": 0, "evictions": 0,
            "bytes": 0}

    @property
    def version(self) -> int:
        return self._version

    def invalidate(self) -> None:
        with self._lock:
            self._version += 1
            if self._cache:
                self.stats["invalidations"] += 1
            self._drop_all()

    def evict_entries(self) -> int:
        """Drop every cached stack (registry memory reclaim; the model
        version is NOT bumped). Returns the bytes freed."""
        with self._lock:
            freed = self.stats["bytes"]
            if self._cache:
                self.stats["evictions"] += 1
            self._drop_all()
            return freed

    def _drop_all(self) -> None:
        self._cache.clear()
        self._entry_bytes.clear()
        self._gate_delta.clear()
        self.stats["bytes"] = 0

    def device_bytes(self) -> int:
        """Current device memory held by cached stacks."""
        with self._lock:
            return self.stats["bytes"]

    def _get(self, key: Tuple, build: Callable[[], Any]) -> Any:
        from .. import telemetry
        with self._lock:
            key = key + (self._version,)
            if self.enabled:
                hit = self._cache.get(key)
                if hit is not None:
                    self._cache.move_to_end(key)
                    self.stats["hits"] += 1
                    telemetry.counter_add("predict/stack_cache_hit", 1)
                    # serving/* mirror: the hit-rate series the export
                    # surfaces next to the latency histogram
                    telemetry.counter_add("serving/stack_cache_hit", 1)
                    return hit
            value = build()
            self.stats["restacks"] += 1
            telemetry.counter_add("predict/restack", 1)
            telemetry.counter_add("serving/restack", 1)
            if self.enabled:
                self._cache[key] = value
                self._entry_bytes[key] = _tree_bytes(value)
                self.stats["bytes"] += self._entry_bytes[key]
                while len(self._cache) > _MAX_ENTRIES:
                    old_key, _ = self._cache.popitem(last=False)
                    self.stats["bytes"] -= self._entry_bytes.pop(old_key, 0)
            return value

    # ------------------------------------------------------------------
    # accuracy-gate ledger (boosting/gbdt.py runs the comparison; the
    # ledger lives here so it drops with the entries it describes)
    def gate_delta(self, key: Tuple) -> Optional[float]:
        with self._lock:
            return self._gate_delta.get(key + (self._version,))

    def record_gate(self, key: Tuple, delta: float) -> None:
        with self._lock:
            self._gate_delta[key + (self._version,)] = float(delta)

    # ------------------------------------------------------------------
    # stacked layouts (each build counts as ONE restack regardless of
    # class count — the unit the invalidation tests probe)
    def value_stacks(self, models, k: int, total: int,
                     quantize: str = "none"):
        """Per-class stacks for raw-score prediction.

        quantize="none": [(MatmulForest|None, DeviceTree|None)] — the
        layout choice of GBDT._predict_raw_matrix (gather-free MXU path
        when the path tensor fits, walk otherwise), bit-identical.
        quantize="f16": same structure with f16 leaf values and bf16
        path/category tables. quantize="int8": [QuantForest] fixed-point
        layout (raises ops.predict.QuantRefused when the forest cannot
        be coded). Distinct cache keys, so all three coexist."""
        if quantize == "int8":
            def build_q():
                import jax.numpy as jnp

                from ..ops.predict import stack_trees_quant, stack_trees_raw
                stacks = []
                for cls in range(k):
                    class_trees = [models[i] for i in range(cls, total, k)]
                    qf = stack_trees_quant(class_trees) \
                        if class_trees else None
                    st = None
                    if class_trees and qf is None:
                        # over the path/cat budgets: walk layout with
                        # f16 leaves (same quantized-leaf contract)
                        st = stack_trees_raw(class_trees)
                        st = st._replace(
                            leaf_value=st.leaf_value.astype(jnp.float16))
                    stacks.append((qf, st))
                return stacks
            return self._get(("value", total, k, "int8"), build_q)

        def build():
            from ..ops.predict import stack_trees_matmul, stack_trees_raw
            stacks = []
            for cls in range(k):
                class_trees = [models[i] for i in range(cls, total, k)]
                mf = stack_trees_matmul(class_trees) if class_trees else None
                st = stack_trees_raw(class_trees) \
                    if class_trees and mf is None else None
                stacks.append((mf, st))
            if quantize == "f16":
                return [_stacks_to_f16(mf, st) for mf, st in stacks]
            return stacks
        return self._get(("value", total, k, quantize), build)

    def leaf_stacks(self, models, total: int):
        """(MatmulForest|None, DeviceTree|None) over ALL trees for
        pred_leaf — the same cap/layout choice as the value path, so
        both routes share one stacking implementation. Always f32:
        leaf indices are exact by contract, quantize never routes
        here."""
        def build():
            from ..ops.predict import stack_trees_matmul, stack_trees_raw
            mf = stack_trees_matmul(models[:total])
            st = stack_trees_raw(models[:total]) if mf is None else None
            return (mf, st)
        return self._get(("leaf", total), build)

    def early_stop_stacks(self, models, k: int, t_iters: int):
        """[K, T, ...] DeviceTree for margin-based prediction early stop
        (ops/predict.predict_forest_raw_early_stop)."""
        def build():
            import jax
            import jax.numpy as jnp
            from ..ops.predict import stack_trees_raw
            stacked = stack_trees_raw(models[:t_iters * k])
            return jax.tree.map(
                lambda a: jnp.swapaxes(
                    a.reshape((t_iters, k) + a.shape[1:]), 0, 1), stacked)
        return self._get(("early_stop", t_iters, k), build)


class SingleFlightExpired(Exception):
    """A follower's bounded wait for the leader's build ran out (the
    caller converts this into its deadline/shed rejection)."""


class SingleFlight:
    """Cold-start-storm protection: N concurrent first requests on an
    unseen key (a shape bucket about to pay its first trace) run
    exactly ONE build — the leader proceeds and everyone else waits for
    its program, bounded by their own deadlines.

    Without this, a freshly restarted replica taking a traffic burst
    compiles the same 29-81s wide-shape program once PER CONCURRENT
    REQUEST (jit caches the result, but the storm of identical traces
    races in before the first one lands). `begin(key)` returns True for
    exactly one caller per unseen key; followers block until the leader
    `finish()`es (success marks the key done forever) or their timeout
    expires (`SingleFlightExpired` — shed under the deadline instead of
    queueing on a compile). A FAILED leader wakes the followers and the
    next one through becomes the new leader, so one poisoned build
    cannot wedge the key."""

    def __init__(self):
        self._lock = threading.Lock()
        self._done: set = set()
        self._leading: Dict[Any, threading.Event] = {}
        self.counts: Dict[str, int] = {"leads": 0, "waits": 0,
                                       "expired": 0}

    def seen(self, key) -> bool:
        with self._lock:
            return key in self._done

    def mark(self, key) -> None:
        """Record a key as already-built (warmup marks its whole
        ladder so warmed traffic never enters the flight path)."""
        with self._lock:
            self._done.add(key)

    def begin(self, key, timeout: Optional[float] = None) -> bool:
        """True = caller is the leader and MUST call finish(). False =
        a leader already built the key (possibly after a wait)."""
        from .. import telemetry
        deadline = None if timeout is None \
            else time.monotonic() + max(0.0, timeout)
        while True:
            with self._lock:
                if key in self._done:
                    return False
                ev = self._leading.get(key)
                if ev is None:
                    self._leading[key] = threading.Event()
                    self.counts["leads"] += 1
                    telemetry.counter_add("serving/single_flight_leads", 1)
                    return True
                self.counts["waits"] += 1
            telemetry.counter_add("serving/single_flight_waits", 1)
            remaining = None if deadline is None \
                else deadline - time.monotonic()
            if remaining is not None and remaining <= 0:
                with self._lock:
                    self.counts["expired"] += 1
                telemetry.counter_add("serving/single_flight_expired", 1)
                raise SingleFlightExpired(key)
            if not ev.wait(timeout=remaining):
                with self._lock:
                    self.counts["expired"] += 1
                telemetry.counter_add("serving/single_flight_expired", 1)
                raise SingleFlightExpired(key)
            # woken: either the leader succeeded (key in done -> return
            # False) or it failed (loop; first caller back in becomes
            # the new leader)

    def finish(self, key, ok: bool) -> None:
        with self._lock:
            if ok:
                self._done.add(key)
            ev = self._leading.pop(key, None)
        if ev is not None:
            ev.set()


_COMPILE_CACHE_ARMED: Optional[str] = None
_COMPILE_CACHE_LOCK = threading.Lock()


def enable_compile_cache(path: str) -> bool:
    """Arm JAX's persistent compilation cache for a booster or predictor
    that names `tpu_compile_cache_dir`: every program the shape-bucket
    ladder compiles is written to disk, and a RESTARTED replica's
    warmup() loads the same ladder back instead of re-tracing it. The
    persistence thresholds are dropped to zero so even small bucket
    programs persist (the default 1s floor would skip exactly the
    small-batch programs a serving replica warms first).

    WHERE the cache lives follows the package rule
    (lightgbm_tpu/__init__.py): with JAX_COMPILATION_CACHE_DIR set the
    environment wins — the directory is left alone and that is logged
    once; otherwise the cache is re-pointed at `path`. Idempotent per
    path; returns False when the cache could not be armed (a warning
    names the path and the error; serving proceeds uncached)."""
    global _COMPILE_CACHE_ARMED
    from .. import compile_cache_dir_from_env, log
    env_dir = compile_cache_dir_from_env()
    path = env_dir or os.path.abspath(path)
    with _COMPILE_CACHE_LOCK:
        if _COMPILE_CACHE_ARMED == path:
            return True
        if env_dir:
            log.info("tpu_compile_cache_dir is ignored: "
                     "JAX_COMPILATION_CACHE_DIR=%s places the persistent "
                     "compile cache", env_dir)
        elif _COMPILE_CACHE_ARMED is not None:
            # the cache is PROCESS-GLOBAL (one jax config): two
            # resident models naming different dirs cannot each get
            # their own — the flip is honored but loudly, because the
            # earlier model's future compiles now persist to the new
            # path and its restarted replicas will find a cold cache
            log.warning(
                "tpu_compile_cache_dir is process-global: re-pointing "
                "the persistent compile cache from %s to %s (programs "
                "compiled from now on land in the new dir)",
                _COMPILE_CACHE_ARMED, path)
        try:
            import jax
            jax.config.update("jax_persistent_cache_min_compile_time_secs",
                              0.0)
            jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                              -1)
            if not env_dir:
                # a cache already initialized at another dir (the
                # package default) must be re-pointed, not ignored
                from jax.experimental.compilation_cache import \
                    compilation_cache as cc
                cc.set_cache_dir(path)
                cc.reset_cache()
        except Exception as exc:
            log.warning("persistent compile cache at %s could not be "
                        "armed: %r", path, exc)
            return False
        _COMPILE_CACHE_ARMED = path
    return True


def _stacks_to_f16(mf, st):
    """The f16 quantized layout: identical algorithm, leaf values
    stored f16 (upcast to f32 inside the kernels before accumulation)
    and the big ±1/0 tensors stored bf16 (exact — they hold only
    -1/0/+1). Split thresholds stay f32: decisions remain bit-exact,
    only leaf storage is lossy. When no NUMERIC node carries a missing
    type, `missing` is nulled out so the eval kernel
    (ops/predict.predict_forest_f16) skips the NaN-mask selection
    einsum and missing-resolution chain outright — categorical nodes
    resolve NaN through the block expansion regardless.

    linear_tree forests refuse (QuantRefused, surfaced by the gbdt
    accuracy-gate wrapper as a named LightGBMError): coefficient tables
    have no designed f16 storage contract yet, and silently truncating
    slopes would break the train/serve agreement."""
    import jax.numpy as jnp
    from ..ops.predict import QuantRefused
    if any(x is not None and x.leaf_coeff is not None
           and x.leaf_coeff.shape[-1] > 0 for x in (mf, st)):
        raise QuantRefused(
            "linear_tree leaf coefficients have no f16 layout; "
            "predict linear forests with tpu_predict_quantize=none (f32)")
    if mf is not None:
        numeric_missing = np.asarray(mf.missing)[~np.asarray(mf.is_cat)]
        clean = not numeric_missing.any()
        mf = mf._replace(
            leaf_value=mf.leaf_value.astype(jnp.float16),
            path=mf.path.astype(jnp.bfloat16),
            cat_table=mf.cat_table.astype(jnp.bfloat16),
            missing=None if clean else mf.missing)
    if st is not None:
        st = st._replace(leaf_value=st.leaf_value.astype(jnp.float16))
    return (mf, st)
