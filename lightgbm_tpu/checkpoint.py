"""Crash-consistent checkpoint/resume for preemptible training.

TPU pods are preemptible; a 50k-iteration boosting run must survive its
host dying between any two iterations. This module provides:

- `atomic_write_bytes` / `atomic_write_text` — tmp file in the target
  directory + flush + fsync + atomic rename (+ directory fsync), so a
  reader never observes a partially-written file. `GBDT.save_model` and
  the snapshot store both write through it.
- `CheckpointManager` — a keep-last-K rotation of versioned full-state
  snapshots, one file per (iteration, process rank). Every snapshot
  carries a self-describing header with a SHA-256 of the payload;
  `load_latest` validates it and silently falls back past corrupt or
  truncated snapshots to the newest good one.
- `config_fingerprint` — a digest of every training-trajectory-relevant
  parameter plus the dataset shape. Resume refuses a snapshot whose
  fingerprint differs, because restoring RNG/score state into a run with
  different semantics would produce a model that is neither the old nor
  the new configuration's.
- array/RNG codecs used by `GBDT.checkpoint_state()` to serialize the
  exact f32 score arrays and numpy RNG states, which is what makes a
  resumed run *bit-identical* to an uninterrupted one (the deterministic
  JAX core does the rest: bagging/GOSS masks are pure functions of
  (seed, iteration)).

Snapshot file layout (`ckpt_00000023.r0`):

    LGBMTPU-CKPT/1 sha256=<hex> bytes=<payload-len>\\n
    <canonical-JSON payload>

The payload holds the model string, the boosting state dict, callback
states (early stopping / recorded evaluations) and the fingerprint.
"""
from __future__ import annotations

import base64
import hashlib
import json
import os
import re
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from . import durable, log
from .testing import faults

FORMAT_VERSION = 1
_HEADER_RE = re.compile(
    rb"^LGBMTPU-CKPT/(\d+) sha256=([0-9a-f]{64}) bytes=(\d+)\n")

# params that do not change the training trajectory (or are expected to
# legitimately differ between the original and the resumed invocation)
_FINGERPRINT_EXCLUDE = {
    "tpu_checkpoint_dir", "tpu_checkpoint_interval", "tpu_checkpoint_keep",
    # observability never changes the training trajectory: a resumed run
    # may add/move/drop its telemetry sinks freely
    "tpu_telemetry_dir", "tpu_telemetry", "tpu_telemetry_prometheus",
    # ingest mechanics are bit-transparent (streamed/in-memory/cached
    # construction produce identical datasets at any chunk size or
    # landing, tests/test_ingest.py) — a resumed run may change them
    "tpu_ingest", "tpu_ingest_chunk_rows", "tpu_ingest_device_shards",
    # the histogram-merge collective is bit-transparent (scatter and
    # allreduce grow bit-identical trees, tests/test_scatter_reduce.py)
    # — a resumed run may switch schedules
    "tpu_hist_reduce",
    # sweep membership never changes a model's trajectory: a model
    # trained inside a vmapped sweep is byte-identical to training its
    # config alone (tests/test_sweep.py), and the registry name prefix
    # is serving-side bookkeeping
    "tpu_sweep_size", "tpu_sweep_name_prefix",
    # world-size-elastic resume (ISSUE 11): everything that names or
    # derives from the world size must stay OUT of the fingerprint —
    # a snapshot taken at W ranks must be accepted at W' ranks (trees
    # are bit-identical across device counts; scores re-shard through
    # restore). The watchdog/heartbeat knobs never change the
    # trajectory either; a resumed run may re-arm them freely
    "num_machines", "num_machine", "local_listen_port", "local_port",
    "time_out", "machine_list_filename",
    "tpu_collective_timeout_s", "tpu_heartbeat_dir",
    "tpu_heartbeat_lease_s", "tpu_elastic_resume",
    # serving-side admission/overload knobs (ISSUE 12) shape request
    # handling, never the training trajectory — and the compile cache
    # only changes WHERE programs load from, not what they compute
    "tpu_serving_max_queue", "tpu_serving_max_inflight",
    "tpu_serving_deadline_ms", "tpu_serving_model_qps",
    "tpu_serving_breaker_failures", "tpu_serving_breaker_reset_s",
    "tpu_serving_budget_mb", "tpu_compile_cache_dir",
    # predict-path layout/batching knobs (ISSUE 13 config-hygiene
    # sweep): bucket ladders, micro-batching, warmup, and the quantized
    # SERVING stacks change how predictions are dispatched, never how
    # trees are grown (quantized layouts are build-time derived from
    # the exact f32 forest; split decisions stay bit-exact) — a resumed
    # run may reshape its serving tier freely
    "tpu_predict_cache", "tpu_predict_bucket_min", "tpu_predict_chunk",
    "tpu_predict_pipeline", "tpu_predict_quantize",
    "tpu_predict_quantize_tol", "tpu_predict_warmup_rows",
    "tpu_predict_micro_batch", "tpu_predict_micro_batch_window_ms",
    # the train-side quantize GATE (ISSUE 20) only decides whether a
    # lossy config is ACCEPTED at setup; once training is running the
    # tolerance never touches the trajectory — a resumed run may
    # tighten or relax it freely (the MODE itself is fingerprinted
    # below)
    "tpu_hist_quantize_tol",
    # exported-forest artifacts (ISSUE 16): exporting serializes the
    # already-trained forest for serving replicas — which layouts and
    # buckets get packed never feeds back into training numerics
    "tpu_export_dir", "tpu_export_layouts", "tpu_export_buckets",
    # durable-IO retry policy (ISSUE 18, lightgbm_tpu/durable.py):
    # retries/backoff/deadline decide whether a run SURVIVES writing
    # its state, never what that state is — a resumed run may harden
    # or relax its storage policy freely
    "tpu_io_retries", "tpu_io_backoff_s", "tpu_io_deadline_s",
    "output_model", "output_result", "input_model", "convert_model",
    "config_file", "machine_list_file", "snapshot_freq", "verbose",
    "metric_freq", "num_iterations", "num_threads", "task",
}

# tpu_* params that DELIBERATELY participate in the fingerprint: each
# one changes the training trajectory (numerics, grow order, or failure
# behavior), so resume must refuse a snapshot taken under a different
# value. `config_fingerprint` hashes everything not excluded — this set
# is the EXPLICIT record of that decision for the tpu_* namespace, and
# graftlint's config-hygiene rule cross-checks it against config.py:
# every tpu_* field must appear in exactly one of the two sets, so a
# new knob cannot ship with its resume semantics undecided.
_FINGERPRINT_INCLUDED = {
    # histogram numerics/order: bf16 accumulation, the chunk and the
    # compaction threshold reshape the f32 summation tree (compaction
    # is bit-identical on order-invariant sums TODAY, but that identity
    # is a test-enforced property of the current kernels, not a
    # contract — keep it fingerprinted so resume never blends paths)
    "tpu_hist_chunk", "tpu_hist_bf16", "tpu_compact_threshold",
    # quantized-gradient training (ISSUE 20): stochastically-rounded
    # integer gradients change every histogram sum and therefore every
    # split — resume must never blend a quantized trajectory with an
    # f32 one (the gate TOLERANCE is excluded above)
    "tpu_hist_quantize",
    # nonfinite guard aborts the trajectory instead of continuing it
    "tpu_guard_nonfinite",
    # piecewise-linear leaves: the per-leaf design width changes every
    # fitted coefficient table (linear_tree/linear_lambda are non-tpu
    # params and hash automatically)
    "tpu_linear_max_features",
}

assert not (_FINGERPRINT_INCLUDED & _FINGERPRINT_EXCLUDE), \
    "a tpu_* param cannot be both fingerprint-included and excluded"


class CheckpointError(log.LightGBMError):
    """A snapshot failed validation (corrupt, truncated, wrong version)."""


# ---------------------------------------------------------------------------
# atomic file IO — the implementation moved to lightgbm_tpu/durable.py
# (ISSUE 18), which adds retry/backoff/deadline and the criticality
# policy on top of the same tmp+fsync+rename publish. These wrappers
# stay as the historical import surface; the "checkpoint.*" injection
# sites keep their names (`checkpoint.write` / `checkpoint.rename`).
# ---------------------------------------------------------------------------
def atomic_write_bytes(path: str, data: bytes, site: str = "checkpoint",
                       **kw) -> None:
    """Write `data` to `path` crash-consistently (same-dir tmp + fsync +
    atomic rename + directory fsync), retrying transient storage faults
    per the durable-IO policy; raises `durable.DurableWriteError` when
    the budget is exhausted."""
    durable.atomic_write_bytes(path, data, site=site, **kw)


def atomic_write_text(path: str, text: str, site: str = "checkpoint",
                      **kw) -> None:
    durable.atomic_write_text(path, text, site=site, **kw)


# ---------------------------------------------------------------------------
# codecs (JSON-safe encodings of numpy arrays and RNG states)
# ---------------------------------------------------------------------------
def encode_array(arr: np.ndarray) -> Dict[str, Any]:
    arr = np.ascontiguousarray(arr)
    return {"dtype": str(arr.dtype), "shape": list(arr.shape),
            "b64": base64.b64encode(arr.tobytes()).decode("ascii")}


def decode_array(enc: Dict[str, Any]) -> np.ndarray:
    raw = base64.b64decode(enc["b64"])
    return np.frombuffer(raw, dtype=np.dtype(enc["dtype"])).reshape(
        enc["shape"]).copy()


def encode_rng(rng: np.random.RandomState) -> Dict[str, Any]:
    """Serialize the exact Mersenne-Twister position so feature-fraction
    and DART drop sampling continue the original sequence on resume."""
    alg, keys, pos, has_gauss, cached = rng.get_state()
    return {"alg": alg, "keys": encode_array(np.asarray(keys)),
            "pos": int(pos), "has_gauss": int(has_gauss),
            "cached": float(cached)}


def decode_rng(enc: Dict[str, Any]) -> np.random.RandomState:
    rng = np.random.RandomState()
    rng.set_state((enc["alg"], decode_array(enc["keys"]).astype(np.uint32),
                   int(enc["pos"]), int(enc["has_gauss"]),
                   float(enc["cached"])))
    return rng


# ---------------------------------------------------------------------------
# config fingerprint
# ---------------------------------------------------------------------------
def config_fingerprint(raw_params: Dict[str, Any], num_data: int,
                       num_features: int, boosting_type: str) -> str:
    """Digest of the training trajectory's inputs. Two runs with the same
    fingerprint and the same data bytes walk identical iteration
    sequences, so a snapshot from one may seed the other."""
    items = sorted((str(k), str(v)) for k, v in raw_params.items()
                   if str(k) not in _FINGERPRINT_EXCLUDE)
    blob = json.dumps({"params": items, "rows": int(num_data),
                       "features": int(num_features),
                       "boosting": boosting_type,
                       "format": FORMAT_VERSION},
                      sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode("utf-8")).hexdigest()


# ---------------------------------------------------------------------------
# snapshot store
# ---------------------------------------------------------------------------
class CheckpointManager:
    """Keep-last-K rotation of checksummed snapshots in one directory.

    Files are `ckpt_<iteration:08d>.r<rank>`; under multi-host training
    every process writes its own rank file (scores are row-shard-local)
    and resumes from its own series — `lightgbm_tpu.engine` aligns the
    resume iteration across ranks."""

    _NAME_RE = re.compile(r"^ckpt_(\d{8})\.r(\d+)$")

    def __init__(self, directory: str, keep_last: int = 3,
                 rank: Optional[int] = None):
        self.directory = directory
        self.keep_last = max(1, int(keep_last))
        if rank is None:
            try:
                import jax
                rank = jax.process_index()
            except Exception:  # backend not initialized yet
                rank = 0
        self.rank = int(rank)
        os.makedirs(directory, exist_ok=True)
        self._sweep_stale_tmp()

    def _sweep_stale_tmp(self) -> None:
        """A REAL preemption between mkstemp and rename orphans a tmp
        file; nothing would ever reclaim it (the in-process cleanup only
        runs if the process survives), so each repeatedly-preempted run
        would leak one per kill. Sweep this rank's leftovers at startup
        — the single writer per rank makes any existing tmp stale by
        definition."""
        marker = f".r{self.rank}.tmp."
        for name in os.listdir(self.directory):
            if self._NAME_RE.match(name) is None and marker in name \
                    and name.startswith("ckpt_"):
                try:
                    os.unlink(os.path.join(self.directory, name))
                except OSError:  # pragma: no cover
                    pass

    # -- paths ----------------------------------------------------------
    def path_for(self, iteration: int) -> str:
        return os.path.join(self.directory,
                            f"ckpt_{int(iteration):08d}.r{self.rank}")

    def snapshots(self) -> List[Tuple[int, str]]:
        """(iteration, path) pairs for this rank, oldest first."""
        out = []
        for name in os.listdir(self.directory):
            m = self._NAME_RE.match(name)
            if m and int(m.group(2)) == self.rank:
                out.append((int(m.group(1)),
                            os.path.join(self.directory, name)))
        return sorted(out)

    def available_iterations(self) -> List[int]:
        return [it for it, _ in self.snapshots()]

    # -- cross-rank discovery (world-size-elastic resume) ---------------
    def snapshots_all_ranks(self) -> Dict[int, List[Tuple[int, str]]]:
        """{rank: [(iteration, path), ...]} across EVERY rank series in
        the directory — the elastic-resume view: a shrunken cohort must
        read the dead ranks' row shards, a grown cohort's new ranks
        have no series of their own at all."""
        out: Dict[int, List[Tuple[int, str]]] = {}
        for name in os.listdir(self.directory):
            m = self._NAME_RE.match(name)
            if m:
                out.setdefault(int(m.group(2)), []).append(
                    (int(m.group(1)), os.path.join(self.directory, name)))
        for files in out.values():
            files.sort()
        return out

    def load_latest_any_rank(self) -> Optional[Tuple[Dict[str, Any], str]]:
        """Newest validating snapshot across ALL rank series (own rank
        preferred at equal iteration, then the lowest rank) — the
        starting point when THIS rank has no series (a cohort grown
        past the original world size)."""
        candidates: List[Tuple[int, int, str]] = []
        for rank, files in self.snapshots_all_ranks().items():
            for iteration, path in files:
                # own rank sorts first at equal iteration
                candidates.append(
                    (iteration, 0 if rank == self.rank else rank + 1, path))
        for iteration, _, path in sorted(candidates,
                                         key=lambda t: (-t[0], t[1])):
            try:
                return self.load(path), path
            except (CheckpointError, OSError) as exc:
                log.warning("Skipping unusable checkpoint %s (%s)",
                            path, exc)
        return None

    def load_world_iteration(self, iteration: int,
                             expected_ranks: Optional[int] = None
                             ) -> Dict[int, Dict[str, Any]]:
        """Every rank's VALIDATING payload at `iteration`; corrupt or
        truncated files are skipped (a rank that died mid-write is the
        expected producer of those). With `expected_ranks` (the
        snapshot's recorded world size), an incomplete set raises —
        reassembling a partial world would silently drop rows — and
        the error names which files were absent vs unreadable."""
        out: Dict[int, Dict[str, Any]] = {}
        bad: Dict[int, str] = {}
        for rank, files in self.snapshots_all_ranks().items():
            for it, path in files:
                if it == int(iteration):
                    try:
                        out[rank] = self.load(path)
                    except (CheckpointError, OSError) as exc:
                        bad[rank] = str(exc)
        if expected_ranks is not None:
            missing = [r for r in range(int(expected_ranks))
                       if r not in out]
            if missing:
                raise CheckpointError(
                    "Elastic resume needs every original rank's snapshot "
                    "at iteration %d, but rank file(s) %s are missing "
                    "from %s%s (the checkpoint directory must be shared "
                    "storage reachable by the resuming cohort)"
                    % (int(iteration), missing, self.directory,
                       "; unreadable: %s" % bad if bad else ""))
            # drop ranks BEYOND the recorded world: an earlier larger
            # cohort's leftover files (never rotated once their ranks
            # died) would otherwise pollute the reassembly with stale
            # overlapping row ownership
            out = {r: p for r, p in out.items()
                   if r < int(expected_ranks)}
        return out

    def latest_complete_iteration(
            self, expected_ranks: int, before: Optional[int] = None
    ) -> Optional[Tuple[int, Dict[int, Dict[str, Any]]]]:
        """Newest iteration at which EVERY rank 0..expected_ranks-1 has
        a validating snapshot (optionally capped at `before`, exclusive)
        — the elastic-resume fallback when a dying rank left the series
        skewed: rank 0 wrote iteration k but rank 1 only reached k-1,
        so k-1 is the newest state the whole world can reassemble.
        Returns (iteration, {rank: payload}) — the validated payloads
        ride along so callers don't decode every snapshot twice."""
        by_rank = self.snapshots_all_ranks()
        ranks = range(int(expected_ranks))
        if any(r not in by_rank for r in ranks):
            return None
        common = set.intersection(
            *(set(it for it, _ in by_rank[r]) for r in ranks))
        for it in sorted(common, reverse=True):
            if before is not None and it >= int(before):
                continue
            payloads = {}
            try:
                for r in ranks:
                    payloads[r] = self.load(dict(by_rank[r])[it])
            except (CheckpointError, OSError):
                continue
            return it, payloads
        return None

    # -- write ----------------------------------------------------------
    def save(self, payload: Dict[str, Any], iteration: int) -> str:
        """Durably publish one snapshot, THEN rotate. The ordering is
        the crash-safety invariant: old snapshots are deleted only
        after the new one is fully durable (fsync'd + renamed), so a
        save that dies anywhere mid-write leaves the previous newest
        snapshot loadable. On ENOSPC the oldest prunable snapshot is
        evicted (never the newest durable one) and the write retried
        once — the escape hatch for a checkpoint directory that filled
        up under keep_last pressure."""
        data = json.dumps(payload, sort_keys=True,
                          separators=(",", ":")).encode("utf-8")
        header = (f"LGBMTPU-CKPT/{FORMAT_VERSION} "
                  f"sha256={hashlib.sha256(data).hexdigest()} "
                  f"bytes={len(data)}\n").encode("ascii")
        path = self.path_for(iteration)
        durable.atomic_write_bytes(path, header + data, site="checkpoint",
                                   on_enospc=self._evict_for_space)
        self._rotate()
        return path

    def _evict_for_space(self) -> bool:
        """ENOSPC escape hatch: free the OLDEST prunable snapshot of
        this rank's series. The newest durable snapshot is never a
        candidate — it is the state a preempted run resumes from."""
        snaps = self.snapshots()
        for _, path in snaps[:-1]:
            try:
                os.unlink(path)
            except OSError:  # already gone / unremovable: try the next
                continue
            log.warning("Checkpoint save hit ENOSPC; evicted oldest "
                        "snapshot %s to retry", path)
            return True
        return False

    def _rotate(self) -> None:
        # runs ONLY after the new snapshot is fully durable (see save);
        # the injection site lets tests kill a run in the write->rotate
        # window and prove both neighbors stay loadable
        faults.inject("checkpoint.rotate")
        snaps = self.snapshots()
        for _, path in snaps[:-self.keep_last]:
            try:
                os.unlink(path)
            except OSError as exc:  # pragma: no cover
                log.warning("Could not remove old checkpoint %s: %s",
                            path, exc)

    # -- read -----------------------------------------------------------
    def load(self, path: str) -> Dict[str, Any]:
        """Parse + validate one snapshot; raises CheckpointError on any
        corruption (bad header, truncation, checksum or JSON failure)."""
        faults.inject("checkpoint.read")
        with open(path, "rb") as fh:
            blob = fh.read()
        m = _HEADER_RE.match(blob)
        if not m:
            raise CheckpointError(f"{path}: missing/garbled header")
        version, digest, nbytes = (int(m.group(1)), m.group(2).decode(),
                                   int(m.group(3)))
        if version > FORMAT_VERSION:
            raise CheckpointError(
                f"{path}: format version {version} is newer than this "
                f"build supports ({FORMAT_VERSION})")
        payload = blob[m.end():]
        if len(payload) != nbytes:
            raise CheckpointError(
                f"{path}: truncated ({len(payload)} of {nbytes} payload "
                "bytes)")
        if hashlib.sha256(payload).hexdigest() != digest:
            raise CheckpointError(f"{path}: payload checksum mismatch")
        try:
            return json.loads(payload.decode("utf-8"))
        except (UnicodeDecodeError, json.JSONDecodeError) as exc:
            raise CheckpointError(f"{path}: payload not parseable "
                                  f"({exc})") from exc

    def load_iteration(self, iteration: int) -> Dict[str, Any]:
        return self.load(self.path_for(iteration))

    def load_latest(self) -> Optional[Tuple[Dict[str, Any], str]]:
        """Newest snapshot that validates; corrupt ones are QUARANTINED
        (renamed `*.corrupt`, pruned keep-last-1) and skipped with a
        warning — crash-mid-write leaves either no file or, with a
        non-atomic filesystem, a file this rejects; the previous
        snapshot then restores a slightly older but consistent state,
        and the quarantine keeps the bad bytes from being re-validated
        on every later resume."""
        for iteration, path in reversed(self.snapshots()):
            try:
                return self.load(path), path
            except (CheckpointError, OSError) as exc:
                log.warning("Skipping unusable checkpoint %s (%s); "
                            "falling back to the previous snapshot",
                            path, exc)
                if isinstance(exc, CheckpointError):
                    durable.quarantine(path, reason="checkpoint failed "
                                       "validation")
        return None


# ---------------------------------------------------------------------------
# world-size-elastic reassembly (ISSUE 11)
# ---------------------------------------------------------------------------
def payload_world(payload: Dict[str, Any]) -> Dict[str, Any]:
    """The world-size record a snapshot was taken under. Pre-elastic
    snapshots carry none — treat them as single-process (their scores
    cover the whole dataset, which is exactly what processes=1 means)."""
    return dict(payload.get("state", {}).get("world")
                or {"processes": 1, "rank": 0})


def elastic_local_state(payloads: Dict[int, Dict[str, Any]],
                        new_row_index: np.ndarray,
                        base_rank: Optional[int] = None) -> Dict[str, Any]:
    """Re-shard a W-rank snapshot set onto ONE rank of a W'-rank world.

    Every original rank's state carries its real-row score block plus
    the global row indices those rows came from (`row_index`, recorded
    by GBDT.checkpoint_state under multi-process training; implicit
    arange for processes=1). The blocks concatenate into the exact
    global [k, n_global] f32 score matrix, from which the new rank's
    partition (`new_row_index`) is sliced — per-row f32 values move
    untouched, so the elastically-resumed run stays byte-identical to
    an uninterrupted one.

    Returns a state dict (the `payload["state"]` shape) for the new
    rank: the base rank's state with `score`/`num_data`/`row_index`
    replaced. Host-RNG and callback state are replicated across ranks
    by construction, so any base rank is equivalent; `base_rank`
    defaults to the lowest available."""
    if not payloads:
        raise CheckpointError("Elastic resume: no snapshot payloads")
    ranks = sorted(payloads)
    if base_rank is None or base_rank not in payloads:
        base_rank = ranks[0]
    base = payloads[base_rank]

    blocks = []       # (global_indices, [k, n_local] real-row scores)
    n_global = 0
    k = None
    for rank in ranks:
        state = payloads[rank].get("state", {})
        if "num_data" not in state:
            raise CheckpointError(
                "Elastic resume: rank %d's snapshot predates world-size "
                "metadata (written by an older build); it can only be "
                "restored at its original world size" % rank)
        n_local = int(state["num_data"])
        score = decode_array(state["score"])
        if k is None:
            k = score.shape[0]
        elif score.shape[0] != k:
            raise CheckpointError(
                "Elastic resume: rank %d's score has %d classes, "
                "expected %d" % (rank, score.shape[0], k))
        if "row_index" in state:
            gidx = decode_array(state["row_index"]).astype(np.int64)
            if gidx.shape[0] != n_local:
                raise CheckpointError(
                    "Elastic resume: rank %d records %d row indices for "
                    "%d rows" % (rank, gidx.shape[0], n_local))
        elif len(ranks) == 1:
            gidx = np.arange(n_local, dtype=np.int64)
        else:
            raise CheckpointError(
                "Elastic resume: rank %d's snapshot carries no global "
                "row indices (pre-partitioned data files record none); "
                "restore at the original world size instead" % rank)
        blocks.append((gidx, score[:, :n_local]))
        n_global = max(n_global, int(gidx.max()) + 1 if n_local else 0)

    global_score = np.zeros((k, n_global), np.float32)
    covered = np.zeros(n_global, bool)
    for gidx, score in blocks:
        if covered[gidx].any():
            raise CheckpointError(
                "Elastic resume: overlapping row ownership across rank "
                "snapshots — the series mixes incompatible runs")
        global_score[:, gidx] = score
        covered[gidx] = True
    if not covered.all():
        raise CheckpointError(
            "Elastic resume: rank snapshots cover %d of %d global rows "
            "— a rank series is missing or stale"
            % (int(covered.sum()), n_global))

    new_idx = np.asarray(new_row_index, np.int64)
    if new_idx.size and (new_idx.min() < 0 or new_idx.max() >= n_global):
        raise CheckpointError(
            "Elastic resume: the resuming rank's partition indexes row "
            "%d but the snapshot world only covers %d rows — the "
            "dataset differs from the checkpointed run"
            % (int(new_idx.max()), n_global))
    state = dict(base["state"])
    state["score"] = encode_array(
        np.ascontiguousarray(global_score[:, new_idx]))
    state["num_data"] = int(new_idx.size)
    state["row_index"] = encode_array(new_idx)
    return state
