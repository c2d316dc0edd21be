"""Public Dataset / Booster API.

Mirrors the reference python-package surface (`python-package/lightgbm/
basic.py` — lazy `Dataset` at :548, `Booster` at :1223) directly over the
TPU engine; there is no ctypes boundary because the "C API layer" of the
reference (src/c_api.cpp) collapses into in-process Python + device calls.
A C-compatible shim for external bindings lives in `lightgbm_tpu/capi.py`.
"""
from __future__ import annotations

import copy
from typing import Any, Dict, List, Optional, Sequence, Union

import numpy as np

from . import log
from .boosting import create_boosting
from .config import (Config, _parse_value, key_alias_transform,
                     params_str2map)
from .dataset import Dataset as _InnerDataset
from .metrics import default_metric_for_objective
from .objectives import create_objective

LightGBMError = log.LightGBMError

# one-time (per process) acknowledgement that data_has_header/is_reshape
# have no effect in this build (see Booster.predict)
_PREDICT_COMPAT_WARNED = False


def _data_to_2d(data) -> np.ndarray:
    if isinstance(data, str):
        from .io.parser import load_data_file
        arr, _ = load_data_file(data)
        return arr
    try:
        import pandas as pd
        if isinstance(data, pd.DataFrame):
            return data.values.astype(np.float64)
    except ImportError:
        pass
    try:
        import scipy.sparse as sp
        if sp.issparse(data):
            # still called with a sparse matrix by Booster.predict (and
            # refit, and the sklearn wrappers' predict): scoring densifies
            # its input. Dataset() does not come here: a sparse training
            # set is streamed by ingest.SparseSource (_lazy_init)
            return np.asarray(data.todense(), np.float64)
    except ImportError:
        pass
    arr = np.asarray(data, np.float64)
    if arr.ndim == 1:
        arr = arr.reshape(1, -1)
    return arr


def _is_sparse(data) -> bool:
    """A scipy sparse matrix? Whoever holds one has imported
    `scipy.sparse`; a dense input must not pay that import here (0.7-1.3 s
    of every `construct()`, my chip runs, PR 38)."""
    import sys
    sp = sys.modules.get("scipy.sparse")
    return sp is not None and sp.issparse(data)


def _device_landing_factory(params: Dict[str, Any]):
    """Per-device row sharding at ingest time (tpu_ingest_device_shards):
    under a single-process data/voting-parallel run, pass 2 lands binned
    chunks straight into per-device HBM blocks (ingest.ShardedLanding)
    instead of a host matrix, so the dataset can exceed one device's HBM
    (and, with the host blocks freed as they ship, most of host RAM).
    Returns None (host landing) when the conditions don't hold."""
    if not _parse_value(params.get("tpu_ingest_device_shards", False), bool):
        return None
    learner = str(params.get("tree_learner", "serial"))
    if learner not in ("data", "voting"):
        log.warning("tpu_ingest_device_shards needs tree_learner=data or "
                    "voting (got %s); landing on host", learner)
        return None
    import jax
    if jax.process_count() > 1:
        # multi-process rows ride the loader partition + the grower's
        # global_row_array assembly; per-device landing is the
        # single-process N x HBM story
        log.warning("tpu_ingest_device_shards is single-process only; "
                    "landing on host")
        return None

    def factory(num_rows, num_groups, dtype, max_group_bin):
        from .ingest import ShardedLanding
        from .learner.schedule import plan_row_layout
        layout = plan_row_layout(
            num_rows, num_groups, max_group_bin,
            tpu_hist_chunk=int(params.get("tpu_hist_chunk", 65536)),
            tree_learner=learner, ndev=len(jax.devices()),
            nproc=jax.process_count())
        log.info("Ingest: landing %d rows (padded %d) as %d-way "
                 "per-device row shards", num_rows, layout.n_pad,
                 layout.ndev)
        return ShardedLanding(num_rows, num_groups, dtype, layout)

    return factory


class Dataset:
    """Lazy dataset wrapper (reference: basic.py:548-1222)."""

    def __init__(self, data, label=None, max_bin: int = 255, reference: Optional["Dataset"] = None,
                 weight=None, group=None, init_score=None, silent: bool = False,
                 feature_name: Union[str, Sequence[str]] = "auto",
                 categorical_feature: Union[str, Sequence] = "auto",
                 params: Optional[Dict[str, Any]] = None, free_raw_data: bool = False):
        self.data = data
        self.label = label
        self.max_bin = max_bin
        self.reference = reference
        self.weight = weight
        self.group = group
        self.init_score = init_score
        self.params = dict(params or {})
        self.feature_name = feature_name
        self.categorical_feature = categorical_feature
        self.free_raw_data = free_raw_data
        self._inner: Optional[_InnerDataset] = None
        self.used_indices: Optional[np.ndarray] = None
        self._predictor = None
        self._constructed_max_bin: Optional[int] = None
        # pre-computed BinMappers (C API sampled-column streaming path)
        self._preset_mappers = None

    @classmethod
    def _from_inner(cls, inner) -> "Dataset":
        """Wrap an already-constructed _InnerDataset (binary fast path /
        two-round loader)."""
        ds = cls.__new__(cls)
        ds.data = None
        ds.label = inner.metadata.label
        ds.max_bin = inner.max_bin
        ds.reference = None
        ds.weight = None
        ds.group = None
        ds.init_score = None
        ds.params = {}
        ds.feature_name = "auto"
        ds.categorical_feature = "auto"
        ds.free_raw_data = True
        ds._inner = inner
        ds.used_indices = None
        ds._predictor = None
        ds._constructed_max_bin = inner.max_bin
        ds._preset_mappers = None
        return ds

    def _update_params(self, params: Dict[str, Any]) -> "Dataset":
        """Fold training-time params into the not-yet-constructed dataset
        (reference: basic.py Dataset._update_params — binning params like
        max_bin given to lgb.train() must reach the lazy construction)."""
        if not params:
            return self
        if self._inner is not None:
            pk = key_alias_transform(dict(params))
            new_bin = pk.get("max_bin")
            if new_bin is not None and int(new_bin) != self._constructed_max_bin:
                log.warning(
                    "Dataset already constructed with max_bin=%d; "
                    "ignoring max_bin=%s from training params",
                    self._constructed_max_bin, new_bin)
            # any construction-time param that differs from what the lazy
            # init saw can no longer take effect (reference: "Cannot
            # change ... after constructed")
            bin_defaults = {
                "min_data_in_bin": 3, "bin_construct_sample_cnt": 200000,
                "enable_bundle": True, "max_conflict_rate": 0.0,
                "use_missing": True, "zero_as_missing": False,
                "sparse_threshold": 0.8, "data_random_seed": 1}
            for key, default in bin_defaults.items():
                if key in pk and \
                        str(pk[key]) != str(self.params.get(key, default)):
                    log.warning(
                        "Dataset already constructed; ignoring %s=%s from "
                        "training params", key, pk[key])
            return self
        self.params.update(params)
        return self

    # ------------------------------------------------------------------
    def _lazy_init(self) -> _InnerDataset:
        if self._inner is not None:
            return self._inner
        params = key_alias_transform(self.params)
        max_bin = int(params.get("max_bin", self.max_bin))
        data = self.data
        streamed_source = None
        if isinstance(data, str):
            # file inputs stream through the ingest subsystem (two-pass
            # chunked binning, lightgbm_tpu/ingest) — the raw float
            # matrix never materializes. tpu_ingest=false keeps the old
            # load-everything path; libsvm and subset() fall back too.
            use_stream = _parse_value(params.get("tpu_ingest", True), bool) \
                and self.used_indices is None
            if use_stream:
                from .ingest import FileSource
                try:
                    streamed_source = FileSource(
                        data,
                        chunk_rows=int(params.get("tpu_ingest_chunk_rows",
                                                  65536)),
                        has_header=_parse_value(
                            params.get("has_header", False), bool))
                except ValueError:
                    streamed_source = None  # libsvm: dense-load below
            if streamed_source is None:
                from .io.parser import load_data_file
                arr, label = load_data_file(
                    data, has_header=_parse_value(
                        params.get("has_header", False), bool))
                if self.label is None and label is not None:
                    self.label = label
                data = arr
        elif _is_sparse(data):
            # a scipy sparse matrix is binned and bundled from its stored
            # entries (ingest.SparseSource): the dense [rows, features]
            # matrix is never formed. The input's type decides; the
            # default chunk holds more rows than a dense source's, since
            # a row is a handful of entries
            from .ingest import SparseSource
            from .ingest.sources import SPARSE_CHUNK_ROWS
            if self.used_indices is not None:
                data = data.tocsr()[self.used_indices]
            streamed_source = SparseSource(data, chunk_rows=int(params.get(
                "tpu_ingest_chunk_rows", SPARSE_CHUNK_ROWS)))
        elif not (isinstance(data, np.ndarray) and data.ndim == 2
                  and data.dtype == np.float32):
            # a float32 table stays as it is: ingest widens each chunk as
            # it bins it (float32 -> float64 is exact, so the bins are the
            # same), where a float64 copy of 1M x 2000 floats was 16.8 GB
            # of host memory and a third of construct() (PERF.md, PR 28)
            data = _data_to_2d(data)
        if self.used_indices is not None and streamed_source is None:
            data = data[self.used_indices]

        feature_names = None
        cat_indices: Optional[List[int]] = None
        if self.feature_name != "auto" and self.feature_name is not None:
            feature_names = list(self.feature_name)
        try:
            import pandas as pd
            if isinstance(self.data, pd.DataFrame):
                if feature_names is None:
                    feature_names = [str(c) for c in self.data.columns]
                if self.categorical_feature == "auto":
                    cat_indices = [i for i, dt in enumerate(self.data.dtypes)
                                   if str(dt) == "category"]
        except ImportError:
            pass
        cat_param = self.categorical_feature
        if cat_param == "auto" and params.get("categorical_column"):
            # params-passed categorical features (the reference's
            # categorical_column / categorical_feature parameter,
            # config.h io section): "0,1,2" or "name:c1,c2" or a list
            cp = params["categorical_column"]
            if isinstance(cp, str):
                if cp.startswith("name:"):
                    # name:-prefixed entries resolve strictly through the
                    # feature-name table, even when the names are numeric
                    # strings (the reference's contract)
                    cat_param = [c for c in cp[5:].split(",") if c != ""]
                else:
                    cat_param = []
                    for c in cp.split(","):
                        if c == "":
                            continue
                        try:
                            cat_param.append(int(c))
                        except ValueError:
                            log.fatal(
                                "categorical_column: cannot parse '%s' as "
                                "a feature index; use integer indices or "
                                "the name: prefix for feature names" % c)
            elif isinstance(cp, (int, np.integer)):
                cat_param = [int(cp)]
            else:
                cat_param = list(cp)
        if isinstance(cat_param, (list, tuple)):
            cat_indices = []
            for c in cat_param:
                if isinstance(c, str) and feature_names and c in feature_names:
                    cat_indices.append(feature_names.index(c))
                elif isinstance(c, (int, np.integer)):
                    cat_indices.append(int(c))
                elif isinstance(c, str):
                    # the reference warns about unmatched names
                    # (dataset_loader.cpp categorical handling) instead
                    # of silently dropping them
                    log.warning(
                        "categorical_column entry '%s' does not match "
                        "any feature name; ignored", c)

        label = self.label
        if label is not None:
            label = np.asarray(label, np.float32).ravel()
            if self.used_indices is not None:
                label = label[self.used_indices]
        weight = self.weight
        if weight is not None and self.used_indices is not None:
            weight = np.asarray(weight)[self.used_indices]
        group = self.group
        init_score = self.init_score
        if init_score is not None and self.used_indices is not None:
            init_score = np.asarray(init_score)[self.used_indices]

        ref_inner = self.reference._lazy_init() if self.reference is not None else None
        build_kwargs = dict(
            label=label, max_bin=max_bin,
            min_data_in_bin=int(params.get("min_data_in_bin", 3)),
            bin_construct_sample_cnt=int(params.get("bin_construct_sample_cnt", 200000)),
            data_random_seed=int(params.get("data_random_seed", 1)),
            categorical_features=cat_indices,
            use_missing=_parse_value(params.get("use_missing", True), bool),
            zero_as_missing=_parse_value(
                params.get("zero_as_missing", False), bool),
            feature_names=feature_names,
            weight=weight, group=group, init_score=init_score,
            reference=ref_inner,
            # EFB (dataset.cpp:66-211); feature-parallel shards features
            # 1:1 onto stored columns, so bundling is disabled there
            # (warned below — sparse data keeps its full dense width)
            enable_bundle=(_parse_value(params.get("enable_bundle", True), bool)
                           and params.get("tree_learner", "serial") != "feature"),
            max_conflict_rate=float(params.get("max_conflict_rate", 0.0)),
            sparse_threshold=float(params.get("sparse_threshold", 0.8)),
            mappers=self._preset_mappers,
            # device landing is for the TRAINING matrix only: valid sets
            # (reference datasets) are consumed host-side by add_valid
            landing_factory=(_device_landing_factory(params)
                             if ref_inner is None else None))
        # linear_tree fits per-leaf regressions on RAW feature values:
        # arm keep_raw automatically so params-routed training (engine,
        # sklearn, CLI) never trips the booster's keep_raw refusal
        linear_tree = _parse_value(
            params.get("linear_tree", params.get("linear_trees", False)),
            bool)
        if streamed_source is not None:
            from .ingest import build_inner
            self._inner = build_inner(streamed_source,
                                      keep_raw=linear_tree, **build_kwargs)
        else:
            self._inner = _InnerDataset.from_numpy(
                data, keep_raw=(not self.free_raw_data) or linear_tree,
                chunk_rows=int(params.get("tpu_ingest_chunk_rows", 65536)),
                **build_kwargs)
        self._constructed_max_bin = max_bin
        if (params.get("tree_learner", "serial") == "feature"
                and _parse_value(params.get("enable_bundle", True), bool)):
            log.warning(
                "tree_learner=feature stores features UNBUNDLED (EFB "
                "disabled): sparse/high-dimensional data keeps its full "
                "dense column width. Prefer tree_learner=data for sparse "
                "data, or set enable_bundle=false to silence this.")
        return self._inner

    def construct(self) -> "Dataset":
        self._lazy_init()
        return self

    # ------------------------------------------------------------------
    def create_valid(self, data, label=None, weight=None, group=None,
                     init_score=None, silent: bool = False,
                     params: Optional[dict] = None) -> "Dataset":
        """Reference: basic.py Dataset.create_valid."""
        return Dataset(data, label=label, reference=self, weight=weight,
                       group=group, init_score=init_score, silent=silent,
                       params=params or self.params)

    def set_reference(self, reference: "Dataset") -> "Dataset":
        self.reference = reference
        self._inner = None
        return self

    def subset(self, used_indices, params: Optional[dict] = None) -> "Dataset":
        """Reference: basic.py Dataset.subset (used by cv)."""
        ds = Dataset(self.data, label=self.label, max_bin=self.max_bin,
                     reference=self.reference or self, weight=self.weight,
                     group=None, init_score=None,
                     feature_name=self.feature_name,
                     categorical_feature=self.categorical_feature,
                     params=params or self.params)
        ds.used_indices = np.asarray(sorted(used_indices))
        if self.group is not None:
            log.warning("subset() with query data drops group info; "
                        "regroup manually for ranking cv")
        return ds

    # ------------------------------------------------------------------
    def set_label(self, label) -> "Dataset":
        self.label = label
        if self._inner is not None:
            self._inner.metadata.set_label(np.asarray(label, np.float32).ravel())
        return self

    def set_weight(self, weight) -> "Dataset":
        self.weight = weight
        if self._inner is not None:
            self._inner.metadata.set_weights(weight)
        return self

    def set_group(self, group) -> "Dataset":
        self.group = group
        if self._inner is not None:
            self._inner.metadata.set_group(group)
        return self

    def set_init_score(self, init_score) -> "Dataset":
        self.init_score = init_score
        if self._inner is not None:
            self._inner.metadata.set_init_score(init_score)
        return self

    def get_label(self):
        if self._inner is not None and self._inner.metadata.label is not None:
            return self._inner.metadata.label
        return self.label

    def get_weight(self):
        return self.weight

    def get_group(self):
        return self.group

    def get_init_score(self):
        return self.init_score

    def num_data(self) -> int:
        return self._lazy_init().num_data

    def num_feature(self) -> int:
        return self._lazy_init().num_total_features

    def save_binary(self, filename: str) -> "Dataset":
        self._lazy_init().save_binary(filename)
        return self

    def get_field(self, name: str):
        inner = self._lazy_init()
        if name == "label":
            return inner.metadata.label
        if name == "weight":
            return inner.metadata.weights
        if name == "group":
            qb = inner.metadata.query_boundaries
            return None if qb is None else np.diff(qb)
        if name == "init_score":
            return inner.metadata.init_score
        raise LightGBMError(f"Unknown field {name}")

    def set_field(self, name: str, data) -> None:
        inner = self._lazy_init()
        if name == "label":
            inner.metadata.set_label(data)
        elif name == "weight":
            inner.metadata.set_weights(data)
        elif name == "group":
            inner.metadata.set_group(data)
        elif name == "init_score":
            inner.metadata.set_init_score(data)
        else:
            raise LightGBMError(f"Unknown field {name}")


class Booster:
    """Reference: basic.py:1223+ over c_api Booster (c_api.cpp:28-308)."""

    def __init__(self, params: Optional[dict] = None, train_set: Optional[Dataset] = None,
                 model_file: Optional[str] = None, model_str: Optional[str] = None,
                 silent: bool = False):
        self.params = dict(params or {})
        self.train_set = train_set
        self._valid_sets: List[Dataset] = []
        self.name_valid_sets: List[str] = []
        self.best_iteration = -1
        self.best_score: Dict = {}
        self._train_data_name = "training"

        if train_set is not None:
            cfg = Config.from_params(self.params)
            self.config = cfg
            inner_train = train_set._lazy_init()
            objective = create_objective(cfg)
            self._inner = create_boosting(cfg.boosting_type, cfg)
            metric_names = cfg.metric.metric_types or \
                [default_metric_for_objective(cfg.objective)]
            self._metric_names = metric_names
            self._inner.init(inner_train, objective, metric_names)
        elif model_file is not None:
            with open(model_file) as fh:
                text = fh.read()
            self._from_string(text)
        elif model_str is not None:
            self._from_string(model_str)
        else:
            raise LightGBMError("Booster needs train_set, model_file or model_str")

    def _from_string(self, text: str) -> None:
        first = text.strip().splitlines()[0].strip()
        boosting_type = {"tree": "gbdt", "gbdt": "gbdt", "dart": "dart",
                         "goss": "goss"}.get(first, "gbdt")
        params = dict(self.params)
        # objective from model text so convert_output works
        for line in text.splitlines()[:20]:
            if line.startswith("objective="):
                obj = line.split("=", 1)[1].split()
                params.setdefault("objective", obj[0])
                for tok in obj[1:]:
                    if ":" in tok:
                        k, v = tok.split(":", 1)
                        params.setdefault(k, v)
        cfg = Config.from_params(params)
        self.config = cfg
        self._inner = create_boosting(boosting_type, cfg)
        self._inner.load_model_from_string(text)
        if "objective" in params:
            self._inner.objective = create_objective(cfg)
        self._metric_names = []
        # the shared Predictor (if any) is bound to the replaced engine
        self._serving_default = None

    # ------------------------------------------------------------------
    def _reset_training_data(self, train_set: Dataset) -> "Booster":
        """Swap the training set, keep the ensemble (reference:
        Booster::ResetTrainingData, c_api.cpp:95-105 ->
        GBDT::ResetTrainingData, gbdt.cpp:722-775): objective and metrics
        re-initialize against the new data and training scores are
        rebuilt by replaying the existing trees."""
        import jax.numpy as jnp

        old = self._inner
        models = old.models
        it = old.iter_
        inner_train = train_set._lazy_init()
        # schema guard (the reference fatals on mismatched bin mappers,
        # Dataset::CheckAlign semantics): a different feature count or
        # binning would silently replay trees into wrong bins
        old_ds = old.train_data
        if old_ds is not None:
            a = old_ds.feature_meta_arrays()
            b = inner_train.feature_meta_arrays()
            same = (old_ds.num_features == inner_train.num_features
                    and all(np.array_equal(a[key], b[key]) for key in a))
            if not same:
                raise LightGBMError(
                    "Cannot reset training data: feature/bin schema differs "
                    "from the original dataset (construct the new Dataset "
                    "with reference= the original)")
        self.train_set = train_set
        objective = create_objective(self.config)
        fresh = create_boosting(self.config.boosting_type, self.config)
        fresh.init(inner_train, objective, self._metric_names)
        fresh.models = models
        fresh.iter_ = it
        # a GBDT ensemble already carries the boost-from-average bias
        # inside its first tree (AddBias, gbdt.cpp:445-447) — undo the
        # fresh init's score bump so the replay doesn't double-count it.
        # RF trees never fold the bias (rf.py), so its bump stays.
        if models and not fresh.average_output \
                and fresh.init_score_bias != 0.0:
            fresh._score = fresh._score - fresh.init_score_bias
            fresh._pending_bias = 0.0
            fresh.init_score_bias = 0.0
        # replay the ensemble into the new training scores (the
        # reference's train_score_updater_ rebuild); RF keeps scores as
        # the running AVERAGE of tree contributions (rf.py:72-81)
        k = fresh.num_tree_per_iteration
        acc = jnp.zeros_like(fresh._score)
        for i, tree in enumerate(models):
            if tree.num_leaves > 1:
                # linear trees replay via leaf ids + raw values (the
                # binned-only path refuses them); fresh.init landed _raw
                # when the config has linear_tree=true
                acc = acc.at[i % k].add(fresh._tree_values_device(
                    tree.to_device(), fresh._binned,
                    getattr(fresh, "_raw", None)))
        if fresh.average_output and it > 0:
            acc = acc / float(it)
        fresh._score = fresh._score + acc
        # valid sets carry over untouched (reference keeps them)
        for vi, vs in enumerate(getattr(old, "valid_sets", [])):
            fresh.add_valid(vs, old.valid_names[vi], self._metric_names)
        self._inner = fresh
        # the shared Predictor (if any) is bound to the replaced engine
        self._serving_default = None
        return self

    def add_valid(self, data: Dataset, name: str) -> "Booster":
        if data.reference is None and self.train_set is not None:
            data.set_reference(self.train_set)
        inner = data._lazy_init()
        self._valid_sets.append(data)
        self.name_valid_sets.append(name)
        self._inner.add_valid(inner, name, self._metric_names)
        return self

    def update(self, train_set=None, fobj=None) -> bool:
        """One boosting iteration; returns True if no further splits
        (reference: basic.py Booster.update -> LGBM_BoosterUpdateOneIter)."""
        if fobj is None:
            return self._inner.train_one_iter()
        grad, hess = fobj(self.__pred_for_fobj(), self.train_set)
        return self.__boost(grad, hess)

    def __pred_for_fobj(self):
        return self._inner._train_score_unpadded()

    def __boost(self, grad, hess) -> bool:
        grad = np.asarray(grad, np.float32)
        hess = np.asarray(hess, np.float32)
        k = self._inner.num_tree_per_iteration
        n = self._inner._n
        if grad.size != n * k:
            raise LightGBMError(
                f"Lengths of gradients ({grad.size}) doesn't equal "
                f"num_data*num_class ({n * k})")
        n_pad = self._inner._n_pad
        g = np.zeros((k, n_pad), np.float32)
        h = np.zeros((k, n_pad), np.float32)
        g[:, :n] = grad.reshape(k, n)
        h[:, :n] = hess.reshape(k, n)
        return self._inner.train_one_iter(g, h)

    def rollback_one_iter(self) -> "Booster":
        self._inner.rollback_one_iter()
        return self

    def current_iteration(self) -> int:
        return self._inner.current_iteration()

    def num_trees(self) -> int:
        return self._inner.num_trees()

    # ------------------------------------------------------------------
    def set_train_data_name(self, name: str) -> "Booster":
        """Name used for the training data in eval results (reference:
        basic.py Booster.set_train_data_name)."""
        self._train_data_name = name
        return self

    def eval_train(self, feval=None) -> List:
        return self.__inner_eval(self._train_data_name, -1, feval)

    def eval_valid(self, feval=None) -> List:
        out = []
        for i in range(len(self._valid_sets)):
            out.extend(self.__inner_eval(self.name_valid_sets[i], i, feval))
        return out

    def eval(self, data: Dataset, name: str, feval=None) -> List:
        for i, v in enumerate(self._valid_sets):
            if v is data:
                return self.__inner_eval(name, i, feval)
        self.add_valid(data, name)
        return self.__inner_eval(name, len(self._valid_sets) - 1, feval)

    def __inner_eval(self, name: str, idx: int, feval=None) -> List:
        out = []
        if idx < 0:
            if self._inner.metrics:
                score = self._inner._train_score_unpadded()
                for m in self._inner.metrics:
                    for mname, val in m.eval(score, self._inner.objective):
                        out.append((name, mname, val, m.is_bigger_better))
        else:
            score = np.asarray(self._inner._valid_score[idx], np.float64).reshape(-1)
            for m in self._inner.valid_metrics[idx]:
                for mname, val in m.eval(score, self._inner.objective):
                    out.append((name, mname, val, m.is_bigger_better))
        if feval is not None:
            ds = self.train_set if idx < 0 else self._valid_sets[idx]
            if idx < 0:
                preds = self._inner._train_score_unpadded()
            else:
                preds = np.asarray(self._inner._valid_score[idx], np.float64).reshape(-1)
            ret = feval(preds, ds)
            if isinstance(ret, list):
                for mname, val, bigger in ret:
                    out.append((name, mname, val, bigger))
            else:
                mname, val, bigger = ret
                out.append((name, mname, val, bigger))
        return out

    # ------------------------------------------------------------------
    def serving_predictor(self, **kwargs) -> "Predictor":
        """A serving front end bound to this booster (reference:
        Predictor, predictor.hpp:24-205): warmup over the bucket
        ladder, micro-batching of concurrent requests, and
        latency/throughput/cache counters. Kwargs fix the default
        predict arguments (num_iteration, raw_score, ...)."""
        from .serving import Predictor
        return Predictor(self, **kwargs)

    def export_forest(self, path: str, num_iteration: int = -1,
                      layouts=None, buckets=None,
                      calibration=None) -> dict:
        """Pack this booster's compiled-forest layouts into a
        self-contained serving artifact (`lightgbm_tpu/export/`): f32
        plus the requested quantized stacks, per bucket of the
        power-of-two row ladder, traced through `jax.export` so a
        replica serves them WITHOUT the training stack. Defaults come
        from `tpu_export_layouts` / `tpu_export_buckets`; `calibration`
        (real feature rows) freezes the quantize accuracy-gate deltas
        into the manifest. Returns the writer's summary dict."""
        from .export import write_artifact
        return write_artifact(self._inner, path,
                              num_iteration=num_iteration,
                              layouts=layouts, buckets=buckets,
                              calibration=calibration)

    def _serving(self) -> "Predictor":
        """Shared default Predictor every Booster.predict routes
        through, so serving counters accumulate per booster."""
        p = getattr(self, "_serving_default", None)
        if p is None:
            p = self.serving_predictor()
            self._serving_default = p
        return p

    def predict(self, data, num_iteration: int = -1, raw_score: bool = False,
                pred_leaf: bool = False, pred_contrib: bool = False,
                data_has_header: bool = False, is_reshape: bool = True,
                pred_early_stop: bool = False, pred_early_stop_freq: int = 10,
                pred_early_stop_margin: float = 10.0):
        # data_has_header only applies to file inputs the reference CLI
        # parses and is_reshape to its flat C-API outputs; neither has an
        # effect here (files are parsed headers-and-all by load_data_file
        # and outputs are already [n, k]-shaped). Acknowledge the knob
        # once instead of silently ignoring it.
        global _PREDICT_COMPAT_WARNED
        if (data_has_header or not is_reshape) and not _PREDICT_COMPAT_WARNED:
            _PREDICT_COMPAT_WARNED = True
            log.warning(
                "Booster.predict ignores data_has_header/is_reshape: "
                "file inputs are parsed by the loader directly and "
                "outputs are always reshaped to [num_data, num_class] "
                "(warned once)")
        arr = _data_to_2d(data)
        return self._serving().predict(
            arr, num_iteration=num_iteration, raw_score=raw_score,
            pred_leaf=pred_leaf, pred_contrib=pred_contrib,
            pred_early_stop=pred_early_stop,
            pred_early_stop_freq=pred_early_stop_freq,
            pred_early_stop_margin=pred_early_stop_margin)

    # ------------------------------------------------------------------
    # checkpoint/resume (lightgbm_tpu/checkpoint.py): the payload wraps
    # the engine state with the model string so any snapshot doubles as
    # a loadable model file source
    def checkpoint_state(self) -> dict:
        from . import checkpoint as ckpt_mod
        inner_state = self._inner.checkpoint_state()
        return {
            "format": ckpt_mod.FORMAT_VERSION,
            "iteration": int(inner_state["iter"]),
            "boosting_type": self.config.boosting_type,
            "model": self._inner.save_model_to_string(),
            "state": inner_state,
            "booster": {
                "best_iteration": int(self.best_iteration),
                "best_score": {d: dict(m)
                               for d, m in self.best_score.items()},
            },
        }

    def restore_state(self, payload: dict) -> "Booster":
        """Apply a snapshot payload to this (freshly constructed, same
        config/data) booster. Engine-level concerns — fingerprint check,
        callback state — live in `lightgbm_tpu.engine`."""
        import collections as _collections
        self._inner.restore_state(payload["state"], payload["model"])
        meta = payload.get("booster", {})
        self.best_iteration = int(meta.get("best_iteration", -1))
        self.best_score = {
            d: _collections.OrderedDict(m)
            for d, m in meta.get("best_score", {}).items()}
        return self

    # ------------------------------------------------------------------
    def save_model(self, filename: str, num_iteration: int = -1) -> "Booster":
        self._inner.save_model(filename, num_iteration)
        return self

    def model_to_string(self, num_iteration: int = -1) -> str:
        return self._inner.save_model_to_string(num_iteration)

    def dump_model(self, num_iteration: int = -1) -> dict:
        return self._inner.dump_model(num_iteration)

    def feature_importance(self, importance_type: str = "split",
                           iteration: int = -1) -> np.ndarray:
        return self._inner.feature_importance(importance_type, iteration)

    def feature_name(self) -> List[str]:
        return list(self._inner.feature_names)

    def num_feature(self) -> int:
        return self._inner.max_feature_idx + 1

    def num_model_per_iteration(self) -> int:
        return self._inner.num_tree_per_iteration

    # pickling support (reference: test_engine.py:382 pickling tests)
    def __getstate__(self):
        state = {"params": self.params,
                 "model_str": self.model_to_string(),
                 "best_iteration": self.best_iteration,
                 "best_score": self.best_score}
        return state

    def __setstate__(self, state):
        self.params = state["params"]
        self.train_set = None
        self._valid_sets = []
        self.name_valid_sets = []
        self.best_iteration = state.get("best_iteration", -1)
        self.best_score = state.get("best_score", {})
        self._from_string(state["model_str"])

    def __copy__(self):
        return self.__deepcopy__(None)

    def __deepcopy__(self, _):
        b = Booster(params=self.params, model_str=self.model_to_string())
        b.best_iteration = self.best_iteration
        return b
