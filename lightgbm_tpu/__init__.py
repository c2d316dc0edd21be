"""lightgbm_tpu — a TPU-native gradient boosting framework.

A from-scratch JAX/XLA re-design of the LightGBM feature set: leaf-wise
histogram GBDT with data/feature/voting-parallel distributed training over
`jax.sharding.Mesh` collectives, objectives/metrics for regression, binary,
multiclass and lambdarank, DART/GOSS/RF variants, and a LightGBM-compatible
Python API and text model format.
"""
import os as _os

# Persistent XLA compilation cache: a re-run of an already-seen (shape,
# config) signature loads its programs from disk instead of compiling.
# ONE rule, kept here. If JAX_COMPILATION_CACHE_DIR is set, jax reads it
# itself and this package sets no cache directory in code — not at
# import, not from `tpu_compile_cache_dir` — so whoever launches the
# process decides where the cache lives. Otherwise the cache sits at a
# fixed path inside the checkout: the directory is part of what makes a
# later process hit, so it is never derived from a home directory, a
# temp dir, a pid or a clock. LIGHTGBM_TPU_COMPILE_CACHE=0 opts out of
# the default (the environment variable, read by jax, still applies).
DEFAULT_COMPILE_CACHE_DIR = _os.path.join(
    _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
    ".jax_cache")


def compile_cache_dir_from_env() -> str:
    """The cache directory placed from outside ("" when unset). While it
    is non-empty no code in this package may re-point the cache
    (`serving.forest.enable_compile_cache` asks here)."""
    return _os.environ.get("JAX_COMPILATION_CACHE_DIR", "")


if (_os.environ.get("LIGHTGBM_TPU_COMPILE_CACHE", "1") != "0"
        and not compile_cache_dir_from_env()):
    try:
        # jax.config.update is safe pre-backend: it initializes nothing
        import jax as _jax
        _jax.config.update("jax_compilation_cache_dir",
                           DEFAULT_COMPILE_CACHE_DIR)
    except Exception as _exc:  # training proceeds uncached, but says so
        from . import log as _log
        _log.warning("persistent compile cache at %s could not be armed: "
                     "%r", DEFAULT_COMPILE_CACHE_DIR, _exc)

# The public names below resolve lazily (PEP 562).  Training-free serving
# replicas import `lightgbm_tpu.export.runtime` with the trainer modules
# (boosting/, learner/, ingest/, parallel/) absent or import-blocked; an
# eager `from .basic import ...` here would drag the whole training stack
# into every child process and defeat the export subsystem's isolation.
_LAZY_ATTRS = {
    "Booster": ("lightgbm_tpu.basic", "Booster"),
    "Dataset": ("lightgbm_tpu.basic", "Dataset"),
    "cv": ("lightgbm_tpu.engine", "cv"),
    "train": ("lightgbm_tpu.engine", "train"),
    "log": ("lightgbm_tpu.log", None),
    "LGBMClassifier": ("lightgbm_tpu.sklearn", "LGBMClassifier"),
    "LGBMModel": ("lightgbm_tpu.sklearn", "LGBMModel"),
    "LGBMRanker": ("lightgbm_tpu.sklearn", "LGBMRanker"),
    "LGBMRegressor": ("lightgbm_tpu.sklearn", "LGBMRegressor"),
}


def __getattr__(name):
    try:
        module_name, attr = _LAZY_ATTRS[name]
    except KeyError:
        raise AttributeError(
            f"module {__name__!r} has no attribute {name!r}") from None
    import importlib
    try:
        module = importlib.import_module(module_name)
    except ImportError as exc:
        # sklearn wrappers are optional; surface the same AttributeError a
        # missing eager import used to.
        raise AttributeError(
            f"module {__name__!r} has no attribute {name!r} "
            f"(importing {module_name} failed: {exc})") from None
    value = module if attr is None else getattr(module, attr)
    globals()[name] = value
    return value


def __dir__():
    return sorted(set(globals()) | set(_LAZY_ATTRS))


__version__ = "0.1.0"
