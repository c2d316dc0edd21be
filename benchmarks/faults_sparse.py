"""The faults of `faults.py`, which wrap the tree step and apply to a
bundled training set unchanged, and one more for what the sparse cell
adds: `default_unrepaired`, under which a bundled feature's default bin
is left as its group's histogram has it (empty: the rows at a member's
default sit in the group's bin 0) where the program sets it to the node's
totals less the feature's other bins (`grow._extract_feature_hist`, the
reference's FixHistogram). The grow program is traced anew with the
repair left out, and again without the fault afterwards. For
`readings_sparse.py` and the tests only: a benchmark run never imports
this file."""
from __future__ import annotations

import contextlib

import faults

FAULTS = faults.FAULTS + ("default_unrepaired",)


@contextlib.contextmanager
def planted(name):
    if name != "default_unrepaired":
        with faults.planted(name):
            yield
        return
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.learner import grow
    real = grow._extract_feature_hist

    def default_unrepaired(group_hist, sum_g, sum_h, count, fmeta, cfg):
        unbundled = dict(fmeta, is_bundled=jnp.zeros_like(fmeta["is_bundled"]))
        return real(group_hist, sum_g, sum_h, count, unbundled, cfg)

    grow._extract_feature_hist = default_unrepaired
    jax.clear_caches()
    try:
        yield
    finally:
        grow._extract_feature_hist = real
        jax.clear_caches()
