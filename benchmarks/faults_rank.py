"""The faults of `faults.py`, which wrap the tree step and apply to a
ranking job unchanged, and one more for the layer the ranking cell adds:
`pairs_dropped`, under which the queries of the program's longest length
bucket (more than half the next power of two over the longest query) get
no lambdas and no hessians. It wraps `GBDT._compute_gradients`, the call
every iteration makes, so the trees are grown from the thinned gradients
and the mode's own reading after the window sees them too. For
`readings_rank.py` and the tests only: a benchmark run never imports this
file."""
from __future__ import annotations

import contextlib

import numpy as np

import faults

FAULTS = faults.FAULTS + ("pairs_dropped",)


@contextlib.contextmanager
def planted(name):
    if name != "pairs_dropped":
        with faults.planted(name):
            yield
        return
    import jax.numpy as jnp
    from lightgbm_tpu.boosting import gbdt
    real = gbdt.GBDT._compute_gradients

    def pairs_dropped(self, score):
        grad, hess = real(self, score)
        bounds = np.asarray(self.objective.query_boundaries)
        sizes = np.diff(bounds)
        longest = sizes > 2 ** int(np.ceil(np.log2(sizes.max()))) // 2
        keep = np.ones(grad.shape[-1], np.float32)
        keep[:bounds[-1]] = np.repeat(~longest, sizes)
        return grad * jnp.asarray(keep), hess * jnp.asarray(keep)

    gbdt.GBDT._compute_gradients = pairs_dropped
    try:
        yield
    finally:
        gbdt.GBDT._compute_gradients = real
