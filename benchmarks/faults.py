"""Faults planted under the timed path, for `test_correct.py` and
`readings.py` only: the benchmark's own runs never import this file.
Each wraps `lightgbm_tpu.boosting.gbdt._grow_and_update`, the one device
program a serial training iteration dispatches."""
from __future__ import annotations

import contextlib

FAULTS = ("state_unchanged", "half_batch", "answer_altered")


@contextlib.contextmanager
def planted(name):
    if name is None:
        yield
        return
    if name not in FAULTS:
        raise KeyError(f"unknown fault {name!r}; have {FAULTS}")
    import jax.numpy as jnp
    from lightgbm_tpu.boosting import gbdt
    real = gbdt._grow_and_update

    def state_unchanged(score, *args, **kw):
        # the step returns its state (the score) as it got it
        _, small = real(score, *args, **kw)
        return score, small

    def half_batch(score, binned, grad, hess, row_weight, *args, **kw):
        # every second row left out of the histograms; leaf values are
        # means over the rest
        keep = (jnp.arange(row_weight.shape[0]) % 2 == 0)
        return real(score, binned, grad, hess,
                    row_weight * keep.astype(row_weight.dtype), *args, **kw)

    def answer_altered(score, *args, **kw):
        # one split's threshold moved by a bin in the tree handed back,
        # after the device used the real one
        new_score, small = real(score, *args, **kw)
        small = dict(small)
        small["node_threshold"] = small["node_threshold"].at[1].add(1)
        return new_score, small

    gbdt._grow_and_update = locals()[name]
    try:
        yield
    finally:
        gbdt._grow_and_update = real
