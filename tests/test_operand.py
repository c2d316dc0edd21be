"""The contraction's channel operand (`ops/histogram._channel_operand`).

Until PR 37 the operand u [chunk, S] was a rank-3 `member x channels`
product reshaped into the matmul's columns; that form is written out
here (`_parent_u`, `_parent_fold`). The helper builds the same columns
at their final shape, so it must give the parent's matrix to the bit
in every path that builds one, and `_fold_lo` must read them back to
the parent's histogram. The one bit that may differ is the sign of a
zero: the
product wrote -0 where a row outside the node has a negative channel,
the helper writes +0 (a zero either way in the sum).
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.ops import histogram as H

CHUNK = 256
# path -> (bf16, quantize)
PATHS = {"bf16_hi_lo": (True, "none"), "float32": (False, "none"),
         "int8": (True, "int8"), "int16": (True, "int16")}


def _bits(a):
    """The array's bit patterns, every zero taken as +0."""
    a = np.asarray(a)
    a = np.where(a == 0, np.zeros((), a.dtype), a)
    return a.view(np.uint16 if a.dtype.itemsize == 2 else np.uint32)


def _parent_u(member, base, lo):
    """The operand as the parent built it: [chunk, K, 3] (and [chunk, K,
    2]) products of membership and channels, reshaped and concatenated."""
    k = member.shape[1]
    mb = member[:, :, None].astype(base.dtype)
    u = (mb * base[:, None, :]).reshape(-1, k * 3)
    if lo is not None:
        u_lo = (mb[:, :, 0:2] * lo[:, None, :]).reshape(-1, k * 2)
        u = jnp.concatenate([u, u_lo], axis=1)
    return u


def _parent_fold(hist, k, n_lo, mult):
    """The parent's post-loop reassembly of [F, B, S] in ITS order."""
    f, b, _ = hist.shape
    main = hist[:, :, :k * 3].reshape(f, b, k, 3).copy()
    if n_lo:
        corr = hist[:, :, k * 3:].reshape(f, b, k, 2)
        main[..., 0:2] = main[..., 0:2] * mult + corr
    return main.transpose(2, 0, 1, 3)                # [K, F, B, 3]


def _chunk(k, quantize, seed):
    rng = np.random.RandomState(seed)
    if quantize == "none":
        w = rng.randn(CHUNK, 3).astype(np.float32)
        w[:, 2] = rng.rand(CHUNK) < 0.8
    else:
        qmax = 127 if quantize == "int8" else 32767
        w = rng.randint(-qmax, qmax + 1, size=(CHUNK, 3)).astype(np.float32)
        w[:, 2] = rng.rand(CHUNK) < 0.8
    lid = rng.randint(0, 2 * k + 1, size=CHUNK).astype(np.int32)
    ids = rng.permutation(2 * k + 1)[:k].astype(np.int32)
    return w, lid, ids


@pytest.mark.parametrize("masked", [False, True],
                         ids=["all_rows", "live_mask"])
@pytest.mark.parametrize("path", list(PATHS))
@pytest.mark.parametrize("k", [1, 8, 24, 25])
def test_operand_is_the_parents_matrix(k, path, masked):
    bf16, quantize = PATHS[path]
    w, lid, ids = _chunk(k, quantize, seed=k)
    member = jnp.asarray(lid)[:, None] == jnp.asarray(ids)[None, :]
    if masked:     # the gathered kernel: dead buffer slots carry zeros
        live = jnp.arange(CHUNK) < CHUNK - 37
        w = jnp.where(live[:, None], w, 0.0)
        member = member & live[:, None]
    base, lo = H._channels(jnp.asarray(w), bf16, quantize)
    n_lo = H._n_lo(bf16, quantize)
    assert (lo is None) == (n_lo == 0)
    u = H._channel_operand(jnp.asarray(lid), jnp.asarray(ids), base, lo)
    want = _parent_u(member, base, lo)
    assert u.shape == (CHUNK, k * (3 + n_lo)) and u.dtype == want.dtype
    assert np.array_equal(_bits(u), _bits(want))
    assert np.asarray(u).any()


def _dyadic_rows(n, groups, widths, k, seed):
    """Rows whose channels are small dyadic rationals: every partial sum
    is exact in float32, so two contractions that sum the same numbers
    in another order still agree to the bit."""
    rng = np.random.RandomState(seed)
    binned = np.stack([rng.randint(0, widths[g], size=n)
                       for g in range(groups)], axis=1).astype(np.uint8)
    w = (rng.randint(-7, 8, size=(n, 3))
         + rng.randint(0, 256, size=(n, 3)) / 256.0).astype(np.float32)
    w[:, 2] = 1.0
    leaf_id = rng.randint(0, k + 2, size=n).astype(np.int32)
    return binned, w, leaf_id


@pytest.mark.parametrize("kernel", ["batched", "gathered"])
@pytest.mark.parametrize("plan", ["uniform_28x63", "ragged"])
def test_leaves_histograms_equal_leaf_histogram_a_node(plan, kernel):
    n, chunk, k, num_bins = 4096, 512, 5, 63
    widths = (63,) * 28 if plan == "uniform_28x63" else \
        (63, 16, 16, 4, 40, 63, 2, 33, 16, 9)
    group_widths = None if plan == "uniform_28x63" else widths
    binned, w, leaf_id = _dyadic_rows(n, len(widths), widths, k, seed=3)
    ids = np.array([4, 0, 2, 5, 1], np.int32)        # label 3 and 6: nobody's
    n_valid = n - 700                                # a padded suffix
    w[n_valid:] = 0.0
    kw = dict(num_bins=num_bins, chunk=chunk, group_widths=group_widths)
    if kernel == "batched":
        got = H.batched_leaves_histogram(
            jnp.asarray(binned), jnp.asarray(w), jnp.asarray(leaf_id),
            jnp.asarray(ids), n_valid=jnp.int32(n_valid), **kw)
    else:           # the members' rows, shuffled, in a buffer with dead slots
        rows = np.flatnonzero(np.isin(leaf_id[:n_valid], ids)).astype(np.int32)
        np.random.RandomState(9).shuffle(rows)
        buf = np.zeros(n, np.int32)
        buf[:rows.size] = rows
        got = H.gathered_leaves_histogram(
            jnp.asarray(binned), jnp.asarray(w), jnp.asarray(leaf_id),
            jnp.asarray(buf), jnp.asarray(ids), n_valid=jnp.int32(rows.size),
            **kw)
    got = np.asarray(got)
    assert got.shape == (k, len(widths), num_bins, 3)
    for slot, node in enumerate(ids):
        wn = np.where((leaf_id == node)[:, None], w, 0.0).astype(np.float32)
        one = np.asarray(H.leaf_histogram(jnp.asarray(binned),
                                          jnp.asarray(wn), **kw))
        assert one[:, :, 2].sum() > 0
        assert np.array_equal(_bits(got[slot]), _bits(one))


def test_no_rank3_operand_in_the_lowered_kernel():
    """The rank-3 intermediate cannot come back unnoticed: at chunk 1,024
    and 24 ids the lowered kernel holds the operand at [1024, 120] and
    no [1024, 24, 3] or [1024, 24, 2] tensor."""
    chunk, k, f = 1024, 24, 28
    text = H.batched_leaves_histogram.lower(
        jax.ShapeDtypeStruct((4 * chunk, f), jnp.uint8),
        jax.ShapeDtypeStruct((4 * chunk, 3), jnp.float32),
        jax.ShapeDtypeStruct((4 * chunk,), jnp.int32),
        jax.ShapeDtypeStruct((k,), jnp.int32),
        num_bins=63, chunk=chunk).as_text()
    assert f"{chunk}x{k * 5}xbf16" in text
    assert f"{chunk}x{k}x3x" not in text and f"{chunk}x{k}x2x" not in text
    assert not re.search(rf"reshape.*{chunk}x{k * 3}xbf16", text)


@pytest.mark.parametrize("path", list(PATHS))
@pytest.mark.parametrize("k", [1, 24])
def test_fold_lo_is_the_parents_reassembly(k, path):
    bf16, quantize = PATHS[path]
    n_lo = H._n_lo(bf16, quantize)
    dtype = np.float32 if quantize == "none" else np.int32
    rng = np.random.RandomState(k)
    hist = rng.randint(-900, 900, size=(3, 7, k * (3 + n_lo))).astype(dtype)
    got = np.asarray(H._fold_lo(jnp.asarray(hist), k, quantize))
    want = _parent_fold(hist, k, n_lo, 256 if quantize == "int16" else 1)
    assert got.shape == (3, 7, k, 3) and got.dtype == dtype
    assert np.array_equal(got.transpose(2, 0, 1, 3), want)
