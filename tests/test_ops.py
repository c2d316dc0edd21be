"""Device op tests: histogram kernel and vectorized split finder against
brute-force numpy references (the kernel-vs-reference equality tests
SURVEY.md §4 calls for)."""
import numpy as np
import pytest

import jax.numpy as jnp

from lightgbm_tpu.binning import MISSING_NAN, MISSING_NONE, MISSING_ZERO
from lightgbm_tpu.ops.histogram import leaf_histogram, leaf_weights
from lightgbm_tpu.ops.split import find_best_splits, leaf_output, leaf_split_gain


def _np_histogram(binned, weights, num_bins):
    n, f = binned.shape
    out = np.zeros((f, num_bins, 3))
    for j in range(f):
        for b in range(num_bins):
            mask = binned[:, j] == b
            out[j, b] = weights[mask].sum(axis=0)
    return out


def test_histogram_matches_numpy():
    rng = np.random.RandomState(0)
    n, f, B = 512, 4, 16
    binned = rng.randint(0, B, size=(n, f)).astype(np.int32)
    g = rng.randn(n).astype(np.float32)
    h = rng.rand(n).astype(np.float32)
    w = np.stack([g, h, np.ones(n, np.float32)], axis=1)
    ref = _np_histogram(binned, w, B)
    # f32 path: exact to f32 round-off
    hist = np.asarray(leaf_histogram(jnp.asarray(binned), jnp.asarray(w), B,
                                     chunk=128, bf16=False))
    np.testing.assert_allclose(hist, ref, rtol=1e-5, atol=1e-5)
    # bf16 hi+lo path: ~2^-16 relative per product, f32 accumulation;
    # counts must stay EXACT (0/1 values are bf16-representable)
    hist16 = np.asarray(leaf_histogram(jnp.asarray(binned), jnp.asarray(w), B,
                                       chunk=128, bf16=True))
    np.testing.assert_allclose(hist16, ref, rtol=2e-4, atol=2e-4)
    np.testing.assert_array_equal(hist16[:, :, 2], ref[:, :, 2])


def test_batched_leaves_histogram_matches_per_leaf():
    from lightgbm_tpu.ops.histogram import batched_leaves_histogram
    rng = np.random.RandomState(3)
    n, f, B, C = 512, 4, 16, 6
    binned = rng.randint(0, B, size=(n, f)).astype(np.uint8)
    g = rng.randn(n).astype(np.float32)
    h = rng.rand(n).astype(np.float32)
    w = np.stack([g, h, np.ones(n, np.float32)], axis=1)
    leaf_id = rng.randint(0, 6, size=n).astype(np.int32)
    # -1 = the padding id the speculative grower uses for invalid slots
    ids = np.asarray([0, 2, 5, 99, -1, 3], np.int32)
    out = np.asarray(batched_leaves_histogram(
        jnp.asarray(binned), jnp.asarray(w), jnp.asarray(leaf_id),
        jnp.asarray(ids), B, chunk=128, bf16=False))
    assert out.shape == (C, f, B, 3)
    for k, leaf in enumerate(ids):
        sel = leaf_id == leaf
        ref = _np_histogram(binned[sel], w[sel], B) if sel.any() else \
            np.zeros((f, B, 3))
        np.testing.assert_allclose(out[k], ref, rtol=1e-5, atol=1e-5)


def test_histogram_masked_leaf():
    rng = np.random.RandomState(1)
    n, f, B = 256, 3, 8
    binned = rng.randint(0, B, size=(n, f)).astype(np.int32)
    g = rng.randn(n).astype(np.float32)
    h = np.ones(n, np.float32)
    leaf_id = rng.randint(0, 3, size=n).astype(np.int32)
    bag = np.ones(n, np.float32)
    w = np.asarray(leaf_weights(jnp.asarray(g), jnp.asarray(h),
                                jnp.asarray(leaf_id), 1, jnp.asarray(bag)))
    hist = np.asarray(leaf_histogram(jnp.asarray(binned), jnp.asarray(w), B,
                                     chunk=256, bf16=False))
    sel = leaf_id == 1
    ref = _np_histogram(binned[sel], np.stack(
        [g[sel], h[sel], np.ones(sel.sum(), np.float32)], axis=1), B)
    np.testing.assert_allclose(hist, ref, rtol=1e-5, atol=1e-5)


def _np_best_split_no_missing(hist_f, pg, ph, pc, l1, l2, min_data, min_hess,
                              min_gain):
    """Brute force scan over thresholds, left = bins <= t."""
    B = hist_f.shape[0]
    parent_gain = max(abs(pg) - l1, 0.0) ** 2 / (ph + l2)
    best = (-np.inf, -1)
    for t in range(B - 1):
        lg = hist_f[:t + 1, 0].sum()
        lh = hist_f[:t + 1, 1].sum()
        lc = hist_f[:t + 1, 2].sum()
        rg, rh, rc = pg - lg, ph - lh, pc - lc
        if lc < min_data or rc < min_data or lh < min_hess or rh < min_hess:
            continue
        gain = (max(abs(lg) - l1, 0.0) ** 2 / (lh + l2)
                + max(abs(rg) - l1, 0.0) ** 2 / (rh + l2))
        if gain - parent_gain - min_gain > best[0]:
            best = (gain - parent_gain - min_gain, t)
    return best


def test_split_finder_matches_bruteforce():
    rng = np.random.RandomState(2)
    F, B = 5, 16
    hist = rng.randn(F, B, 3).astype(np.float32)
    hist[:, :, 1] = np.abs(hist[:, :, 1]) + 0.1   # positive hessians
    hist[:, :, 2] = rng.randint(1, 50, size=(F, B))
    pg = hist[0, :, 0].sum()
    ph = hist[0, :, 1].sum()
    pc = hist[0, :, 2].sum()
    # make totals consistent across features
    for j in range(1, F):
        scale_g = pg / hist[j, :, 0].sum() if hist[j, :, 0].sum() != 0 else 1.0
        hist[j, :, 0] *= scale_g
        hist[j, :, 1] *= ph / hist[j, :, 1].sum()
        hist[j, :, 2] *= pc / hist[j, :, 2].sum()

    num_bin = np.full(F, B, np.int32)
    missing = np.full(F, MISSING_NONE, np.int32)
    default_bin = np.zeros(F, np.int32)
    is_cat = np.zeros(F, bool)
    res = find_best_splits(
        jnp.asarray(hist), jnp.float32(pg), jnp.float32(ph), jnp.float32(pc),
        jnp.asarray(num_bin), jnp.asarray(missing), jnp.asarray(default_bin),
        jnp.asarray(is_cat),
        lambda_l1=0.0, lambda_l2=0.01, min_gain_to_split=0.0,
        min_data_in_leaf=1, min_sum_hessian_in_leaf=1e-3)
    for j in range(F):
        ref_gain, ref_t = _np_best_split_no_missing(
            hist[j], pg, ph, pc, 0.0, 0.01, 1, 1e-3, 0.0)
        got_gain = float(res.gain[j])
        if ref_gain == -np.inf:
            assert got_gain == -np.inf
        else:
            assert got_gain == pytest.approx(ref_gain, rel=1e-3, abs=1e-3)
            assert int(res.threshold[j]) == ref_t


def test_split_left_right_sums_consistent():
    rng = np.random.RandomState(3)
    F, B = 3, 8
    hist = np.abs(rng.randn(F, B, 3)).astype(np.float32)
    hist[:, :, 2] = rng.randint(5, 20, size=(F, B))
    pg = float(hist[0, :, 0].sum())
    ph = float(hist[0, :, 1].sum())
    pc = float(hist[0, :, 2].sum())
    for j in range(1, F):
        hist[j] *= np.array([pg, ph, pc]) / hist[j].sum(axis=0)
    res = find_best_splits(
        jnp.asarray(hist), jnp.float32(pg), jnp.float32(ph), jnp.float32(pc),
        jnp.asarray(np.full(F, B, np.int32)),
        jnp.asarray(np.zeros(F, np.int32)),
        jnp.asarray(np.zeros(F, np.int32)),
        jnp.asarray(np.zeros(F, bool)),
        lambda_l1=0.0, lambda_l2=0.0, min_gain_to_split=0.0,
        min_data_in_leaf=1, min_sum_hessian_in_leaf=1e-3)
    for j in range(F):
        if np.isfinite(float(res.gain[j])):
            assert float(res.left_count[j]) + float(res.right_count[j]) == \
                pytest.approx(pc, rel=1e-5)
            assert float(res.left_sum_g[j]) + float(res.right_sum_g[j]) == \
                pytest.approx(pg, rel=1e-4, abs=1e-4)


def test_nan_missing_dual_direction():
    """With a NaN bin holding strong gradient mass, default-left must win
    when grouping NaN with the low bins is better."""
    B = 8
    hist = np.zeros((1, B, 3), np.float32)
    # bins 0-2: negative grads; bins 3-6: positive; bin 7 = NaN bin, negative
    hist[0, 0:3, 0] = -5.0
    hist[0, 3:7, 0] = +5.0
    hist[0, 7, 0] = -20.0
    hist[0, :, 1] = 1.0
    hist[0, :, 2] = 10.0
    pg = float(hist[0, :, 0].sum())
    ph = float(hist[0, :, 1].sum())
    pc = float(hist[0, :, 2].sum())
    res = find_best_splits(
        jnp.asarray(hist), jnp.float32(pg), jnp.float32(ph), jnp.float32(pc),
        jnp.asarray([B], dtype=jnp.int32),
        jnp.asarray([MISSING_NAN], dtype=jnp.int32),
        jnp.asarray([0], dtype=jnp.int32),
        jnp.asarray([False]),
        lambda_l1=0.0, lambda_l2=0.0, min_gain_to_split=0.0,
        min_data_in_leaf=1, min_sum_hessian_in_leaf=1e-3)
    assert bool(res.default_left[0])
    assert int(res.threshold[0]) == 2  # split between negative and positive


def test_categorical_one_vs_rest():
    B = 6
    hist = np.zeros((1, B, 3), np.float32)
    hist[0, :, 0] = [1.0, 1.0, -30.0, 1.0, 1.0, 1.0]
    hist[0, :, 1] = 5.0
    hist[0, :, 2] = 20.0
    pg, ph, pc = (float(hist[0, :, i].sum()) for i in range(3))
    res = find_best_splits(
        jnp.asarray(hist), jnp.float32(pg), jnp.float32(ph), jnp.float32(pc),
        jnp.asarray([B], dtype=jnp.int32),
        jnp.asarray([MISSING_NONE], dtype=jnp.int32),
        jnp.asarray([0], dtype=jnp.int32),
        jnp.asarray([True]),
        lambda_l1=0.0, lambda_l2=0.0, min_gain_to_split=0.0,
        min_data_in_leaf=1, min_sum_hessian_in_leaf=1e-3)
    assert bool(res.is_categorical[0])
    assert int(res.threshold[0]) == 2  # category 2 isolated
    assert not bool(res.default_left[0])


def test_min_data_in_leaf_blocks_split():
    B = 4
    hist = np.zeros((1, B, 3), np.float32)
    hist[0, :, 0] = [-10, 10, -10, 10]
    hist[0, :, 1] = 1.0
    hist[0, :, 2] = 3.0  # 12 total, min_data 10 -> no valid split
    pg, ph, pc = (float(hist[0, :, i].sum()) for i in range(3))
    res = find_best_splits(
        jnp.asarray(hist), jnp.float32(pg), jnp.float32(ph), jnp.float32(pc),
        jnp.asarray([B], dtype=jnp.int32),
        jnp.asarray([MISSING_NONE], dtype=jnp.int32),
        jnp.asarray([0], dtype=jnp.int32),
        jnp.asarray([False]),
        lambda_l1=0.0, lambda_l2=0.0, min_gain_to_split=0.0,
        min_data_in_leaf=10, min_sum_hessian_in_leaf=1e-3)
    assert float(res.gain[0]) == -np.inf


def test_leaf_output_formula():
    # -sign(G) * max(|G|-l1, 0) / (H + l2), hpp:220-225
    assert float(leaf_output(4.0, 2.0, 1.0, 1.0)) == pytest.approx(-1.0)
    assert float(leaf_output(-4.0, 2.0, 1.0, 1.0)) == pytest.approx(1.0)
    assert float(leaf_output(0.5, 2.0, 1.0, 0.0)) == pytest.approx(0.0)


def test_batched_leaves_histogram_bf16_single_pass():
    """The fused hi+lo bf16 contraction must stay within f32-ish tolerance
    and keep counts EXACT (0/1 values are bf16-representable)."""
    from lightgbm_tpu.ops.histogram import batched_leaves_histogram
    rng = np.random.RandomState(7)
    n, f, B, C = 512, 4, 16, 4
    binned = rng.randint(0, B, size=(n, f)).astype(np.uint8)
    g = rng.randn(n).astype(np.float32)
    h = rng.rand(n).astype(np.float32)
    w = np.stack([g, h, np.ones(n, np.float32)], axis=1)
    leaf_id = rng.randint(0, 6, size=n).astype(np.int32)
    ids = np.asarray([0, 2, 3, 5], np.int32)
    ref = np.asarray(batched_leaves_histogram(
        jnp.asarray(binned), jnp.asarray(w), jnp.asarray(leaf_id),
        jnp.asarray(ids), B, chunk=128, bf16=False))
    fast = np.asarray(batched_leaves_histogram(
        jnp.asarray(binned), jnp.asarray(w), jnp.asarray(leaf_id),
        jnp.asarray(ids), B, chunk=128, bf16=True))
    np.testing.assert_allclose(fast, ref, rtol=2e-4, atol=2e-4)
    np.testing.assert_array_equal(fast[:, :, :, 2], ref[:, :, :, 2])


# --- groups wider than SUB_BINS, contracted as sub-groups (PR 39) ---------

def _today_blocks(widths, chunk, budget=1 << 26):
    """plan_group_blocks before PR 39, for widths of at most SUB_BINS."""
    blocks, i = [], 0
    while i < len(widths):
        bw, j = max(1, widths[i]), i + 1
        while j < len(widths):
            nbw = max(bw, widths[j])
            if nbw * (j + 1 - i) * chunk > budget:
                break
            bw, j = nbw, j + 1
        blocks.append((i, j - i, bw))
        i = j
    return tuple(blocks)


@pytest.mark.parametrize("widths,chunk", [
    ((63,) * 28, 65536), ((63,) * 137, 65536), ((15,) * 2000, 8192),
    ((2,) * 700, 65536), ((64, 3, 17, 64, 1, 40) * 50, 16384)])
def test_group_blocks_unchanged_up_to_sub_bins(widths, chunk):
    """Nothing wider than SUB_BINS: the parent's blocks, none split."""
    from lightgbm_tpu.ops import histogram as H
    assert H.plan_group_blocks(widths, chunk) == _today_blocks(widths, chunk)
    assert all(k == 1 and s == w for _, _, w, k, s in
               H.plan_contraction(widths, chunk, max(widths)))


def _kernel_case(kernel, precision, widths, chunk=256):
    """One kernel's histograms of a table in which every group holds each
    of its bins 0..w-1, with the channels `precision` contracts exactly
    (float32: sixteenths; int8 / int16: integers in range); and the
    float64 (int64) np.add.at histograms of the same rows."""
    from lightgbm_tpu.ops import histogram as H
    rng = np.random.RandomState(sum(widths))
    n, f, nb = 2 * chunk, len(widths), max(widths)
    binned = np.stack([np.concatenate(
        [np.arange(w), rng.randint(0, w, n - w)]) for w in widths],
        axis=1).astype(np.uint8)
    q = precision in ("int8", "int16")
    if q:
        qm = 127 if precision == "int8" else 32767
        g, h = rng.randint(-qm, qm + 1, n), rng.randint(0, qm + 1, n)
    elif precision == "float32":
        g, h = rng.randint(-64, 65, n) / 16, rng.randint(1, 65, n) / 16
    else:
        g, h = rng.randn(n), rng.rand(n)
    w = np.stack([g, h, rng.rand(n) < 0.8], axis=1).astype(np.float32)
    w[:, :2] *= w[:, 2:]
    leaf_id = rng.randint(0, 4, n).astype(np.int32)
    ids = np.asarray([0, 2, 3, -1], np.int32)
    rows = np.zeros(n, np.int32)
    rows[:300] = np.sort(rng.choice(n, 300, replace=False))
    static = dict(num_bins=nb, chunk=chunk, bf16=precision == "bf16",
                  group_widths=tuple(widths),
                  quantize=precision if q else "none")
    args = {"leaf": (binned, w),
            "batched": (binned, w, leaf_id, ids),
            "gathered": (binned, w, leaf_id, rows, ids)}[kernel]
    fn = {"leaf": H.leaf_histogram, "batched": H.batched_leaves_histogram,
          "gathered": H.gathered_leaves_histogram}[kernel]
    extra = {"n_valid": 300} if kernel == "gathered" else {}

    def run():
        # a fresh program each call: plan_contraction is read at trace
        import functools
        import jax
        return np.asarray(jax.jit(functools.partial(
            fn.__wrapped__, **static))(*map(jnp.asarray, args), **extra))

    dt = np.int64 if q else np.float64
    member = rows[:300] if kernel == "gathered" else np.arange(n)
    refs = []
    for lab in ([None] if kernel == "leaf" else ids):
        r = member if lab is None else member[leaf_id[member] == lab]
        ref = np.zeros((f, nb, 3), dt)
        for j in range(f):
            np.add.at(ref[j], binned[r, j], w[r].astype(dt))
        refs.append(ref)
    return run, (refs[0] if kernel == "leaf" else np.stack(refs))


@pytest.mark.parametrize("widths", [
    (45, 63, 64, 65, 100, 127, 128, 255),
    (255, 255, 255, 255, 45, 45, 45)])
@pytest.mark.parametrize("precision", ["float32", "bf16", "int8", "int16"])
@pytest.mark.parametrize("kernel", ["leaf", "batched", "gathered"])
def test_groups_over_sub_bins_contract_exactly(kernel, precision, widths,
                                               monkeypatch):
    """A group wider than SUB_BINS reaches the matmul as sub-groups of at
    most SUB_BINS bins: the histogram every caller receives is the one
    np.add.at gives, exactly in float32 and the quantized modes, and in
    bf16 hi+lo the unsplit form's to the bit. The small budget makes
    blocks of several groups, split and not, at 256-row chunks."""
    import functools
    from lightgbm_tpu.ops import histogram as H
    monkeypatch.setattr(H, "plan_group_blocks", functools.partial(
        H.plan_group_blocks, budget=256 * 520))
    plan = H.plan_contraction(widths, 256, max(widths))
    assert any(k > 1 for *_, k, _ in plan) and all(
        s <= H.SUB_BINS and k * s >= w for _, _, w, k, s in plan)
    run, ref = _kernel_case(kernel, precision, widths)
    got = run()
    if precision == "bf16":
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)
        monkeypatch.setattr(H, "SUB_BINS", 1 << 16)
        assert all(k == 1 for *_, k, _ in
                   H.plan_contraction(widths, 256, max(widths)))
        np.testing.assert_array_equal(got, run())
    else:
        np.testing.assert_array_equal(got, ref)


@pytest.mark.parametrize("widths,split,columns", [
    ((45,) * 4 + (255,) * 7, 7, 1972),          # expo-11mx700's groups
    ((63,) * 28, 0, 1764)])                      # higgs-10m5x28's
def test_contraction_counters(widths, split, columns):
    from lightgbm_tpu.ops.histogram import contraction_counters
    assert contraction_counters(widths, 65536, max(widths)) == {
        "split_groups": split, "sub_width": 64, "onehot_columns": columns}


def test_schedule_info_carries_the_contraction_plan(capfd):
    """Every run's `schedule_info["hist"]` is the kernels' plan; the
    `Schedule: hist` line is written where a group is split."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.ops.histogram import contraction_counters
    rng = np.random.default_rng(0)
    X = rng.standard_normal((3000, 3)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    inner = lgb.Booster({"objective": "binary", "verbose": 1,
                         "max_bin": 255, "num_leaves": 7},
                        lgb.Dataset(X, y))._inner
    cfg, info = inner._grower_cfg, inner._schedule_info
    assert info["hist"] == contraction_counters(
        cfg.group_widths, info["chunk"], cfg.max_bins)
    assert info["hist"]["split_groups"] == 3
    assert "Schedule: hist split_groups=3 sub_width=64" in \
        capfd.readouterr().err


@pytest.fixture(scope="module")
def v5e_chip():
    """One chip of a described v5e: the chip's compiler, no chip. The
    persistent cache is off meanwhile: a program compiled for a chip
    that is not attached is written there but cannot be read back."""
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 - libtpu cannot describe one
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("widths", [(255,) * 4, (63,) * 16])
def test_matmul_output_keeps_channels_minor_on_v5e(widths, v5e_chip):
    """One chunk step of the contraction compiled for a described v5e:
    every 120-channel matmul comes out `{2,1,0}` (channels on lanes). A
    255-bin one-hot presented whole came out `{1,2,0}` and ran at a fifth
    of the MXU's rate (PR 39); this guards against a compiler that lays
    the sub-groups out that way again."""
    import re
    import jax
    from lightgbm_tpu.ops import histogram as H
    chunk = 65536
    blocks = H.plan_contraction(widths, chunk, max(widths))

    def step(b, u):
        return H._contract_block_parts(
            lambda gs, gc: jax.lax.slice_in_dim(b, gs, gs + gc, axis=1),
            blocks, u, True)

    text = jax.jit(step).lower(
        jax.ShapeDtypeStruct((chunk, len(widths)), jnp.uint8,
                             sharding=v5e_chip),
        jax.ShapeDtypeStruct((chunk, 120), jnp.bfloat16, sharding=v5e_chip)
    ).compile().as_text()
    found = re.findall(r"= f32\[(\d+),(\d+),120\]\{([\d,]+)[:}][^\n]*"
                       r"(?:fusion|convolution)\([^\n]*dot_general", text)
    assert found, "no matmul in the listing"
    assert {(int(b), lay) for _, b, lay in found} == {
        (min(max(widths), H.SUB_BINS), "2,1,0")}
