"""`ops/lookup.row_lookup` moves the table's bits exactly as the gather
`table[ids]` does, and the two per-row lookups at the end of the fused
per-tree program (`grow_tree`'s finalize, `_grow_and_update_impl`'s score
update) go through it: the gather form is kept here as the oracle, and
the lowered program is searched so that a row-length gather cannot come
back unnoticed. Bits and program text on the CPU; no device number."""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

import lightgbm_tpu as lgb
from lightgbm_tpu.boosting import gbdt as gbdt_mod
from lightgbm_tpu.learner import grow as grow_mod
from lightgbm_tpu.learner.grow import FMETA_KEYS
from lightgbm_tpu.ops import lookup
from lightgbm_tpu.ops.lookup import row_lookup

_F32 = np.array([0.0, -0.0, 1e-45, 1e-38, np.inf, -np.inf, np.nan, np.nan,
                 -1.0, 3.4028235e38], np.float32)
_F32.view(np.uint32)[7] |= 0x1234       # a NaN with a payload
SPECIALS = {
    np.float32: _F32,
    np.int32: np.array([0, -1, 2**31 - 1, -2**31, 255, 256, 65535, 1 << 24],
                       np.int32),
}


def _table(m, dtype, seed=0):
    """`m` entries: the special values first, then magnitudes over 60
    decades (f32) or every bit at random (s32)."""
    rng = np.random.default_rng(seed)
    if dtype is np.float32:
        body = (rng.choice([-1.0, 1.0], m) * 10.0 ** rng.uniform(-38, 22, m)
                ).astype(np.float32)
    else:
        body = rng.integers(0, 2**32, m, dtype=np.uint32).view(np.int32)
    k = min(m, len(SPECIALS[dtype]))
    body[:k] = SPECIALS[dtype][:k]
    return body


def _ids(m, n, seed=1):
    """both ends of the range first and last, every entry hit when n
    allows, the rest at random"""
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, m, n, dtype=np.int32)
    k = min(m, n - 2)
    ids[1:1 + k] = np.arange(k)
    ids[0], ids[-1] = m - 1, 0
    return ids


def _bits(x):
    return np.asarray(x).view(np.uint32)


@pytest.mark.parametrize("dtype", [np.float32, np.int32],
                         ids=["f32", "s32"])
@pytest.mark.parametrize("m", [1, 255, 256, 257, 3112, 4000])
def test_row_lookup_equals_gather_bits(m, dtype):
    table = _table(m, dtype)
    ids = _ids(m, 5000)
    out = jax.jit(row_lookup)(jnp.asarray(table), jnp.asarray(ids))
    assert out.dtype == table.dtype and out.shape == ids.shape
    np.testing.assert_array_equal(_bits(out), _bits(table[ids]))


@pytest.mark.parametrize("n", [
    3, lookup.BLOCK - 1, lookup.BLOCK, 2 * lookup.BLOCK,
    2 * lookup.BLOCK + 77])
@pytest.mark.parametrize("m,dtype", [(255, np.float32), (3112, np.int32)],
                         ids=["leaf_values", "node_table"])
def test_row_lookup_row_counts(m, dtype, n):
    """rows that are and are not a multiple of the block length"""
    table = _table(m, dtype, seed=2)
    ids = _ids(m, n, seed=3)
    out = jax.jit(row_lookup)(jnp.asarray(table), jnp.asarray(ids))
    np.testing.assert_array_equal(_bits(out), _bits(table[ids]))


def test_row_lookup_denormals_survive():
    """the reason the lookup moves bits: a value split into bf16 digits
    would flush these where bf16 does"""
    table = np.array([1e-45, -1e-45, 1e-40, 1.1754942e-38], np.float32)
    out = row_lookup(jnp.asarray(table), jnp.arange(4, dtype=jnp.int32))
    np.testing.assert_array_equal(_bits(out), _bits(table))
    assert (np.asarray(out) != 0).all()


@pytest.mark.parametrize("m,dtype", [(255, np.float32), (3112, np.int32)],
                         ids=["leaf_values", "node_table"])
@pytest.mark.parametrize("shared_table", [False, True])
def test_row_lookup_under_vmap(m, dtype, shared_table):
    """the class axis of the sweep grower's vmap: a table a class, or one"""
    classes, n = 3, 700
    tables = np.stack([_table(m, dtype, seed=c) for c in range(classes)])
    ids = np.stack([_ids(m, n, seed=10 + c) for c in range(classes)])
    if shared_table:
        out = jax.vmap(row_lookup, in_axes=(None, 0))(
            jnp.asarray(tables[0]), jnp.asarray(ids))
        want = tables[0][ids]
    else:
        out = jax.vmap(row_lookup)(jnp.asarray(tables), jnp.asarray(ids))
        want = np.take_along_axis(tables, ids, axis=1)
    np.testing.assert_array_equal(_bits(out), _bits(want))


def test_row_lookup_in_shard_map():
    """replicated table, per-shard ids, no collective: the data-parallel
    growers' finalize"""
    m, n, shards = 3112, 4096, 4
    table = _table(m, np.int32, seed=5)
    ids = _ids(m, n, seed=6)
    mesh = Mesh(np.array(jax.devices()[:shards]), ("data",))
    fn = jax.jit(jax.shard_map(row_lookup, mesh=mesh,
                               in_specs=(P(), P("data")),
                               out_specs=P("data")))
    lowered = fn.lower(jnp.asarray(table), jnp.asarray(ids)).as_text()
    assert not re.search(r"all_reduce|all_gather|collective_permute|"
                         r"all_to_all|reduce_scatter", lowered)
    np.testing.assert_array_equal(
        _bits(fn(jnp.asarray(table), jnp.asarray(ids))), _bits(table[ids]))


def test_row_lookup_refuses_other_widths():
    with pytest.raises(TypeError):
        row_lookup(jnp.zeros(4, jnp.bfloat16), jnp.zeros(4, jnp.int32))


# ---------------------------------------------------------------------------
# the two call sites, against the gather form
# ---------------------------------------------------------------------------
ROWS = 6000     # padded by the booster to a length no table has


def _gather(table, ids):
    return table[ids]


@pytest.fixture(scope="module")
def inner():
    rng = np.random.RandomState(11)
    X = rng.randn(ROWS, 8).astype(np.float32)
    X[rng.rand(ROWS, 8) < 0.05] = np.nan
    y = (np.nan_to_num(X[:, 0]) - np.nan_to_num(X[:, 1]) ** 2
         + 0.3 * rng.randn(ROWS) > 0).astype(np.float32)
    p = {"objective": "binary", "num_leaves": 31, "max_bin": 63,
         "min_data_in_leaf": 5, "verbose": -1, "tpu_hist_chunk": 1024}
    booster = lgb.Booster(p, lgb.Dataset(X, y, params=p))
    booster.update()    # a score that is not all zero
    return booster._inner


def _fused_args(inner):
    grad, hess = inner._compute_gradients(inner._score)
    return (inner._score, inner._binned, grad, hess, inner._base_weight,
            jnp.ones(inner._num_features_padded, bool), jnp.float32(0.1),
            jnp.int32(inner._n),
            tuple(inner._fmeta[k] for k in FMETA_KEYS))


def _fresh_fused(monkeypatch, lookup_fn):
    """`_grow_and_update_impl` and `grow_tree` with `lookup_fn` for the
    per-row lookup, each under a jit of a function made here and
    `grow_tree` traced inline, so that no cached trace of the other form
    serves either"""
    monkeypatch.setattr(gbdt_mod, "grow_tree", grow_mod.grow_tree.__wrapped__)
    monkeypatch.setattr(gbdt_mod, "row_lookup", lookup_fn)
    monkeypatch.setattr(grow_mod, "row_lookup", lookup_fn)

    def fused(*args, cls, cfg):
        return gbdt_mod._grow_and_update_impl(*args, cls=cls, cfg=cfg)

    def grow(*args, cfg, n_valid):
        return grow_mod.grow_tree.__wrapped__(*args, cfg, n_valid=n_valid)

    return (jax.jit(fused, static_argnames=("cls", "cfg")),
            jax.jit(grow, static_argnames=("cfg",)))


@pytest.fixture(scope="module")
def fused_pair(inner):
    """(score, small state, leaf_id) of one tree by the program as it is
    and by the gather form"""
    out = {}
    args = _fused_args(inner)
    for name, fn in (("lookup", row_lookup), ("gather", _gather)):
        with pytest.MonkeyPatch.context() as mp:
            fused, grow = _fresh_fused(mp, fn)
            score, small = fused(*args, cls=0, cfg=inner._grower_cfg)
            state = grow(*args[1:6], *args[8], cfg=inner._grower_cfg,
                         n_valid=args[7])
        out[name] = (np.asarray(score), jax.device_get(small),
                     np.asarray(state.leaf_id))
    assert int(out["gather"][1]["num_leaves_used"]) > 10
    return out


def test_fused_score_equals_gather_expression(fused_pair, inner):
    """the parent's expression, `score + (leaf_value * shrinkage)[leaf]`,
    op by op in numpy on the gather form's tree"""
    _, small, leaf = fused_pair["gather"]
    start = np.asarray(inner._score)
    delta = (small["leaf_value"] * np.float32(0.1))[leaf]
    assert np.abs(delta).max() > 0
    want = start.copy()
    want[0] += delta
    got = fused_pair["lookup"][0]
    np.testing.assert_array_equal(_bits(got), _bits(want))
    # the gather form as XLA:CPU compiles it fuses the product through the
    # gather into the add as one fused multiply-add, which rounds once
    # where the expression rounds twice: one unit in the last place, no more
    np.testing.assert_allclose(got, fused_pair["gather"][0], rtol=0,
                               atol=np.spacing(np.abs(want).max()))


@pytest.mark.parametrize("key", gbdt_mod._SMALL_STATE_KEYS)
def test_fused_small_state_equals_gather_form(fused_pair, key):
    got, want = fused_pair["lookup"][1][key], fused_pair["gather"][1][key]
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.atleast_1d(got).view(np.uint8),
                                  np.atleast_1d(want).view(np.uint8))


def test_grow_tree_leaf_id_equals_gather_form(fused_pair):
    got, want = fused_pair["lookup"][2], fused_pair["gather"][2]
    assert got.dtype == want.dtype == np.int32
    assert len(np.unique(want)) > 10
    np.testing.assert_array_equal(got, want)


_GATHER = re.compile(r'stablehlo\.gather"?\(.*->\s*tensor<([0-9x]*)x?[a-z]')


def _row_length_gathers(text, rows):
    found = []
    for line in text.splitlines():
        hit = _GATHER.search(line)
        if hit and str(rows) in hit.group(1).split("x"):
            found.append(line.strip()[:200])
    return found


def test_fused_program_has_no_row_length_gather(inner, monkeypatch):
    rows = inner._binned.shape[0]
    cfg = inner._grower_cfg
    assert rows not in (cfg.num_leaves, lookup.LO, lookup.BLOCK)
    args = _fused_args(inner)
    text = _fresh_fused(monkeypatch, row_lookup)[0].lower(
        *args, cls=0, cfg=cfg).as_text()
    assert _row_length_gathers(text, rows) == []
    # and the search does find the two in the gather form
    text = _fresh_fused(monkeypatch, _gather)[0].lower(
        *args, cls=0, cfg=cfg).as_text()
    assert len(_row_length_gathers(text, rows)) == 2
