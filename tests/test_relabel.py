"""The relabel pass (`grow.route`) against a plain per-row routing.

`route` moves the rows of the K selected nodes to their children by each
node's cached split, in one of two forms (`block` 0: whole bin columns
threaded through K selects; `block` > 0: a loop over blocks of rows).
The reference below is NumPy, one row at a time, written from the
split's definition (EFB decode, the two missing types with
`default_left`, categorical `==`, numerical `<=`), not from the program:
the new labels of both forms must equal it to the bit.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from lightgbm_tpu.binning import MISSING_NAN, MISSING_NONE, MISSING_ZERO
from lightgbm_tpu.learner.grow import _NodeTable, route
from lightgbm_tpu.learner.schedule import (RELABEL_BLOCK, pick_schedule,
                                           plan_row_layout, relabel_rows)

M = 200          # node-table slots; `sel` pads its empty slots with M


# --- a small table of every kind of feature -----------------------------
# (name, group, offset, num_bin, default_bin, missing, bundled, categorical)
_FEATURES = [
    ("num", 0, 0, 40, 0, MISSING_NONE, False, False),
    ("num_nan", 1, 0, 33, 0, MISSING_NAN, False, False),
    ("num_zero", 2, 0, 25, 7, MISSING_ZERO, False, False),
    ("cat", 3, 0, 12, 0, MISSING_NONE, False, True),
    ("cat_nan", 4, 0, 9, 0, MISSING_NAN, False, True),
    # one EFB bundle, group 5: three features in slices of its bin space
    ("bun_a", 5, 1, 10, 3, MISSING_NONE, True, False),
    ("bun_b", 5, 11, 8, 0, MISSING_ZERO, True, False),
    ("bun_c", 5, 19, 6, 0, MISSING_NAN, True, False),
    ("num2", 6, 0, 63, 0, MISSING_NAN, False, False),
]
_GROUP_BINS = {0: 40, 1: 33, 2: 25, 3: 12, 4: 9, 5: 25, 6: 63}


def _fmeta(groups=None):
    """The grower's per-feature tables for `_FEATURES`; with `groups`
    the numerical feature "num" is repeated to that many stored groups
    (the jaxpr case wants 28)."""
    feats = list(_FEATURES)
    for g in range(len(_GROUP_BINS), groups or 0):
        feats.append((f"num{g}", g, 0, 40, 0, MISSING_NONE, False, False))
    cols = list(zip(*feats))
    return {"group": np.array(cols[1], np.int32),
            "offset": np.array(cols[2], np.int32),
            "num_bin": np.array(cols[3], np.int32),
            "default_bin": np.array(cols[4], np.int32),
            "missing_type": np.array(cols[5], np.int32),
            "is_bundled": np.array(cols[6], bool),
            "is_categorical": np.array(cols[7], bool)}


def _case(seed, n, K, *, groups=None, classes=None, pad_rows=0):
    """Random bins, labels and K selected splits. Rows sit in nodes
    0..2K-1 (some selected, some not); valid slots select distinct
    nodes, the others are padded with M or repeat a live node with
    `valid` false; the children are fresh ids past every node."""
    rng = np.random.default_rng(seed)
    fm = _fmeta(groups)
    F = len(fm["group"])
    G = int(fm["group"].max()) + 1
    binned = np.stack(
        [rng.integers(0, _GROUP_BINS.get(g, 40), n) for g in range(G)],
        axis=1).astype(np.uint8)
    if pad_rows:
        binned[n - pad_rows:] = 0          # the padded suffix: all zeros

    def one_tree():
        nodes = 2 * K
        lid = rng.integers(0, nodes, n).astype(np.int32)
        if pad_rows:
            lid[n - pad_rows:] = 0         # padding never left the root
        feature = rng.integers(0, F, M).astype(np.int32)
        # every kind of feature is selected somewhere at K >= 8
        feature[:len(_FEATURES)] = rng.permutation(len(_FEATURES))
        nb = fm["num_bin"][feature]
        threshold = (rng.integers(0, 1 << 30, M) % nb).astype(np.int32)
        default_left = rng.integers(0, 2, M).astype(bool)
        is_cat = fm["is_categorical"][feature]
        sel = rng.permutation(nodes)[:K].astype(np.int32)
        valid = rng.random(K) < 0.75
        valid[0] = True
        # an empty slot is padded with M, or keeps a node's id unselected
        sel = np.where(valid | (rng.random(K) < 0.5), sel, M).astype(np.int32)
        cl = (nodes + 2 * np.arange(K)).astype(np.int32)
        table = _NodeTable.zeros(M)._replace(
            feature=jnp.asarray(feature), threshold=jnp.asarray(threshold),
            default_left=jnp.asarray(default_left),
            is_cat=jnp.asarray(is_cat))
        return lid, table, sel, valid, cl, cl + 1

    if classes is None:
        return binned, fm, one_tree()
    trees = [one_tree() for _ in range(classes)]
    stacked = jax.tree.map(lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]),
                           *trees)
    return binned, fm, stacked


def _reference(binned, fm, lid, table, sel, valid, cl, cr):
    """New labels, one row and one slot at a time."""
    feature = np.asarray(table.feature)
    threshold = np.asarray(table.threshold)
    default_left = np.asarray(table.default_left)
    is_cat = np.asarray(table.is_cat)
    out = np.array(lid, np.int32)
    for i in range(len(out)):
        for k in range(len(sel)):
            if not valid[k] or lid[i] != sel[k]:
                continue
            f = feature[sel[k]]
            b = int(binned[i, fm["group"][f]])
            if fm["is_bundled"][f]:
                off, nb = fm["offset"][f], fm["num_bin"][f]
                b = b - off if off <= b < off + nb else fm["default_bin"][f]
            if is_cat[sel[k]]:
                left = b == threshold[sel[k]]
            elif ((fm["missing_type"][f] == MISSING_NAN
                   and b == fm["num_bin"][f] - 1)
                  or (fm["missing_type"][f] == MISSING_ZERO
                      and b == fm["default_bin"][f])):
                left = default_left[sel[k]]
            else:
                left = b <= threshold[sel[k]]
            out[i] = cl[k] if left else cr[k]
            break
    return out


def _jfm(fm):
    return {k: jnp.asarray(v) for k, v in fm.items()}


@pytest.mark.parametrize("K", [8, 12, 24])
@pytest.mark.parametrize("n,block", [(1000, 256), (777, 1024), (2048, 512),
                                     (513, 512), (1000, 0)])
def test_route_equals_per_row_routing(K, n, block):
    """Rows that are and are not a multiple of the block, fewer rows
    than one block, one row past a block, and the column form (block 0);
    every feature kind; slots with `valid` false and `sel` padded with
    M."""
    binned, fm, (lid, table, sel, valid, cl, cr) = _case(K * 1000 + n, n, K)
    got = route(jnp.asarray(lid), jnp.asarray(binned).T, _jfm(fm), table,
                jnp.asarray(sel), jnp.asarray(valid), jnp.asarray(cl),
                jnp.asarray(cr), block_rows=block)
    want = _reference(binned, fm, lid, table, sel, valid, cl, cr)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), want)
    assert (want != lid).any() and (want == lid).any()


@pytest.mark.parametrize("kind", [f[0] for f in _FEATURES])
@pytest.mark.parametrize("default_left", [False, True])
@pytest.mark.parametrize("block", [0, 256])
def test_each_feature_kind(kind, default_left, block):
    """One selected node splitting on one kind of feature, every
    threshold of it, both `default_left`: numerical, categorical,
    MISSING_NAN, MISSING_ZERO, and the three features of an EFB bundle
    (rows inside and outside the feature's slice of the group's bins)."""
    f = [x[0] for x in _FEATURES].index(kind)
    fm = _fmeta()
    rng = np.random.default_rng(f)
    n, K = 600, 8
    G = len(_GROUP_BINS)
    binned = np.stack([rng.integers(0, _GROUP_BINS[g], n) for g in range(G)],
                      axis=1).astype(np.uint8)
    lid = rng.integers(0, 3, n).astype(np.int32)
    sel = np.array([1] + [M] * (K - 1), np.int32)
    valid = np.array([True] + [False] * (K - 1))
    cl = (10 + 2 * np.arange(K)).astype(np.int32)
    for thr in range(int(fm["num_bin"][f])):
        table = _NodeTable.zeros(M)._replace(
            feature=jnp.full(M, f, jnp.int32),
            threshold=jnp.full(M, thr, jnp.int32),
            default_left=jnp.full(M, default_left),
            is_cat=jnp.full(M, bool(fm["is_categorical"][f])))
        got = route(jnp.asarray(lid), jnp.asarray(binned).T, _jfm(fm), table,
                    jnp.asarray(sel), jnp.asarray(valid), jnp.asarray(cl),
                    jnp.asarray(cl + 1), block_rows=block)
        want = _reference(binned, fm, lid, table, sel, valid, cl, cl + 1)
        np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("block", [0, 512])
def test_zero_weight_padded_rows_keep_to_the_rule(block):
    """The padded suffix (all-zero bins, the root's label, weight 0 in
    the grower) is routed like any row: by its bins. What keeps it out
    of the histograms is its weight, not its label."""
    binned, fm, (lid, table, sel, valid, cl, cr) = _case(
        5, 1500, 12, pad_rows=476)
    sel[0], valid[0] = 0, True             # the root is selected
    sel[1:][sel[1:] == 0] = M
    got = route(jnp.asarray(lid), jnp.asarray(binned).T, _jfm(fm), table,
                jnp.asarray(sel), jnp.asarray(valid), jnp.asarray(cl),
                jnp.asarray(cr), block_rows=block)
    want = _reference(binned, fm, lid, table, sel, valid, cl, cr)
    np.testing.assert_array_equal(np.asarray(got), want)
    assert len(set(want[-476:])) == 1 and want[-1] in (cl[0], cr[0])


@pytest.mark.parametrize("K,block", [(8, 0), (12, 0), (24, 256)])
def test_route_under_vmap_as_the_class_trees(K, block):
    """A multiclass iteration grows its class trees under `jax.vmap`:
    labels, splits and slots carry a class axis, the bins do not."""
    classes, n = 3, 900
    binned, fm, (lid, table, sel, valid, cl, cr) = _case(
        31 + K, n, K, classes=classes)
    bT, jfm = jnp.asarray(binned).T, _jfm(fm)
    got = jax.vmap(lambda l, t, s, v, a, b: route(
        l, bT, jfm, t, s, v, a, b, block_rows=block))(
            lid, table, sel, valid, cl, cr)
    for c in range(classes):
        one = jax.tree.map(lambda x: np.asarray(x[c]),
                           (lid, table, sel, valid, cl, cr))
        np.testing.assert_array_equal(
            np.asarray(got[c]), _reference(binned, fm, *one))


@pytest.mark.parametrize("K,block", [(8, 0), (12, 128), (24, 128)])
def test_route_inside_shard_map_each_shard_its_rows(K, block):
    """The data-parallel learner relabels inside `shard_map`: each
    device transposes and routes its own rows, no collective."""
    devs = jax.devices()[:4]
    n = 4 * 300                           # 300 rows a shard, block 128
    binned, fm, (lid, table, sel, valid, cl, cr) = _case(77 + K, n, K)
    mesh = Mesh(np.array(devs), ("data",))
    jfm = _jfm(fm)

    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=(P("data"), P("data", None)),
        out_specs=P("data"))
    def relabel(lid, binned):
        return route(lid, binned.T, jfm, table, jnp.asarray(sel),
                     jnp.asarray(valid), jnp.asarray(cl), jnp.asarray(cr),
                     block_rows=block)

    got = jax.jit(relabel)(jnp.asarray(lid), jnp.asarray(binned))
    np.testing.assert_array_equal(
        np.asarray(got), _reference(binned, fm, lid, table, sel, valid, cl, cr))


def _all_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _all_eqns(sub)


def _row_length_values(K, n, block):
    """(primitive, shape, dtype) of every value of a pass's jaxpr that
    has a row-length axis, and the set of all shapes."""
    binned, fm, (lid, table, sel, valid, cl, cr) = _case(3, n, K, groups=28)
    assert binned.shape == (n, 28)
    jaxpr = jax.make_jaxpr(functools.partial(route, block_rows=block))(
        jnp.asarray(lid), jnp.asarray(binned).T, _jfm(fm), table,
        jnp.asarray(sel), jnp.asarray(valid), jnp.asarray(cl),
        jnp.asarray(cr))
    values = [(eqn.primitive.name, v.aval.shape, str(v.aval.dtype))
              for eqn in _all_eqns(jaxpr.jaxpr) for v in eqn.outvars
              if hasattr(v.aval, "shape")]
    return ([v for v in values if n in v[1]], {v[1] for v in values})


def test_a_blocked_pass_holds_nothing_row_length_but_the_labels():
    """28 groups, 24 nodes a pass (the HIGGS cells' shape): the jaxpr of
    one blocked pass has no `(1, n)`, `(K, n)` or `(n,)` value of any
    dtype but `leaf_id`'s own, which the block loop carries and updates
    in place; the bins are read `[G, block]` and compared `[K, block]`."""
    K, n, block = 24, 4096, 512
    row_length, shapes = _row_length_values(K, n, block)
    assert row_length, "the pass writes its labels somewhere"
    in_place = {"scan", "while", "dynamic_update_slice", "pjit"}
    for prim, shape, dtype in row_length:
        assert prim in in_place and shape == (n,) and dtype == "int32", \
            (prim, shape, dtype)
    assert (K, block) in shapes and (28, block) in shapes


def test_the_column_form_is_what_the_blocked_one_replaces():
    """The column form at the same shape: 24 `(1, n)` uint8 slices, each
    widened to an `(n,)` int32 column. XLA:TPU fuses them into the select
    up to about 12 nodes a pass and writes them out at 24."""
    K, n = 24, 4096
    row_length, _ = _row_length_values(K, n, 0)
    assert sum(v == ("dynamic_slice", (1, n), "uint8")
               for v in row_length) == K
    assert sum(p == "convert_element_type" and shape == (n,)
               and dtype == "int32" for p, shape, dtype in row_length) == K


V5E_BYTES = 16_909_336_064


@pytest.mark.parametrize("groups,rows,want_k,want_block", [
    (28, 21_000_000, 24, RELABEL_BLOCK),     # higgs-train-1chip, -dp4
    (137, 12_582_912, 8, 0),                 # msltr-rank-1chip
    (2000, 1_048_576, 8, 0),                 # epsilon-train-1chip
])
def test_where_the_relabel_form_turns_on_the_cells(groups, rows, want_k,
                                                   want_block):
    """The three shapes the cells hand `route`: the blocked form where a
    pass routes 24 nodes over few groups, the column form on the wide
    side's 8."""
    layout = plan_row_layout(rows, groups, 63)
    picked = pick_schedule(groups, 63, rows, layout.n_pad, layout.chunk,
                           num_leaves=255, device_bytes=V5E_BYTES)
    assert picked.batch_k == want_k
    assert relabel_rows(groups, 63, picked.batch_k, layout.n_pad) \
        == want_block


@pytest.mark.parametrize("groups,max_bins,batch_k,rows,want", [
    # the sweep's six readings, column against blocked
    (28, 63, 8, 25_165_824, RELABEL_BLOCK),
    (28, 63, 12, 25_165_824, RELABEL_BLOCK),
    (28, 63, 24, 25_165_824, RELABEL_BLOCK),
    (137, 63, 8, 12_582_912, 0),
    (137, 63, 12, 12_582_912, 0),
    (137, 63, 24, 12_582_912, RELABEL_BLOCK),
    # between them: where the two costs cross
    (50, 63, 8, 25_165_824, RELABEL_BLOCK), (51, 63, 8, 25_165_824, 0),
    (93, 63, 12, 25_165_824, RELABEL_BLOCK), (94, 63, 12, 25_165_824, 0),
    (6, 255, 24, 25_165_824, RELABEL_BLOCK),   # under 28 groups: as 28
    (28, 63, 40, 25_165_824, RELABEL_BLOCK),   # int8's wider batch: as 24
    # no reading there: the column form stays
    (28, 63, 4, 25_165_824, 0), (138, 15, 24, 25_165_824, 0),
    (28, 256, 24, 25_165_824, RELABEL_BLOCK),
    (28, 257, 24, 25_165_824, 0),              # uint16 bins
    (28, 63, 24, 4096, 4096),                  # a shard shorter than a block
])
def test_where_the_relabel_form_turns(groups, max_bins, batch_k, rows, want):
    assert relabel_rows(groups, max_bins, batch_k, rows) == want
    assert relabel_rows(groups, max_bins, batch_k, rows, classes=3) == 0


def test_the_blocked_form_refuses_bins_past_uint8():
    binned, fm, (lid, table, sel, valid, cl, cr) = _case(1, 600, 24)
    with pytest.raises(TypeError, match="bfloat16"):
        route(jnp.asarray(lid), jnp.asarray(binned.astype(np.uint16)).T,
              _jfm(fm), table, jnp.asarray(sel), jnp.asarray(valid),
              jnp.asarray(cl), jnp.asarray(cr), block_rows=256)


@pytest.mark.parametrize("params,form", [
    ({}, "blocked"),                                  # the cache: 24 a pass
    ({"num_class": 3, "objective": "multiclass"}, "columns"),   # 12 a pass
])
def test_schedule_info_names_the_relabel_form(params, form):
    """`schedule_info["relabel"]`: the form a run took and, blocked, its
    block rows (the whole shard where it is shorter than a block); the
    grower's config carries the same."""
    import lightgbm_tpu as lgb
    rng = np.random.default_rng(0)
    X = rng.standard_normal((3000, 6)).astype(np.float32)
    y = (X[:, 0] + X[:, 1] > 0).astype(np.float32)
    booster = lgb.Booster(
        dict({"objective": "binary", "verbose": -1, "num_leaves": 15},
             **params), lgb.Dataset(X, y))
    inner = booster._inner
    info = inner._schedule_info
    want = min(RELABEL_BLOCK, info["rows_padded"]) if form == "blocked" else 0
    assert info["relabel"] == {"form": form, "block_rows": want}
    assert inner._grower_cfg.relabel_rows == want
    assert info["grower"]["relabel_rows"] == want
    booster.update()
