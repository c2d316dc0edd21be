"""The auto-chosen gather-compaction threshold: `schedule.compact_threshold`
(shape in, row fraction out) and how `GBDT.init` uses it. Unset,
`tpu_compact_threshold` is the break-even of a full pass against an index
build plus gathers; given, it is used as given."""
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.learner.schedule import (COMPACT_FRACTION_MAX,
                                           COMPACT_FRACTION_MIN,
                                           CompactChoice, compact_threshold)

# the four widths read on the chip (PERF.md section 6, PR 27): features,
# rows, rows as GBDT.init pads them; max_bin 63 everywhere
HIGGS = (28, 21_000_000, 25_165_824)       # the benchmark's cell
MSLTR = (137, 2_270_000, 2_621_440)
YAHOO = (700, 473_000, 524_288)
EPSILON = (2000, 400_000, 458_752)
MEASURED = [HIGGS, MSLTR, YAHOO, EPSILON]


def _fraction(groups, rows, padded, bins=63):
    return compact_threshold(groups, bins, rows, padded).fraction


def test_the_cells_shape_never_compacts():
    choice = compact_threshold(28, 63, 21_000_000, 25_165_824)
    assert isinstance(choice, CompactChoice)
    assert choice.fraction == 0.0
    # the index build alone costs more than a full pass there
    assert choice.index_ns > choice.full_ns
    # and would even with no padding at all
    assert _fraction(28, 25_165_824, 25_165_824) == 0.0


@pytest.mark.parametrize("shape,compacts", [
    (HIGGS, False), (MSLTR, False), (YAHOO, True), (EPSILON, True)])
def test_measured_widths_follow_their_chip_pairs(shape, compacts):
    """Where the on/off pair on the chip says compaction pays, the model
    keeps it on; where it says it loses, off."""
    assert (_fraction(*shape) > 0.0) == compacts


def test_the_widest_measured_shape_keeps_the_old_threshold():
    assert _fraction(*EPSILON) == COMPACT_FRACTION_MAX == 0.25


@pytest.mark.parametrize("groups", [1, 8, 28, 100, 137, 500, 700, 2000,
                                    5000, 100_000])
@pytest.mark.parametrize("bins", [2, 15, 63, 255, 1024])
def test_fraction_stays_inside_the_old_buffer(groups, bins):
    for rows, padded in ((400_000, 458_752), (10**6, 10**6), (1, 65536)):
        f = compact_threshold(groups, bins, rows, padded).fraction
        assert 0.0 <= f <= COMPACT_FRACTION_MAX


@pytest.mark.parametrize("groups", [28, 137, 700, 2000])
def test_fraction_does_not_fall_as_the_histogram_widens(groups):
    """Fixed gather cost (same stored groups), wider histogram (more
    bins): a full pass only gets dearer, so compaction only pays sooner."""
    fractions = [_fraction(groups, 10**6, 10**6, bins)
                 for bins in (4, 15, 63, 127, 255, 511, 1023, 4095)]
    assert fractions == sorted(fractions)


def test_fraction_rises_between_measured_widths_until_clipped():
    """Unclipped, so the interpolation between readings shows."""
    def raw(groups):
        c = compact_threshold(groups, 63, 10**6, 10**6)
        return (c.full_ns - c.index_ns) / (c.gather_ns + c.full_ns)
    values = [raw(g) for g in (28, 60, 137, 300, 700, 1200, 2000)]
    assert values == sorted(values) and values[0] < 0 < values[-1]
    # and the clipped answer is the same break-even where neither clip
    # bites (no padding here, so padded and real fractions coincide)
    assert _fraction(200, 10**6, 10**6) == pytest.approx(raw(200))
    assert COMPACT_FRACTION_MIN < raw(200) < COMPACT_FRACTION_MAX


def test_fraction_is_of_the_padded_rows():
    """`compact_capacity` multiplies the PADDED rows, so the break-even
    member count over the real rows is handed over as a share of those."""
    rows, padded = 1_000_000, 1_200_000
    c = compact_threshold(250, 63, rows, padded)
    cnt = rows * (c.full_ns - c.index_ns) / (c.gather_ns + c.full_ns)
    assert COMPACT_FRACTION_MIN < c.fraction < COMPACT_FRACTION_MAX
    assert c.fraction * padded == pytest.approx(cnt)


def test_a_sliver_of_a_threshold_is_no_threshold():
    """137 features with no padding at all: the three terms leave a
    break-even under 2% of the rows, and on the chip thresholds of 3% to
    10% at this width ran slower than none (PERF.md section 6, PR 27)."""
    c = compact_threshold(137, 63, 10**7, 10**7)
    assert 0 < (c.full_ns - c.index_ns) / (c.gather_ns + c.full_ns) \
        < COMPACT_FRACTION_MIN
    assert c.fraction == 0.0


def test_fraction_falls_as_padding_grows():
    """The index is built over the PADDED rows whatever it finds."""
    groups, rows = 250, 1_000_000
    fractions = [_fraction(groups, rows, int(rows * pad))
                 for pad in (1.0, 1.1, 1.25, 1.5, 2.0, 4.0, 16.0)]
    assert fractions == sorted(fractions, reverse=True)
    assert fractions[0] > fractions[1] > fractions[-1] == 0.0


def test_no_reading_is_trusted_past_the_measured_widths():
    beyond = compact_threshold(20_000, 63, 10**6, 10**6)
    widest = compact_threshold(2000, 63, 10**6, 10**6)
    assert beyond == widest
    narrower = compact_threshold(4, 15, 10**6, 10**6)
    assert narrower.full_ns == compact_threshold(28, 63, 10**6,
                                                 10**6).full_ns
    assert narrower.fraction == 0.0


# ---------------------------------------------------------------------------
# GBDT.init: unset means "from the shape"; an explicit value wins
# ---------------------------------------------------------------------------
PARAMS = {"objective": "binary", "num_leaves": 15, "max_bin": 255,
          "min_data_in_leaf": 1, "verbose": -1, "tpu_hist_chunk": 2048}


def _inner(rows=8192, features=8, **params):
    rng = np.random.RandomState(5)
    X = rng.randn(rows, features).astype(np.float32)
    y = (X[:, 0] + 0.3 * rng.randn(rows) > 0).astype(np.float32)
    p = dict(PARAMS, **params)
    return lgb.Booster(p, lgb.Dataset(X, y, params=p))._inner


# 200 features x 255 bins: wider than any measured table whose full pass
# is dearer than the index build
WIDE = {"rows": 8192, "features": 200}


@pytest.mark.parametrize("params,compact,fraction", [
    ({}, False, 0.0),                                    # narrow, unset
    ({"tpu_compact_threshold": 0.25}, True, 0.25),
    ({"tpu_compact_threshold": 1.0}, True, 1.0),
    ({"tpu_compact_threshold": 0}, False, 0.0),
    ({"tpu_compact_threshold": -1.0}, False, -1.0),       # <= 0 disables
])
def test_explicit_threshold_wins_on_a_narrow_table(params, compact, fraction):
    inner = _inner(**params)
    info = inner._schedule_info
    assert inner._grower_cfg.hist_compact == info["compact"] == compact
    assert inner._grower_cfg.compact_fraction == fraction \
        == info["compact_fraction"]
    # the model's own answer is logged beside what was used
    assert info["compact_model"]["fraction"] == 0.0
    assert set(info["compact_model"]) == set(CompactChoice._fields)


@pytest.mark.parametrize("params,compact,fraction", [
    ({}, True, None),                                    # wide, unset
    ({"tpu_compact_threshold": 0.1}, True, 0.1),
    ({"tpu_compact_threshold": 0}, False, 0.0),
    ({"tpu_compact_threshold": 1.0}, True, 1.0),
])
def test_unset_threshold_compacts_a_wide_table(params, compact, fraction):
    inner = _inner(**WIDE, **params)
    model = compact_threshold(
        int(inner.train_data.num_groups), inner._max_bins, inner._n,
        inner._n_pad)
    assert 0.0 < model.fraction <= COMPACT_FRACTION_MAX
    assert inner._schedule_info["compact_model"] == model._asdict()
    assert inner._grower_cfg.hist_compact == compact
    assert inner._grower_cfg.compact_fraction == (
        model.fraction if fraction is None else fraction)


def test_per_shard_rows_are_what_the_model_and_the_gate_see():
    """Under tree_learner=data each shard compacts its own block: the
    model is asked about one shard's rows, and a shard of fewer than two
    chunks keeps the full pass whatever the threshold."""
    import jax
    ndev = jax.device_count()
    assert ndev >= 2
    inner = _inner(rows=4096 * ndev, features=WIDE["features"],
                   tree_learner="data")
    per_shard = compact_threshold(
        inner._schedule_info["groups"], inner._max_bins,
        inner._n // ndev, inner._n_pad // ndev)
    assert inner._schedule_info["compact_model"] == per_shard._asdict()
    assert inner._grower_cfg.hist_compact            # two chunks a shard
    one_chunk = _inner(rows=2048 * ndev, features=WIDE["features"],
                       tree_learner="data", tpu_compact_threshold=0.25)
    assert one_chunk._n_pad >= 2 * 2048              # enough, were it serial
    assert not one_chunk._grower_cfg.hist_compact


def test_compaction_past_the_model_gets_the_buffer_asked_for():
    """An explicit threshold on a shape whose model says 0 compacts with
    the buffer the user asked for (how `scripts/profile_train.py`
    re-measures the model's constants)."""
    inner = _inner(tpu_compact_threshold=COMPACT_FRACTION_MAX)
    assert inner._schedule_info["compact_model"]["fraction"] == 0.0
    assert inner._grower_cfg.hist_compact
    assert inner._grower_cfg.compact_fraction == COMPACT_FRACTION_MAX


def test_schedule_line_says_why(capsys):
    inner = _inner(verbose=1)
    model = inner._schedule_info["compact_model"]
    want = ("compact=False@0.000 (ns a row: full=%.1f index=%.1f "
            "gather=%.1f)" % (model["full_ns"], model["index_ns"],
                              model["gather_ns"]))
    assert want in capsys.readouterr().err
