"""Wide passes and missing values on the MXU growth path, at small sizes
on the CPU (Pallas in interpret mode), held against untiled kernels and
against the plain reference benchmark/reference/gbdt_missing_numpy.py:

- a pass built over the feature axis in tiles (fused_route_hist_mxu
  with feature_tile, the slot-grouped build under a tile) gives the
  untiled histograms bit for bit at one, two and three tiles, the last
  one ragged;
- NaN rows route by their node's default direction, either way;
- the split search picks the reference's direction when the NaN rows'
  gradients favour either side, and may send the NaN rows alone right;
- a fused block's score update is what the dumped trees give raw rows
  with NaN in them.
"""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import lightgbm_tpu as lgb  # noqa: E402
from lightgbm_tpu.learner import histogram_pallas as hp  # noqa: E402
from lightgbm_tpu.learner.grower_mxu import (  # noqa: E402
    GROWTH_COUNTERS, _count_routed)
from lightgbm_tpu.learner.histogram_mxu import (  # noqa: E402
    feature_tile, fits_v2, fused_route_hist_mxu, pack_route_tables)
from lightgbm_tpu.learner.split import (  # noqa: E402
    SplitHyperParams, find_best_splits)
from benchmark.reference import gbdt_missing_numpy as ref  # noqa: E402

B = 63                       # bins, the NaN bin the last of them
ROWS = 2600


def _bins(f, seed=0, nan_share=0.6):
    """[ROWS, f] uint8 bins, half the columns with a NaN bin (B - 1)
    that holds `nan_share` of their rows; and which columns those are."""
    rng = np.random.RandomState(seed)
    bins = rng.randint(0, B - 1, (ROWS, f)).astype(np.uint8)
    nan_col = rng.rand(f) < 0.5
    miss = (rng.rand(ROWS, f) < nan_share) & nan_col[None, :]
    bins[miss] = B - 1
    return bins, nan_col


def _tables(split_feature, threshold, default_left, m1=8):
    """Route tables of a root split on `split_feature`: rows go to node 1
    (kernel slot 0) or node 2 (slot 1)."""
    split = np.zeros(m1, bool)
    split[0] = True
    feat = np.zeros(m1, np.int32)
    feat[0] = split_feature
    thr = np.zeros(m1, np.int32)
    thr[0] = threshold
    dl = np.zeros(m1, bool)
    dl[0] = default_left
    child_l = np.full(m1, m1, np.int32)
    child_r = np.full(m1, m1, np.int32)
    child_l[0], child_r[0] = 1, 2
    slot = np.full(m1, -1, np.int32)
    slot[1], slot[2] = 0, 1
    return pack_route_tables(
        jnp.asarray(split), jnp.asarray(feat), jnp.asarray(thr),
        jnp.asarray(dl), jnp.zeros(m1, bool), jnp.asarray(child_l),
        jnp.asarray(child_r), jnp.asarray(slot),
        jnp.zeros((m1, 2), jnp.uint32), 128, B)


def _sweep(bins, nan_col, split_feature, threshold, default_left, tile,
           slots=2):
    rng = np.random.RandomState(1)
    g = jnp.asarray(rng.randn(ROWS).astype(np.float32))
    h = jnp.asarray(rng.rand(ROWS).astype(np.float32))
    cnt = jnp.ones(ROWS, jnp.float32)
    f = bins.shape[1]
    ftbl = jnp.stack([jnp.full(f, B, jnp.float32),
                      jnp.asarray(nan_col.astype(np.float32))], axis=1)
    tbl, member = _tables(split_feature, threshold, default_left)
    return fused_route_hist_mxu(
        jnp.asarray(bins), g, h, cnt, jnp.zeros(ROWS, jnp.int32), tbl,
        member, ftbl, num_slots=slots, bmax=B, has_cat=False,
        row_block=1024, feature_tile=tile, interpret=True)


@pytest.mark.parametrize("features,tiles", [(100, 1), (200, 2), (300, 3)])
def test_the_tiled_sweep_is_the_untiled_one_bit_for_bit(features, tiles):
    bins, nan_col = _bins(features)
    f0 = int(np.flatnonzero(nan_col)[-1])
    assert -(-features // 128) == tiles and features % 128   # ragged
    want = _sweep(bins, nan_col, f0, 20, True, tile=0)
    got = _sweep(bins, nan_col, f0, 20, True, tile=128)
    assert np.asarray(want[0]).tobytes() == np.asarray(got[0]).tobytes()
    assert np.asarray(want[1]).tobytes() == np.asarray(got[1]).tobytes()


@pytest.mark.parametrize("default_left", [True, False])
def test_nan_rows_route_by_the_default_direction(default_left):
    bins, nan_col = _bins(300, seed=2)
    f0 = int(np.flatnonzero(nan_col)[0])
    _, node = _sweep(bins, nan_col, f0, 30, default_left, tile=128)
    x = bins[:, f0]
    left = np.where(x == B - 1, default_left, x <= 30)
    assert np.array_equal(np.asarray(node), np.where(left, 1, 2))
    assert (x == B - 1).sum() > ROWS // 3


@pytest.mark.parametrize("features,tiles", [(100, 1), (200, 2), (300, 3)])
@pytest.mark.parametrize("impl", ["auto", "rank"])
def test_the_tiled_grouped_build_is_the_untiled_one(features, tiles, impl,
                                                    monkeypatch):
    bins, _ = _bins(features, seed=3)
    rng = np.random.RandomState(4)
    g = jnp.asarray(rng.randn(ROWS).astype(np.float32))
    h = jnp.asarray(rng.rand(ROWS).astype(np.float32))
    cnt = jnp.ones(ROWS, jnp.float32)
    slot = jnp.asarray(np.where(rng.rand(ROWS) < 0.5,
                                rng.randint(0, 30, ROWS), -1), jnp.int32)
    kw = dict(num_slots=30, bmax=B, row_block=512, partition_impl=impl,
              interpret=True)
    want = hp.build_histograms_scatter(jnp.asarray(bins), g, h, cnt, slot,
                                       **kw)
    monkeypatch.setattr(hp, "feature_tile", lambda *a, **k: 128)
    jax.clear_caches()
    got = hp.build_histograms_scatter(jnp.asarray(bins), g, h, cnt, slot,
                                      **kw)
    jax.clear_caches()
    assert -(-features // 128) == tiles
    assert np.asarray(want).tobytes() == np.asarray(got).tobytes()


def test_one_predicate_owns_the_tiling():
    # Bosch's shape: 968 columns at 63 bins, five channels
    assert fits_v2(2, 968, 64, row_block=2048)
    # fits whole, but 968 features are too many for one kernel body
    assert feature_tile(2, 968, 64, row_block=2048) == 128
    assert feature_tile(2, 256, 64, row_block=2048) == 0
    assert not fits_v2(8, 968, 64, row_block=2048)
    assert feature_tile(8, 968, 64, row_block=2048) == 128
    assert fits_v2(8, 128, 64, row_block=2048)
    # the slot-grouped build's 25 slots a group
    assert feature_tile(25, 968, 64, row_block=2048) == 128
    # the four cells' shapes stay untiled
    for slots, f, b in ((25, 28, 255), (25, 137, 255), (25, 17, 255)):
        assert feature_tile(slots, f, b, row_block=2048) == 0
    # a frontier too wide for a lane tile of features falls through
    assert feature_tile(4096, 968, 64, row_block=2048) == -1


def _node_hist(rng, nan_g_sign, nb=12):
    """One node's histogram over one NaN column [1, 1, nb, 3]: valued
    bins with gradients rising through the bins, the NaN bin's of sign
    `nan_g_sign`."""
    n = rng.randint(40, 80, nb).astype(np.float64)
    g = np.linspace(-1.0, 1.0, nb) * n
    g[-1] = nan_g_sign * 1.5 * n[-1]
    h = 0.25 * n
    return np.stack([g, h, n], axis=-1)


def _program_choice(hist):
    s = jnp.asarray(hist[None, None].astype(np.float32))
    tot = hist.sum(axis=0)
    nb = hist.shape[0]
    bs = find_best_splits(
        s, jnp.asarray([tot[0]], jnp.float32),
        jnp.asarray([tot[1]], jnp.float32),
        jnp.asarray([tot[2]], jnp.float32), jnp.zeros(1, jnp.float32),
        jnp.asarray([nb], jnp.int32), jnp.asarray([True]),
        jnp.asarray([False]), jnp.ones(1, jnp.float32),
        SplitHyperParams(min_data_in_leaf=20))
    return int(bs.threshold_bin[0]), bool(bs.default_left[0])


@pytest.mark.parametrize("nan_g_sign", [-1.0, 1.0])
def test_the_split_search_picks_the_references_direction(nan_g_sign):
    rng = np.random.RandomState(5)
    for _ in range(4):
        hist = _node_hist(rng, nan_g_sign)
        gain, t, left = ref.missing_rule(
            hist[:, 0], hist[:, 1], hist[:, 2], has_nan=True,
            min_data_in_leaf=20, min_sum_hessian_in_leaf=1e-3,
            lambda_l2=0.0)
        assert np.isfinite(gain)
        # negative NaN gradients sit with the low (negative) bins
        assert left == (nan_g_sign < 0)
        assert _program_choice(hist) == (t, left)


def test_the_nan_rows_alone_may_go_right():
    # every valued bin alike, the NaN rows apart: the best split is the
    # forward scan's last threshold, NaN alone on the right
    hist = np.zeros((10, 3))
    hist[:, 2] = 50.0
    hist[:, 1] = 12.5
    hist[:-1, 0] = -5.0
    hist[-1, 0] = 40.0
    gain, t, left = ref.missing_rule(
        hist[:, 0], hist[:, 1], hist[:, 2], has_nan=True,
        min_data_in_leaf=20, min_sum_hessian_in_leaf=1e-3, lambda_l2=0.0)
    assert (t, left) == (8, False)
    assert _program_choice(hist) == (8, False)


def test_the_routed_row_counters_saturate():
    near = jnp.zeros(len(GROWTH_COUNTERS), jnp.int32).at[
        GROWTH_COUNTERS.index("routed_rows")].set(2 ** 31 - 10)
    out = np.asarray(_count_routed(near, jnp.float32(100.0),
                                   jnp.float32(40.0)))
    assert out[GROWTH_COUNTERS.index("routed_rows")] == 2 ** 31 - 1
    assert out[GROWTH_COUNTERS.index("default_rows")] == 40


def test_a_fused_blocks_scores_are_the_dumped_trees_on_raw_rows():
    rng = np.random.RandomState(6)
    X = rng.randn(700, 6).astype(np.float32)
    y = (X[:, 0] - 0.5 * np.nan_to_num(X[:, 1], nan=1.5) > 0.2) \
        .astype(np.float32)
    X[rng.rand(700, 6) < 0.45] = np.nan
    params = {"objective": "binary", "num_leaves": 7, "max_bin": 31,
              "learning_rate": 0.3, "verbosity": -1, "min_data_in_leaf": 5}
    bst = lgb.Booster(params=params, train_set=lgb.Dataset(
        X, label=y, params={"max_bin": 31}))
    g = bst.gbdt
    g._hist_impl = "mxu"
    g._mxu_interpret = True
    assert g._fused_eligible()
    bst.update_batch(3)
    trees = [ref.flatten_tree(t["tree_structure"])
             for t in bst.dump_model()["tree_info"]]
    assert any(t["nan_missing"].any() and t["default_left"].any()
               for t in trees)
    raw = sum(t["leaf_value"][ref.route(t, X)] for t in trees)
    np.testing.assert_allclose(np.asarray(g.train_score), raw, rtol=0,
                               atol=1e-5)
