"""The Bosch cell's own files: the generator's shape and what the seed
decides, the plain reference's rule for a column with missing values,
and the three readers of its per-layer metrics against hand-made
readings."""

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import harness  # noqa: E402
from benchmark.generators import bosch  # noqa: E402
from benchmark.reference import gbdt_missing_numpy as ref  # noqa: E402

READERS = harness.layer_metric_readers()


def test_the_layout_and_routes_are_the_published_shape():
    assert bosch.NUM_FEATURES == 968
    assert bosch.LINE_STATIONS == (24, 2, 3, 23)
    assert [sum(line) for line in bosch.STATION_COLUMNS] == \
        [168, 513, 42, 245]
    present, freq = bosch.route_patterns()
    assert freq.sum() == pytest.approx(1.0)
    missing = 1.0 - (present.mean(axis=1) * freq).sum()
    assert 0.79 < missing < 0.83
    # every column is missing somewhere and present somewhere
    assert present.any(axis=0).all() and not present.all(axis=0).any()


def test_every_seed_gives_the_same_routes_and_not_the_same_rows():
    a, _, _ = bosch.make_bosch_rows(5000, 11)
    b, _, _ = bosch.make_bosch_rows(5000, 12)
    na, nb = np.isnan(a), np.isnan(b)
    # the same number of missing cells per column, on other rows
    assert np.array_equal(na.sum(axis=0), nb.sum(axis=0))
    assert not np.array_equal(na, nb)
    again, _, _ = bosch.make_bosch_rows(5000, 11)
    assert np.array_equal(np.isnan(again), na)
    assert np.array_equal(np.nan_to_num(again), np.nan_to_num(a))


def test_the_label_is_as_rare_and_as_learnable_as_the_source():
    X, latent, score = bosch.make_bosch_rows(200_000, 3)
    cut = float(np.quantile(score, 1.0 - bosch.FAILURE_RATE))
    y = score > cut
    assert abs(y.mean() - bosch.FAILURE_RATE) < 5e-4
    # the latent without its noise: about the published 0.7248
    assert 0.74 < ref.auc(y, latent) < 0.82
    _, yh, _ = bosch.make_bosch_like(50_000, 3, stream=1, threshold=cut)
    assert 0.003 < yh.mean() < 0.009


def _hist(g, n, h_row=0.25):
    g = np.asarray(g, float)
    n = np.asarray(n, float)
    return g, n * h_row, n


@pytest.mark.parametrize("nan_g,want_left", [(-30.0, True), (30.0, False)])
def test_the_rule_sends_the_missing_rows_where_they_gain_more(nan_g,
                                                               want_left):
    g, h, n = _hist([-10, -8, -6, 6, 8, 10, nan_g], [40] * 7)
    gains = ref.scan(g, h, n, has_nan=True, min_data_in_leaf=20,
                     min_sum_hessian_in_leaf=1e-3, lambda_l2=0.0)
    # the reverse scan stops short of the last valued bin; the forward
    # scan reaches it
    assert len(gains[True]) == len(gains[False]) == 6
    assert not np.isfinite(gains[True][5]) and np.isfinite(gains[False][5])
    gain, t, left = ref.missing_rule(g, h, n, has_nan=True,
                                     min_data_in_leaf=20,
                                     min_sum_hessian_in_leaf=1e-3,
                                     lambda_l2=0.0)
    assert (t, left) == (2, want_left)
    assert gain == pytest.approx(ref.gain_at(g, h, t, left, 0.0))


def test_a_tie_goes_to_the_reverse_scan():
    # the missing rows' gradient is zero: either direction gains alike
    g, h, n = _hist([-10, -10, 10, 10, 0], [40] * 5)
    _, t, left = ref.missing_rule(g, h, n, has_nan=True,
                                  min_data_in_leaf=20,
                                  min_sum_hessian_in_leaf=1e-3,
                                  lambda_l2=0.0)
    assert (t, left) == (1, True)


def test_raw_missing_values_route_by_the_default_direction():
    tree = {"feature": np.array([0]), "threshold": np.array([0.5]),
            "default_left": np.array([True]),
            "nan_missing": np.array([True]), "left": np.array([-1]),
            "right": np.array([-2]), "leaf_value": np.array([1.0, 2.0])}
    X = np.array([[0.1], [0.9], [np.nan]], np.float32)
    assert ref.route(tree, X).tolist() == [0, 1, 0]
    tree["default_left"] = np.array([False])
    assert ref.route(tree, X).tolist() == [0, 1, 1]
    # no missing type: a missing value is 0.0
    tree["nan_missing"] = np.array([False])
    assert ref.route(tree, X).tolist() == [0, 1, 0]


def test_the_check_finds_a_flipped_direction():
    rng = np.random.RandomState(2)
    n = 4000
    x = rng.rand(n)
    x[rng.rand(n) < 0.5] = np.nan
    y = np.where(np.isnan(x), rng.rand(n) < 0.2,
                 rng.rand(n) < 0.2 + 0.6 * (x > 0.5)).astype(np.float64)
    bins = np.where(np.isnan(x), 10, np.minimum(
        (np.nan_to_num(x) * 10).astype(int), 9)).astype(np.uint8)
    X = x[:, None].astype(np.float32)
    tree = {"feature": np.array([0]), "threshold": np.array([0.5]),
            "default_left": np.array([True]),
            "nan_missing": np.array([True]), "left": np.array([-1]),
            "right": np.array([-2]), "leaf_value": np.zeros(2)}
    kw = dict(learning_rate=0.1, min_data_in_leaf=20,
              min_sum_hessian_in_leaf=1e-3)
    got = ref.check_step(0, [tree], X, y, bins[:, None], **kw)
    assert got["nan_nodes"] == 1 and got["nan_direction_flips"] == 0
    flipped = dict(tree, default_left=np.array([False]))
    got = ref.check_step(0, [flipped], X, y, bins[:, None], **kw)
    assert got["nan_direction_flips"] == 1
    assert got["nan_gain_shortfall_ulps"] > 64


def _binned_column():
    rng = np.random.RandomState(4)
    x = rng.rand(2000)
    x[rng.rand(2000) < 0.4] = np.nan
    bins = np.where(np.isnan(x), 10, np.minimum(
        (np.nan_to_num(x) * 10).astype(int), 9)).astype(np.uint8)
    tree = {"feature": np.array([0, 0]), "threshold": np.array([0.5, 0.2])}
    return x, bins, tree


@pytest.mark.parametrize("fault", ["none", "boundary", "nan_valued",
                                   "value_in_nan_bin"])
def test_the_binning_is_held_to_the_raw_values(fault):
    x, bins, tree = _binned_column()
    want = 0
    if fault == "boundary":
        # the values of the bin over 0.5 put in the bin of 0.5
        moved = bins == 5
        bins[moved] = 4
        want = int(moved.sum())
    elif fault == "nan_valued":
        at = np.flatnonzero(np.isnan(x))[:7]
        bins[at] = 3
        want = 7 * 2        # at each of the column's two splits
    elif fault == "value_in_nan_bin":
        at = np.flatnonzero(x > 0.95)[:5]
        bins[at] = 10
        want = 5 * 2
    got, worst = ref.binning_disagreements(tree, x[:, None].astype(
        np.float32), bins[None, :])
    assert got == want
    assert (got > 0) == (worst["rows"] > 0)


def _unpack(iter_, routed, default):
    return {"name": "entry.unpack_block", "id": iter_, "parent_id": 0,
            "ts": float(iter_), "dur": 0.1,
            "attrs": {"iter": iter_, "k": 10, "routed_rows": routed,
                      "default_rows": default}}


def test_the_default_routed_share_reads_the_windows_blocks():
    read = READERS["growth.default_routed_row_share"].read
    r = {"kind": "train", "warm_trees": 20, "window_trees": 20,
         "spans": [_unpack(10, 999, 999), _unpack(20, 1000, 600),
                   _unpack(30, 3000, 1400)]}
    assert read(r) == pytest.approx(50.0)
    del r["spans"][1]["attrs"]["routed_rows"]
    assert read(r) is None
    assert read({"kind": "train", "warm_trees": 20, "window_trees": 20,
                 "spans": []}) is None


def test_the_wide_pass_time_reads_per_tree():
    read = READERS["growth.wide_pass_ms_per_tree"].read
    assert read({"wide_pass_s": 2.5, "window_trees": 20}) == \
        pytest.approx(125.0)
    assert read({"window_trees": 20}) is None


#: a tiled one-hot sweep's HLO text as a capture names it: 40 rows of
#: channels x slots, 8 tiles of 128 features at 128 bin lanes, 1M rows
TEXT = ("%fused_route_hist_tiled.3 = (f32[1,40,131072]{2,1,0}, "
        "s32[1,1007616]{1,0}) custom-call(s32[1,1007616]{1,0} %a, "
        "u8[1007616,1024]{1,0} %b, u8[992,1007616]{1,0} %c, "
        "f32[8,1007616]{1,0} %d, bf16[32,128]{1,0} %e, "
        "f32[128,128]{1,0} %f, f32[992,1]{1,0} %g), "
        'custom_call_target="tpu_custom_call"')


def test_the_roofline_counts_what_the_sweep_issues():
    mod = READERS["kernels.wide_hist_roofline"]
    ops, moved = mod.onehot_work(TEXT, 968)
    assert ops == 2.0 * 1007616 * 40 * 968 * 128
    # the operands once and the results once
    assert moved == pytest.approx(
        1007616 * (4 + 1024 + 992 + 32) + 32 * 128 * 2 + 128 * 128 * 4 +
        992 * 4 + 40 * 131072 * 4 + 1007616 * 4)
    least = ops / 197e12
    r = {"wide_onehot": {"seconds": least * 4, "flops": ops,
                         "bytes": moved}, "device_kind": "TPU v5 lite"}
    assert mod.read(r) == pytest.approx(25.0)
    assert mod.read(dict(r, device_kind="cpu")) is None
    assert mod.read({"device_kind": "TPU v5 lite"}) is None


def test_the_cell_trains_on_one_table_under_every_seed():
    from benchmark.runners import train_missing
    cfg = dict(harness.load_cell("bosch_missing_train")["config"],
               num_data=3000, held_out_rows=1000)
    a = train_missing.TASK.draw(cfg, 5)
    b = train_missing.TASK.draw(cfg, 6)
    assert np.array_equal(a["X"], b["X"], equal_nan=True)
    assert np.array_equal(a["y"], b["y"])
    # the held-out rows are the seed's
    assert not np.array_equal(a["Xho"], b["Xho"], equal_nan=True)
    X, y, _ = bosch.make_bosch_like(3000, bosch.TRAIN_ROWS_SEED)
    assert np.array_equal(a["X"], X, equal_nan=True)
