"""Both plain references against the program at a tiny size, on the CPU,
and the generators they are fed from."""

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.generators import forest as forest_gen  # noqa: E402
from benchmark.generators import requests as req_gen  # noqa: E402
from benchmark.generators.higgs import make_higgs_like  # noqa: E402
from benchmark.reference import forest_numpy, gbdt_numpy  # noqa: E402


def test_the_data_is_a_function_of_the_seed_and_nothing_else():
    a = make_higgs_like(300_000, 28, 5)
    b = make_higgs_like(300_000, 28, 5)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    assert a[0].dtype == np.float32 and a[1].mean() == 0.5
    c = make_higgs_like(1000, 28, 6)
    assert not np.array_equal(a[0][:1000], c[0])
    # a held-out set follows the training set's rule, not its own median
    held = make_higgs_like(1000, 28, 5, stream=1, threshold=a[2])
    assert held[2] == a[2] and not np.array_equal(held[0], a[0][:1000])


@pytest.fixture(scope="module")
def tiny_training():
    import lightgbm_tpu as lgb
    X, y, _ = make_higgs_like(6000, 28, 11)
    ds = lgb.Dataset(X, label=y, params={"max_bin": 63})
    ds.construct()
    params = {"objective": "binary", "num_leaves": 7, "max_bin": 63,
              "learning_rate": 0.1, "verbosity": -1}
    bst = lgb.train(params, ds, num_boost_round=3)
    return bst, X, y, ds._binned.bins


def test_the_gbdt_reference_agrees_with_the_program(tiny_training):
    bst, X, y, bins = tiny_training
    trees = [gbdt_numpy.flatten_tree(t["tree_structure"])
             for t in bst.dump_model()["tree_info"]]
    for k in range(3):
        got = gbdt_numpy.check_step(
            k, trees, X, y, bins, learning_rate=0.1, min_data_in_leaf=20,
            min_sum_hessian_in_leaf=1e-3)
        # the program found the best root there is, and its leaves are
        # Newton steps to float32 sums' accuracy
        assert abs(got["root_gain_shortfall"]) < 1e-9, got
        assert got["empty_leaves"] == 0 and got["leaves"] == 7
        assert got["leaf_sum_err_root_ulps"] < 64, got
        assert got["leaf_value_max_rel_err"] < 1e-2, got


def test_the_gbdt_reference_sees_a_wrong_leaf_and_a_worse_root(
        tiny_training):
    bst, X, y, bins = tiny_training
    trees = [gbdt_numpy.flatten_tree(t["tree_structure"])
             for t in bst.dump_model()["tree_info"]]
    kw = dict(learning_rate=0.1, min_data_in_leaf=20,
              min_sum_hessian_in_leaf=1e-3)
    # a leaf value as a bfloat16 accumulation would leave it: 3 digits
    bad = [dict(t) for t in trees]
    bad[1]["leaf_value"] = trees[1]["leaf_value"] * (1 + 4e-3)
    assert gbdt_numpy.check_step(1, bad, X, y, bins, **kw)[
        "leaf_sum_err_root_ulps"] > 256
    # a root split one bin off the best
    worse = [dict(t) for t in trees]
    worse[0]["threshold"] = trees[0]["threshold"].copy()
    worse[0]["threshold"][0] += 0.5
    assert gbdt_numpy.check_step(0, worse, X, y, bins, **kw)[
        "root_gain_shortfall"] > 1e-4


def test_auc_by_ranks():
    y = np.array([0, 0, 1, 1, 1, 0])
    s = np.array([0.1, 0.4, 0.35, 0.8, 0.4, 0.2])
    pairs = [(p, n) for p in s[y == 1] for n in s[y == 0]]
    want = np.mean([1.0 if p > n else 0.5 if p == n else 0.0
                    for p, n in pairs])
    assert gbdt_numpy.auc(y, s) == pytest.approx(want)
    with pytest.raises(ValueError):
        gbdt_numpy.auc(np.ones(4), s[:4])


@pytest.fixture(scope="module")
def tiny_forest():
    X, _, _ = make_higgs_like(4000, 28, 3, stream=3)
    grid = forest_gen.quantile_grid(X, 63)
    text, stats = forest_gen.make_forest_text(3, trees=12, leaves=31,
                                              grid=grid, beta=1.0)
    return X, grid, text, stats


def test_the_forest_is_a_function_of_the_seed_and_leaf_wise_deep(
        tiny_forest):
    X, grid, text, stats = tiny_forest
    again, _ = forest_gen.make_forest_text(3, trees=12, leaves=31,
                                           grid=grid, beta=1.0)
    other, _ = forest_gen.make_forest_text(4, trees=12, leaves=31,
                                           grid=grid, beta=1.0)
    assert again == text and other != text
    # deeper than the 5 levels a complete tree of 31 leaves has
    assert stats["leaf_depth_max"] > 5 >= stats["leaf_depth_min"]
    model = forest_numpy.parse_model_text(text)
    assert len(model["trees"]) == 12 and model["features"] == 28
    for tree in model["trees"]:
        assert len(tree["leaf_value"]) == 31
        # every threshold is one of the grid's
        assert all(t in grid[f] for f, t in zip(tree["split_feature"],
                                                tree["threshold"]))


def test_the_forest_reference_agrees_with_the_program(tiny_forest):
    import lightgbm_tpu as lgb
    from lightgbm_tpu.serving import Server
    X, _, text, _ = tiny_forest
    want = forest_numpy.predict_proba(
        forest_numpy.parse_model_text(text), X[:300])
    assert 0.005 < want.std()        # the rows do reach different leaves
    host = lgb.Booster(model_str=text).predict(X[:300])
    assert np.max(np.abs(host - want)) < 1e-12
    with Server() as srv:
        srv.load_model("m", model_str=text)
        got = np.asarray(srv.predict("m", X[:300])).reshape(-1)
        snap = srv.metrics_snapshot("m")["models"]["m"]
    assert np.max(np.abs(got - want)) < 1e-6
    assert snap["fallbacks"] == 0 and snap["device_resident"]


def test_the_forest_reference_refuses_what_it_does_not_model(tiny_forest):
    text = tiny_forest[2]
    with pytest.raises(ValueError):
        forest_numpy.parse_model_text(
            text.replace("objective=binary sigmoid:1", "objective=lambdarank"))
    with pytest.raises(ValueError):       # a NaN-routing split
        forest_numpy.parse_model_text(
            text.replace("decision_type=2 2", "decision_type=10 2", 1))


def test_request_sizes_and_arrivals():
    rng = np.random.Generator(np.random.PCG64(1))
    pareto = req_gen.draw_sizes(rng, {"dist": "bounded_pareto", "shape": 1.3,
                                      "scale": 2, "min": 1, "max": 64}, 20000)
    assert pareto.min() == 1 and pareto.max() == 64
    assert np.median(pareto) <= 3 < pareto.mean()      # heavy-tailed
    logu = req_gen.draw_sizes(rng, {"dist": "log_uniform", "min": 512,
                                    "max": 8192}, 20000)
    assert 512 <= logu.min() and logu.max() <= 8192
    assert 1800 < np.median(logu) < 2300               # sqrt(512 * 8192)
    stream = {"loop": "open", "rate_per_s": 200.0,
              "sizes": {"dist": "fixed", "rows": 4}}
    a = req_gen.open_schedule(stream, 9, 0, 30.0, 1000)
    b = req_gen.open_schedule(stream, 9, 0, 30.0, 1000)
    assert np.array_equal(a["due_s"], b["due_s"])
    assert np.all(np.diff(a["due_s"]) > 0) and a["due_s"][-1] < 30.0
    assert abs(len(a["due_s"]) - 6000) < 4 * np.sqrt(6000)   # Poisson
    gaps = np.diff(a["due_s"])
    assert abs(gaps.std() / gaps.mean() - 1.0) < 0.1          # exponential
    # bursts: 5 x the rate for 200 ms of every 2 s
    bursty = req_gen.open_schedule(
        dict(stream, burst={"factor": 5, "on_ms": 200, "period_ms": 2000}),
        9, 0, 30.0, 1000)
    inside = np.mod(bursty["due_s"] * 1e3, 2000) < 200
    assert abs(inside.sum() - 200 * 5 * 3.0) < 5 * np.sqrt(3000)
    assert abs((~inside).sum() - 200 * 27.0) < 5 * np.sqrt(5400)
    with pytest.raises(ValueError, match="knee"):
        req_gen.check_streams([dict(stream, rate_per_s=0)])
