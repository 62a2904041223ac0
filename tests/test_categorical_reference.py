"""Categorical splits, held against an independent implementation of the
published rule (`benchmark/reference/gbdt_cat_numpy.py`, NumPy, float64,
no code shared with the program) on seeded random data at a small size:
the split search branch by branch, the routing of values a tree has not
seen, the model text, and the MXU grower (interpret mode) against the
portable one.

CPU only.
"""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import lightgbm_tpu as lgb  # noqa: E402
from benchmark.reference import gbdt_cat_numpy as ref  # noqa: E402

FLOORS = dict(min_data_in_leaf=20, min_sum_hessian_in_leaf=1e-3)


# ----------------------------------------------------------------------
# the reference's rule, by hand
def _hist(rows):
    """(g, h, n) from [(g, h, n)] of bins 1.., bin 0 empty."""
    a = np.asarray([(0.0, 0.0, 0)] + list(rows), np.float64)
    return a[:, 0], a[:, 1], a[:, 2].astype(np.int64)


def test_one_against_rest_uses_the_plain_l2():
    g, h, n = _hist([(-30.0, 25.0, 100), (10.0, 25.0, 100),
                     (20.0, 25.0, 100)])
    got = ref.categorical_rule(g, h, n, **FLOORS)
    assert got["left_bins"] == [1] and got["sorted"] is False
    want = 900 / 25 + 900 / 50 - 0.0
    assert got["gain"] == pytest.approx(want)


def test_the_sorted_scan_walks_from_both_ends_and_adds_cat_l2():
    # ratios -0.5, -0.2, 0.1, 0.3, 0.6: the best set is the two lowest
    # from the low end, or the three highest from the high end; a set
    # holds at most (5 + 1) / 2 = 3 bins
    rows = [(6.0, 0, 100), (-2.0, 0, 100), (1.0, 0, 100), (-5.0, 0, 100),
            (3.0, 0, 100)]
    g, h, n = _hist([(r[0] * 10, 90.0, r[2]) for r in rows])
    got = ref.categorical_rule(g, h, n, **FLOORS)
    assert got["sorted"] is True
    assert sorted(got["left_bins"]) == [2, 4]
    lg, lh, G, H = -70.0, 180.0, 30.0, 450.0
    assert got["gain"] == pytest.approx(
        lg ** 2 / (lh + 10) + (G - lg) ** 2 / (H - lh + 10) - G ** 2 / H)
    # the high end reaches the same partition as {1, 3, 5} with the same
    # gain, and a gain has to be strictly larger to win: the low end's
    # stays. With the signs flipped {1, 3, 5} is the low end's
    got = ref.categorical_rule(-g, h, n, **FLOORS)
    assert got["left_bins"] == [1, 5, 3]       # the extreme bin first


@pytest.mark.parametrize("limit,want", [(1, 1), (2, 2), (32, 3)])
def test_max_cat_threshold_bounds_the_left_set(limit, want):
    g, h, n = _hist([(-9.0 + 3 * k, 50.0, 200) for k in range(7)])
    got = ref.categorical_rule(g, h, n, max_cat_threshold=limit, **FLOORS)
    assert len(got["left_bins"]) == want


def test_a_bin_under_cat_smooth_rows_is_not_scanned_and_bin_0_stays_right():
    g, h, n = _hist([(-50.0, 2.0, 8), (-5.0, 50.0, 200), (1.0, 50.0, 200),
                     (2.0, 50.0, 200), (4.0, 50.0, 200), (6.0, 50.0, 200)])
    g[0], h[0], n[0] = -400.0, 100.0, 400      # the dummy bin, a strong one
    low, high = ref.sorted_orders(g, h, n, cat_smooth=10)
    assert sorted(low) == [2, 3, 4, 5, 6] and list(high) == list(low[::-1])
    low, _ = ref.sorted_orders(g, h, n, cat_smooth=5)
    assert low[0] == 1 and 0 not in low
    for smooth in (5, 10):
        got = ref.categorical_rule(g, h, n, cat_smooth=smooth, **FLOORS)
        assert 0 not in got["left_bins"]


def test_min_data_per_group_batches_the_evaluations():
    """Bins of 40 rows: with groups of 100 rows a gain is evaluated at
    every third step only, and the right side keeps 100 rows."""
    g, h, n = _hist([(-8.0 + k, 10.0, 40) for k in range(12)])
    G, H, N = g.sum(), h.sum(), int(n.sum())
    order, _ = ref.sorted_orders(g, h, n, cat_smooth=10)
    kw = dict(lambda_l2=0.0, cat_l2=10.0, **FLOORS)
    steps = ref.scan_steps(order, 6, g, h, n, G, H, N,
                           min_data_per_group=100, **kw)
    assert [i for i, _ in steps] == [2, 5]
    steps = ref.scan_steps(order, 6, g, h, n, G, H, N,
                           min_data_per_group=100, batching=False, **kw)
    assert [i for i, _ in steps] == [0, 1, 2, 3, 4, 5]
    # a left side under min_data_in_leaf adds to the group and goes on
    steps = ref.scan_steps(order, 6, g, h, n, G, H, N,
                           min_data_per_group=30, **dict(
                               kw, min_data_in_leaf=90))
    assert [i for i, _ in steps] == [2, 3, 4, 5]
    # the right side's floor ends the scan
    steps = ref.scan_steps(order, 12, g, h, n, G, H, N,
                           min_data_per_group=100, **kw)
    assert [i for i, _ in steps] == [2, 5, 8]


def test_feasibility_tells_a_prefix_from_a_set_the_rule_cannot_give():
    g, h, n = _hist([(-8.0 + k, 10.0, 40) for k in range(12)])
    rule = dict(lambda_l2=0.0, cat_smooth=10.0, cat_l2=10.0,
                max_cat_threshold=32, max_cat_to_onehot=4,
                min_data_per_group=100, **FLOORS)
    noise = np.full(13, 1e-7)
    ok = ref.feasibility([1, 2, 3], g, h, n, noise, noise, slack_ulps=64,
                         rule=rule)
    assert ok["evaluated"] and ok["prefix_slack_ulps"] == 0.0
    # a prefix that ends inside a group: the rule never evaluates it
    mid = ref.feasibility([1, 2], g, h, n, noise, noise, slack_ulps=64,
                          rule=rule)
    assert not mid["evaluated"] and mid["prefix_slack_ulps"] == 0.0
    # not a prefix of either order
    gap = ref.feasibility([1, 2, 4], g, h, n, noise, noise, slack_ulps=64,
                          rule=rule)
    assert gap["prefix_slack_ulps"] > 1e3
    # the dummy bin never goes left
    assert ref.feasibility([0, 1, 2], g, h, n, noise, noise, slack_ulps=64,
                           rule=rule)["prefix_slack_ulps"] == float("inf")


# ----------------------------------------------------------------------
# the program against the rule
def _data(seed, rows=24000, levels=(300, 3, 12), skew=True):
    """Three categorical columns (many levels under a bounded Zipf law,
    at most three levels, a dozen) and two numerical ones; the label
    follows fixed effects of the levels."""
    rng = np.random.default_rng(seed)
    cols = []
    for lv in levels:
        p = 1.0 / (np.arange(lv) + (8.0 if skew else 1e9))
        cols.append(rng.choice(lv, rows, p=p / p.sum()))
    X = np.column_stack(cols + [rng.normal(size=rows),
                                rng.normal(size=rows)]).astype(np.float32)
    effects = [rng.normal(size=lv) for lv in levels]
    latent = sum(w * e[X[:, j].astype(int)] for j, (w, e) in enumerate(
        zip((1.0, 0.5, 0.4), effects)))
    y = (latent + 0.5 * X[:, 3] + rng.normal(size=rows) > 0)
    return X, y.astype(np.float32), [0, 1, 2]


CASES = {
    "defaults": {},
    "one_against_rest_up_to_12_levels": {"max_cat_to_onehot": 13},
    "more_levels_than_max_bin": {"max_bin": 63},
    "small_groups": {"min_data_per_group": 30},
    "large_groups": {"min_data_per_group": 400},
    "max_cat_threshold_4": {"max_cat_threshold": 4},
    "cat_l2_1": {"cat_l2": 1.0},
    "cat_l2_50": {"cat_l2": 50.0},
    "cat_smooth_40": {"cat_smooth": 40.0},
    "lambda_l2_5": {"lambda_l2": 5.0},
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_program_grows_what_the_published_rule_gives(case):
    X, y, cats = _data(seed=sorted(CASES).index(case))
    params = {"objective": "binary", "num_leaves": 31, "verbosity": -1,
              **CASES[case]}
    ds = lgb.Dataset(X, label=y, categorical_feature=cats,
                     params={"max_bin": params.get("max_bin", 255)})
    bst = lgb.train(params, ds, 3)
    trees = [ref.flatten_tree(t["tree_structure"])
             for t in bst.dump_model()["tree_info"]]
    rule = {**ref.DEFAULTS, "lambda_l2": 0.0,
            **{k: v for k, v in CASES[case].items() if k != "max_bin"}}
    routed, cat_nodes, directions = {}, 0, set()
    for k in range(3):
        got = ref.check_step(k, trees, X, y, ds._binned.bins, cats,
                             learning_rate=0.1, routed=routed, **FLOORS,
                             **rule)
        assert got["root_gain_shortfall"] <= 1e-5, got
        assert got["cat_infeasible_nodes"] == 0, got
        assert got["cat_gain_shortfall_ulps"] <= 64, got
        assert got["leaf_sum_err_root_ulps"] <= 64, got
        assert got["empty_leaves"] == 0
        cat_nodes += got["cat_nodes"]
    assert cat_nodes >= 6
    leaves = np.asarray(bst.predict(X, pred_leaf=True))
    for k in range(3):
        assert np.array_equal(leaves[:, k], routed[k])
    if case == "more_levels_than_max_bin":
        # 300 levels, 62 bins of their own: the rest share bin 0, which
        # never goes left
        assert int(ds._binned.bins[:, 0].max()) == 62
        assert (ds._binned.bins[:, 0] == 0).mean() > 0.2
    if case == "max_cat_threshold_4":
        assert max(len(t["left_values"][i]) for t in trees
                   for i in np.flatnonzero(t["is_cat"])) <= 4
    if case == "one_against_rest_up_to_12_levels":
        # columns 1 and 2 are searched one bin against the rest
        assert all(len(t["left_values"][i]) == 1 for t in trees
                   for i in np.flatnonzero(t["is_cat"])
                   if t["feature"][i] in (1, 2))


@pytest.mark.parametrize("control", ["no_batching", "ignored_tie_order"])
def test_the_check_sees_a_rule_that_is_not_the_published_one(control):
    """The comparison is not vacuous: held against the rule WITHOUT the
    min_data_per_group batching the program's trees fall short of that
    rule's best; and a tree whose left set skips a bin of the sorted
    order is not one the rule can produce."""
    X, y, cats = _data(seed=3)
    params = {"objective": "binary", "num_leaves": 31, "verbosity": -1}
    ds = lgb.Dataset(X, label=y, categorical_feature=cats)
    bst = lgb.train(params, ds, 2)
    trees = [ref.flatten_tree(t["tree_structure"])
             for t in bst.dump_model()["tree_info"]]
    kw = dict(learning_rate=0.1, **FLOORS)
    if control == "no_batching":
        worst = max(ref.check_step(k, trees, X, y, ds._binned.bins, cats,
                                   batching=False, **kw)
                    ["cat_gain_shortfall_ulps"] for k in range(2))
        assert worst > 1000
    else:
        tree = trees[0]
        node = next(i for i in np.flatnonzero(tree["is_cat"])
                    if len(tree["left_values"][i]) >= 3
                    and tree["feature"][i] == 0)
        tree["left_values"][node] = tree["left_values"][node][:-1]
        got = ref.check_step(0, trees, X, y, ds._binned.bins, cats, **kw)
        assert got["cat_infeasible_nodes"] >= 1 or \
            got["cat_gain_shortfall_ulps"] > 1000


@pytest.mark.parametrize("value", ["unseen_level", "negative_code", "nan",
                                   "seen_level_as_float"])
def test_a_value_the_trees_have_not_seen_goes_right(value):
    X, y, cats = _data(seed=11, rows=8000, levels=(40, 3, 12), skew=False)
    ds = lgb.Dataset(X, label=y, categorical_feature=cats)
    bst = lgb.train({"objective": "binary", "num_leaves": 15,
                     "verbosity": -1}, ds, 4)
    trees = [ref.flatten_tree(t["tree_structure"])
             for t in bst.dump_model()["tree_info"]]
    assert trees[0]["is_cat"][0] and trees[0]["feature"][0] == 0
    Xq = X[:500].copy()
    if value == "seen_level_as_float":
        Xq[:, 0] += 0.4                      # truncates to the level
    else:
        Xq[:, 0] = {"unseen_level": 977.0, "negative_code": -3.0,
                    "nan": np.nan}[value]
    leaves = np.asarray(bst.predict(Xq, pred_leaf=True))
    for k, tree in enumerate(trees):
        assert np.array_equal(leaves[:, k], ref.route(tree, Xq))
    if value != "seen_level_as_float":
        # at the root every such row takes the right child
        right = ref.route(
            {**trees[0], "left": np.where(np.arange(len(
                trees[0]["left"])) == 0, ~0, trees[0]["left"]),
             "right": np.where(np.arange(len(trees[0]["right"])) == 0, ~1,
                               trees[0]["right"])}, Xq)
        assert (right == 1).all()
    else:
        assert np.array_equal(leaves, bst.predict(X[:500], pred_leaf=True))


@pytest.mark.parametrize("form", ["string", "file"])
def test_the_model_text_carries_the_category_sets(form, tmp_path):
    X, y, cats = _data(seed=5, rows=8000)
    ds = lgb.Dataset(X, label=y, categorical_feature=cats)
    bst = lgb.train({"objective": "binary", "num_leaves": 15,
                     "verbosity": -1}, ds, 3)
    text = bst.model_to_string()
    assert "cat_boundaries=" in text and "cat_threshold=" in text
    if form == "string":
        back = lgb.Booster(model_str=text)
    else:
        path = str(tmp_path / "model.txt")
        bst.save_model(path)
        back = lgb.Booster(model_file=path)
    np.testing.assert_allclose(back.predict(X, raw_score=True),
                               bst.predict(X, raw_score=True), rtol=1e-12)
    a, b = bst.dump_model(), back.dump_model()
    for ta, tb in zip(a["tree_info"], b["tree_info"]):
        fa, fb = (ref.flatten_tree(t["tree_structure"]) for t in (ta, tb))
        assert json.dumps([v if v is None else v.tolist()
                           for v in fa["left_values"]]) == \
            json.dumps([v if v is None else v.tolist()
                        for v in fb["left_values"]])
        for key in ("feature", "threshold", "left", "right", "leaf_value"):
            np.testing.assert_allclose(fa[key], fb[key], rtol=1e-12,
                                       atol=1e-30)
    # the dump names category VALUES, joined by "||", and the text's
    # bitset words hold exactly those
    tree = ref.flatten_tree(b["tree_info"][0]["tree_structure"])
    node = int(np.flatnonzero(tree["is_cat"])[0])
    words = [int(w) for w in text.split("cat_threshold=")[1].split(
        "\n")[0].split()]
    bounds = [int(w) for w in text.split("cat_boundaries=")[1].split(
        "\n")[0].split()]
    first = [n for n in range(len(tree["is_cat"])) if tree["is_cat"][n]]
    values = tree["left_values"][node]
    assert (np.diff(values) > 0).all() and values.min() >= 0
    # a node's words: the c-th categorical node of the text is not
    # necessarily the c-th in the dump's order, so compare as sets
    sets = set()
    for c in range(len(bounds) - 1):
        w = words[bounds[c]:bounds[c + 1]]
        sets.add(tuple(32 * i + bit for i, word in enumerate(w)
                       for bit in range(32) if (word >> bit) & 1))
    assert len(first) == len(bounds) - 1
    assert {tuple(tree["left_values"][n].tolist()) for n in first} == sets
    leaves = np.asarray(back.predict(X, pred_leaf=True))
    assert np.array_equal(leaves[:, 0], ref.route(tree, X))


# ----------------------------------------------------------------------
# the MXU grower (interpret mode) against the portable one
@pytest.mark.parametrize("levels", [(40, 3, 12), (300, 3, 12)],
                         ids=["40_levels", "300_levels_63_bins"])
def test_the_mxu_grower_grows_the_portable_growers_tree(levels):
    import jax.numpy as jnp
    from lightgbm_tpu.data import BinnedDataset, Metadata
    from lightgbm_tpu.learner.grower import grow_tree
    from lightgbm_tpu.learner.grower_mxu import grow_tree_mxu
    from lightgbm_tpu.learner.split import SplitHyperParams
    X, y, cats = _data(seed=2, rows=3000, levels=levels)
    ds = BinnedDataset.from_raw(X, Metadata(len(y), label=y), max_bin=63,
                                categorical_features=cats)
    n = len(y)
    g = jnp.asarray(0.5 - y)
    h = jnp.full(n, 0.25, jnp.float32)
    args = (jnp.asarray(ds.bins), g, h, jnp.ones(n, jnp.float32),
            jnp.ones(ds.num_features, jnp.float32),
            jnp.asarray(ds.num_bins), jnp.asarray(ds.missing_types == 2),
            jnp.asarray(ds.is_categorical))
    hp = SplitHyperParams(
        min_data_in_leaf=20, has_categorical=True, min_data_per_group=50,
        cat_columns=tuple(int(j) for j in np.flatnonzero(
            ds.is_categorical)))
    kw = dict(num_leaves=7, max_depth=0, hp=hp, bmax=int(ds.num_bins.max()))
    t_ref, r_ref = grow_tree(*args, leafwise=False, **kw)
    t_mxu, r_mxu = grow_tree_mxu(*args, interpret=True, **kw)
    nn = int(t_ref.num_nodes)
    assert int(t_mxu.num_nodes) == nn
    assert np.asarray(t_ref.is_cat)[:nn].sum() >= 2
    for field in ("split_feature", "is_cat", "cat_bitset"):
        np.testing.assert_array_equal(
            np.asarray(getattr(t_ref, field))[:nn],
            np.asarray(getattr(t_mxu, field))[:nn], err_msg=field)
    np.testing.assert_allclose(np.asarray(t_ref.leaf_value)[:nn],
                               np.asarray(t_mxu.leaf_value)[:nn],
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(r_ref), np.asarray(r_mxu))
    # and without the static column list the search is the same
    t_all, _ = grow_tree(*args, leafwise=False, **dict(
        kw, hp=SplitHyperParams(min_data_in_leaf=20, has_categorical=True,
                                min_data_per_group=50)))
    for field in ("split_feature", "is_cat", "cat_bitset", "leaf_value"):
        np.testing.assert_array_equal(
            np.asarray(getattr(t_ref, field))[:nn],
            np.asarray(getattr(t_all, field))[:nn], err_msg=field)


def test_the_scopes_name_both_halves_of_the_split_search():
    """`split.categorical` and `split.numerical` reach the compiled
    program's metadata, where the benchmark's runner reads them; with no
    categorical column nothing of the categorical half is traced."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.learner.split import (SplitHyperParams,
                                            find_best_splits)
    s, f, b = 4, 3, 16
    hist = jnp.ones((s, f, b, 3), jnp.float32)
    ones = jnp.ones(s, jnp.float32)
    args = (hist, ones, ones * 48, ones * 48, ones * 0,
            jnp.full(f, b, jnp.int32), jnp.zeros(f, bool))
    cat = jnp.asarray([True, False, False])
    text = find_best_splits.lower(
        *args, cat, jnp.ones(f), hp=SplitHyperParams(
            has_categorical=True, cat_columns=(0,)),
        cat_columns=(0,)).compile().as_text()
    assert "split.categorical" in text and "split.numerical" in text
    text = find_best_splits.lower(
        *args, jnp.zeros(f, bool), jnp.ones(f),
        hp=SplitHyperParams()).compile().as_text()
    assert "split.categorical" not in text and "sort" not in text
    assert jax.default_backend() == "cpu"
