"""The categorical cell's own pieces: the data generator (every seed the
same level frequencies and label share; the carriers' and airports'
codes the seed's; the training table the same bins under every seed),
the two readers,
the question the runner asks of the program, what the cell is (its
rehearsal, traced and not, is a case of
`test_rehearsals.test_a_cell_rehearses_end_to_end`), and the comparison
that decides `correct` held against three programs that are not the
published one: leaf sums in bfloat16, the rule without its batching, a
model that does not read the categorical columns.

CPU only.
"""

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "helpers"))

from benchmark import harness  # noqa: E402
from benchmark.generators import expo  # noqa: E402
from benchmark.layer_metrics import (  # noqa: E402
    growth_categorical_scan_ms_per_tree, growth_categorical_split_share)
from benchmark.reference import gbdt_cat_numpy as ref  # noqa: E402

CELL = "expo_categorical_train"


# ----------------------------------------------------------------------
# the data
def _levels(X, seed):
    """The id columns of X as the tables' own level numbers."""
    codes = expo.level_codes(seed)
    return [np.searchsorted(codes[name], X[:, col].astype(np.int64))
            for name, col in (("carrier", 3), ("origin", 4), ("dest", 5))]


def test_every_seed_gives_the_same_work_on_other_rows():
    """Sets drawn FROM the seed (the held-out rows; the training rows
    where the configuration gives no `train_rows_seed`)."""
    X1, y1, t1 = expo.make_expo_like(60000, 2147484401)
    X2, y2, t2 = expo.make_expo_like(60000, 2147484402)
    assert X1.shape == X2.shape == (60000, 17) and X1.dtype == np.float32
    assert y1.mean() == y2.mean() == 0.5            # cut at the median
    assert not np.array_equal(_levels(X1, 2147484401)[1],
                              _levels(X2, 2147484402)[1])   # other rows
    for col, levels in zip(expo.CATEGORICAL[:3], expo.LEVELS[:3]):
        for X in (X1, X2):
            v = X[:, col]
            assert np.array_equal(v, np.round(v))   # integer codes
            assert v.min() == 1 and v.max() == levels
    assert sum(expo.LEVELS) == 689 and sum(expo.LEVELS) + 11 == 700
    # one fixed frequency table: the same airports are common in both
    f1 = np.bincount(_levels(X1, 2147484401)[1], minlength=305) / 60000
    f2 = np.bincount(_levels(X2, 2147484402)[1], minlength=305) / 60000
    assert np.argmax(f1) == np.argmax(f2)
    assert np.corrcoef(f1, f2)[0, 1] > 0.95
    # the same seed gives the same set; a second stream of it other rows
    # under the first one's threshold
    X1b, y1b, _ = expo.make_expo_like(60000, 2147484401)
    assert np.array_equal(X1, X1b) and np.array_equal(y1, y1b)
    Xh, yh, th = expo.make_expo_like(20000, 2147484401, stream=1,
                                     threshold=t1)
    assert th == t1 and abs(yh.mean() - 0.5) < 0.02
    assert not np.array_equal(Xh[:, 4], X1[:20000, 4])


@pytest.mark.parametrize("seed", [5, 2147484402, 3000000019])
def test_the_seed_gives_the_carriers_and_airports_their_codes(seed):
    codes = expo.level_codes(seed)
    for (name, col, levels) in (("carrier", 3, 29), ("origin", 4, 305),
                                ("dest", 5, 305)):
        c = codes[name]
        assert len(c) == levels and c.min() >= 1 and c.max() <= 2 * levels
        assert np.all(np.diff(c) > 0)               # distinct, in order
        assert not np.array_equal(c, expo.level_codes(seed + 1)[name])
    X, _, _ = expo.make_expo_like(50000, seed)
    for name, col in (("carrier", 3), ("origin", 4), ("dest", 5)):
        assert np.isin(X[:, col], codes[name]).all()
    assert len(np.unique(X[:, 4])) > 255            # more levels than bins
    assert np.array_equal(codes["origin"], expo.level_codes(seed)["origin"])


def test_the_training_table_is_the_same_work_under_every_seed():
    """The cell's training rows (`rows_seed=TRAIN_ROWS_SEED`): other
    values under another seed, the SAME binned matrix and labels, so
    the same trees and the same work; the held-out rows are the
    seed's."""
    import lightgbm_tpu as lgb
    cfg = harness.load_cell(CELL)["config"]
    assert cfg["train_rows_seed"] == expo.TRAIN_ROWS_SEED
    sets = []
    for seed in (2147484401, 7):
        X, y, t = expo.make_expo_like(120000, seed,
                                      rows_seed=cfg["train_rows_seed"])
        d = lgb.Dataset(X, label=y, categorical_feature=list(
            expo.CATEGORICAL), params={"max_bin": 255})
        d.construct()
        Xh, _, _ = expo.make_expo_like(5000, seed, stream=1, threshold=t)
        sets.append((X, y, t, np.asarray(d._binned.bins), Xh,
                     [m.num_bin for m in d._binned.mappers]))
    a, b = sets
    assert not np.array_equal(a[0][:, 3:6], b[0][:, 3:6])   # other codes
    assert np.array_equal(a[0][:, :3], b[0][:, :3])
    assert np.array_equal(a[0][:, 6:], b[0][:, 6:])
    assert np.array_equal(a[1], b[1]) and a[2] == b[2]
    assert np.array_equal(a[3], b[3]) and a[5] == b[5]      # the same bins
    assert a[5][4] == a[5][5] == 255                # max_bin binds
    assert not np.array_equal(a[4][:, 6:], b[4][:, 6:])     # other rows
    for (_, _, _, _, Xh, _), seed in zip(sets, (2147484401, 7)):
        assert np.isin(Xh[:, 4], expo.level_codes(seed)["origin"]).all()


def test_the_frequency_law_is_the_stated_one():
    p = expo.zipf(305)
    assert p[0] == pytest.approx(0.034, abs=0.001)      # commonest airport
    assert p[-1] * 11e6 == pytest.approx(9500, rel=0.05)  # rarest, in rows
    assert p[:254].sum() == pytest.approx(0.952, abs=0.001)
    X, _, _ = expo.make_expo_like(200000, 5)
    got = np.sort(np.bincount(X[:, 5].astype(int)))[::-1][:305]
    assert got[0] / 200000 == pytest.approx(p[0], rel=0.05)
    assert got[:254].sum() / 200000 == pytest.approx(0.952, abs=0.005)


def test_the_draw_does_not_depend_on_the_thread_count(monkeypatch):
    monkeypatch.setattr(expo, "CHUNK_ROWS", 512)
    want = expo.make_expo_like(3000, 5)
    monkeypatch.setattr(expo.os, "cpu_count", lambda: 2)   # one worker
    got = expo.make_expo_like(3000, 5)
    for a, b in zip(want, got):
        assert np.array_equal(a, b)


def test_the_labels_follow_the_categories_more_than_the_numbers():
    """The effects' own sum separates the classes far better than a
    line through the numerical columns."""
    X, y, _ = expo.make_expo_like(100000, 9)
    t = expo.tables()
    car, org, dst = _levels(X, 9)
    signal = (t.effect["carrier"][car] + t.effect["origin"][org]
              + t.effect["dest"][dst] + t.effect["interaction"][org, car]
              + t.effect["month"][X[:, 0].astype(int)]
              + t.effect["day_of_week"][X[:, 2].astype(int)])
    assert ref.auc(y, signal) > 0.74
    num = X[:, 6:] - X[:, 6:].mean(0)
    w = np.linalg.lstsq(num / num.std(0), y - 0.5, rcond=None)[0]
    assert 0.5 < ref.auc(y, (num / num.std(0)) @ w) < 0.62


# ----------------------------------------------------------------------
# the readers, and the scope they read
def test_the_readers_read_what_the_runner_and_the_program_give():
    scan = growth_categorical_scan_ms_per_tree
    assert scan.read({"categorical_busy_s": 0.5, "window_trees": 20}) == 25.0
    assert scan.read({"window_trees": 20}) is None
    assert scan.read({"categorical_busy_s": None,
                      "window_trees": 20}) is None
    spans = [{"name": "entry.unpack_block",
              "attrs": {"iter": it, "k": 10, "nodes": 2540,
                        "cat_nodes": cat}}
             for it, cat in ((10, 2000), (20, 1270), (30, 635), (40, 9))]
    r = {"kind": "train", "spans": spans, "warm_trees": 20,
         "window_trees": 20}
    share = growth_categorical_split_share
    assert share.read(r) == pytest.approx(100.0 * 1905 / 5080)
    # a program without the counters (the parent of the PR that added
    # them), or a run with no fused block in its window
    bare = [dict(s, attrs={k: v for k, v in s["attrs"].items()
                           if k in ("iter", "k")}) for s in spans]
    assert share.read(dict(r, spans=bare)) is None
    assert share.read(dict(r, spans=[])) is None
    assert share.read({"kind": "serve"}) is None


def test_the_scope_is_found_in_a_compiled_programs_text():
    from benchmark import training
    from benchmark.runners import train_cat
    text = "\n".join([
        'HloModule jit_program',
        '  %sort.7 = (f32[4,6,256]{2,1,0}) sort(%a), dimensions={2}, '
        'metadata={op_name="jit(program)/while/body/find_best_splits/'
        'split.categorical/sort"}',
        '  ROOT %fusion.3 = f32[8]{0} fusion(%p), kind=kLoop, calls=%c, '
        'metadata={op_name="jit(program)/while/body/find_best_splits/'
        'split.categorical/while/body/add" stack_frame_id=3}',
        '  %fusion.4 = f32[8]{0} fusion(%p), kind=kLoop, calls=%d, '
        'metadata={op_name="jit(program)/while/body/find_best_splits/'
        'split.numerical/dot_general"}',
        '  %copy.1 = f32[8]{0} copy(%q)'])
    names = {m.group(1) for m in map(training.INSTRUCTION.match,
                                     text.splitlines())
             if m and train_cat.CATEGORICAL_SCOPE in m.group(2)}
    assert names == {"sort.7", "fusion.3"}


# ----------------------------------------------------------------------
# what the runner asks of the program before it trains
def test_a_program_whose_dump_names_no_categories_is_refused(monkeypatch):
    """The parent of PR 35 writes the index of a node's bitset where the
    reference's JSON names the category values: the run ends with an
    error and no record, at once."""
    from benchmark.runners import train_cat
    import lightgbm_tpu.tree as program
    train_cat.ask_the_program()              # this program can say
    monkeypatch.delattr(program.HostTree, "cat_values_left")
    with pytest.raises(harness.BenchmarkError, match="cannot be held"):
        train_cat.ask_the_program()


# ----------------------------------------------------------------------
# the cell (its rehearsal, untraced and traced, is a case of
# test_rehearsals.test_a_cell_rehearses_end_to_end, held to what
# runners/train_cat.py says it prints)
def test_the_cell_is_the_published_job_on_one_chip():
    cell = harness.load_cell(CELL)
    assert cell["config"]["kind"] == "train_cat" and cell["chips"] == 1
    assert cell["config_entry"]["reduced"] == ["num_iterations"]
    assert cell["config"]["num_data"] == 11_000_000
    assert cell["config"]["num_leaves"] == cell["config"]["max_bin"] == 255
    assert cell["config"]["params"] == ref.DEFAULTS
    assert {"growth.categorical_scan_ms_per_tree",
            "growth.categorical_split_share"} <= {
        m["name"] for m in cell["per_layer"]}
    from benchmark.runners import train_cat
    says = train_cat.TASK.rehearsal_says(cell)
    assert "(6 categorical; training rows of the fixed table 20092, codes " \
        "of seed " in says
    # the six columns of the rehearsal's 40,000 rows hold 689 levels
    assert "'levels': 689," in says


# ----------------------------------------------------------------------
# the comparison that decides `correct`, against what is not correct
@pytest.fixture(scope="module")
def rehearsed():
    """The cell's job at its rehearsal size, in this process: data,
    parameters, the trained model, its first two trees, the limits."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.config import Config
    from benchmark.runners import train_cat
    cell = harness.load_cell(CELL)
    cfg, _ = harness.rehearsal_overlay(cell["config"], cell["traffic"])
    X, y, thr = expo.make_expo_like(int(cfg["num_data"]), 2147484401)
    Xh, yh, _ = expo.make_expo_like(int(cfg["held_out_rows"]), 2147484401,
                                    stream=1, threshold=thr)
    cats = list(cfg["categorical_feature"])
    params = {"objective": cfg["objective"], "num_leaves": cfg["num_leaves"],
              "max_bin": cfg["max_bin"],
              "learning_rate": cfg["learning_rate"], "verbosity": -1,
              **cfg["params"]}
    ds = lgb.Dataset(X, label=y, categorical_feature=cats,
                     params={"max_bin": cfg["max_bin"]})
    bst = lgb.train(dict(params), ds, int(cfg["expect"]["auc_trees"]))
    trees = [ref.flatten_tree(t["tree_structure"])
             for t in bst.dump_model(num_iteration=2)["tree_info"]]
    kw = train_cat._rule_params(Config(dict(params)))
    kw["slack_ulps"] = float(cfg["expect"]["cat_prefix_slack_ulps"])
    return dict(cfg=cfg, X=X, y=y, Xh=Xh, yh=yh, cats=cats, params=params,
                bins=ds._binned.bins, bst=bst, trees=trees, kw=kw,
                agrees=train_cat.TASK.agrees)


def test_the_program_itself_reads_as_correct(rehearsed):
    r = rehearsed
    routed = {}
    for k in (0, 1):
        got = ref.check_step(k, r["trees"], r["X"], r["y"], r["bins"],
                             r["cats"], routed=routed, **r["kw"])
        assert r["agrees"](got, r["cfg"]["expect"]), got
        assert got["cat_nodes"] >= got["nodes"] // 2
    auc = ref.auc(r["yh"], r["bst"].predict(r["Xh"], raw_score=True))
    assert auc > r["cfg"]["expect"]["auc_floor"]


@pytest.mark.parametrize("how", ["accumulated", "rounded"])
def test_leaf_sums_in_bfloat16_read_as_not_correct(rehearsed, how):
    from expo_controls import plant
    r = rehearsed
    routed, read = {}, []
    for k in (0, 1):
        ref.check_step(k, r["trees"], r["X"], r["y"], r["bins"], r["cats"],
                       routed=routed, **r["kw"])
        bias = ref.init_score(r["y"])
        score = np.full(len(r["y"]), bias) if k == 0 else \
            r["trees"][0]["leaf_value"][routed[0]]
        grad, hess = ref.grad_hess(score, r["y"])
        sorted_column = np.zeros(17, bool)
        sorted_column[r["cats"]] = True          # every column over 4 bins
        l2 = ref.leaf_l2(r["trees"][k], sorted_column, lambda_l2=0.0,
                         cat_l2=10.0)
        planted = r["trees"][:k] + [plant(
            r["trees"][k], routed[k], grad, hess, l2, how,
            learning_rate=0.1, bias=bias if k == 0 else 0.0)]
        got = ref.check_step(k, planted, r["X"], r["y"], r["bins"],
                             r["cats"], routed=routed, **r["kw"])
        read.append(got["leaf_sum_err_root_ulps"])
        if how == "accumulated" or k == 1:
            assert not r["agrees"](got, r["cfg"]["expect"]), got
    # tree 0's gradients are two values that bfloat16 holds nearly
    # exactly: rounding them moves little; accumulating does
    assert max(read) > 10 * r["cfg"]["expect"]["leaf_sum_err_root_ulps"]


def test_the_rule_without_its_batching_reads_as_not_correct(rehearsed):
    r = rehearsed
    routed, worst = {}, 0.0
    for k in (0, 1):
        got = ref.check_step(k, r["trees"], r["X"], r["y"], r["bins"],
                             r["cats"], routed=routed, batching=False,
                             **r["kw"])
        worst = max(worst, got["cat_gain_shortfall_ulps"])
    assert worst > 10 * r["cfg"]["expect"]["cat_gain_shortfall_ulps"]
    assert not r["agrees"](dict(got, cat_gain_shortfall_ulps=worst),
                           r["cfg"]["expect"])


def test_a_program_without_the_batching_reads_as_not_correct(rehearsed):
    """The other way round, and the one no float32 noise blurs: a PROGRAM
    that evaluates every step (min_data_per_group=1, as before PR 35)
    held against the published rule at 100 chooses left sets that end
    inside a group."""
    import lightgbm_tpu as lgb
    r = rehearsed
    ds = lgb.Dataset(r["X"], label=r["y"], categorical_feature=r["cats"],
                     params={"max_bin": r["cfg"]["max_bin"]})
    loose = lgb.train(dict(r["params"], min_data_per_group=1), ds, 2)
    trees = [ref.flatten_tree(t["tree_structure"])
             for t in loose.dump_model()["tree_info"]]
    routed, bad = {}, 0
    for k in (0, 1):
        got = ref.check_step(k, trees, r["X"], r["y"], r["bins"], r["cats"],
                             routed=routed, **r["kw"])
        bad += got["cat_infeasible_nodes"]
        if got["cat_infeasible_nodes"]:
            assert not r["agrees"](got, r["cfg"]["expect"])
            assert "not one the rule evaluates" in \
                got["cat_infeasible"][0]["why"]
    assert bad >= 3


@pytest.mark.parametrize("how", ["numerical_columns_only",
                                 "categories_read_as_numbers"])
def test_a_program_that_ignores_the_categories_reads_as_not_correct(
        rehearsed, how):
    """The AUC floor sits ABOVE what a model reaches that does not
    search category sets (PR 32's floor sat under the unranked reading
    and let a wrong generator pass)."""
    import lightgbm_tpu as lgb
    r = rehearsed
    cols = [c for c in range(17) if c not in r["cats"]] \
        if how == "numerical_columns_only" else list(range(17))
    ds = lgb.Dataset(r["X"][:, cols], label=r["y"],
                     params={"max_bin": r["cfg"]["max_bin"]})
    bst = lgb.train(dict(r["params"]), ds,
                    int(r["cfg"]["expect"]["auc_trees"]))
    auc = ref.auc(r["yh"], bst.predict(r["Xh"][:, cols], raw_score=True))
    assert 0.5 < auc < r["cfg"]["expect"]["auc_floor"] - 0.01, auc
