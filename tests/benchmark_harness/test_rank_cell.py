"""The ranking cell's own pieces: the plain reference against a literal
double loop over pairs, NDCG@k against hand values, the data generator
(every seed the same work on other rows), the objective's share read off a capture,
and what the cell is (its rehearsal, traced and not, is a case of
`test_rehearsals.test_a_cell_rehearses_end_to_end`).

CPU only.
"""

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import harness  # noqa: E402
from benchmark.generators import mslr  # noqa: E402
from benchmark.layer_metrics import (objective_device_ms_per_tree,  # noqa: E402
                                     objective_pair_fill)
from benchmark.reference import lambdarank_numpy as ref  # noqa: E402

CELL = "mslr_rank_train"


# ----------------------------------------------------------------------
# the reference
def _gradients_by_two_loops(score, label, sizes, *, sigmoid=1.0,
                               truncation_level=30, norm=True):
    """LambdarankNDCG::GetGradientsForOneQuery as the reference writes
    it: two loops over pairs, one pair at a time."""
    score = np.asarray(score, np.float64)
    label = np.asarray(label, np.float64)
    g, h = np.zeros(len(score)), np.zeros(len(score))
    lo = 0
    for cnt in np.asarray(sizes, np.int64).tolist():
        sc, lb = score[lo:lo + cnt], label[lo:lo + cnt]
        idx = sorted(range(cnt), key=lambda a: -sc[a])    # sorted is stable
        inv = ref.inverse_max_dcg(lb, truncation_level)
        best, worst = (sc[idx[0]], sc[idx[-1]]) if cnt else (0.0, 0.0)
        lam, hes, total = np.zeros(cnt), np.zeros(cnt), 0.0
        for i in range(min(cnt - 1, truncation_level)):
            for j in range(i + 1, cnt):
                a, b = idx[i], idx[j]
                if lb[a] == lb[b]:
                    continue
                (hi, hi_rank), (low, low_rank) = \
                    ((a, i), (b, j)) if lb[a] > lb[b] else ((b, j), (a, i))
                delta = sc[hi] - sc[low]
                dndcg = (2.0 ** lb[hi] - 2.0 ** lb[low]) * \
                    abs(1.0 / np.log2(hi_rank + 2.0)
                        - 1.0 / np.log2(low_rank + 2.0)) * inv
                if norm and best != worst:
                    dndcg /= 0.01 + abs(delta)
                p = 1.0 / (1.0 + np.exp(sigmoid * delta))
                p_lambda = -sigmoid * dndcg * p
                p_hess = sigmoid * sigmoid * dndcg * p * (1.0 - p)
                lam[hi] += p_lambda
                lam[low] -= p_lambda
                hes[hi] += p_hess
                hes[low] += p_hess
                total -= 2.0 * p_lambda
        if norm and total > 0:
            lam *= np.log2(1.0 + total) / total
            hes *= np.log2(1.0 + total) / total
        g[lo:lo + cnt], h[lo:lo + cnt] = lam, hes
        lo += cnt
    return g, h


@pytest.mark.parametrize("norm", [True, False], ids=["norm", "raw"])
@pytest.mark.parametrize("truncation", [3, 30])
@pytest.mark.parametrize("sigmoid", [1.0, 2.0])
def test_the_vectorised_reference_is_the_two_loops(norm, truncation,
                                                   sigmoid):
    rng = np.random.RandomState(7)
    sizes = np.array([1, 2, 5, 31, 12, 40, 1, 3])
    n = int(sizes.sum())
    label = rng.randint(0, 5, n).astype(np.float64)
    label[8:39] = 1.0                       # a query of equal labels
    score = np.round(rng.randn(n), 1)       # ties
    kw = dict(sigmoid=sigmoid, truncation_level=truncation, norm=norm)
    g, h = ref.lambdarank_gradients(score, label, sizes, **kw)
    gl, hl = _gradients_by_two_loops(score, label, sizes, **kw)
    np.testing.assert_allclose(g, gl, rtol=1e-12, atol=1e-15)
    np.testing.assert_allclose(h, hl, rtol=1e-12, atol=1e-15)
    assert not g[8:39].any() and g[0] == 0.0
    # every pair gives and takes the same lambda: a query's sum is zero
    lo = 0
    for cnt in sizes:
        assert abs(g[lo:lo + cnt].sum()) < 1e-12
        assert (h[lo:lo + cnt] >= 0).all()
        lo += cnt


def test_a_pair_by_hand():
    """Two documents, the lower-scored one relevant: one pair, i = 0."""
    g, h = ref.lambdarank_gradients(
        np.array([1.0, 0.0]), np.array([0.0, 1.0]), [2], norm=False)
    inv = 1.0                                # max DCG: gain 1 at rank 0
    dndcg = 1.0 * abs(1.0 - 1.0 / np.log2(3.0)) * inv
    p = 1.0 / (1.0 + np.exp(-1.0))           # delta = 0 - 1
    np.testing.assert_allclose(g, [dndcg * p, -dndcg * p])
    np.testing.assert_allclose(h, [dndcg * p * (1 - p)] * 2)
    # with norm: /(0.01 + |delta|), then the query scaled by
    # log2(1 + sum) / sum with sum = 2 |lambda|
    gn, _ = ref.lambdarank_gradients(
        np.array([1.0, 0.0]), np.array([0.0, 1.0]), [2], norm=True)
    lam = dndcg / 1.01 * p
    np.testing.assert_allclose(
        gn, np.array([lam, -lam]) * np.log2(1 + 2 * lam) / (2 * lam))


@pytest.mark.parametrize("k,want", [
    (1, (0.0 + 1.0 + 1.0) / 3),
    (2, ((3 / np.log2(3)) / (3 + 1 / np.log2(3)) + 1.0 + 1.0) / 3),
    (10, ((3 / np.log2(3) + 1 / np.log2(4)) / (3 + 1 / np.log2(3))
          + 1.0 + 1.0) / 3),
])
def test_ndcg_at_k_by_hand(k, want):
    # query 0: labels 0, 2, 1 ranked in that order; query 1: no relevant
    # document (counts 1); query 2: one document, relevant, ranked first
    label = np.array([0, 2, 1, 0, 0, 3], np.float64)
    score = np.array([3.0, 2.0, 1.0, 0.5, 0.7, 9.0])
    assert ref.ndcg_at_k(label, score, [3, 2, 1], k) == pytest.approx(want)


def test_check_step_agrees_with_a_tree_built_from_its_own_gradients():
    """A depth-1 tree whose split and Newton leaves are computed here
    from the reference's gradients passes; the same tree with one leaf
    value off by a thousandth does not pass the leaf check."""
    rng = np.random.RandomState(3)
    sizes = np.array([20, 35, 8, 60, 1, 17])
    n = int(sizes.sum())
    X = rng.randn(n, 3)
    y = rng.randint(0, 4, n).astype(np.float64)
    bins = np.stack([np.digitize(X[:, f], np.quantile(
        X[:, f], np.linspace(0, 1, 17)[1:-1])) for f in range(3)], axis=1)
    g, h = ref.lambdarank_gradients(np.zeros(n), y, sizes)
    best, f, b = ref.best_root_gain(bins, g, h, min_data_in_leaf=5,
                                    min_sum_hessian_in_leaf=1e-3)
    thr = float(X[bins[:, f] <= b, f].max())
    left = X[:, f] <= thr
    values = np.array([-g[left].sum() / h[left].sum(),
                       -g[~left].sum() / h[~left].sum()]) * 0.1
    tree = {"feature": np.array([f]), "threshold": np.array([thr]),
            "left": np.array([~0]), "right": np.array([~1]),
            "leaf_value": values}
    kw = dict(learning_rate=0.1, min_data_in_leaf=5,
              min_sum_hessian_in_leaf=1e-3)
    got = ref.check_step(0, [tree], X, y, sizes, bins, **kw)
    assert got["root_gain_shortfall"] < 1e-12
    assert got["leaf_sum_err_root_ulps"] < 1e-3 and got["empty_leaves"] == 0
    assert got["distinct_scores"] == 1
    off = dict(tree, leaf_value=values * np.array([1.0, 1.001]))
    assert ref.check_step(0, [off], X, y, sizes, bins,
                          **kw)["leaf_sum_err_root_ulps"] > 100


def _as_dumped(tree):
    """A flattened tree back in `Booster.dump_model()`'s nested form."""
    def node(i):
        if i < 0:
            return {"leaf_index": int(~i),
                    "leaf_value": float(tree["leaf_value"][~i])}
        return {"split_index": int(i), "decision_type": "<=",
                "split_feature": int(tree["feature"][i]),
                "threshold": float(tree["threshold"][i]),
                "left_child": node(int(tree["left"][i])),
                "right_child": node(int(tree["right"][i]))}
    return {"tree_structure": node(0)}


class _Dumped:
    def __init__(self, trees):
        self.trees = trees

    def dump_model(self, num_iteration=None):
        return {"tree_info": [_as_dumped(t)
                              for t in self.trees[:num_iteration]]}


@pytest.mark.parametrize("how,correct", [
    (None, True), ("accumulated", False), ("rounded", False)])
def test_leaf_sums_made_in_bfloat16_are_not_correct(how, correct):
    """The runner's own comparison under the configuration's own limits:
    a tree whose leaves are the Newton step over the reference's float64
    gradients is correct; the same tree with its leaf sums made in
    bfloat16 is not. (`rounded` fails at this size only: its fault grows
    like the square root of a leaf's rows, the unit like the rows, and
    at the cell's size it reads under the limit, as the configuration's
    `expect.reason` says with the chip's numbers. `accumulated` is the
    control the limit is set against.)"""
    import types
    from benchmark.runners import rank
    from helpers.rank_controls import plant
    lengths = mslr.query_lengths(60, 4000, 200)
    X, y, sizes, _ = mslr.make_mslr_like(lengths, 12, 2147483909)
    X = X.astype(np.float64)
    bins = np.stack([np.digitize(X[:, f], np.quantile(
        X[:, f], np.linspace(0, 1, 33)[1:-1])) for f in range(12)], axis=1)
    g, h = ref.lambdarank_gradients(np.zeros(len(y)), y, sizes)
    _, f, b = ref.best_root_gain(bins, g, h, min_data_in_leaf=20,
                                 min_sum_hessian_in_leaf=1e-3)
    thr = float(X[bins[:, f] <= b, f].max())
    other = (f + 1) % 12
    tree = {"feature": np.array([f, other, other]),
            "threshold": np.array([thr, 0.0, 0.3]),
            "left": np.array([1, ~0, ~2]), "right": np.array([2, ~1, ~3]),
            "leaf_value": np.zeros(4)}
    leaf = ref.route(tree, X)
    tree["leaf_value"], n = ref.newton_leaf_values(
        leaf, g, h, 4, learning_rate=0.1)
    assert n.min() > 100
    if how:
        tree = plant(tree, leaf, g, h, how, learning_rate=0.1)
    cfg = harness.load_cell(CELL)["config"]
    cfg = {**cfg, "expect": {**cfg["expect"], "check_trees": [0]}}
    resolved = types.SimpleNamespace(
        learning_rate=0.1, min_data_in_leaf=20, min_sum_hessian_in_leaf=1e-3,
        lambda_l2=0.0, sigmoid=1.0, lambdarank_truncation_level=30,
        lambdarank_norm=True)
    assert rank.TASK.check_trees(
        _Dumped([tree]), {"X": X, "y": y, "sizes": sizes}, bins, cfg,
        resolved) is correct


# ----------------------------------------------------------------------
# the generator
@pytest.mark.parametrize("queries,documents,longest", [
    (300, 12000, 200), (18919, 2270296, 1251), (50, 50, 9), (7, 63, 9)])
def test_the_lengths_are_one_fixed_list(queries, documents, longest):
    a = mslr.query_lengths(queries, documents, longest)
    b = mslr.query_lengths(queries, documents, longest)
    assert (a == b).all() and len(a) == queries
    assert a.sum() == documents and a.min() >= 1 and a.max() <= longest
    assert (np.diff(a) >= 0).all()
    if queries >= 300:
        assert a[0] == 1 and np.median(a) < a.mean()    # heavy-tailed


def test_every_seed_gives_the_same_work_on_other_rows():
    """Two seeds: the same multiset of lengths in another order, the same
    label shares, other rows."""
    lengths = mslr.query_lengths(300, 12000, 200)
    X1, y1, s1, cuts1 = mslr.make_mslr_like(lengths, 137, 2147483901)
    X2, y2, s2, cuts2 = mslr.make_mslr_like(lengths, 137, 2147483902)
    assert X1.shape == X2.shape == (12000, 137) and X1.dtype == np.float32
    assert sorted(s1) == sorted(s2) == sorted(lengths)
    assert not np.array_equal(s1, s2)
    assert (np.diff(s1) < 0).any()          # shuffled, not ascending
    for y in (y1, y2):
        np.testing.assert_allclose(np.bincount(y.astype(int)) / 12000,
                                   mslr.LABEL_SHARES, atol=1e-3)
    assert np.array_equal(np.bincount(y1.astype(int)),
                          np.bincount(y2.astype(int)))
    assert not np.array_equal(y1, y2)
    assert not np.isin(X1[:50, 0], X2[:, 0]).any()      # other rows
    assert abs(X1.mean()) < 0.01 and abs(X1.std() - 1.0) < 0.01
    # the same seed gives the same set; a second stream of it other rows
    # under the first one's cuts
    X1b, y1b, s1b, _ = mslr.make_mslr_like(lengths, 137, 2147483901)
    assert np.array_equal(X1, X1b) and np.array_equal(y1, y1b) \
        and np.array_equal(s1, s1b)
    Xh, yh, sh, cuts_h = mslr.make_mslr_like(
        lengths[::5], 137, 2147483901, stream=1, cuts=cuts1)
    assert np.array_equal(cuts_h, cuts1) and len(yh) == lengths[::5].sum()
    assert sorted(sh) == sorted(lengths[::5])
    assert not np.isin(Xh[:50, 0], X1[:, 0]).any()
    assert set(np.unique(yh)) <= {0.0, 1.0, 2.0, 3.0, 4.0}


def test_the_draw_does_not_depend_on_the_thread_count(monkeypatch):
    lengths = mslr.query_lengths(40, 3000, 200)
    monkeypatch.setattr(mslr, "CHUNK_ROWS", 512)
    want = mslr.make_mslr_like(lengths, 12, 5)
    monkeypatch.setattr(mslr.os, "cpu_count", lambda: 2)   # one worker
    got = mslr.make_mslr_like(lengths, 12, 5)
    for a, b in zip(want, got):
        assert np.array_equal(a, b)


def test_the_labels_follow_the_features():
    """The rule is learnable: a least-squares line through the features
    ranks held-out queries far better than an unranked list."""
    lengths = mslr.query_lengths(200, 8000, 200)
    X, y, sizes, cuts = mslr.make_mslr_like(lengths, 20, 9)
    Xh, yh, sizes_h, _ = mslr.make_mslr_like(lengths[::4], 20, 9, stream=1,
                                             cuts=cuts)
    w = np.linalg.lstsq(X, y, rcond=None)[0]
    unranked = ref.ndcg_at_k(yh, np.zeros(len(yh)), sizes_h, 10)
    assert ref.ndcg_at_k(yh, Xh @ w, sizes_h, 10) > unranked + 0.2
    assert ref.ndcg_at_k(y, X @ w, sizes, 10) > unranked + 0.2


# ----------------------------------------------------------------------
# the readers
def test_the_readers_read_what_the_runner_and_the_program_give():
    assert objective_device_ms_per_tree.read(
        {"objective_busy_s": 0.5, "window_trees": 20}) == 25.0
    assert objective_device_ms_per_tree.read({"window_trees": 20}) is None
    assert objective_device_ms_per_tree.read(
        {"objective_busy_s": None, "window_trees": 20}) is None
    spans = [{"name": "boosting.init", "attrs": {}},
             {"name": "objective.init",
              "attrs": {"pairs": 30, "pair_slots": 120}}]
    assert objective_pair_fill.read(
        {"kind": "train", "spans": spans}) == 25.0
    # a program without the span (the parent of the PR that added it)
    assert objective_pair_fill.read(
        {"kind": "train", "spans": spans[:1]}) is None
    assert objective_pair_fill.read({"kind": "serve"}) is None


def test_the_scope_is_found_in_a_compiled_programs_text():
    from benchmark import training
    from benchmark.runners import rank
    text = "\n".join([
        'HloModule jit_program',
        '  %fusion.12 = f32[8]{0} fusion(%p), kind=kLoop, calls=%c, '
        'metadata={op_name="jit(program)/while/body/objective.lambdarank'
        '/mul" stack_frame_id=3}',
        '  ROOT %sort.4 = (f32[8]{0}) sort(%a), dimensions={0}, '
        'metadata={op_name="jit(program)/while/body/objective.lambdarank'
        '/sort"}',
        '  %fusion.13 = f32[8]{0} fusion(%p), kind=kLoop, calls=%d, '
        'metadata={op_name="jit(program)/while/body/grow/add"}',
        '  %copy.1 = f32[8]{0} copy(%q)'])
    names = {m.group(1) for m in map(training.INSTRUCTION.match,
                                     text.splitlines())
             if m and rank.OBJECTIVE_SCOPE in m.group(2)}
    assert names == {"fusion.12", "sort.4"}


# ----------------------------------------------------------------------
# what the runner asks of the program before it trains
def test_the_runner_asks_for_the_pair_slots_the_program_will_state():
    from benchmark.runners import rank
    from lightgbm_tpu.observability import registry
    from lightgbm_tpu.objectives_rank import LambdarankNDCG
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.data import Metadata
    sizes = mslr.query_lengths(120, 6000, 400)
    meta = Metadata(6000, label=np.zeros(6000, np.float32), group=sizes)
    LambdarankNDCG(Config({"objective": "lambdarank"})).init(meta, 6000)
    stated = [s["attrs"] for s in registry.trace.spans()
              if s["name"] == "objective.init"][-1]
    assert rank.pair_slots_a_tree(sizes, 30) == stated["pair_slots"]
    # padded to the longest query it would be 120 x 400 x 400
    assert stated["pairs"] < stated["pair_slots"] < 120 * 400 * 400 / 8


def test_a_program_that_cannot_say_is_refused_before_anything_runs(
        monkeypatch):
    """The parent of PR 32 pads every query to the longest and has no
    `pair_slots`: the run ends with an error and no record, at once."""
    from benchmark.runners import rank
    import lightgbm_tpu.objectives_rank as program
    monkeypatch.delattr(program, "pair_slots")
    with pytest.raises(harness.BenchmarkError, match="cannot say"):
        rank.pair_slots_a_tree(mslr.query_lengths(120, 6000, 400), 30)


# ----------------------------------------------------------------------
# the cell (its rehearsal, untraced and traced, is a case of
# test_rehearsals.test_a_cell_rehearses_end_to_end, held to what
# runners/rank.py says it prints and reads)
def test_the_cell_is_the_published_job_on_one_chip():
    cell = harness.load_cell(CELL)
    assert cell["config"]["kind"] == "rank" and cell["chips"] == 1
    assert cell["config_entry"]["reduced"] == ["num_iterations"]
    assert cell["config"]["num_data"] == 2_270_296
    assert cell["config"]["num_features"] == 137
    from benchmark.runners import rank
    reads = rank.TASK.rehearsal_reads(cell)
    assert set(reads) <= {m["name"] for m in cell["per_layer"]}
    # most of a rehearsal's pair slots hold a pair, and none holds two
    assert reads["objective.pair_fill"] == (25, 100)
    assert reads["objective.device_ms_per_tree"] == (0, None)
    says = rank.TASK.rehearsal_says(cell)
    assert "under 300 queries of 1 to 200 documents" in says
    assert "held-out NDCG" in says
