"""The ranking objectives over UNEVEN queries: the bucketed layout of
lightgbm_tpu/objectives_rank.py against the plain reference
(benchmark/reference/lambdarank_numpy.py, float64), against a float64
transcription of XE-NDCG, and against the dense layout it replaced
(every query padded to the longest, full [L, L] pair tensors), which is
kept HERE as an oracle.

Tolerance: the program computes in float32 (exponentials, divisions,
sums over up to 30 x L pairs), the reference in float64, so a document's
lambda may differ by float32 rounding of the LARGEST terms that met in
its query: 1e-5 of the query's largest |lambda| (measured 2e-7 to 9e-7
on these cases).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu import objectives_rank
from lightgbm_tpu.config import Config
from lightgbm_tpu.objectives_rank import (LambdarankNDCG, RankXENDCG,
                                          bucket_queries, reference_pairs)

from benchmark.reference import lambdarank_numpy

RTOL_OF_QUERY_MAX = 1e-5


class _Meta:
    weight = None

    def __init__(self, sizes, label):
        self.query_boundaries = np.concatenate([[0], np.cumsum(sizes)])
        self.label = label


def _edge_sizes():
    return np.array([1, 2, 29, 30, 31, 200, 7, 8, 9, 64, 65, 1, 33])


def _heavy_tailed_sizes():
    rng = np.random.RandomState(3)
    return np.minimum(np.ceil(rng.pareto(1.3, 60) * 12 + 1), 400).astype(int)


SIZES = {"edges": _edge_sizes, "heavy_tailed": _heavy_tailed_sizes}


def _case(sizes, seed=0):
    """Labels 0-4 with one query of equal labels; scores rounded to one
    decimal, so every longer query holds ties."""
    rng = np.random.RandomState(seed)
    n = int(sizes.sum())
    meta = _Meta(sizes, rng.randint(0, 5, n).astype(np.float32))
    q = int(np.argmax(sizes >= 29))
    lo, hi = meta.query_boundaries[q], meta.query_boundaries[q + 1]
    meta.label[lo:hi] = 2.0
    score = np.round(rng.randn(n), 1).astype(np.float32)
    return meta, score, n


def _assert_close_by_query(got, want, sizes):
    lo = 0
    for cnt in sizes:
        g, w = got[lo:lo + cnt], want[lo:lo + cnt]
        scale = max(np.abs(w).max(), 1e-30)
        assert np.abs(g - w).max() <= RTOL_OF_QUERY_MAX * scale, \
            (lo, cnt, np.abs(g - w).max(), scale)
        lo += cnt


# ----------------------------------------------------------------------
# the dense layout this PR replaced, as an oracle
def _dense_lambdarank(meta, score, n, cfg):
    """Every query padded to the longest, full [L, L] pairs, one query
    at a time (the code of objectives_rank.py before the buckets, less
    its batching)."""
    sizes = np.diff(meta.query_boundaries)
    lmax = int(sizes.max())
    sig = float(cfg.sigmoid)
    trunc = int(cfg.lambdarank_truncation_level)
    gain_tbl = jnp.asarray(2.0 ** np.arange(32) - 1.0, jnp.float32)

    def per_query(labels, scores, valid, inv_max_dcg):
        sc = jnp.where(valid, scores, -1e30)
        order = jnp.argsort(-sc, stable=True)
        s_lbl = labels[order].astype(jnp.int32)
        s_sc = sc[order]
        s_valid = valid[order]
        n_valid = jnp.sum(s_valid.astype(jnp.int32))
        gains = gain_tbl[s_lbl]
        ranks = jnp.arange(lmax)
        discount = 1.0 / jnp.log2(ranks + 2.0)
        best = s_sc[0]
        worst = s_sc[jnp.maximum(n_valid - 1, 0)]
        pair_ok = (ranks[:, None] < ranks[None, :]) & \
            s_valid[:, None] & s_valid[None, :] & \
            (ranks[:, None] < trunc) & (s_lbl[:, None] != s_lbl[None, :])
        hi_is_i = s_lbl[:, None] > s_lbl[None, :]
        hi_sc = jnp.where(hi_is_i, s_sc[:, None], s_sc[None, :])
        lo_sc = jnp.where(hi_is_i, s_sc[None, :], s_sc[:, None])
        delta_score = hi_sc - lo_sc
        delta_ndcg = jnp.abs(gains[:, None] - gains[None, :]) * \
            jnp.abs(discount[:, None] - discount[None, :]) * inv_max_dcg
        if cfg.lambdarank_norm:
            delta_ndcg = jnp.where(
                best != worst,
                delta_ndcg / (0.01 + jnp.abs(delta_score)), delta_ndcg)
        p = 1.0 / (1.0 + jnp.exp(jnp.clip(delta_score * sig, -100., 100.)))
        p_lambda = jnp.where(pair_ok, -sig * delta_ndcg * p, 0.0)
        p_hess = jnp.where(pair_ok, p * (1.0 - p) * sig * sig * delta_ndcg,
                           0.0)
        lam = jnp.sum(jnp.where(hi_is_i, p_lambda, -p_lambda), axis=1) + \
            jnp.sum(jnp.where(hi_is_i, -p_lambda, p_lambda), axis=0)
        hes = jnp.sum(p_hess, axis=1) + jnp.sum(p_hess, axis=0)
        total = -2.0 * jnp.sum(p_lambda)
        if cfg.lambdarank_norm:
            factor = jnp.where(total > 0, jnp.log2(1.0 + total) /
                               jnp.maximum(total, 1e-30), 1.0)
            lam, hes = lam * factor, hes * factor
        return (jnp.zeros(lmax).at[order].set(lam),
                jnp.zeros(lmax).at[order].set(hes))

    per_query = jax.jit(per_query)
    g, h = np.zeros(n), np.zeros(n)
    for q, cnt in enumerate(sizes):
        lo = int(meta.query_boundaries[q])
        lab = np.zeros(lmax, np.float32)
        sc = np.zeros(lmax, np.float32)
        lab[:cnt], sc[:cnt] = meta.label[lo:lo + cnt], score[lo:lo + cnt]
        inv = lambdarank_numpy.inverse_max_dcg(meta.label[lo:lo + cnt],
                                               trunc)
        lam, hes = per_query(lab, sc, np.arange(lmax) < cnt,
                             np.float32(inv))
        g[lo:lo + cnt], h[lo:lo + cnt] = lam[:cnt], hes[:cnt]
    return g, h


def _xendcg_float64(label, score, uniform, sizes):
    """rank_objective.hpp:301-355 in float64, with the draws given."""
    g, h = np.zeros(len(score)), np.zeros(len(score))
    lo = 0
    for cnt in sizes:
        s = slice(lo, lo + cnt)
        lo += cnt
        if cnt <= 1:
            continue
        sc = score[s].astype(np.float64)
        rho = np.exp(sc - sc.max())
        rho /= rho.sum()
        phi = 2.0 ** label[s].astype(np.float64) - uniform[s]
        l1 = -phi / max(phi.sum(), 1e-15) + rho
        p1 = l1 / (1.0 - rho + 1e-15)
        l2 = rho * (p1.sum() - p1)
        p2 = l2 / (1.0 - rho + 1e-15)
        g[s] = l1 + l2 + rho * (p2.sum() - p2)
        h[s] = rho * (1.0 - rho)
    return g, h


# ----------------------------------------------------------------------
@pytest.mark.parametrize("sizes", sorted(SIZES))
@pytest.mark.parametrize("norm", [True, False], ids=["norm", "raw"])
@pytest.mark.parametrize("truncation", [5, 30])
def test_lambdarank_against_the_plain_reference(sizes, norm, truncation):
    sizes = SIZES[sizes]()
    meta, score, n = _case(sizes)
    obj = LambdarankNDCG(Config({
        "objective": "lambdarank", "lambdarank_norm": norm,
        "lambdarank_truncation_level": truncation}))
    obj.init(meta, n)
    g, h = (np.asarray(a, np.float64)
            for a in obj.get_gradients(jnp.asarray(score)))
    gr, hr = lambdarank_numpy.lambdarank_gradients(
        score, meta.label, sizes, truncation_level=truncation, norm=norm)
    _assert_close_by_query(g, gr, sizes)
    _assert_close_by_query(h, hr, sizes)
    # the query of equal labels and the queries of one document have no
    # pairs: exactly zero, not small
    q = int(np.argmax(sizes >= 29))
    lo, hi = meta.query_boundaries[q], meta.query_boundaries[q + 1]
    assert not g[lo:hi].any() and not h[lo:hi].any()
    for q in np.flatnonzero(sizes == 1):
        assert g[meta.query_boundaries[q]] == 0.0


@pytest.mark.parametrize("sizes", sorted(SIZES))
@pytest.mark.parametrize("norm,truncation", [(True, 30), (False, 5)],
                         ids=["defaults", "raw_top5"])
def test_the_buckets_give_what_the_dense_layout_gave(sizes, norm,
                                                     truncation):
    sizes = SIZES[sizes]()
    meta, score, n = _case(sizes, seed=1)
    cfg = Config({"objective": "lambdarank", "lambdarank_norm": norm,
                  "lambdarank_truncation_level": truncation})
    obj = LambdarankNDCG(cfg)
    obj.init(meta, n)
    g, h = (np.asarray(a, np.float64)
            for a in obj.get_gradients(jnp.asarray(score)))
    gd, hd = _dense_lambdarank(meta, score, n, cfg)
    _assert_close_by_query(g, gd, sizes)
    _assert_close_by_query(h, hd, sizes)


@pytest.mark.parametrize("sizes", sorted(SIZES))
def test_a_bucket_in_several_batches_gives_the_same(sizes, monkeypatch):
    sizes = SIZES[sizes]()
    meta, score, n = _case(sizes, seed=2)
    cfg = Config({"objective": "lambdarank"})
    whole = LambdarankNDCG(cfg)
    whole.init(meta, n)
    assert all(b.batches == 1 for b in whole.plan.buckets)
    # room for 30 x 32 x 2 elements: two queries at a time at length
    # 32, one at a time beyond
    monkeypatch.setattr(objectives_rank, "_PAIR_ELEMS", 30 * 32 * 2)
    split = LambdarankNDCG(cfg)
    split.init(meta, n)
    assert any(b.batches > 1 for b in split.plan.buckets)
    # the same numbers up to the order XLA adds them in inside a loop
    for a, b in zip(whole.get_gradients(jnp.asarray(score)),
                    split.get_gradients(jnp.asarray(score))):
        _assert_close_by_query(np.asarray(b, np.float64),
                               np.asarray(a, np.float64), sizes)


@pytest.mark.parametrize("sizes", sorted(SIZES))
def test_xendcg_against_a_float64_transcription(sizes):
    sizes = SIZES[sizes]()
    meta, score, n = _case(sizes, seed=4)
    obj = RankXENDCG(Config({"objective": "rank_xendcg", "seed": 11}))
    obj.init(meta, n)
    # the draws of the first call, a document's taken from its slot
    key = jax.random.fold_in(jax.random.PRNGKey(11), 0)
    slot_uniform = np.asarray(jax.random.uniform(
        key, obj.slot_doc.shape, jnp.float32, 1e-7, 1.0), np.float64)
    uniform = np.zeros(n + 1)
    uniform[np.asarray(obj.slot_doc)] = slot_uniform
    g, h = (np.asarray(a, np.float64)
            for a in obj.get_gradients(jnp.asarray(score)))
    gr, hr = _xendcg_float64(meta.label, score, uniform[:n], sizes)
    _assert_close_by_query(g, gr, sizes)
    _assert_close_by_query(h, hr, sizes)


@pytest.mark.parametrize("pair_rows", [1, 5, 30])
def test_the_layout_holds_every_document_once(pair_rows):
    sizes = _heavy_tailed_sizes()
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    n = int(bounds[-1])
    buckets, slot_doc = bucket_queries(bounds, pair_rows)
    assert sorted(slot_doc[slot_doc < n]) == list(range(n))
    assert len(buckets) <= 9 and len(slot_doc) < 2 * n + 8 * len(sizes)
    for b in buckets:
        rows = slot_doc[b.slot0:b.slot0 + b.rows * b.length].reshape(
            b.rows, b.length)
        live = rows < n
        # a query is a run of consecutive documents at the row's start
        assert (np.diff(rows, axis=1)[live[:, 1:]] == 1).all()
        assert (live[:, :-1] >= live[:, 1:]).all()
        cnt = live.sum(axis=1)
        assert ((cnt == 0) | (cnt > b.length // 2) |
                (b.length == 8)).all() and (cnt <= b.length).all()
    # the pairs the reference visits, against a literal count
    want = sum(1 for c in sizes for i in range(min(c - 1, pair_rows))
               for _ in range(i + 1, c))
    assert reference_pairs(sizes, pair_rows) == want


def _ranking_set(seed, sizes):
    rng = np.random.RandomState(seed)
    n = int(sizes.sum())
    X = rng.randn(n, 6).astype(np.float32)
    raw = X[:, 0] - 0.7 * X[:, 1] + 0.5 * X[:, 2] * X[:, 3] + \
        0.8 * rng.randn(n)
    y = np.digitize(raw, np.quantile(raw, [0.5, 0.75, 0.9, 0.97]))
    return X, y.astype(np.float32)


def _built(fun):
    from lightgbm_tpu.observability import registry
    return registry.compiles.snapshot().get(fun, {}).get("built", 0)


@pytest.mark.parametrize("objective", ["lambdarank", "rank_xendcg"])
def test_permuted_lengths_build_no_second_program(objective):
    """Two data sets whose query lengths are one multiset in two orders
    share one layout plan, so the second builds nothing new for its
    gradients: the number of programs does not move with the seed."""
    # lengths no other test of this process has laid out
    sizes = np.concatenate([_heavy_tailed_sizes(), [3, 77, 5]])
    plans = []
    before = _built("objective_gradients")
    for seed in (0, 1):
        order = np.random.RandomState(seed).permutation(sizes)
        X, y = _ranking_set(seed, order)
        bst = lgb.train({"objective": objective, "num_leaves": 7,
                         "min_data_in_leaf": 5, "verbosity": -1},
                        lgb.Dataset(X, label=y, group=order), 2)
        plans.append(bst.gbdt.objective.plan)
        if seed == 0:
            first = _built("objective_gradients") - before
    assert plans[0] == plans[1] and hash(plans[0]) == hash(plans[1])
    assert first == 1
    assert _built("objective_gradients") - before == 1


def test_the_fused_program_takes_the_layout_as_arguments():
    """The tables are operands of the fused block's program, not values
    its trace closes over (a closed-over table is a literal in the
    program: a copy of it, and a cache key that moves with the data)."""
    sizes = _heavy_tailed_sizes()
    X, y = _ranking_set(5, sizes)
    bst = lgb.Booster(params={"objective": "lambdarank", "num_leaves": 7,
                              "verbosity": -1},
                      train_set=lgb.Dataset(X, label=y, group=sizes))
    g = bst.gbdt
    g._hist_impl = "mxu"
    g._mxu_interpret = True
    run = g._build_fused()
    obj = g.objective
    tables = run.operands[2]
    assert [t.shape for t in tables] == \
        [getattr(obj, name).shape for name in obj.table_state]
    assert len(tables) == 4 and tables[0] is obj.slot_doc
    traced = run.program.trace(*run.arguments(g.train_score, 0, k=2))
    slots = int(obj.slot_doc.shape[0])
    assert not [c for c in traced.jaxpr.consts
                if getattr(c, "size", 0) >= slots]


def test_fused_blocks_equal_per_iteration_on_a_ranking_set():
    # tier 1 at a size that allows it: the MXU grower in interpret mode,
    # 16 uneven queries, one fused block of two trees against two updates
    sizes = _heavy_tailed_sizes()[:16]
    X, y = _ranking_set(6, sizes)

    def booster():
        bst = lgb.Booster(
            params={"objective": "lambdarank", "num_leaves": 7,
                    "min_data_in_leaf": 5, "verbosity": -1},
            train_set=lgb.Dataset(X, label=y, group=sizes,
                                  params={"max_bin": 31}))
        bst.gbdt._hist_impl = "mxu"
        bst.gbdt._mxu_interpret = True
        return bst

    a, b = booster(), booster()
    a.update_batch(2)
    for _ in range(2):
        b.update()
    assert a.current_iteration() == b.current_iteration() == 2
    # the same trees: every split the same, and the leaf values to
    # float32 rounding (inside the scan XLA fuses the objective's sums
    # with other neighbours and adds them in another order: 2e-7)
    ta, tb = (x.dump_model()["tree_info"] for x in (a, b))

    def walk(node, out):
        if "split_index" in node:
            out.append((node["split_feature"], node["threshold"]))
            walk(node["left_child"], out)
            walk(node["right_child"], out)
        else:
            out.append(node["leaf_value"])
        return out

    for x, y_ in zip(ta, tb):
        wa, wb = walk(x["tree_structure"], []), walk(y_["tree_structure"], [])
        assert len(wa) == len(wb)
        for p, q in zip(wa, wb):
            if isinstance(p, tuple):
                assert p == q
            else:
                assert abs(p - q) <= 1e-5 * max(abs(p), 1e-3)
    np.testing.assert_allclose(np.asarray(a.gbdt.train_score),
                               np.asarray(b.gbdt.train_score), atol=2e-6)
