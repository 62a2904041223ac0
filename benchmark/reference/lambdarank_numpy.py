"""Plain reference for a ranking job: the LambdaRank-NDCG gradients and
NDCG@k in NumPy and float64, following the reference's
`src/objective/rank_objective.hpp` (LambdarankNDCG) and
`src/metric/dcg_calculator.cpp` as published:

- a query's documents are ordered by a STABLE sort, descending by score
  (equal scores keep document order);
- `inverse_max_dcg` is 1 over the DCG of the query's labels sorted
  descending, cut at the truncation level, gain 2^label - 1, discount
  1 / log2(rank + 2); 0 where that DCG is 0;
- for every i < min(cnt - 1, truncation_level) and j > i in sorted
  order whose labels differ, with `high` the one of the larger LABEL:
  delta = score_high - score_low; dNDCG = (gain_high - gain_low) *
  |discount(rank_high) - discount(rank_low)| * inverse_max_dcg; with
  `norm` and best score != worst score, dNDCG /= 0.01 + |delta|;
  p = 1 / (1 + exp(sigmoid * delta)); lambda = -sigmoid * dNDCG * p;
  hess = sigmoid^2 * dNDCG * p * (1 - p); g[high] += lambda, g[low] -=
  lambda, h[high] += hess, h[low] += hess, sum -= 2 * lambda;
- with `norm` and sum > 0 the query's g and h are scaled by
  log2(1 + sum) / sum.

One Python loop over queries; the two loops over pairs are one
[rows, cnt] array a query, which
tests/benchmark_harness/test_rank_cell.py holds to the two loops spelled
out. Departures from the reference: the exact exponential where it
reads a 1M-entry sigmoid table; the initial score is 0 (the ranking
objectives do not boost from an average).

For the tree checks it imports the split search, the routing and the
Newton step of `gbdt_numpy` and brings its own `check_step` with these
gradients. Shares no code with lightgbm_tpu.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from .gbdt_numpy import (best_root_gain, gain_of_split, newton_leaf_values,
                         route)


def _discount(rank: np.ndarray) -> np.ndarray:
    return 1.0 / np.log2(np.asarray(rank, np.float64) + 2.0)


def _gain(label: np.ndarray) -> np.ndarray:
    return 2.0 ** np.asarray(label, np.float64) - 1.0


def inverse_max_dcg(label: np.ndarray, k: int) -> float:
    top = np.sort(_gain(label))[::-1][:k]
    dcg = float((top * _discount(np.arange(len(top)))).sum())
    return 1.0 / dcg if dcg > 0 else 0.0


def _one_query(score, label, *, sigmoid, truncation_level, norm):
    cnt = len(score)
    g, h = np.zeros(cnt), np.zeros(cnt)
    rows = min(cnt - 1, truncation_level)
    if rows <= 0:
        return g, h
    order = np.argsort(-score, kind="stable")
    s, lbl = score[order], label[order]
    gain, disc = _gain(lbl), _discount(np.arange(cnt))
    inv = inverse_max_dcg(label, truncation_level)
    i, j = np.arange(rows)[:, None], np.arange(cnt)[None, :]
    pair = (j > i) & (lbl[:rows, None] != lbl[None, :])
    i_high = lbl[:rows, None] > lbl[None, :]
    delta = np.where(i_high, 1.0, -1.0) * (s[:rows, None] - s[None, :])
    dndcg = np.abs(gain[:rows, None] - gain[None, :]) * \
        np.abs(disc[:rows, None] - disc[None, :]) * inv
    if norm and s[0] != s[-1]:
        dndcg = dndcg / (0.01 + np.abs(delta))
    with np.errstate(over="ignore"):
        p = 1.0 / (1.0 + np.exp(sigmoid * delta))
    lam = np.where(pair, -sigmoid * dndcg * p, 0.0)
    hes = np.where(pair, sigmoid * sigmoid * dndcg * p * (1.0 - p), 0.0)
    to_i = np.where(i_high, lam, -lam)            # what the pair gives i
    gs = -to_i.sum(axis=0)
    gs[:rows] += to_i.sum(axis=1)
    hs = hes.sum(axis=0)
    hs[:rows] += hes.sum(axis=1)
    total = -2.0 * lam.sum()
    if norm and total > 0:
        gs *= np.log2(1.0 + total) / total
        hs *= np.log2(1.0 + total) / total
    g[order], h[order] = gs, hs
    return g, h


def lambdarank_gradients(score: np.ndarray, label: np.ndarray,
                         sizes: np.ndarray, *, sigmoid: float = 1.0,
                         truncation_level: int = 30, norm: bool = True
                         ) -> Tuple[np.ndarray, np.ndarray]:
    """(gradients, hessians), float64 [documents], of documents grouped
    into consecutive queries of `sizes`."""
    score = np.asarray(score, np.float64)
    label = np.asarray(label, np.float64)
    g, h = np.empty(len(score)), np.empty(len(score))
    lo = 0
    for cnt in np.asarray(sizes, np.int64).tolist():
        g[lo:lo + cnt], h[lo:lo + cnt] = _one_query(
            score[lo:lo + cnt], label[lo:lo + cnt], sigmoid=sigmoid,
            truncation_level=truncation_level, norm=norm)
        lo += cnt
    if lo != len(score):
        raise ValueError("sizes sum to %d, there are %d documents"
                         % (lo, len(score)))
    return g, h


def ndcg_at_k(label: np.ndarray, score: np.ndarray, sizes: np.ndarray,
              k: int) -> float:
    """Mean NDCG@k over the queries (dcg_calculator.cpp: stable sort by
    score, gain 2^label - 1, discount 1 / log2(rank + 2)); a query with
    no relevant document counts 1, as the reference's metric does."""
    label = np.asarray(label, np.float64)
    score = np.asarray(score, np.float64)
    total, lo = 0.0, 0
    sizes = np.asarray(sizes, np.int64).tolist()
    for cnt in sizes:
        lb, sc = label[lo:lo + cnt], score[lo:lo + cnt]
        lo += cnt
        inv = inverse_max_dcg(lb, k)
        if inv == 0.0:
            total += 1.0
            continue
        top = lb[np.argsort(-sc, kind="stable")[:k]]
        total += float((_gain(top) * _discount(np.arange(len(top)))).sum()) \
            * inv
    return total / len(sizes)


def check_step(k: int, trees: List[Dict[str, np.ndarray]], X: np.ndarray,
               y: np.ndarray, sizes: np.ndarray, bins: np.ndarray, *,
               learning_rate: float, min_data_in_leaf: int,
               min_sum_hessian_in_leaf: float, lambda_l2: float = 0.0,
               sigmoid: float = 1.0, truncation_level: int = 30,
               norm: bool = True,
               routed: Optional[Dict[int, np.ndarray]] = None
               ) -> Dict[str, float]:
    """Boosting step k of a lambdarank model against this reference, as
    `gbdt_numpy.check_step` does for binary log-loss and in its units.
    `trees` are the flattened trees 0..k; the scores of step k are the
    model's own trees 0..k-1 over the raw features, from 0. Step 0 has
    every score equal, so its gradients follow document order alone and
    check the stable sort's tie order; step 1 has as many distinct scores
    as tree 0 has leaves, so it checks ties inside queries.

    `leaf_sum_err_root_ulps` is the error of a leaf value taken back to
    the gradient sum it implies, in float32 roundings of the root's sums
    (gbdt_numpy says why that is the unit). Here it also holds what the
    program's float32 exponentials, divisions and per-query sums put on
    each gradient, which a sum over a leaf's rows partly averages out."""
    routed = {} if routed is None else routed
    for j in range(k + 1):
        if j not in routed:
            routed[j] = route(trees[j], X)
    score = np.zeros(len(y), np.float64)
    for j in range(k):
        score += trees[j]["leaf_value"][routed[j]]
    grad, hess = lambdarank_gradients(
        score, y, sizes, sigmoid=sigmoid, truncation_level=truncation_level,
        norm=norm)
    tree = trees[k]
    best, bf, bb = best_root_gain(
        bins, grad, hess, min_data_in_leaf=min_data_in_leaf,
        min_sum_hessian_in_leaf=min_sum_hessian_in_leaf,
        lambda_l2=lambda_l2)
    got = gain_of_split(X[:, tree["feature"][0]], tree["threshold"][0],
                        grad, hess, lambda_l2)
    leaf = routed[k]
    want, n = newton_leaf_values(
        leaf, grad, hess, len(tree["leaf_value"]),
        learning_rate=learning_rate, lambda_l2=lambda_l2)
    err = np.abs(tree["leaf_value"] - want)
    H = np.bincount(leaf, weights=hess, minlength=len(want))
    in_sum = err * (H + lambda_l2) / learning_rate
    scale = np.abs(grad).sum() + np.abs(want) / learning_rate * hess.sum()
    with np.errstate(invalid="ignore"):
        ulps = np.where(n > 0, in_sum / (np.finfo(np.float32).eps * scale),
                        0.0)
    worst = int(np.nanargmax(ulps))
    return {"tree": k, "root_gain_model": got, "root_gain_best": best,
            "root_gain_shortfall": (best - got) / abs(best),
            "best_root": [bf, bb],
            "model_root": [int(tree["feature"][0]),
                           float(tree["threshold"][0])],
            "leaves": int(len(want)), "empty_leaves": int((n == 0).sum()),
            "smallest_leaf_rows": int(n.min()),
            "distinct_scores": int(len(np.unique(score))),
            "leaf_value_max_abs_err": float(err[n > 0].max()),
            "leaf_sum_err_root_ulps": float(np.nanmax(ulps)),
            "worst_leaf": {"leaf": worst, "rows": int(n[worst]),
                           "model": float(tree["leaf_value"][worst]),
                           "reference": float(want[worst])}}
