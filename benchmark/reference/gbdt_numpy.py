"""Plain reference for training: one boosting step of binary log-loss
GBDT in NumPy and float64, from the reference's own description
(LightGBM Features.rst / the GBDT paper: histogram-based split finding,
leaf-wise growth, Newton leaf values).

It does not grow a forest at the published size: that is the ten
minutes the chip is for. It checks the trees the program grew, one
boosting step at a time, against what that step has to satisfy:

- the root split the program chose has, recomputed here from the raw
  rows and labels, a gain within `root_gain_rtol` of the best gain any
  (feature, bin boundary) offers in a float64 histogram of the binned
  matrix (the ingest layer's output, which this reference takes as
  given) under the same `min_data_in_leaf` and
  `min_sum_hessian_in_leaf`;
- every leaf's value is the Newton step -G / (H + lambda_l2) times the
  learning rate over the rows that a traversal of the RAW features
  against the model's published thresholds routes to it (plus the
  initial score in tree 0, which LightGBM folds into its leaves).

Gradients for step k come from the scores the model's own trees
0..k-1 give, so an error does not compound from tree to tree. Shares
no code with lightgbm_tpu.learner or lightgbm_tpu.boosting.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Tuple

import numpy as np


def init_score(y: np.ndarray) -> float:
    """BoostFromAverage of the binary objective (sigmoid 1)."""
    p = min(max(float(np.mean(y, dtype=np.float64)), 1e-15), 1 - 1e-15)
    return float(np.log(p / (1.0 - p)))


def grad_hess(score: np.ndarray, y: np.ndarray
              ) -> Tuple[np.ndarray, np.ndarray]:
    p = 1.0 / (1.0 + np.exp(-np.asarray(score, np.float64)))
    return p - y, p * (1.0 - p)


def _leaf_gain(G, H, lambda_l2):
    return G * G / (H + lambda_l2)


def best_root_gain(bins: np.ndarray, grad: np.ndarray, hess: np.ndarray, *,
                   min_data_in_leaf: int, min_sum_hessian_in_leaf: float,
                   lambda_l2: float = 0.0) -> Tuple[float, int, int]:
    """(gain, feature, bin) of the best root split `value's bin <= bin`
    over every feature and bin boundary of the binned matrix."""
    G, H, n = grad.sum(), hess.sum(), len(grad)
    parent = _leaf_gain(G, H, lambda_l2)
    best = (-np.inf, -1, -1)
    for f in range(bins.shape[1]):
        col = np.ascontiguousarray(bins[:, f]).astype(np.intp)
        gl = np.cumsum(np.bincount(col, weights=grad))[:-1]
        hl = np.cumsum(np.bincount(col, weights=hess))[:-1]
        cl = np.cumsum(np.bincount(col))[:-1]
        ok = ((cl >= min_data_in_leaf) & (n - cl >= min_data_in_leaf) &
              (hl >= min_sum_hessian_in_leaf) &
              (H - hl >= min_sum_hessian_in_leaf))
        if not ok.any():
            continue
        with np.errstate(divide="ignore", invalid="ignore"):
            gain = _leaf_gain(gl, hl, lambda_l2) + \
                _leaf_gain(G - gl, H - hl, lambda_l2) - parent
        gain = np.where(ok, gain, -np.inf)
        b = int(np.argmax(gain))
        if gain[b] > best[0]:
            best = (float(gain[b]), f, b)
    return best


def gain_of_split(x: np.ndarray, threshold: float, grad: np.ndarray,
                  hess: np.ndarray, lambda_l2: float = 0.0) -> float:
    """Gain of `x <= threshold` at the root, from the raw column."""
    left = np.asarray(x, np.float64) <= threshold
    gl, hl = grad[left].sum(), hess[left].sum()
    G, H = grad.sum(), hess.sum()
    return float(_leaf_gain(gl, hl, lambda_l2) +
                 _leaf_gain(G - gl, H - hl, lambda_l2) -
                 _leaf_gain(G, H, lambda_l2))


def flatten_tree(structure: Dict) -> Dict[str, np.ndarray]:
    """`Booster.dump_model()["tree_info"][k]["tree_structure"]` (nested
    dicts, the public JSON form) as arrays: node i has feature[i],
    threshold[i], left[i], right[i]; a negative child c is leaf ~c."""
    feature, threshold, left, right = [], [], [], []
    leaf_value: Dict[int, float] = {}

    def visit(node) -> int:
        if "leaf_index" in node or "split_index" not in node:
            k = int(node.get("leaf_index", 0))
            leaf_value[k] = float(node["leaf_value"])
            return ~k
        if node["decision_type"] != "<=":
            raise ValueError("only numerical splits are in the reference")
        i = len(feature)
        feature.append(int(node["split_feature"]))
        threshold.append(float(node["threshold"]))
        left.append(0)
        right.append(0)
        left[i] = visit(node["left_child"])
        right[i] = visit(node["right_child"])
        return i

    visit(structure)
    return {"feature": np.asarray(feature, np.int64),
            "threshold": np.asarray(threshold, np.float64),
            "left": np.asarray(left, np.int64),
            "right": np.asarray(right, np.int64),
            "leaf_value": np.asarray(
                [leaf_value[k] for k in range(len(leaf_value))],
                np.float64)}


def route(tree: Dict[str, np.ndarray], X: np.ndarray) -> np.ndarray:
    """Leaf index of every row of X (raw features, no missing values).
    Above a million rows the rows go to a few threads in contiguous
    slices (NumPy's gathers release the interpreter lock): 10.5M rows
    took 14 to 22 s a tree in one thread (my chip run, PR 22)."""
    if len(X) > 1_000_000:
        cuts = np.linspace(0, len(X), 9).astype(np.int64)
        with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
            return np.concatenate(list(pool.map(
                lambda k: route(tree, X[cuts[k]:cuts[k + 1]]), range(8))))
    if not len(tree["feature"]):
        return np.zeros(len(X), np.int64)
    node = np.zeros(len(X), np.int64)
    rows = np.arange(len(X))
    while True:
        live = np.flatnonzero(node >= 0)
        if not len(live):
            return ~node
        idx = node[live]
        go_left = X[rows[live], tree["feature"][idx]] <= \
            tree["threshold"][idx]
        node[live] = np.where(go_left, tree["left"][idx],
                              tree["right"][idx])


def newton_leaf_values(leaf: np.ndarray, grad: np.ndarray, hess: np.ndarray,
                       num_leaves: int, *, learning_rate: float,
                       lambda_l2: float = 0.0, bias: float = 0.0
                       ) -> Tuple[np.ndarray, np.ndarray]:
    """(values, rows per leaf): learning_rate * -G/(H + lambda_l2) + bias."""
    G = np.bincount(leaf, weights=grad, minlength=num_leaves)
    H = np.bincount(leaf, weights=hess, minlength=num_leaves)
    n = np.bincount(leaf, minlength=num_leaves)
    with np.errstate(divide="ignore", invalid="ignore"):
        values = -G / (H + lambda_l2) * learning_rate + bias
    return values, n


def auc(y: np.ndarray, score: np.ndarray) -> float:
    """Area under the ROC curve by ranks (ties get their mean rank)."""
    y = np.asarray(y) > 0.5
    score = np.asarray(score, np.float64)
    order = np.argsort(score, kind="mergesort")
    ranks = np.empty(len(score), np.float64)
    ranks[order] = np.arange(1, len(score) + 1)
    sorted_scores = score[order]
    # mean rank over runs of equal scores
    edges = np.flatnonzero(np.diff(sorted_scores)) + 1
    starts = np.concatenate([[0], edges])
    ends = np.concatenate([edges, [len(score)]])
    mean_rank = (starts + 1 + ends) / 2.0
    ranks[order] = np.repeat(mean_rank, ends - starts)
    pos = int(y.sum())
    neg = len(y) - pos
    if not pos or not neg:
        raise ValueError("AUC needs both classes")
    return float((ranks[y].sum() - pos * (pos + 1) / 2.0) / (pos * neg))


def check_step(k: int, trees: List[Dict[str, np.ndarray]], X: np.ndarray,
               y: np.ndarray, bins: np.ndarray, *, learning_rate: float,
               min_data_in_leaf: int, min_sum_hessian_in_leaf: float,
               lambda_l2: float = 0.0,
               routed: Optional[Dict[int, np.ndarray]] = None
               ) -> Dict[str, float]:
    """Boosting step k of the model against this reference. `trees` are
    the flattened trees 0..k. Returns the two errors the configuration's
    `expect` bounds, and what they were taken from. `routed` keeps each
    tree's leaf assignment from one step to the next.

    `leaf_sum_err_root_ulps`: the program sums gradients in float32 and
    gets a child's histogram as its parent's less its sibling's, so a
    leaf's sums carry an ABSOLUTE error that scales with the root's
    sums, however few rows the leaf holds (measured on the CPU path at
    20,000 rows: 33 roundings of the root's sum, 2e-3 of a 475-row
    leaf's value). The error of each leaf value is therefore taken back
    to the gradient sum it implies, |value - reference| * (H + lambda) /
    learning_rate, and counted in float32 roundings (eps = 2^-23) of
    sum|g| + |G/H| * sum(h) over all rows. A bfloat16 ACCUMULATION is
    2^16 times that; bfloat16 rounding of each row's gradient before an
    exact sum is not, and below this program's own noise at millions of
    rows: this check cannot see it."""
    routed = {} if routed is None else routed
    for j in range(k + 1):
        if j not in routed:
            routed[j] = route(trees[j], X)
    bias = init_score(y)
    if k == 0:
        score = np.full(len(y), bias, np.float64)
    else:
        score = np.zeros(len(y), np.float64)
        for j in range(k):
            score += trees[j]["leaf_value"][routed[j]]
    grad, hess = grad_hess(score, y)
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
        learning_rate=learning_rate, lambda_l2=lambda_l2,
        bias=bias if k == 0 else 0.0)
    err = np.abs(tree["leaf_value"] - want)
    H = np.bincount(leaf, weights=hess, minlength=len(want))
    # the error as one of the leaf's gradient SUM, in units of float32
    # rounding of the root's sums: see leaf_sum_err_root_ulps below
    in_sum = err * (H + lambda_l2) / learning_rate
    scale = np.abs(grad).sum() + np.abs(want - (bias if k == 0 else 0.0)) \
        / learning_rate * hess.sum()
    ulps = np.where(n > 0, in_sum / (np.finfo(np.float32).eps * scale), 0.0)
    worst = int(np.argmax(ulps))
    return {"tree": k, "root_gain_model": got, "root_gain_best": best,
            "root_gain_shortfall": (best - got) / abs(best),
            "best_root": [bf, bb],
            "model_root": [int(tree["feature"][0]),
                           float(tree["threshold"][0])],
            "leaves": int(len(want)), "empty_leaves": int((n == 0).sum()),
            "smallest_leaf_rows": int(n.min()),
            "leaf_value_max_abs_err": float(err[n > 0].max()),
            "leaf_value_max_rel_err": float(
                (err[n > 0] / np.abs(want[n > 0])).max()),
            "leaf_sum_err_root_ulps": float(ulps.max()),
            "worst_leaf": {"leaf": worst, "rows": int(n[worst]),
                           "model": float(tree["leaf_value"][worst]),
                           "reference": float(want[worst])}}
