"""Plain reference for training on data with missing values: one
boosting step of binary log-loss GBDT in NumPy and float64, with the
published rule for a column that has missing values written out from
its description (LightGBM Advanced-Topics.rst, "Missing Value Handle":
missing values go to whichever side reduces the loss more; the
parameters `use_missing` and `zero_as_missing` in Parameters.rst;
SURVEY.md places the scan at
`FeatureHistogram::FindBestThresholdSequentially`,
feature_histogram.hpp:85-270).

The rule, for one numerical column with a NaN bin (the last bin, which
every missing value of the column lands in) at one node
(`missing_rule`):

- the reverse scan: the missing values go LEFT with the bins up to the
  threshold, for thresholds 0 .. num_bins - 3 (a bin with values stays
  on the right); the forward scan: they go RIGHT, for thresholds 0 ..
  num_bins - 2 (at the last, the right side is the missing rows alone);
- a threshold counts only where both sides hold `min_data_in_leaf` rows
  and `min_sum_hessian_in_leaf`, and its gain has to beat the unsplit
  node's;
- the reverse scan runs first and walks from the high thresholds down,
  the forward scan from the low ones up, and the first strictly larger
  gain wins: a tie goes to the reverse scan (missing left) at its
  highest threshold.

A column without a NaN bin is searched forward alone, missing values
having none to go by. The model routes a RAW missing value by its
node's `default_left` (missing_type "NaN"); that is how `route` sends
every row.

Departures from the C++, each deliberate: counts are the rows' own;
the 1e-15 the reference adds to hessian sums is left out; no L1, no
max_delta_step, no path smoothing, no monotone constraints, no
extra_trees (the configuration uses none).

`check_step` does what `gbdt_numpy.check_step` does (root gain against
the best over ALL columns and both directions, leaf values against
Newton steps over the rows a traversal of the raw features routes to
each leaf) and, at every node that splits on a column with a NaN bin,
holds the program's choice against the rule on that node's own float64
histogram of that column: its direction has to be the better of the two
at its threshold, and its gain within a tolerance of the best the rule
finds for the column. The histograms are built from the program's
binned matrix, so `check_step` first holds that matrix to the raw
values at every threshold the model wrote (`binning_disagreements`).
Shares no code with lightgbm_tpu.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Tuple

import numpy as np

from .gbdt_numpy import auc, grad_hess, init_score  # noqa: F401

EPS32 = float(np.finfo(np.float32).eps)


def _leaf_gain(G, H, l2):
    with np.errstate(divide="ignore", invalid="ignore"):
        return G * G / (H + l2)


def scan(g, h, n, *, has_nan: bool, min_data_in_leaf: int,
         min_sum_hessian_in_leaf: float, lambda_l2: float
         ) -> Dict[bool, np.ndarray]:
    """Gain (less the unsplit node's) of every threshold of one column's
    histogram (g, h, n over its bins), by direction: {True: missing
    left (the reverse scan), False: missing right (the forward scan)};
    -inf where a threshold does not count. Index t is the threshold
    `bin <= t`."""
    nb = len(g)
    valued = nb - 1 if has_nan else nb      # the bins that hold values
    G, H, N = g.sum(), h.sum(), n.sum()
    parent = _leaf_gain(G, H, lambda_l2)
    cg, ch, cn = (np.cumsum(a[:valued]) for a in (g, h, n))
    out = {}
    for left_nan in ((True, False) if has_nan else (False,)):
        lg, lh, ln = cg, ch, cn
        if left_nan:
            lg, lh, ln = cg + g[-1], ch + h[-1], cn + n[-1]
        last = valued - 2 if left_nan else (valued - 1 if has_nan
                                            else valued - 2)
        ok = ((ln >= min_data_in_leaf) & (N - ln >= min_data_in_leaf) &
              (lh >= min_sum_hessian_in_leaf) &
              (H - lh >= min_sum_hessian_in_leaf) &
              (np.arange(valued) <= last))
        gain = _leaf_gain(lg, lh, lambda_l2) + \
            _leaf_gain(G - lg, H - lh, lambda_l2) - parent
        out[left_nan] = np.where(ok & (gain > 0), gain, -np.inf)
    return out


def missing_rule(g, h, n, **kw) -> Tuple[float, int, bool]:
    """(gain, threshold, missing left) the rule picks for one column's
    histogram (`scan`'s keywords), (-inf, -1, False) where no threshold
    counts."""
    gains = scan(g, h, n, **kw)
    best = (-np.inf, -1, False)
    if True in gains:
        # the reverse scan: high thresholds first, strictly larger wins
        rev = gains[True][::-1]
        t = len(rev) - 1 - int(np.argmax(rev))
        if rev.max() > best[0]:
            best = (float(gains[True][t]), t, True)
    fwd = gains[False]
    t = int(np.argmax(fwd))
    if fwd[t] > best[0]:
        best = (float(fwd[t]), t, False)
    return best


def flatten_tree(structure: Dict) -> Dict[str, np.ndarray]:
    """`Booster.dump_model()["tree_info"][k]["tree_structure"]` as
    arrays: node i has feature[i], threshold[i], default_left[i],
    nan_missing[i] (missing_type "NaN"), left[i], right[i]; a negative
    child c is leaf ~c."""
    feature, threshold, default_left, nan_missing = [], [], [], []
    left, right = [], []
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
        default_left.append(bool(node["default_left"]))
        nan_missing.append(node.get("missing_type") == "NaN")
        left.append(0)
        right.append(0)
        left[i] = visit(node["left_child"])
        right[i] = visit(node["right_child"])
        return i

    visit(structure)
    return {"feature": np.asarray(feature, np.int64),
            "threshold": np.asarray(threshold, np.float64),
            "default_left": np.asarray(default_left, bool),
            "nan_missing": np.asarray(nan_missing, bool),
            "left": np.asarray(left, np.int64),
            "right": np.asarray(right, np.int64),
            "leaf_value": np.asarray(
                [leaf_value[k] for k in range(len(leaf_value))],
                np.float64)}


def goes_left(tree, i: int, x: np.ndarray) -> np.ndarray:
    """Which of the raw values `x` node i sends left: a missing value by
    its default direction (missing_type "NaN") or as 0.0 (no missing
    type), any other `x <= threshold`."""
    x = np.asarray(x, np.float64)
    nan = np.isnan(x)
    if not tree["nan_missing"][i]:
        x = np.where(nan, 0.0, x)
        return x <= tree["threshold"][i]
    with np.errstate(invalid="ignore"):
        return np.where(nan, bool(tree["default_left"][i]),
                        x <= tree["threshold"][i])


def route(tree: Dict[str, np.ndarray], X: np.ndarray) -> np.ndarray:
    """Leaf index of every row of X (raw features, NaN where missing).
    Above a million rows the rows go to a few threads in slices."""
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
        x = np.asarray(X[rows[live], tree["feature"][idx]], np.float64)
        nan = np.isnan(x)
        xv = np.where(nan, 0.0, x)
        left = np.where(nan & tree["nan_missing"][idx],
                        tree["default_left"][idx],
                        xv <= tree["threshold"][idx])
        node[live] = np.where(left, tree["left"][idx], tree["right"][idx])


def leaves_under(tree) -> List[np.ndarray]:
    """The leaves below every node."""
    out: List[np.ndarray] = [np.zeros(0, np.int64)] * len(tree["feature"])

    def visit(c):
        if c < 0:
            return [~c]
        got = visit(tree["left"][c]) + visit(tree["right"][c])
        out[c] = np.asarray(got, np.int64)
        return got

    if len(tree["feature"]):
        visit(0)
    return out


def column_histograms(col: np.ndarray, leaf: np.ndarray, num_leaves: int,
                      num_bin: int, grad, hess):
    """(g, h, n, |g|) [num_leaves, num_bin] of one binned column."""
    key = leaf * num_bin + np.ascontiguousarray(col).astype(np.int64)
    size, shape = num_leaves * num_bin, (num_leaves, num_bin)
    return (np.bincount(key, weights=grad, minlength=size).reshape(shape),
            np.bincount(key, weights=hess, minlength=size).reshape(shape),
            np.bincount(key, minlength=size).reshape(shape),
            np.bincount(key, weights=np.abs(grad),
                        minlength=size).reshape(shape))


def gain_at(g, h, t: int, left_nan: bool, lambda_l2: float) -> float:
    """Gain (less the unsplit node's) of the threshold `bin <= t`, the
    missing values (the last bin) going left or right."""
    lg, lh = g[:t + 1].sum(), h[:t + 1].sum()
    if left_nan:
        lg, lh = lg + g[-1], lh + h[-1]
    G, H = g.sum(), h.sum()
    return float(_leaf_gain(lg, lh, lambda_l2) +
                 _leaf_gain(G - lg, H - lh, lambda_l2) -
                 _leaf_gain(G, H, lambda_l2))


def gain_noise(g, h, noise_g, noise_h, t: int, left_nan: bool,
               lambda_l2: float) -> float:
    """What ONE float32 rounding of the histogram does to `gain_at`:
    the program's left sums carry an absolute error of the size of
    `noise_g`, `noise_h` summed over the left bins (one rounding of
    each bin's sums at the ROOT: a child's histogram is its parent's
    less its sibling's), its right sums are the node's less the left
    ones, and the gain's three terms are each rounded once."""
    sel = np.zeros(len(g), bool)
    sel[:t + 1] = True
    sel[-1] = left_nan
    lg, lh = g[sel].sum(), h[sel].sum()
    G, H = g.sum(), h.sum()
    out_l, out_r = lg / (lh + lambda_l2), (G - lg) / (H - lh + lambda_l2)
    return float(2.0 * abs(out_l - out_r) * noise_g[sel].sum()
                 + abs(out_l ** 2 - out_r ** 2) * noise_h[sel].sum()
                 + EPS32 * (_leaf_gain(lg, lh, lambda_l2)
                            + _leaf_gain(G - lg, H - lh, lambda_l2)
                            + _leaf_gain(G, H, lambda_l2)))


def threshold_bin(x: np.ndarray, col: np.ndarray, threshold: float) -> int:
    """The bin a model's threshold value stands for: the last bin of the
    values at or under it (the ingest's value -> bin table, read off
    the rows, as gbdt_cat_numpy reads its own)."""
    with np.errstate(invalid="ignore"):
        under = np.asarray(x, np.float64) <= threshold
    return int(col[under].max()) if under.any() else -1


def binning_disagreements(tree, X: np.ndarray, bins_t: np.ndarray
                          ) -> Tuple[int, Dict]:
    """Rows whose bin in the program's binned matrix (`bins_t`, a row a
    column) does not say what their raw value says, at every split of
    `tree`, and the split with the most: a value over the split's
    threshold in a bin at or under the bin the threshold stands for
    (`threshold_bin`), and, in a column with missing values, a missing
    value anywhere but the column's one NaN bin past every valued bin,
    or a value in it. The binned matrix is the program's; this holds it
    to the raw X at the thresholds the model wrote."""
    bad, worst = 0, {"rows": 0}
    seen = set()
    for i in range(len(tree["feature"])):
        f, thr = int(tree["feature"][i]), float(tree["threshold"][i])
        if (f, thr) in seen:
            continue
        seen.add((f, thr))
        x = np.asarray(X[:, f], np.float64)
        col = bins_t[f]
        nan = np.isnan(x)
        with np.errstate(invalid="ignore"):
            under = x <= thr
        t = threshold_bin(x, col, thr)
        wrong = int(np.count_nonzero(~nan & ~under & (col <= t)))
        if nan.any():
            nan_bin = int(col[nan].max())
            wrong += int(np.count_nonzero(nan & (col != nan_bin)) +
                         np.count_nonzero(~nan & (col >= nan_bin)))
        bad += wrong
        if wrong > worst["rows"]:
            worst = {"rows": wrong, "node": i, "feature": f,
                     "threshold": thr, "threshold_bin": t}
    return bad, worst


# ----------------------------------------------------------------------
def check_step(k: int, trees: List[Dict], X: np.ndarray, y: np.ndarray,
               bins: np.ndarray, *, learning_rate: float,
               min_data_in_leaf: int, min_sum_hessian_in_leaf: float,
               lambda_l2: float = 0.0, slack_ulps: float = 64.0,
               routed: Optional[Dict[int, np.ndarray]] = None,
               bins_t: Optional[np.ndarray] = None) -> Dict:
    """Boosting step k of the model against this reference (`trees` are
    the flattened trees 0..k, `bins` the binned matrix over the same
    columns as X, `bins_t` its transpose if the caller has one).
    Returns what the configuration's `expect` bounds:

    root_gain_shortfall      the model's root split, recomputed from the
                             raw rows (missing values by its default
                             direction), against the best the rule
                             finds over all columns and both directions
    leaf_sum_err_root_ulps   as gbdt_numpy.check_step defines it
    nan_direction_flips      nodes on a column with a NaN bin whose
                             direction, at the model's own threshold,
                             loses to the other by more than `slack_ulps`
                             float32 roundings of the gains (`gain_noise`)
    nan_gain_shortfall_ulps  the largest (best - model's) over those
                             nodes, both gains from the node's float64
                             histogram of its column, in the same unit;
                             `nan_worst_gain` says at which node
    binning_disagreements    rows whose bin in `bins` disagrees with
                             their raw value at a split of tree k
                             (`binning_disagreements`): the histograms
                             above are built from `bins`, so they stand
                             only where this reads 0
    """
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
    tree, leaf = trees[k], routed[k]
    num_leaves = len(tree["leaf_value"])
    if bins_t is None:
        bins_t = np.ascontiguousarray(bins.T)
    rule = dict(min_data_in_leaf=min_data_in_leaf,
                min_sum_hessian_in_leaf=min_sum_hessian_in_leaf,
                lambda_l2=lambda_l2)
    zeros = np.zeros(len(y), np.int64)

    def column(f, by_leaf):
        col = bins_t[f]
        nb = int(col.max()) + 1
        has_nan = bool(np.isnan(X[:, f]).any())
        return col, nb, has_nan, column_histograms(
            col, leaf if by_leaf else zeros, num_leaves if by_leaf else 1,
            nb, grad, hess)

    # ---- the root, over all columns and both directions
    def root_best(f):
        _, _, has_nan, (g, h, n, _) = column(f, False)
        gain, t, left_nan = missing_rule(g[0], h[0], n[0], has_nan=has_nan,
                                         **rule)
        return gain, (f, t, left_nan)

    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
        found = list(pool.map(root_best, range(bins_t.shape[0])))
    best, best_at = max(found, key=lambda r: r[0])
    f0 = int(tree["feature"][0])
    left0 = goes_left(tree, 0, X[:, f0])
    gl, hl = grad[left0].sum(), hess[left0].sum()
    G, H = grad.sum(), hess.sum()
    got = float(_leaf_gain(gl, hl, lambda_l2) +
                _leaf_gain(G - gl, H - hl, lambda_l2) -
                _leaf_gain(G, H, lambda_l2))

    # ---- every node on a column with a NaN bin against the rule
    below = leaves_under(tree)
    nan_nodes = np.flatnonzero(tree["nan_missing"])
    flips, whys = 0, []
    worst = {"nan_gain_shortfall_ulps": 0.0}
    by_column: Dict[int, tuple] = {}
    for f in sorted({int(tree["feature"][i]) for i in nan_nodes}):
        by_column[f] = column(f, True)
    for i in nan_nodes:
        f = int(tree["feature"][i])
        col, nb, has_nan, hists = by_column[f]
        if not has_nan:
            continue
        g, h, n, _ = (a[below[i]].sum(0) for a in hists)
        noise_g = EPS32 * hists[3].sum(0)
        noise_h = EPS32 * hists[1].sum(0)
        t = threshold_bin(X[:, f], col, tree["threshold"][i])
        mine_left = bool(tree["default_left"][i])
        mine = gain_at(g, h, t, mine_left, lambda_l2)
        other = gain_at(g, h, t, not mine_left, lambda_l2)
        unit = gain_noise(g, h, noise_g, noise_h, t, mine_left,
                          lambda_l2) + \
            gain_noise(g, h, noise_g, noise_h, t, not mine_left, lambda_l2)
        # the other direction counts only where the rule lets it
        other_ok = np.isfinite(scan(g, h, n, has_nan=True, **rule)[
            not mine_left][min(t, nb - 2)]) if 0 <= t <= nb - 2 else False
        if other_ok and (other - mine) / unit > slack_ulps:
            flips += 1
            whys.append({"node": int(i), "feature": f, "rows": int(n.sum()),
                         "threshold_bin": t, "default_left": mine_left,
                         "gain_model": mine, "gain_other": other,
                         "one_rounding": unit})
        bgain, bt, bleft = missing_rule(g, h, n, has_nan=True, **rule)
        if not np.isfinite(bgain):
            continue
        unit = gain_noise(g, h, noise_g, noise_h, t, mine_left, lambda_l2) \
            + gain_noise(g, h, noise_g, noise_h, bt, bleft, lambda_l2)
        ulps = (bgain - mine) / unit
        if ulps > worst["nan_gain_shortfall_ulps"]:
            worst = {"nan_gain_shortfall_ulps": float(ulps), "node": int(i),
                     "feature": f, "rows": int(n.sum()), "gain_model": mine,
                     "gain_best": bgain, "one_rounding": unit,
                     "model": [t, mine_left], "best": [bt, bleft]}

    misbinned, misbinned_at = binning_disagreements(tree, X, bins_t)

    # ---- leaves
    Gs = np.bincount(leaf, weights=grad, minlength=num_leaves)
    Hs = np.bincount(leaf, weights=hess, minlength=num_leaves)
    n = np.bincount(leaf, minlength=num_leaves)
    first = bias if k == 0 else 0.0
    with np.errstate(divide="ignore", invalid="ignore"):
        want = -Gs / (Hs + lambda_l2) * learning_rate + first
    err = np.abs(tree["leaf_value"] - want)
    in_sum = err * (Hs + lambda_l2) / learning_rate
    scale = np.abs(grad).sum() + np.abs(want - first) / learning_rate \
        * hess.sum()
    ulps = np.where(n > 0, in_sum / (EPS32 * scale), 0.0)
    wl = int(np.argmax(ulps))
    return {"tree": k, "root_gain_model": got, "root_gain_best": best,
            "root_gain_shortfall": (best - got) / abs(best),
            "best_root": [int(best_at[0]), int(best_at[1]),
                          bool(best_at[2])],
            "model_root": [f0, float(tree["threshold"][0]),
                           bool(tree["default_left"][0])],
            "leaves": int(num_leaves), "empty_leaves": int((n == 0).sum()),
            "smallest_leaf_rows": int(n.min()),
            "leaf_value_max_abs_err": float(err[n > 0].max()),
            "leaf_sum_err_root_ulps": float(ulps.max()),
            "worst_leaf": {"leaf": wl, "rows": int(n[wl]),
                           "model": float(tree["leaf_value"][wl]),
                           "reference": float(want[wl])},
            "nodes": int(len(tree["feature"])),
            "nan_nodes": int(len(nan_nodes)),
            "nan_left_nodes": int(tree["default_left"][nan_nodes].sum()),
            "nan_direction_flips": int(flips),
            "nan_flipped": whys[:5],
            "nan_gain_shortfall_ulps": worst["nan_gain_shortfall_ulps"],
            "nan_worst_gain": worst,
            "binning_disagreements": misbinned,
            "binning_worst": misbinned_at}
