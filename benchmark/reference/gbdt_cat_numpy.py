"""Plain reference for training on data with categorical columns: one
boosting step of binary log-loss GBDT in NumPy and float64, with the
published rule for categorical splits written out from its description
(LightGBM Features.rst, "Optimal Split for Categorical Features";
Advanced-Topics.rst, "Categorical Feature Support"; the parameters
`cat_smooth`, `cat_l2`, `max_cat_threshold`, `max_cat_to_onehot`,
`min_data_per_group` in Parameters.rst; SURVEY.md places the rule at
`FeatureHistogram::FindBestThresholdCategoricalInner`,
feature_histogram.hpp:278-485).

The rule, for one categorical column at one node (`categorical_rule`):

- bin 0 holds the unseen, rare, negative and missing values and never
  goes left;
- a column of at most `max_cat_to_onehot` bins is searched one bin
  against the rest, gain with the plain `lambda_l2`;
- any other column: the bins with at least `cat_smooth` rows are sorted
  by g / (h + cat_smooth) (a stable sort) and scanned from the low end
  and from the high end, each for at most min(max_cat_threshold,
  (used + 1) / 2) steps. Every step adds its bin to the left set. A step
  is EVALUATED only if the left side holds `min_data_in_leaf` rows and
  `min_sum_hessian_in_leaf` (else the scan goes on), the right side
  holds max(min_data_in_leaf, min_data_per_group) rows and
  `min_sum_hessian_in_leaf` (else the scan ends), and the bins added
  since the last evaluated step hold `min_data_per_group` rows (else the
  scan goes on; an evaluated step starts a new group). The gain of an
  evaluated step uses lambda_l2 + cat_l2; the gain it must beat, the
  unsplit node's, uses the plain lambda_l2. The first strictly larger
  gain wins: low end before high end, earlier step before later.
- the children of a split found by the sorted scan take their values
  with lambda_l2 + cat_l2 too.

Departures from the C++, each deliberate: counts are the rows' own (the
newer reference estimates a bin's count from its hessian sum); the
1e-15 the reference adds to hessian sums is left out; no L1, no
max_delta_step, no path smoothing, no monotone constraints, no
extra_trees (the configuration uses none).

`check_step` does what `gbdt_numpy.check_step` does (root gain against
the best over ALL columns, leaf values against Newton steps over the
rows a traversal of the raw features routes to each leaf) and, for
every node that decides on a categorical column, holds the program's
left set against the rule on that node's own float64 histogram: the set
has to be one the rule can produce there, and its gain within a
tolerance of the best the rule finds for that column. Shares no code
with lightgbm_tpu.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .gbdt_numpy import auc, grad_hess, init_score  # noqa: F401

EPS32 = float(np.finfo(np.float32).eps)

#: the library's defaults for the rule's parameters
DEFAULTS = dict(cat_smooth=10.0, cat_l2=10.0, max_cat_threshold=32,
                max_cat_to_onehot=4, min_data_per_group=100)


def _leaf_gain(G, H, l2):
    return G * G / (H + l2)


# ----------------------------------------------------------------------
# the rule
def scan_steps(order: Sequence[int], steps: int, g, h, n, G, H, N, *,
               min_data_in_leaf, min_sum_hessian_in_leaf, lambda_l2,
               cat_l2, min_data_per_group, batching: bool = True
               ) -> List[Tuple[int, float]]:
    """One direction of the sorted scan: the first `steps` of the bins
    `order` (the used bins in scan order). [(step, gain)] of the
    evaluated steps, gain not yet less the unsplit node's.
    `batching=False` is the planted control: every step that meets the
    floors is evaluated, whatever its group holds."""
    l2 = lambda_l2 + cat_l2
    floor_right = max(min_data_in_leaf, min_data_per_group)
    out = []
    lg = lh = 0.0
    ln = group = 0
    for i in range(steps):
        t = order[i]
        lg += g[t]
        lh += h[t]
        ln += n[t]
        group += n[t]
        if ln < min_data_in_leaf or lh < min_sum_hessian_in_leaf:
            continue
        if N - ln < floor_right or H - lh < min_sum_hessian_in_leaf:
            break
        if batching:
            if group < min_data_per_group:
                continue
            group = 0
        out.append((i, _leaf_gain(lg, lh, l2) +
                    _leaf_gain(G - lg, H - lh, l2)))
    return out


def sorted_orders(g, h, n, *, cat_smooth) -> Tuple[np.ndarray, np.ndarray]:
    """(low end first, high end first): the bins 1.. with at least
    cat_smooth rows, stably sorted by g / (h + cat_smooth)."""
    used = np.flatnonzero(n[1:] >= cat_smooth) + 1
    ratio = g[used] / (h[used] + cat_smooth)
    asc = used[np.argsort(ratio, kind="stable")]
    return asc, asc[::-1]


def categorical_rule(g, h, n, *, min_data_in_leaf, min_sum_hessian_in_leaf,
                     lambda_l2=0.0, cat_smooth=10.0, cat_l2=10.0,
                     max_cat_threshold=32, max_cat_to_onehot=4,
                     min_data_per_group=100, batching: bool = True
                     ) -> Optional[Dict]:
    """The best split of one categorical column at one node from its
    histogram (g, h, n: float64 / int64 [num_bin], bin 0 the dummy):
    {"gain" (less the unsplit node's), "left_bins", "sorted" (whether
    the sorted scan found it: its children take lambda_l2 + cat_l2)},
    or None where the rule finds no split."""
    g, h = np.asarray(g, np.float64), np.asarray(h, np.float64)
    n = np.asarray(n, np.int64)
    G, H, N = g.sum(), h.sum(), int(n.sum())
    shift = _leaf_gain(G, H, lambda_l2)
    best = None
    if len(n) <= max_cat_to_onehot:
        for t in range(1, len(n)):
            if n[t] < min_data_in_leaf or h[t] < min_sum_hessian_in_leaf \
                    or N - n[t] < min_data_in_leaf \
                    or H - h[t] < min_sum_hessian_in_leaf:
                continue
            gain = _leaf_gain(g[t], h[t], lambda_l2) + \
                _leaf_gain(G - g[t], H - h[t], lambda_l2)
            if gain > shift and (best is None or gain > best["gain"]):
                best = {"gain": gain, "left_bins": [t], "sorted": False}
    else:
        kw = dict(min_data_in_leaf=min_data_in_leaf,
                  min_sum_hessian_in_leaf=min_sum_hessian_in_leaf,
                  lambda_l2=lambda_l2, cat_l2=cat_l2,
                  min_data_per_group=min_data_per_group, batching=batching)
        for order in sorted_orders(g, h, n, cat_smooth=cat_smooth):
            steps = min(len(order), int(max_cat_threshold),
                        (len(order) + 1) // 2)
            for i, gain in scan_steps(order, steps, g, h, n, G, H, N, **kw):
                if gain > shift and (best is None or gain > best["gain"]):
                    best = {"gain": gain, "sorted": True,
                            "left_bins": [int(b) for b in order[:i + 1]]}
    if best is not None:
        best["gain"] = float(best["gain"] - shift)
    return best


def gain_of_left_bins(left_bins, g, h, *, lambda_l2, cat_l2, sorted_scan):
    """Gain (less the unsplit node's) of sending `left_bins` left."""
    l2 = lambda_l2 + (cat_l2 if sorted_scan else 0.0)
    lg, lh = g[left_bins].sum(), h[left_bins].sum()
    G, H = g.sum(), h.sum()
    return float(_leaf_gain(lg, lh, l2) + _leaf_gain(G - lg, H - lh, l2)
                 - _leaf_gain(G, H, lambda_l2))


def gain_noise(left_bins, g, h, noise_g, noise_h, *, lambda_l2, cat_l2,
               sorted_scan) -> float:
    """What ONE float32 rounding of the histogram does to the gain of
    sending `left_bins` left. The program's left sums carry an absolute
    error of the size of `noise_g`, `noise_h` summed over the set (one
    rounding of each bin's sums at the ROOT: a child's histogram is its
    parent's less its sibling's), its right sums are the node's less
    the left ones, and the gain's three terms are each rounded once:
    |d gain / d G_L| dG + |d gain / d H_L| dH + EPS32 * (the terms)."""
    l2 = lambda_l2 + (cat_l2 if sorted_scan else 0.0)
    lg, lh = g[left_bins].sum(), h[left_bins].sum()
    G, H = g.sum(), h.sum()
    out_l, out_r = lg / (lh + l2), (G - lg) / (H - lh + l2)
    return float(2.0 * abs(out_l - out_r) * noise_g[left_bins].sum()
                 + abs(out_l ** 2 - out_r ** 2) * noise_h[left_bins].sum()
                 + EPS32 * (_leaf_gain(lg, lh, l2)
                            + _leaf_gain(G - lg, H - lh, l2)
                            + _leaf_gain(G, H, lambda_l2)))


def feasibility(left_bins, g, h, n, noise_g, noise_h, *, slack_ulps, rule
                ) -> Dict:
    """Whether the rule can produce the left set `left_bins` at a node
    with histogram (g, h, n), and how far it is from that.

    The program holds its histograms in float32, a child's as its
    parent's less its sibling's, so a bin's sums carry an absolute error
    that scales with that bin's sums at the ROOT: `noise_g[b]`,
    `noise_h[b]` are one float32 rounding of them (EPS32 times the
    root's sum of |g| and of h over the bin's rows). Two bins whose
    ratios lie closer than that may stand in either order.

    "prefix_slack_ulps": the smallest multiple of that noise under which
    the set is the first len(left_bins) bins of the sorted order from
    one end (0 where it is so in float64). "evaluated": whether, with
    the set's bins in sorted order (ties inside `slack_ulps` of noise
    tried both ways), its last step is one the rule evaluates: floors,
    step limit and the min_data_per_group batching. One-hot columns:
    the set is one bin that meets the floors."""
    left = np.asarray(sorted(set(int(b) for b in left_bins)), np.int64)
    out = {"prefix_slack_ulps": 0.0, "evaluated": False, "why": ""}
    if not len(left) or left[0] < 1 or left[-1] >= len(n):
        out["why"] = "bin 0 or a bin the column lacks goes left"
        out["prefix_slack_ulps"] = float("inf")
        return out
    G, H, N = g.sum(), h.sum(), int(n.sum())
    mdl, msh = rule["min_data_in_leaf"], rule["min_sum_hessian_in_leaf"]
    if len(n) <= rule["max_cat_to_onehot"]:
        t = left[0]
        out["evaluated"] = bool(
            len(left) == 1 and n[t] >= mdl and h[t] >= msh and
            N - n[t] >= mdl and H - h[t] >= msh)
        out["why"] = "" if out["evaluated"] else "one-hot floors"
        return out
    cs = rule["cat_smooth"]
    used = np.flatnonzero(n[1:] >= cs) + 1
    if not np.isin(left, used).all():
        out["why"] = "a bin under cat_smooth rows goes left"
        out["prefix_slack_ulps"] = float("inf")
        return out
    rest = np.setdiff1d(used, left)
    ratio = g / (h + cs)
    # one float32 rounding of the bin's root sums, taken to the ratio
    noise = noise_g / (h + cs) + np.abs(g) * noise_h / (h + cs) ** 2
    slack = []
    for sign in (1.0, -1.0):                 # low end first, high end first
        if not len(rest):
            slack.append(0.0)
            continue
        gap = sign * (ratio[left][:, None] - ratio[rest][None, :])
        per = gap / (noise[left][:, None] + noise[rest][None, :] + 1e-300)
        slack.append(max(0.0, float(per.max())))
    out["prefix_slack_ulps"] = min(slack)
    kw = {k: rule[k] for k in (
        "min_data_in_leaf", "min_sum_hessian_in_leaf", "lambda_l2",
        "cat_l2", "min_data_per_group")}
    kw["batching"] = rule.get("batching", True)
    limit = min(int(rule["max_cat_threshold"]), (len(used) + 1) // 2)
    if len(left) > limit:
        out["why"] = "%d bins go left, the rule stops at %d" % (len(left),
                                                                 limit)
        return out
    rng = np.random.Generator(np.random.PCG64(len(left)))
    for attempt in range(17):
        jitter = 0.0 if attempt == 0 else \
            rng.uniform(-1.0, 1.0, len(ratio)) * noise * slack_ulps
        for sign in (1.0, -1.0):
            order = left[np.argsort(sign * (ratio + jitter)[left],
                                    kind="stable")]
            steps = scan_steps(order, len(order), g, h, n, G, H, N, **kw)
            if steps and steps[-1][0] == len(order) - 1:
                out["evaluated"] = True
                return out
    out["why"] = "the set's last step is not one the rule evaluates"
    return out


# ----------------------------------------------------------------------
# trees
def flatten_tree(structure: Dict) -> Dict[str, np.ndarray]:
    """`Booster.dump_model()["tree_info"][k]["tree_structure"]` as
    arrays. A node that decides on a categorical column has
    decision_type "==" and its threshold is the category values that go
    left, joined by "||" (the reference's public JSON form); it is kept
    as `left_values[i]`, an int64 array, with threshold[i] NaN. A
    negative child c is leaf ~c."""
    feature, threshold, is_cat, left, right = [], [], [], [], []
    left_values: List[Optional[np.ndarray]] = []
    leaf_value: Dict[int, float] = {}

    def visit(node) -> int:
        if "leaf_index" in node or "split_index" not in node:
            k = int(node.get("leaf_index", 0))
            leaf_value[k] = float(node["leaf_value"])
            return ~k
        i = len(feature)
        feature.append(int(node["split_feature"]))
        if node["decision_type"] == "==":
            is_cat.append(True)
            threshold.append(np.nan)
            left_values.append(np.asarray(
                [int(v) for v in str(node["threshold"]).split("||")],
                np.int64))
        elif node["decision_type"] == "<=":
            is_cat.append(False)
            threshold.append(float(node["threshold"]))
            left_values.append(None)
        else:
            raise ValueError("decision_type %r" % node["decision_type"])
        left.append(0)
        right.append(0)
        left[i] = visit(node["left_child"])
        right[i] = visit(node["right_child"])
        return i

    visit(structure)
    return {"feature": np.asarray(feature, np.int64),
            "threshold": np.asarray(threshold, np.float64),
            "is_cat": np.asarray(is_cat, bool),
            "left_values": left_values,
            "left": np.asarray(left, np.int64),
            "right": np.asarray(right, np.int64),
            "leaf_value": np.asarray(
                [leaf_value[k] for k in range(len(leaf_value))],
                np.float64)}


def _membership(tree) -> Tuple[np.ndarray, np.ndarray]:
    """(row of the table per node, table [categorical nodes, values]):
    table[r, v] says that value v goes left at the node of row r."""
    nodes = np.flatnonzero(tree["is_cat"])
    row = np.full(len(tree["feature"]), -1, np.int64)
    row[nodes] = np.arange(len(nodes))
    width = 1 + max([int(tree["left_values"][i].max()) for i in nodes],
                    default=0)
    table = np.zeros((len(nodes), width), bool)
    for r, i in enumerate(nodes):
        table[r, tree["left_values"][i]] = True
    return row, table


def route(tree: Dict[str, np.ndarray], X: np.ndarray) -> np.ndarray:
    """Leaf index of every row of X (raw features). A numerical node
    sends `x <= threshold` left; a categorical node sends left the rows
    whose value, truncated to an integer, is one of its `left_values`,
    and everything else right: other levels, negative codes, NaN."""
    if len(X) > 1_000_000:
        cuts = np.linspace(0, len(X), 9).astype(np.int64)
        with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
            return np.concatenate(list(pool.map(
                lambda k: route(tree, X[cuts[k]:cuts[k + 1]]), range(8))))
    if not len(tree["feature"]):
        return np.zeros(len(X), np.int64)
    row, table = _membership(tree)
    node = np.zeros(len(X), np.int64)
    while True:
        live = np.flatnonzero(node >= 0)
        if not len(live):
            return ~node
        idx = node[live]
        x = X[live, tree["feature"][idx]]
        go_left = x <= tree["threshold"][idx]      # False at a NaN threshold
        cat = tree["is_cat"][idx]
        if cat.any():
            xc = x[cat]
            ok = np.isfinite(xc) & (xc >= 0) & (xc < table.shape[1])
            v = np.where(ok, xc, 0).astype(np.int64)
            go_left[cat] = ok & table[row[idx[cat]], v]
        node[live] = np.where(go_left, tree["left"][idx],
                              tree["right"][idx])


def leaves_under(tree) -> List[np.ndarray]:
    """For every internal node, the leaves below it."""
    out: List[Optional[np.ndarray]] = [None] * len(tree["feature"])

    def visit(c):
        if c < 0:
            return np.asarray([~c], np.int64)
        out[c] = np.concatenate([visit(int(tree["left"][c])),
                                 visit(int(tree["right"][c]))])
        return out[c]

    if len(out):
        visit(0)
    return out


def leaf_l2(tree, sorted_column: np.ndarray, *, lambda_l2, cat_l2
            ) -> np.ndarray:
    """The L2 term of every leaf's Newton step: a child of a split the
    sorted scan found takes lambda_l2 + cat_l2."""
    l2 = np.full(len(tree["leaf_value"]), float(lambda_l2))
    for i in range(len(tree["feature"])):
        if tree["is_cat"][i] and sorted_column[tree["feature"][i]]:
            for c in (tree["left"][i], tree["right"][i]):
                if c < 0:
                    l2[~c] = lambda_l2 + cat_l2
    return l2


# ----------------------------------------------------------------------
# histograms
def best_numerical_gain(col: np.ndarray, grad, hess, *, min_data_in_leaf,
                        min_sum_hessian_in_leaf, lambda_l2
                        ) -> Tuple[float, int]:
    """(gain, bin) of the best split `bin <= b` of one binned numerical
    column over all rows."""
    G, H, n = grad.sum(), hess.sum(), len(grad)
    col = np.ascontiguousarray(col).astype(np.intp)
    gl = np.cumsum(np.bincount(col, weights=grad))[:-1]
    hl = np.cumsum(np.bincount(col, weights=hess))[:-1]
    cl = np.cumsum(np.bincount(col))[:-1]
    ok = ((cl >= min_data_in_leaf) & (n - cl >= min_data_in_leaf) &
          (hl >= min_sum_hessian_in_leaf) &
          (H - hl >= min_sum_hessian_in_leaf))
    if not ok.any():
        return -np.inf, -1
    with np.errstate(divide="ignore", invalid="ignore"):
        gain = _leaf_gain(gl, hl, lambda_l2) + \
            _leaf_gain(G - gl, H - hl, lambda_l2) - _leaf_gain(G, H, lambda_l2)
    gain = np.where(ok, gain, -np.inf)
    b = int(np.argmax(gain))
    return float(gain[b]), b


def leaf_histograms(col: np.ndarray, leaf: np.ndarray, num_leaves: int,
                    num_bin: int, grad, hess):
    """(g, h, n, |g|) [num_leaves, num_bin] of one binned column."""
    key = leaf * num_bin + np.ascontiguousarray(col).astype(np.int64)
    size = num_leaves * num_bin
    shape = (num_leaves, num_bin)
    return (np.bincount(key, weights=grad, minlength=size).reshape(shape),
            np.bincount(key, weights=hess, minlength=size).reshape(shape),
            np.bincount(key, minlength=size).reshape(shape),
            np.bincount(key, weights=np.abs(grad),
                        minlength=size).reshape(shape))


def bin_of_value(x: np.ndarray, col: np.ndarray) -> np.ndarray:
    """The ingest layer's value -> bin table of one categorical column,
    read off the rows themselves (the binned matrix is taken as given,
    as in gbdt_numpy)."""
    ok = np.isfinite(x) & (x >= 0)
    v = x[ok].astype(np.int64)
    table = np.zeros(int(v.max()) + 1 if len(v) else 1, np.int64)
    table[v] = col[ok]
    return table


# ----------------------------------------------------------------------
def check_step(k: int, trees: List[Dict], X: np.ndarray, y: np.ndarray,
               bins: np.ndarray, categorical: Sequence[int], *,
               learning_rate: float, min_data_in_leaf: int,
               min_sum_hessian_in_leaf: float, lambda_l2: float = 0.0,
               cat_smooth: float = 10.0, cat_l2: float = 10.0,
               max_cat_threshold: int = 32, max_cat_to_onehot: int = 4,
               min_data_per_group: int = 100, slack_ulps: float = 64.0,
               batching: bool = True,
               routed: Optional[Dict[int, np.ndarray]] = None) -> Dict:
    """Boosting step k of the model against this reference (`trees` are
    the flattened trees 0..k, `categorical` the columns passed as
    `categorical_feature`, `bins` the binned matrix over the same
    columns as X). Returns what the configuration's `expect` bounds:

    root_gain_shortfall     the model's root split, recomputed from the
                            raw rows, against the best over all columns
                            (numerical boundaries, and the rule on every
                            categorical column)
    leaf_sum_err_root_ulps  as gbdt_numpy.check_step defines it, each
                            leaf under its own L2 term (leaf_l2)
    cat_infeasible_nodes    categorical nodes whose left set the rule
                            cannot produce from the node's histogram:
                            not a prefix of the sorted order within
                            `slack_ulps` of float32 noise, or not ending
                            at an evaluated step
    cat_prefix_slack_ulps   the largest noise multiple a node needed
    cat_gain_shortfall_ulps the largest (best - model's) over the
                            categorical nodes, both gains from the
                            node's float64 histogram, in float32
                            roundings of what the program computed its
                            gains from (`gain_noise`); `cat_worst_gain`
                            says at which node and by how much
    `batching=False` plants the control: the rule without the
    min_data_per_group batching."""
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
    categorical = [int(f) for f in categorical]
    num_bin = {f: int(bins[:, f].max()) + 1 for f in categorical}
    sorted_column = np.zeros(X.shape[1], bool)
    for f in categorical:
        sorted_column[f] = num_bin[f] > max_cat_to_onehot
    rule = dict(min_data_in_leaf=min_data_in_leaf,
                min_sum_hessian_in_leaf=min_sum_hessian_in_leaf,
                lambda_l2=lambda_l2, cat_smooth=cat_smooth, cat_l2=cat_l2,
                max_cat_threshold=max_cat_threshold,
                max_cat_to_onehot=max_cat_to_onehot,
                min_data_per_group=min_data_per_group, batching=batching)

    # ---- histograms of the categorical columns, per leaf
    hists = {f: leaf_histograms(bins[:, f], leaf, num_leaves, num_bin[f],
                                grad, hess) for f in categorical}
    tables = {f: bin_of_value(X[:, f], bins[:, f]) for f in categorical}

    def left_bins_of(i):
        f = int(tree["feature"][i])
        v = tree["left_values"][i]
        v = v[v < len(tables[f])]
        return np.unique(tables[f][v])

    # ---- the root, over all columns
    best, best_at = -np.inf, (-1, -1)
    for f in range(bins.shape[1]):
        if f in hists:
            g, h, n, _ = (a.sum(0) for a in hists[f])
            found = categorical_rule(g, h, n, **rule)
            gain, at = (found["gain"], (f, found["left_bins"])) \
                if found else (-np.inf, None)
        else:
            gain, b = best_numerical_gain(
                bins[:, f], grad, hess, min_data_in_leaf=min_data_in_leaf,
                min_sum_hessian_in_leaf=min_sum_hessian_in_leaf,
                lambda_l2=lambda_l2)
            at = (f, b)
        if gain > best:
            best, best_at = gain, at
    f0 = int(tree["feature"][0])
    if tree["is_cat"][0]:
        g, h, _, _ = (a.sum(0) for a in hists[f0])
        got = gain_of_left_bins(left_bins_of(0), g, h, lambda_l2=lambda_l2,
                                cat_l2=cat_l2,
                                sorted_scan=bool(sorted_column[f0]))
        model_root = [f0, "==", tree["left_values"][0].tolist()]
    else:
        goes_left = np.asarray(X[:, f0], np.float64) <= tree["threshold"][0]
        gl, hl = grad[goes_left].sum(), hess[goes_left].sum()
        G, H = grad.sum(), hess.sum()
        got = float(_leaf_gain(gl, hl, lambda_l2) +
                    _leaf_gain(G - gl, H - hl, lambda_l2) -
                    _leaf_gain(G, H, lambda_l2))
        model_root = [f0, "<=", float(tree["threshold"][0])]

    # ---- every categorical node against the rule
    below = leaves_under(tree)
    infeasible, worst_slack, whys = 0, 0.0, []
    worst_gain = {"cat_gain_shortfall_ulps": 0.0}
    cat_nodes = np.flatnonzero(tree["is_cat"])
    for i in cat_nodes:
        f = int(tree["feature"][i])
        g, h, n, _ = (a[below[i]].sum(0) for a in hists[f])
        noise_g = EPS32 * hists[f][3].sum(0)
        noise_h = EPS32 * hists[f][1].sum(0)
        left = left_bins_of(i)
        fz = feasibility(left, g, h, n, noise_g, noise_h,
                         slack_ulps=slack_ulps, rule=rule)
        if not fz["evaluated"] or fz["prefix_slack_ulps"] > slack_ulps:
            infeasible += 1
            whys.append({"node": int(i), "feature": f,
                         "rows": int(n.sum()), "left": left.tolist(),
                         "why": fz["why"] or "not a prefix: %.3g ulps"
                         % fz["prefix_slack_ulps"]})
        if np.isfinite(fz["prefix_slack_ulps"]):
            worst_slack = max(worst_slack, fz["prefix_slack_ulps"])
        found = categorical_rule(g, h, n, **rule)
        if found is None or not len(left):
            continue
        kw = dict(lambda_l2=lambda_l2, cat_l2=cat_l2,
                  sorted_scan=bool(sorted_column[f]))
        mine = gain_of_left_bins(left, g, h, **kw)
        # the shortfall in float32 roundings of what both gains were
        # computed from: the program's histograms and its arithmetic
        unit = gain_noise(left, g, h, noise_g, noise_h, **kw) + \
            gain_noise(np.asarray(found["left_bins"]), g, h, noise_g,
                       noise_h, **kw)
        ulps = (found["gain"] - mine) / unit
        if ulps > worst_gain["cat_gain_shortfall_ulps"]:
            worst_gain = {"cat_gain_shortfall_ulps": float(ulps),
                          "node": int(i), "feature": f,
                          "rows": int(n.sum()), "gain_model": mine,
                          "gain_best": found["gain"], "one_rounding": unit,
                          "left": left.tolist()[:8],
                          "best_left": sorted(found["left_bins"])[:8]}

    # ---- leaves
    l2 = leaf_l2(tree, sorted_column, lambda_l2=lambda_l2, cat_l2=cat_l2)
    Gs = np.bincount(leaf, weights=grad, minlength=num_leaves)
    Hs = np.bincount(leaf, weights=hess, minlength=num_leaves)
    n = np.bincount(leaf, minlength=num_leaves)
    first = bias if k == 0 else 0.0
    with np.errstate(divide="ignore", invalid="ignore"):
        want = -Gs / (Hs + l2) * learning_rate + first
    err = np.abs(tree["leaf_value"] - want)
    in_sum = err * (Hs + l2) / learning_rate
    scale = np.abs(grad).sum() + np.abs(want - first) / learning_rate \
        * hess.sum()
    ulps = np.where(n > 0, in_sum / (EPS32 * scale), 0.0)
    worst = int(np.argmax(ulps))
    return {"tree": k, "root_gain_model": got, "root_gain_best": best,
            "root_gain_shortfall": (best - got) / abs(best),
            "best_root": list(best_at), "model_root": model_root,
            "leaves": int(num_leaves), "empty_leaves": int((n == 0).sum()),
            "smallest_leaf_rows": int(n.min()),
            "leaf_value_max_abs_err": float(err[n > 0].max()),
            "leaf_sum_err_root_ulps": float(ulps.max()),
            "worst_leaf": {"leaf": worst, "rows": int(n[worst]),
                           "model": float(tree["leaf_value"][worst]),
                           "reference": float(want[worst]),
                           "l2": float(l2[worst])},
            "nodes": int(len(tree["feature"])),
            "cat_nodes": int(len(cat_nodes)),
            "cat_infeasible_nodes": int(infeasible),
            "cat_infeasible": whys[:5],
            "cat_prefix_slack_ulps": float(worst_slack),
            "cat_gain_shortfall_ulps":
                worst_gain["cat_gain_shortfall_ulps"],
            "cat_worst_gain": worst_gain}
