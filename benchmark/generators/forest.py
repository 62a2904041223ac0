"""A forest of the shape leaf-wise training yields, from a seed, as
LightGBM model text.

Training 500 trees at the published size takes ten minutes a run, so a
serving cell draws its forest instead. Each tree imitates leaf-wise
growth: always split the leaf that holds most of a simulated row mass;
the split takes a feature and a threshold from that feature's quantile
grid (the bin boundaries a 255-bin training would have) inside the
interval the leaf still spans, and divides the mass by a Beta(b, b)
draw, b from the configuration. That gives the unbalanced, deep trees
of `num_leaves=255` (b = 1.0: leaf depths 1 / 10 / 27 as minimum /
median / maximum, my chip run, PR 22) and not the depth-8 complete trees
a level-wise drawing would. Leaf values are N(0, 0.1) shrunk by the
learning rate 0.1. What is ASSUMED is the distribution of depths; every
run prints its own.
"""

from __future__ import annotations

import heapq
from typing import Dict, List

import numpy as np

#: decision_type of a numerical split with missing_type None that sends
#: a missing value left (LightGBM tree.h: bit 1 = default left)
_NUMERICAL_DEFAULT_LEFT = 2


def quantile_grid(X: np.ndarray, bins: int) -> List[np.ndarray]:
    """Per feature, the distinct (bins - 1) inner quantiles of X."""
    qs = np.arange(1, bins) / bins
    return [np.unique(np.quantile(X[:, f].astype(np.float64), qs))
            for f in range(X.shape[1])]


def _grow(rng: np.random.Generator, grid: List[np.ndarray],
          leaves: int, beta: float) -> Dict[str, list]:
    """One tree in LightGBM's numbering: internal nodes 0..leaves-2 in
    the order they were split, leaf k as ~k."""
    nf = len(grid)
    feat_draw = rng.integers(0, nf, size=(leaves - 1, nf))
    frac_draw = rng.beta(beta, beta, size=leaves - 1)
    # a leaf: (-mass, leaf index, parent node, is_left, lo[], hi[])
    lo0 = [0] * nf
    hi0 = [len(g) for g in grid]          # thresholds lo..hi-1 are inside
    heap = [(-1.0, 0, -1, False, lo0, hi0)]
    split_feature, threshold = [], []
    left, right = [], []
    depth_of_leaf = {0: 0}
    next_leaf = 1
    for k in range(leaves - 1):
        neg_mass, leaf, parent, is_left, lo, hi = heapq.heappop(heap)
        f = next((int(c) for c in feat_draw[k] if hi[c] - lo[c] >= 1), None)
        if f is None:
            raise ValueError("a leaf spans no threshold of any feature; "
                             "the grid is too coarse for %d leaves" % leaves)
        span = hi[f] - lo[f]
        t = lo[f] + min(span - 1, int(frac_draw[k] * span))
        share = (t - lo[f] + 1) / (span + 1)
        node = k
        if parent >= 0:
            (left if is_left else right)[parent] = node
        split_feature.append(f)
        threshold.append(float(grid[f][t]))
        left.append(~leaf)               # the split leaf keeps its index
        right.append(~next_leaf)         # on the left, a new one goes right
        d = depth_of_leaf.pop(leaf) + 1
        depth_of_leaf[leaf] = depth_of_leaf[next_leaf] = d
        lhi, rlo = list(hi), list(lo)
        lhi[f], rlo[f] = t, t + 1
        mass = -neg_mass
        heapq.heappush(heap, (-mass * share, leaf, node, True, lo, lhi))
        heapq.heappush(heap, (-mass * (1 - share), next_leaf, node, False,
                              rlo, hi))
        next_leaf += 1
    values = rng.normal(0.0, 0.1, size=leaves) * 0.1
    return {"split_feature": split_feature, "threshold": threshold,
            "left_child": left, "right_child": right,
            "leaf_value": values.tolist(),
            "leaf_depth": [depth_of_leaf[k] for k in range(leaves)]}


def _join(values, fmt="%d") -> str:
    return " ".join(fmt % v for v in values)


def make_forest_text(seed: int, *, trees: int, leaves: int,
                     grid: List[np.ndarray], beta: float = 1.0):
    """(model text, depth_stats). The text is what `Booster.save_model`
    writes for a binary classifier: it loads through
    `Server.load_model(model_str=...)` and `lgb.Booster(model_str=...)`
    like a trained one."""
    rng = np.random.Generator(np.random.PCG64(
        np.random.SeedSequence([int(seed), 0xF0535])))
    nf = len(grid)
    head = ["tree", "version=v3", "num_class=1", "num_tree_per_iteration=1",
            "label_index=0", "max_feature_idx=%d" % (nf - 1),
            "objective=binary sigmoid:1",
            "feature_names=" + " ".join("Column_%d" % f for f in range(nf)),
            "feature_infos=" + " ".join(
                "[%r:%r]" % (float(g[0]), float(g[-1])) for g in grid),
            "tree_sizes=", ""]
    blocks, depths = [], []
    zeros_i = _join([0] * (leaves - 1))
    for t in range(trees):
        tree = _grow(rng, grid, leaves, beta)
        depths += tree["leaf_depth"]
        blocks.append("\n".join([
            "Tree=%d" % t, "num_leaves=%d" % leaves, "num_cat=0",
            "split_feature=" + _join(tree["split_feature"]),
            "split_gain=" + zeros_i,
            "threshold=" + _join(tree["threshold"], "%.17g"),
            "decision_type=" + _join(
                [_NUMERICAL_DEFAULT_LEFT] * (leaves - 1)),
            "left_child=" + _join(tree["left_child"]),
            "right_child=" + _join(tree["right_child"]),
            "leaf_value=" + _join(tree["leaf_value"], "%.17g"),
            "leaf_weight=" + _join([0] * leaves),
            "leaf_count=" + _join([0] * leaves),
            "internal_value=" + zeros_i, "internal_weight=" + zeros_i,
            "internal_count=" + zeros_i, "is_linear=0", "shrinkage=0.1",
            "", ""]))
    text = "\n".join(head) + "\n" + "\n".join(blocks) + \
        "\nend of trees\n\npandas_categorical:null\n"
    d = np.asarray(depths)
    stats = {"trees": trees, "leaves": leaves,
             "leaf_depth_min": int(d.min()),
             "leaf_depth_median": float(np.median(d)),
             "leaf_depth_max": int(d.max()),
             "text_bytes": len(text)}
    return text, stats
