"""The ranking cell's tree check, held against arithmetic one precision
down: what `leaf_sum_err_root_ulps` reads when a tree's leaf values come
from bfloat16 where the configuration states float32.

`benchmark/configs/mslr_rank.json` sets that limit between two readings:
the program's own on the chip, and the reference's when its leaf sums
are made in bfloat16. This script takes the second, through the
comparison the runner uses (`lambdarank_numpy.check_step`) and at the
cell's own size: it trains one fused block on the configuration's data
from `--seed`, takes trees 0 and 1 as the program grew them, and plants
leaf values made from the reference's float64 gradients in two ways:

  rounded      every gradient and hessian rounded to bfloat16, summed
               exactly: what bfloat16 GRADIENTS would give
  accumulated  float32 gradients added one at a time, in document
               order, into a bfloat16 accumulator a leaf: what a
               bfloat16 HISTOGRAM would give

A rounding fault grows like the square root of a leaf's rows and the
unit (float32 roundings of the ROOT's sums) like the rows, so `rounded`
reads less the larger the set; `accumulated` stalls once a sum is 256
times an addend and reads more.

Usage: python helpers/rank_controls.py [--seed N] [--rehearse-cpu]
Writes chiprun_out/rank_controls.json beside the lines it prints.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

CONTROLS = ("rounded", "accumulated")


def leaf_sums_in_bfloat16(leaf, values, num_leaves, how):
    """float64 [num_leaves]: the sum of `values` over each leaf's rows
    under the control `how` (CONTROLS)."""
    from ml_dtypes import bfloat16
    if how == "rounded":
        return np.bincount(leaf, minlength=num_leaves,
                           weights=values.astype(bfloat16).astype(np.float64))
    if how != "accumulated":
        raise ValueError(how)
    order = np.argsort(leaf, kind="stable")        # document order a leaf
    ends = np.cumsum(np.bincount(leaf, minlength=num_leaves))
    v = values[order].astype(np.float32)
    out = np.zeros(num_leaves)
    for k, (lo, hi) in enumerate(zip(np.r_[0, ends[:-1]], ends)):
        if hi > lo:
            # the float32 addend is rounded into the accumulator's type
            # by the addition itself: the sum is what is held in bfloat16
            out[k] = float(np.add.accumulate(v[lo:hi].astype(bfloat16))[-1])
    return out


def plant(tree, leaf, grad, hess, how, *, learning_rate, lambda_l2=0.0):
    """`tree` with the leaf values its sums under `how` imply."""
    n = len(tree["leaf_value"])
    G = leaf_sums_in_bfloat16(leaf, grad, n, how)
    H = leaf_sums_in_bfloat16(leaf, hess, n, how)
    with np.errstate(divide="ignore", invalid="ignore"):
        return dict(tree, leaf_value=-G / (H + lambda_l2) * learning_rate)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=2147484101)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="the configuration's rehearsal size, on a CPU")
    args = ap.parse_args(argv)

    from benchmark import harness
    from benchmark.generators.mslr import make_mslr_like, query_lengths
    from benchmark.reference import gbdt_numpy
    from benchmark.reference import lambdarank_numpy as ref
    cell = harness.load_cell("mslr_rank_train")
    cfg = cell["config"]
    if args.rehearse_cpu:
        cfg, _ = harness.rehearsal_overlay(cfg, cell["traffic"])
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.config import Config
    if jax.default_backend() == "cpu" and not args.rehearse_cpu:
        print("a CPU backend takes --rehearse-cpu", file=sys.stderr)
        return 2

    lengths = query_lengths(int(cfg["num_queries"]), int(cfg["num_data"]),
                            int(cfg["longest_query"]))
    X, y, sizes, _ = make_mslr_like(lengths, int(cfg["num_features"]),
                                    args.seed)
    params = {"objective": cfg["objective"], "num_leaves": cfg["num_leaves"],
              "max_bin": cfg["max_bin"],
              "learning_rate": cfg["learning_rate"], "verbosity": -1}
    params.update(cfg.get("params", {}))
    resolved = Config(dict(params))
    dtrain = lgb.Dataset(X, label=y, group=sizes,
                         params={"max_bin": cfg["max_bin"]})
    t0 = time.perf_counter()
    bst = lgb.train(dict(params), dtrain,
                    num_boost_round=int(resolved.fused_block_size))
    print("# %d trees on %d x %d in %.1fs (%s)"
          % (bst.current_iteration(), X.shape[0], X.shape[1],
             time.perf_counter() - t0, jax.default_backend()), flush=True)
    trees = [gbdt_numpy.flatten_tree(t["tree_structure"])
             for t in bst.dump_model(num_iteration=2)["tree_info"]]
    kw = dict(learning_rate=float(resolved.learning_rate),
              min_data_in_leaf=int(resolved.min_data_in_leaf),
              min_sum_hessian_in_leaf=float(resolved.min_sum_hessian_in_leaf),
              lambda_l2=float(resolved.lambda_l2),
              sigmoid=float(resolved.sigmoid),
              truncation_level=int(resolved.lambdarank_truncation_level),
              norm=bool(resolved.lambdarank_norm))
    routed, out = {}, {"seed": args.seed, "rows": int(X.shape[0]),
                       "leaves": int(cfg["num_leaves"]),
                       "platform": jax.default_backend(),
                       "limit": cfg["expect"]["leaf_sum_err_root_ulps"]}
    bins = dtrain._binned.bins
    for k in (0, 1):
        got = ref.check_step(k, trees, X, y, sizes, bins, routed=routed, **kw)
        out["tree%d.program" % k] = got["leaf_sum_err_root_ulps"]
        score = np.zeros(len(y))
        for j in range(k):
            score += trees[j]["leaf_value"][routed[j]]
        grad, hess = ref.lambdarank_gradients(
            score, y, sizes, sigmoid=kw["sigmoid"],
            truncation_level=kw["truncation_level"], norm=kw["norm"])
        for how in CONTROLS:
            planted = trees[:k] + [plant(
                trees[k], routed[k], grad, hess, how,
                learning_rate=kw["learning_rate"],
                lambda_l2=kw["lambda_l2"])]
            got = ref.check_step(k, planted, X, y, sizes, bins,
                                 routed=routed, **kw)
            out["tree%d.%s" % (k, how)] = got["leaf_sum_err_root_ulps"]
            out["tree%d.%s.root_gain_shortfall" % (k, how)] = \
                got["root_gain_shortfall"]
        print("# tree %d: %s" % (k, {n: v for n, v in out.items()
                                     if n.startswith("tree%d" % k)}),
              flush=True)
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out", "rank_controls.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
