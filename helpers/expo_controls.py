"""The categorical cell's tree check, held against two planted faults:
what `gbdt_cat_numpy.check_step` reads when a tree's leaf values come
from bfloat16 sums where the configuration states float32, and when the
published rule is applied WITHOUT its min_data_per_group batching.

`benchmark/configs/expo_categorical.json` sets its limits between two
readings each: the program's own on the chip, and a control's. This
script takes the second, through the comparison the runner uses and at
the cell's own size: it trains one fused block on the configuration's
training rows (its one fixed table, under the codes of `--seed`), takes
trees 0 and 1 as the program grew them, and

  accumulated  plants leaf values made from the reference's float64
               gradients added one at a time, in row order, into a
               bfloat16 accumulator a leaf (what a bfloat16 HISTOGRAM
               would give): read by `leaf_sum_err_root_ulps`;
  rounded      the same with every gradient rounded to bfloat16 and
               summed exactly (bfloat16 GRADIENTS);
  no_batching  holds the program's trees against the rule with every
               step that meets the floors evaluated (the deviation
               learner/split.py stated before PR 35): read by
               `cat_gain_shortfall_ulps`, the program's batched best against
               that rule's best at the same node.

With --program-without-batching it also trains one block with
`min_data_per_group=1` (a PROGRAM that evaluates every step, as the one
before PR 35 did) and holds its trees against the published rule at the
configuration's 100: read by `cat_infeasible_nodes`, the left sets that
end inside a group, which no float32 noise blurs.

With --as-numbers it also trains `auc_trees` trees on the same rows with
the categorical columns passed as plain numbers (no
`categorical_feature`: what a program that ignores categories does) and
says the held-out AUC, which the configuration's `auc_floor` has to sit
above.

Usage: python helpers/expo_controls.py [--seed N] [--as-numbers]
       [--program-without-batching] [--rehearse-cpu]
Writes chiprun_out/expo_controls.json beside the lines it prints.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "helpers"))

from rank_controls import leaf_sums_in_bfloat16  # noqa: E402

CONTROLS = ("rounded", "accumulated")


def plant(tree, leaf, grad, hess, l2, how, *, learning_rate, bias=0.0):
    """`tree` with the leaf values its sums under `how` imply, each leaf
    under its own L2 term (`gbdt_cat_numpy.leaf_l2`)."""
    n = len(tree["leaf_value"])
    G = leaf_sums_in_bfloat16(leaf, grad, n, how)
    H = leaf_sums_in_bfloat16(leaf, hess, n, how)
    with np.errstate(divide="ignore", invalid="ignore"):
        return dict(tree, leaf_value=-G / (H + l2) * learning_rate + bias)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=2147484501)
    ap.add_argument("--as-numbers", action="store_true")
    ap.add_argument("--program-without-batching", action="store_true")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="the configuration's rehearsal size, on a CPU")
    args = ap.parse_args(argv)

    from benchmark import harness
    from benchmark.generators.expo import make_expo_like
    from benchmark.reference import gbdt_cat_numpy as ref
    from benchmark.runners.train_cat import _rule_params
    cell = harness.load_cell("expo_categorical_train")
    cfg = cell["config"]
    if args.rehearse_cpu:
        cfg, _ = harness.rehearsal_overlay(cfg, cell["traffic"])
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.config import Config
    if jax.default_backend() == "cpu" and not args.rehearse_cpu:
        print("a CPU backend takes --rehearse-cpu", file=sys.stderr)
        return 2

    # the cell's own training rows: one fixed table, the seed's codes
    X, y, cut = make_expo_like(int(cfg["num_data"]), args.seed,
                               rows_seed=int(cfg["train_rows_seed"]))
    cats = [int(c) for c in cfg["categorical_feature"]]
    params = {"objective": cfg["objective"], "num_leaves": cfg["num_leaves"],
              "max_bin": cfg["max_bin"],
              "learning_rate": cfg["learning_rate"], "verbosity": -1}
    params.update(cfg.get("params", {}))
    resolved = Config(dict(params))
    dtrain = lgb.Dataset(X, label=y, categorical_feature=cats,
                         params={"max_bin": cfg["max_bin"]})
    t0 = time.perf_counter()
    n_auc = int(cfg["expect"]["auc_trees"]) if args.as_numbers else 0
    bst = lgb.train(dict(params), dtrain, num_boost_round=max(
        int(resolved.fused_block_size), n_auc))
    print("# %d trees on %d x %d in %.1fs (%s)"
          % (bst.current_iteration(), X.shape[0], X.shape[1],
             time.perf_counter() - t0, jax.default_backend()), flush=True)
    trees = [ref.flatten_tree(t["tree_structure"])
             for t in bst.dump_model(num_iteration=2)["tree_info"]]
    kw = _rule_params(resolved)
    kw["slack_ulps"] = float(cfg["expect"]["cat_prefix_slack_ulps"])
    bins = dtrain._binned.bins
    sorted_column = np.zeros(X.shape[1], bool)
    for f in cats:
        sorted_column[f] = int(bins[:, f].max()) + 1 > kw["max_cat_to_onehot"]
    keys = ("leaf_sum_err_root_ulps", "cat_gain_shortfall_ulps",
            "cat_infeasible_nodes", "cat_prefix_slack_ulps",
            "root_gain_shortfall", "cat_nodes", "nodes")
    routed, out = {}, {"seed": args.seed, "rows": int(X.shape[0]),
                       "leaves": int(cfg["num_leaves"]),
                       "platform": jax.default_backend(),
                       "limits": {k: cfg["expect"][k] for k in (
                           "leaf_sum_err_root_ulps", "cat_gain_shortfall_ulps",
                           "cat_prefix_slack_ulps", "root_gain_rtol")}}
    for k in (0, 1):
        got = ref.check_step(k, trees, X, y, bins, cats, routed=routed, **kw)
        out["tree%d.program" % k] = {n: got[n] for n in keys}
        bias = ref.init_score(y)
        score = np.full(len(y), bias) if k == 0 else sum(
            trees[j]["leaf_value"][routed[j]] for j in range(k))
        grad, hess = ref.grad_hess(score, y)
        l2 = ref.leaf_l2(trees[k], sorted_column,
                         lambda_l2=kw["lambda_l2"], cat_l2=kw["cat_l2"])
        for how in CONTROLS:
            planted = trees[:k] + [plant(
                trees[k], routed[k], grad, hess, l2, how,
                learning_rate=kw["learning_rate"],
                bias=bias if k == 0 else 0.0)]
            got = ref.check_step(k, planted, X, y, bins, cats,
                                 routed=routed, **kw)
            out["tree%d.%s" % (k, how)] = got["leaf_sum_err_root_ulps"]
        got = ref.check_step(k, trees, X, y, bins, cats, routed=routed,
                             batching=False, **kw)
        out["tree%d.no_batching" % k] = {n: got[n] for n in keys}
        print("# tree %d: %s" % (k, {n: v for n, v in out.items()
                                     if n.startswith("tree%d" % k)}),
              flush=True)
    if args.program_without_batching:
        loose = lgb.train(dict(params, min_data_per_group=1), dtrain,
                          int(resolved.fused_block_size))
        ltrees = [ref.flatten_tree(t["tree_structure"])
                  for t in loose.dump_model(num_iteration=2)["tree_info"]]
        lrouted = {}
        for k in (0, 1):
            got = ref.check_step(k, ltrees, X, y, bins, cats,
                                 routed=lrouted, **kw)
            out["tree%d.program_without_batching" % k] = {
                n: got[n] for n in keys}
            print("# tree %d of a program without batching: %s"
                  % (k, out["tree%d.program_without_batching" % k]),
                  flush=True)
    if args.as_numbers:
        Xh, yh, _ = make_expo_like(int(cfg["held_out_rows"]), args.seed,
                                   stream=1, threshold=cut)
        plain = lgb.train(dict(params), lgb.Dataset(
            X, label=y, params={"max_bin": cfg["max_bin"]}), n_auc)
        out["auc_trees"] = n_auc
        out["auc.categories_as_numbers"] = ref.auc(
            yh, plain.predict(Xh, raw_score=True))
        out["auc.program"] = ref.auc(yh, bst.predict(
            Xh, num_iteration=n_auc, raw_score=True))
        out["auc_floor"] = cfg["expect"]["auc_floor"]
        print("# held-out AUC after %d trees: %s"
              % (n_auc, {k: v for k, v in out.items()
                         if k.startswith("auc")}), flush=True)
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out", "expo_controls.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
