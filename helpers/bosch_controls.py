"""The Bosch cell's checks, held against three planted faults: what
`gbdt_missing_numpy.check_step` and the held-out AUC read when the
program's arithmetic or its handling of missing values is made worse
on purpose, at the cell's own size.

`benchmark/configs/bosch_missing.json` sets each limit between two
readings: the program's own on the chip, and a control's. This script
takes the second, through the comparison the runner uses: it trains
`auc_trees` trees on the cell's training rows (its one fixed table,
`TRAIN_ROWS_SEED`, or with `--table SEED` a table drawn from another
seed: the program's own readings on it are then the cell's checks on
other rows, at full size), takes trees 0 and 1 as the program grew
them, and

  accumulated   plants leaf values made from the reference's float64
                gradients added one at a time, in row order, into a
                bfloat16 accumulator a leaf (what a bfloat16 HISTOGRAM
                would give): read by `leaf_sum_err_root_ulps`;
  nan_right     plants every node's default direction as right (a
                program that ignores the learned direction: missing
                values always go right): read by `nan_direction_flips`
                and `nan_gain_shortfall_ulps` on trees 0 and 1, and by
                the held-out AUC after `auc_trees` trees, the model's
                own trees with every missing value sent right;
  tile_dropped  the root a histogram without one feature tile (the 128
                columns that hold the best root column) would choose:
                the best root over the other columns, read by
                `root_gain_shortfall`;
  bins_shifted  the binned matrix with the values of the bin just over
                the root's threshold put in the threshold's bin (a
                binning that puts a boundary in the wrong place): read
                by `binning_disagreements`.

Usage: python helpers/bosch_controls.py [--seed N] [--table SEED]
       [--rehearse-cpu]
Its last line is every reading, one JSON object.
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

#: columns of a feature tile (learner/histogram_mxu.feature_tile)
TILE = 128
KEYS = ("leaf_sum_err_root_ulps", "root_gain_shortfall",
        "nan_direction_flips", "nan_gain_shortfall_ulps", "nan_nodes",
        "nan_left_nodes", "binning_disagreements")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=2147485201,
                    help="the held-out rows' seed")
    ap.add_argument("--table", type=int, default=None,
                    help="the training rows' seed (default: the cell's "
                         "fixed table)")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="the configuration's rehearsal size, on a CPU")
    args = ap.parse_args(argv)

    from benchmark import harness
    from benchmark.generators.bosch import TRAIN_ROWS_SEED, make_bosch_like
    from benchmark.reference import gbdt_missing_numpy as ref
    cell = harness.load_cell("bosch_missing_train")
    cfg = cell["config"]
    if args.rehearse_cpu:
        cfg, _ = harness.rehearsal_overlay(cfg, cell["traffic"])
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.config import Config
    if jax.default_backend() == "cpu" and not args.rehearse_cpu:
        print("a CPU backend takes --rehearse-cpu", file=sys.stderr)
        return 2

    table = TRAIN_ROWS_SEED if args.table is None else args.table
    X, y, cut = make_bosch_like(int(cfg["num_data"]), table)
    Xh, yh, _ = make_bosch_like(int(cfg["held_out_rows"]), args.seed,
                                stream=1, threshold=cut)
    params = {"objective": cfg["objective"], "num_leaves": cfg["num_leaves"],
              "max_bin": cfg["max_bin"],
              "learning_rate": cfg["learning_rate"], "verbosity": -1}
    params.update(cfg.get("params", {}))
    resolved = Config(dict(params))
    dtrain = lgb.Dataset(X, label=y, params={"max_bin": cfg["max_bin"]})
    n_auc = int(cfg["expect"]["auc_trees"])
    t0 = time.perf_counter()
    bst = lgb.train(dict(params), dtrain, num_boost_round=max(
        int(resolved.fused_block_size), n_auc))
    print("# %d trees on %d x %d in %.1fs (%s)"
          % (bst.current_iteration(), X.shape[0], X.shape[1],
             time.perf_counter() - t0, jax.default_backend()), flush=True)
    trees = [ref.flatten_tree(t["tree_structure"])
             for t in bst.dump_model(num_iteration=n_auc)["tree_info"]]
    kw = dict(learning_rate=float(resolved.learning_rate),
              min_data_in_leaf=int(resolved.min_data_in_leaf),
              min_sum_hessian_in_leaf=float(resolved.min_sum_hessian_in_leaf),
              lambda_l2=float(resolved.lambda_l2),
              slack_ulps=float(cfg["expect"]["nan_slack_ulps"]))
    bins = dtrain._binned.bins
    bins_t = np.ascontiguousarray(np.asarray(bins).T)
    out = {"seed": args.seed, "table": table, "rows": int(X.shape[0]),
           "leaves": int(cfg["num_leaves"]),
           "platform": jax.default_backend(),
           "limits": {k: cfg["expect"][k] for k in (
               "leaf_sum_err_root_ulps", "root_gain_rtol",
               "nan_gain_shortfall_ulps", "nan_slack_ulps", "auc_floor")}}
    right = [dict(t, default_left=np.zeros_like(t["default_left"]))
             for t in trees]
    routed, routed_right = {}, {}
    for k in (0, 1):
        best = ref.check_step(k, trees, X, y, bins, routed=routed,
                              bins_t=bins_t, **kw)
        out["tree%d.program" % k] = {n: best[n] for n in KEYS}
        # accumulated: the program's tree, its leaves' sums in bfloat16
        bias = ref.init_score(y)
        score = np.full(len(y), bias) if k == 0 else sum(
            trees[j]["leaf_value"][routed[j]] for j in range(k))
        grad, hess = ref.grad_hess(score, y)
        n = len(trees[k]["leaf_value"])
        G = leaf_sums_in_bfloat16(routed[k], grad, n, "accumulated")
        H = leaf_sums_in_bfloat16(routed[k], hess, n, "accumulated")
        with np.errstate(divide="ignore", invalid="ignore"):
            planted = dict(trees[k], leaf_value=-G / (H + kw["lambda_l2"]) *
                           kw["learning_rate"] + (bias if k == 0 else 0.0))
        got = ref.check_step(k, trees[:k] + [planted], X, y, bins,
                             routed=dict(routed), bins_t=bins_t, **kw)
        out["tree%d.accumulated" % k] = got["leaf_sum_err_root_ulps"]
        # nan_right: every default direction right
        got = ref.check_step(k, right[:k + 1], X, y, bins,
                             routed=routed_right, bins_t=bins_t, **kw)
        out["tree%d.nan_right" % k] = {n: got[n] for n in KEYS}
        # tile_dropped: the best root without the tile of the best column
        tile = best["best_root"][0] // TILE
        nan = np.isnan(X).any(axis=0)
        rest = -np.inf
        for f in range(bins_t.shape[0]):
            if f // TILE == tile:
                continue
            col = bins_t[f].astype(np.int64)
            nb = int(col.max()) + 1
            g = np.bincount(col, weights=grad, minlength=nb)
            h = np.bincount(col, weights=hess, minlength=nb)
            c = np.bincount(col, minlength=nb).astype(np.float64)
            gain, _, _ = ref.missing_rule(
                g, h, c, has_nan=bool(nan[f]),
                min_data_in_leaf=kw["min_data_in_leaf"],
                min_sum_hessian_in_leaf=kw["min_sum_hessian_in_leaf"],
                lambda_l2=kw["lambda_l2"])
            rest = max(rest, gain)
        out["tree%d.tile_dropped" % k] = {
            "root_gain_shortfall": (best["root_gain_best"] - rest) /
            abs(best["root_gain_best"]), "dropped_tile": int(tile)}
        # bins_shifted: the bin over the root's threshold merged into it
        f0 = int(trees[k]["feature"][0])
        t0 = ref.threshold_bin(X[:, f0], bins_t[f0],
                               trees[k]["threshold"][0])
        shifted = bins_t.copy()
        shifted[f0] = np.where(bins_t[f0] == t0 + 1, t0, bins_t[f0])
        misbinned, _ = ref.binning_disagreements(trees[k], X, shifted)
        out["tree%d.bins_shifted" % k] = {
            "binning_disagreements": misbinned, "feature": f0,
            "threshold_bin": t0}
        print("# tree %d: %s" % (k, {n: v for n, v in out.items()
                                     if n.startswith("tree%d" % k)}),
              flush=True)
    # held-out AUC after n_auc trees, and with every missing value right
    prog = bst.predict(Xh, num_iteration=n_auc, raw_score=True)
    raw = sum(t["leaf_value"][ref.route(t, Xh)] for t in right)
    out["auc_trees"] = n_auc
    out["auc.program"] = ref.auc(yh, prog)
    out["auc.nan_right"] = ref.auc(yh, raw)
    print("# held-out AUC after %d trees: %s"
          % (n_auc, {k: v for k, v in out.items() if k.startswith("auc")}),
          flush=True)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
