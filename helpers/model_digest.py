"""The trees a benchmark cell's job grows, as digests: what "the same
work" is checked by when a change claims to do a cell's work better.

Draws the cell's training rows from --seed exactly as its runner does
(benchmark/runners/train.py, train_cat.py), trains --trees trees with
one `lgb.train` under the cell's parameters (fused blocks, as the cell
runs them) and prints, as one JSON line, the SHA-256 of the model text
of those trees (the `parameters:` dump left out: it echoes options, not
trees) and of every tree's own block. Two checkouts that print the same
line under one seed grew the same trees to the last digit of every
threshold and leaf value. Run it in the parent's checkout and in the
change's, on the chip:

    python helpers/model_digest.py --workload higgs_train --seed 7

--rehearse-cpu takes the cell's tiny rehearsal size on the CPU.
Cells of kind `train` and `train_cat`.
"""

import argparse
import hashlib
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--trees", type=int, default=20)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    cell = harness.load_cell(args.workload)
    cfg, traffic = cell["config"], cell["traffic"]
    if args.rehearse_cpu:
        cfg, traffic = harness.rehearsal_overlay(cfg, traffic)
    device = harness.claim_device(cell["chips"], args.rehearse_cpu)

    import lightgbm_tpu as lgb
    rows = int(cfg["num_data"])
    if cfg["kind"] == "train":
        from benchmark.generators.higgs import make_higgs_like
        X, y, _ = make_higgs_like(rows, int(cfg["num_features"]),
                                  args.seed, stream=0)
        dataset_kw = {}
    elif cfg["kind"] == "train_cat":
        from benchmark.generators.expo import make_expo_like
        X, y, _ = make_expo_like(rows, args.seed, stream=0,
                                 rows_seed=int(cfg["train_rows_seed"]))
        dataset_kw = {"categorical_feature":
                      [int(c) for c in cfg["categorical_feature"]]}
    else:
        raise SystemExit("no digest for a cell of kind %r" % cfg["kind"])
    params = {"objective": cfg["objective"],
              "num_leaves": cfg["num_leaves"], "max_bin": cfg["max_bin"],
              "learning_rate": cfg["learning_rate"], "verbosity": -1}
    params.update(cfg.get("params", {}))
    params.update(traffic.get("params", {}))
    dtrain = lgb.Dataset(X, label=y, params={"max_bin": cfg["max_bin"]},
                         **dataset_kw)
    bst = lgb.train(dict(params), dtrain, num_boost_round=args.trees)
    text = bst.model_to_string().split("\nparameters:")[0]
    trees = text.split("\nTree=")[1:]

    def digest(s):
        return hashlib.sha256(s.encode()).hexdigest()

    print(json.dumps({
        "workload": args.workload, "seed": args.seed, "device": device,
        "trees": len(trees), "model_sha256": digest(text),
        "tree_sha256": [digest(t)[:16] for t in trees]}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
