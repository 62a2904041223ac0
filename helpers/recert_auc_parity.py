"""Re-certify 200-tree AUC parity vs the reference binary at TODAY'S
defaults (VERDICT r3 item 8): the recorded 0.98388-vs-0.98394 number
predates quantized gradients, packed bins, EFB-default-on, the
segmented scan, and the fused loop.

Trains both on the identical Higgs-shaped 1M x 28 synthetic set with
255 leaves / 255 bins / 200 trees and compares held-out AUC.

Usage: python helpers/recert_auc_parity.py [n_trees] [rows]
Needs the reference CLI (helpers/build_reference_cli.sh ->
/tmp/lgbbuild/lightgbm).
"""

import os
import subprocess
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
_BIN = os.environ.get("LGBM_REFERENCE_BIN", "/tmp/lgbbuild/lightgbm")


def main():
    n_trees = int(sys.argv[1]) if len(sys.argv) > 1 else 200
    rows = int(sys.argv[2]) if len(sys.argv) > 2 else 1_000_000
    from bench import make_higgs_like, N_FEATURES
    from lightgbm_tpu.metrics import AUCMetric
    X, y = make_higgs_like(rows, N_FEATURES)
    Xva, yva = make_higgs_like(40_000, N_FEATURES, seed=99)
    wva = np.ones_like(yva)

    # ---- ours, today's library DEFAULTS (exact grads) + bench posture
    import lightgbm_tpu as lgb
    out = {}
    for name, extra in [("default", {}),
                        ("bench", {"use_quantized_grad": True})]:
        ds = lgb.Dataset(X, label=y, params={"max_bin": 255})
        bst = lgb.Booster(params={
            "objective": "binary", "num_leaves": 255, "max_bin": 255,
            "learning_rate": 0.1, "min_data_in_leaf": 20,
            "verbosity": -1, **extra}, train_set=ds)
        t0 = time.time()
        # 20-tree dispatches: the block length is a static argument of
        # the fused program, so one length compiles once
        done = 0
        while done < n_trees:
            step = min(20, n_trees - done)
            bst.update_batch(step)
            float(np.asarray(bst.gbdt.train_score[:1])[0])
            done += step
        sc = bst.predict(Xva, raw_score=True)
        out[name] = AUCMetric._auc_fast(sc, yva > 0, wva)
        print(f"ours[{name}]: AUC@{bst.current_iteration()} = "
              f"{out[name]:.5f}  ({time.time() - t0:.0f}s)", flush=True)

    # ---- reference binary, same data/params
    if not os.path.exists(_BIN):
        print("# reference binary absent; ours-only record")
        return
    d = tempfile.mkdtemp(prefix="recert_")
    np.savetxt(os.path.join(d, "train.csv"),
               np.column_stack([y, X]), delimiter=",", fmt="%.7g")
    np.savetxt(os.path.join(d, "valid.csv"),
               np.column_stack([yva, Xva]), delimiter=",", fmt="%.7g")
    conf = os.path.join(d, "train.conf")
    with open(conf, "w") as fh:
        fh.write(f"task=train\ndata={d}/train.csv\nobjective=binary\n"
                 f"num_iterations={n_trees}\nnum_leaves=255\nmax_bin=255\n"
                 "learning_rate=0.1\nmin_data_in_leaf=20\n"
                 "header=false\nlabel_column=0\nverbosity=-1\n"
                 f"output_model={d}/ref_model.txt\n")
    t0 = time.time()
    res = subprocess.run([_BIN, f"config={conf}"], capture_output=True,
                         text=True, timeout=3600)
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-2000:]
    t_ref = time.time() - t0
    pconf = os.path.join(d, "pred.conf")
    with open(pconf, "w") as fh:
        fh.write(f"task=predict\ndata={d}/valid.csv\n"
                 f"input_model={d}/ref_model.txt\n"
                 f"output_result={d}/preds.txt\nheader=false\n"
                 "label_column=0\npredict_raw_score=true\n")
    subprocess.run([_BIN, f"config={pconf}"], check=True,
                   capture_output=True, timeout=600)
    ref_sc = np.loadtxt(os.path.join(d, "preds.txt"))
    ref_auc = AUCMetric._auc_fast(ref_sc, yva > 0, wva)
    print(f"reference: AUC@{n_trees} = {ref_auc:.5f}  "
          f"({t_ref:.0f}s train = {n_trees / t_ref:.2f} trees/s 1-core)")
    for name, auc in out.items():
        print(f"# gap[{name} - reference] = {auc - ref_auc:+.5f}")


if __name__ == "__main__":
    main()
