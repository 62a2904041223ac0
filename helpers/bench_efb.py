"""EFB wide-sparse on-chip benchmark (VERDICT r3 item 3 done-criterion).

200k x 1000, ~95% sparse via 20-feature exclusive groups, max_bin=63,
63 leaves.
Compares the portable EFB grower, the MXU path with the segmented
bundle-space scan (round-4 default), and optionally the round-3
expansion fallback.

Usage: python helpers/bench_efb.py [n_trees] [mode ...]
  modes: portable seg expand   (default: portable seg)
"""

import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def make_sparse(n=200_000, f=1000, group=20, seed=11, card=0):
    """card=0: continuous sparse values (~63 bins/feature — bundles stay
    bin-heavy, the MXU's unfavorable case). card=k>0: k distinct values
    per feature (the classic EFB target — one-hot/discrete encodings —
    where bundling collapses hundreds of features per column)."""
    rng = np.random.RandomState(seed)
    X = np.zeros((n, f), np.float32)
    logit = np.zeros(n, np.float32)
    for g in range(0, f, group):
        which = rng.randint(g, g + group, size=n)
        if card:
            vals = (rng.randint(1, card + 1, size=n) /
                    np.float32(card) + 0.5).astype(np.float32)
        else:
            vals = rng.rand(n).astype(np.float32) + 0.5
        X[np.arange(n), which] = vals
        if g == 0:
            logit += np.where(which == 0, vals * 2.0, 0.0)
    logit += 0.5 * X[:, 500] + 0.3 * rng.randn(n).astype(np.float32)
    y = (logit > np.median(logit)).astype(np.float32)
    return X, y


def run_mode(X, y, mode, n_trees):
    import lightgbm_tpu as lgb
    params = {"objective": "binary", "num_leaves": 63, "max_bin": 63,
              "learning_rate": 0.1, "verbosity": -1,
              "min_data_in_leaf": 20}
    if mode == "portable":
        params["efb_use_mxu"] = False
    elif mode == "expand":
        params["efb_segmented_scan"] = False
    elif mode == "seg_quant":
        # the flagship bench posture (quantized 3-channel histograms)
        params["use_quantized_grad"] = True
    ds = lgb.Dataset(X, label=y, params={"max_bin": 63})
    bst = lgb.Booster(params=params, train_set=ds)
    impl = bst.gbdt._hist_impl
    bst.update()  # warmup/compile tree 1
    float(np.asarray(bst.gbdt.train_score[:1])[0])
    t0 = time.time()
    bst.update_batch(n_trees)
    float(np.asarray(bst.gbdt.train_score[:1])[0])
    dt = time.time() - t0
    from lightgbm_tpu.metrics import AUCMetric
    sc = np.asarray(bst.gbdt.train_score)
    auc = AUCMetric._auc_fast(sc, y > 0, np.ones_like(y))
    print(f"{mode:9s} impl={impl:8s} {n_trees} trees in {dt:7.1f}s = "
          f"{n_trees / dt:5.3f} trees/s  train-AUC@{n_trees + 1} {auc:.5f}",
          flush=True)
    return n_trees / dt


def run_reference(X, y, n_trees):
    """Same-host reference binary at this shape, single core (VERDICT r4
    item 3: the EFB story needs an external anchor, not just internal
    A/Bs). Sparse LibSVM input (a dense 200k x 1000 CSV would be
    ~800 MB); the reference's own EFB (enable_bundle) is on by default.
    Trains twice (2 and n+2 iterations) so its loading/binning time
    cancels out of the per-tree rate."""
    import subprocess
    import tempfile
    bin_ = os.environ.get("LGBM_REFERENCE_BIN", "/tmp/lgbbuild/lightgbm")
    if not os.path.exists(bin_):
        print(f"# reference binary absent ({bin_}); skipping ref row")
        return None
    import shutil
    d = tempfile.mkdtemp(prefix="efb_ref_")
    path = os.path.join(d, "train.svm")
    with open(path, "w") as fh:
        for i in range(X.shape[0]):
            nz = np.nonzero(X[i])[0]
            fh.write("%d %s\n" % (
                int(y[i]),
                " ".join("%d:%.6g" % (j, X[i, j]) for j in nz)))
    times = {}
    for iters in (2, n_trees + 2):
        conf = os.path.join(d, f"train_{iters}.conf")
        with open(conf, "w") as fh:
            fh.write(f"task=train\ndata={path}\nobjective=binary\n"
                     f"num_iterations={iters}\nnum_leaves=63\n"
                     "max_bin=63\nlearning_rate=0.1\n"
                     "min_data_in_leaf=20\nnum_threads=1\nverbosity=-1\n"
                     f"output_model={d}/m{iters}.txt\n")
        t0 = time.time()
        res = subprocess.run([bin_, f"config={conf}"],
                             capture_output=True, text=True, timeout=7200)
        assert res.returncode == 0, \
            res.stdout[-2000:] + res.stderr[-2000:]
        times[iters] = time.time() - t0
    shutil.rmtree(d, ignore_errors=True)
    rate = n_trees / max(times[n_trees + 2] - times[2], 1e-9)
    print(f"reference impl=1-core   {n_trees} trees in "
          f"{times[n_trees + 2] - times[2]:7.1f}s = {rate:5.3f} trees/s "
          f"(loading/binning {times[2]:.0f}s excluded)", flush=True)
    return rate


def main():
    n_trees = int(sys.argv[1]) if len(sys.argv) > 1 else 10
    card = int(os.environ.get("EFB_CARD", 0))
    modes = sys.argv[2:] or ["portable", "seg"]
    X, y = make_sparse(card=card)
    rates = {}
    for mode in modes:
        if mode == "ref":
            r = run_reference(X, y, n_trees)
            if r:
                rates[mode] = r
            continue
        rates[mode] = run_mode(X, y, mode, n_trees)
    if "seg" in rates and "portable" in rates:
        print(f"# card={card}: segmented-MXU / portable speedup: "
              f"{rates['seg'] / rates['portable']:.2f}x")
    if "ref" in rates and "portable" in rates:
        print(f"# card={card}: ours-portable / reference-1-core: "
              f"{rates['portable'] / rates['ref']:.2f}x")


if __name__ == "__main__":
    main()
