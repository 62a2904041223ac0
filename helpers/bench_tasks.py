"""TPU bench records for the reference's non-binary task matrix
(VERDICT r4 item 2): regression, multiclass, lambdarank — each timed on
the TPU AND run through the same-host reference binary on one core with
identical data, tree shape (255 leaves / 255 bins), learning rate, and
tree count, so every task of BASELINE.json's config list has a
comparable perf row (docs/Experiments.rst:111-155 publishes 5 tasks;
round 4 had TPU numbers for 1). Postures differ deliberately and are
printed with the rows: ours runs the BENCH posture (quantized grads +
overshoot 1.75 + bridge gate — the documented headline posture,
bench.py), the reference runs its own defaults (this fork predates
use_quantized_grad); both sides' task metrics are printed so the
quality cost of the posture is visible next to the speed.

Shapes are device-scaled (1M rows x 28 features, 255 leaves / 255
bins — the headline bench shape) so the rows are comparable with the
Higgs record. Metrics are computed by THIS script's own evaluators on
identical held-out predictions from both sides.

Usage: python helpers/bench_tasks.py [task ...] [--trees N]
  tasks: regression multiclass lambdarank (default: all)
Needs the reference CLI for the comparison half
(helpers/build_reference_cli.sh -> /tmp/lgbbuild/lightgbm); without it,
ours-only rows are printed.
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

ROWS = int(os.environ.get("BENCH_ROWS", 1_000_000))
F = 28
VROWS = 40_000
POSTURE = {"num_leaves": 255, "max_bin": 255, "learning_rate": 0.1,
           "min_data_in_leaf": 20, "verbosity": -1,
           "use_quantized_grad": True, "growth_overshoot": 1.75,
           "growth_bridge_gate": 0.93}

# same-host single-core reference rates on these exact synthetic sets
# (run_reference on an idle host) — the
# per-task anchors bench.py's task rows normalize against, mirroring
# SINGLE_CORE_TREES_PER_SEC for the binary headline
SINGLE_CORE_RATES = {"regression": 3.76, "multiclass": 2.93,
                     "lambdarank": 2.47}


# ---------------------------------------------------------------- data
def make_regression(n, seed):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, F).astype(np.float32)
    y = (1.5 * X[:, 0] - 0.9 * X[:, 1] + 0.8 * X[:, 2] * X[:, 3] +
         0.6 * np.abs(X[:, 4]) - 0.5 * X[:, 5] ** 2 +
         0.4 * np.sin(2 * X[:, 6]) + 0.3 * rng.randn(n)).astype(np.float32)
    return X, y, None


def make_multiclass(n, seed, k=5):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, F).astype(np.float32)
    # class geometry must be seed-INDEPENDENT so train and held-out
    # splits share one distribution (only the rows/noise vary by seed)
    centers = np.random.RandomState(7).randn(k, 6) * 1.2
    d = ((X[:, None, :6] - centers[None]) ** 2).sum(-1)
    d += 1.5 * rng.gumbel(size=(n, k))
    y = np.argmin(d, axis=1).astype(np.float32)
    return X, y, None


def make_lambdarank(n, seed, qsize=20):
    rng = np.random.RandomState(seed)
    nq = n // qsize
    n = nq * qsize
    X = rng.randn(n, F).astype(np.float32)
    raw = (1.1 * X[:, 0] - 0.7 * X[:, 1] + 0.5 * X[:, 2] * X[:, 3] +
           0.9 * rng.randn(n))
    # 5-level relevance by global quantile (label_gain default covers it)
    qs = np.quantile(raw, [0.5, 0.75, 0.9, 0.97])
    y = np.digitize(raw, qs).astype(np.float32)
    # uneven queries of the same total (a heavy tail, as real query logs
    # have): equal lengths hid a layout padded to the longest query
    w = rng.lognormal(0.0, 0.8, nq)
    group = np.maximum(1, np.floor(w / w.sum() * (n - nq)).astype(np.int32)
                       + 1)
    group[np.argmax(group)] += n - group.sum()
    return X, y, group


# ------------------------------------------------------------- metrics
def rmse(pred, y):
    return float(np.sqrt(np.mean((pred - y) ** 2)))


def multi_logloss(pred_raw, y, k):
    p = pred_raw.reshape(-1, k)
    p = p - p.max(axis=1, keepdims=True)
    logp = p - np.log(np.exp(p).sum(axis=1, keepdims=True))
    return float(-np.mean(logp[np.arange(len(y)), y.astype(int)]))


def ndcg_at(pred, y, group, at=10):
    out, pos = [], 0
    for g in group:
        s = slice(pos, pos + g)
        pos += g
        order = np.argsort(-pred[s])
        rel = y[s][order][:at]
        dcg = np.sum((2.0 ** rel - 1) / np.log2(np.arange(len(rel)) + 2))
        ideal = np.sort(y[s])[::-1][:at]
        idcg = np.sum((2.0 ** ideal - 1) /
                      np.log2(np.arange(len(ideal)) + 2))
        out.append(dcg / idcg if idcg > 0 else 1.0)
    return float(np.mean(out))


TASKS = {
    "regression": dict(
        make=make_regression, obj="regression", extra={},
        metric="rmse"),
    "multiclass": dict(
        make=make_multiclass, obj="multiclass",
        extra={"num_class": 5}, metric="multi_logloss"),
    "lambdarank": dict(
        make=make_lambdarank, obj="lambdarank", extra={},
        metric="ndcg@10"),
}


def eval_metric(task, pred, y, group):
    if task == "regression":
        return rmse(pred, y)
    if task == "multiclass":
        return multi_logloss(pred, y, 5)
    return ndcg_at(pred, y, group)


def run_ours(task, n_trees):
    import jax.numpy  # noqa: F401  (device init before timing)
    import lightgbm_tpu as lgb
    spec = TASKS[task]
    X, y, group = spec["make"](ROWS, seed=21)
    Xv, yv, gv = spec["make"](VROWS, seed=99)
    t0 = time.time()
    ds = lgb.Dataset(X, label=y, group=group, params={"max_bin": 255})
    ds.construct()
    bin_t = time.time() - t0
    params = {"objective": spec["obj"], **POSTURE, **spec["extra"]}
    bst = lgb.Booster(params=params, train_set=ds)
    kcls = bst.num_trees_per_iteration
    iters = max(1, n_trees // kcls)
    block = max(1, 20 // kcls)
    # warmup: iteration 0 (normal path) + one block compile — clamped so
    # ours never trains more total trees than the reference row.
    # bench._drain waits on the device without copying the [N, k]
    # score to the host inside the timed block
    from bench import _drain
    bst.update_batch(min(1 + block, iters))
    _drain(bst)
    done = min(1 + block, iters)
    rates = []
    while done < iters:
        step = min(block, iters - done)
        t1 = time.time()
        bst.update_batch(step)
        _drain(bst)
        rates.append(step * kcls / (time.time() - t1))
        done += step
    pred = bst.predict(Xv, raw_score=True)
    m = eval_metric(task, np.asarray(pred).ravel(), yv, gv)
    med = float(np.median(rates)) if rates else 0.0
    best = float(np.max(rates)) if rates else 0.0
    print(f"ours[{task}]: {med:.2f} trees/s median (best {best:.2f}, "
          f"{len(rates)} blocks), {spec['metric']}@{done * kcls} trees = "
          f"{m:.5f}, binning {bin_t:.1f}s", flush=True)
    return med, m


def run_reference(task, n_trees):
    if not os.path.exists(_BIN):
        print(f"# reference binary absent ({_BIN}); ours-only record")
        return None, None
    spec = TASKS[task]
    X, y, group = spec["make"](ROWS, seed=21)
    Xv, yv, gv = spec["make"](VROWS, seed=99)
    d = tempfile.mkdtemp(prefix=f"bt_{task}_")
    np.savetxt(os.path.join(d, "train.csv"),
               np.column_stack([y, X]), delimiter=",", fmt="%.7g")
    np.savetxt(os.path.join(d, "valid.csv"),
               np.column_stack([yv, Xv]), delimiter=",", fmt="%.7g")
    if group is not None:
        np.savetxt(os.path.join(d, "train.csv.query"), group, fmt="%d")
        np.savetxt(os.path.join(d, "valid.csv.query"), gv, fmt="%d")
    extra = "".join(f"{k}={v}\n" for k, v in spec["extra"].items())
    kcls = spec["extra"].get("num_class", 1)
    iters = max(1, n_trees // kcls)
    conf = os.path.join(d, "train.conf")
    with open(conf, "w") as fh:
        fh.write(f"task=train\ndata={d}/train.csv\n"
                 f"objective={spec['obj']}\n{extra}"
                 f"num_iterations={iters}\nnum_leaves=255\nmax_bin=255\n"
                 "learning_rate=0.1\nmin_data_in_leaf=20\n"
                 "header=false\nlabel_column=0\nverbosity=-1\n"
                 "num_threads=1\n"
                 f"output_model={d}/ref_model.txt\n")
    t0 = time.time()
    res = subprocess.run([_BIN, f"config={conf}"], capture_output=True,
                         text=True, timeout=7200)
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-2000:]
    t_ref = time.time() - t0
    pconf = os.path.join(d, "pred.conf")
    with open(pconf, "w") as fh:
        fh.write(f"task=predict\ndata={d}/valid.csv\n"
                 f"input_model={d}/ref_model.txt\n"
                 f"output_result={d}/preds.txt\nheader=false\n"
                 "label_column=0\npredict_raw_score=true\n")
    subprocess.run([_BIN, f"config={pconf}"], check=True,
                   capture_output=True, timeout=1200)
    ref = np.loadtxt(os.path.join(d, "preds.txt"))
    m = eval_metric(task, ref.ravel(), yv, gv)
    rate = iters * kcls / t_ref
    print(f"reference[{task}]: {rate:.2f} trees/s 1-core "
          f"({t_ref:.0f}s incl. its own loading/binning), "
          f"{spec['metric']}@{iters * kcls} trees = {m:.5f}", flush=True)
    return rate, m


def main():
    argv = sys.argv[1:]
    n_trees = 100
    if "--trees" in argv:
        i = argv.index("--trees")
        n_trees = int(argv[i + 1])
        argv = argv[:i] + argv[i + 2:]
    tasks = [a for a in argv if not a.startswith("--")] or list(TASKS)
    for task in tasks:
        run_ours(task, n_trees)
        run_reference(task, n_trees)


if __name__ == "__main__":
    main()
