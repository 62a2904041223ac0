"""Benchmark: Higgs-style 1M x 28 binary classification, 255 leaves.

Mirrors the reference's headline benchmark (docs/Experiments.rst:111-123:
Higgs 500 trees, num_leaves=255, 28-core Xeon -> 130.094 s total,
i.e. 3.843 trees/sec). No dataset download is possible here, so a synthetic
Higgs-shaped problem (1M rows x 28 continuous features, balanced binary
labels from a nonlinear rule) stands in; the metric is trees/sec of the
steady-state training loop on the TPU JAX finds.

One process holds the chip from start to end. A platform other than
"tpu" is refused unless --cpu is given, and the record names the
platform it ran on either way. Any exception, and any non-zero fallback
counter, is a non-zero exit: a run that degraded is not a measurement.

Prints ONE JSON line: {"platform", "device_kind", "device_count",
"metric", "value", "unit", "vs_baseline", ...}.
"""

import json
import os
import sys
import time

import numpy as np

N_ROWS = int(os.environ.get("BENCH_ROWS", 1_000_000))
N_FEATURES = 28
NUM_LEAVES = int(os.environ.get("BENCH_LEAVES", 255))
MAX_BIN = int(os.environ.get("BENCH_MAX_BIN", 255))
WARMUP_TREES = 5
BENCH_TREES = int(os.environ.get("BENCH_TREES", 100))
# fewer, longer dispatches mean fewer host drains per tree
BLOCK_TREES = int(os.environ.get("BENCH_BLOCK_TREES", 25))
BASELINE_TREES_PER_SEC = 500.0 / 130.094  # reference CPU Higgs headline
# like-for-like anchor (VERDICT r4 weak #8): the reference binary on
# THIS synthetic 1M x 28 set, single core, idle host, as last measured
# by helpers/recert_auc_parity.py (band so far 2.96 loaded - 4.33 idle)
SINGLE_CORE_TREES_PER_SEC = 4.33


def make_higgs_like(n, f, seed=17):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f).astype(np.float32)
    # nonlinear separation rule on a few "physics" features + noise dims
    logit = (1.2 * X[:, 0] - 0.8 * X[:, 1] + 0.6 * X[:, 2] * X[:, 3] +
             0.5 * np.abs(X[:, 4]) - 0.4 * X[:, 5] ** 2 +
             0.3 * X[:, 6] * X[:, 0] + 0.35 * rng.randn(n))
    y = (logit > np.median(logit)).astype(np.float32)
    return X, y


def device_record(allow_cpu: bool) -> dict:
    """Platform, device kind and count as JAX reports them. Anything
    but a TPU is refused unless the caller passed --cpu: a timing from
    XLA:CPU is never a device number, so it is never taken by
    accident."""
    import jax
    devs = jax.devices()
    rec = {"platform": devs[0].platform,
           "device_kind": devs[0].device_kind, "device_count": len(devs)}
    if rec["platform"] != "tpu" and not allow_cpu:
        raise SystemExit(
            f"{os.path.basename(sys.argv[0])}: JAX found platform "
            f"{rec['platform']!r} ({rec['device_kind']}), not a TPU; "
            f"pass --cpu to run there on purpose")
    return rec


PARAMS = {"objective": "binary", "num_leaves": NUM_LEAVES,
          "learning_rate": 0.1, "max_bin": MAX_BIN, "verbosity": -1,
          "min_data_in_leaf": 20, "use_quantized_grad": True,
          "growth_overshoot": float(os.environ.get("BENCH_OVERSHOOT",
                                                   1.75)),
          "growth_bridge_gate": 0.93,
          # histogram formulation: "auto" chooses one-hot or
          # slot-grouped per pass from static shapes (byte-neutral in
          # the quantized posture). Pin explicitly to measure one
          # formulation on every pass, e.g. LGBM_TPU_HIST_BACKEND=mxu.
          "hist_backend": os.environ.get("LGBM_TPU_HIST_BACKEND",
                                         "auto"),
          # row partition of the slot-grouped build: "auto" resolves
          # to the stream partition (byte-identical to the argsort
          # oracle); pin LGBM_TPU_PARTITION_IMPL=rank or =argsort to
          # measure the others.
          "partition_impl": os.environ.get("LGBM_TPU_PARTITION_IMPL",
                                           "auto")}
# Bench posture vs library defaults (ROADMAP D3): quantized gradients
# with exact leaf refit, growth_overshoot 1.75 (default 2.0) and
# growth_bridge_gate 0.93 (default 0 = full chase). Each trades a few
# 1e-4 of held-out AUC for speed; the held-out AUC is printed below and
# helpers/recert_auc_parity.py re-certifies the cumulative cost against
# the reference binary. None of them has been timed on the current code.


def _drain(booster):
    """Wait for the device to finish everything dispatched so far."""
    import jax
    jax.block_until_ready(booster.gbdt.train_score)


class _Bench:
    """Measurement driver: one Dataset, one Booster, timed blocks. A
    fault in a block propagates — the run exits non-zero instead of
    recording a degraded number."""

    def __init__(self, lgb, X, y):
        self.lgb = lgb
        self.X, self.y = X, y
        self.bin_time = 0.0
        self.booster = None

    def build(self):
        from lightgbm_tpu.utils.timer import global_timer
        before = dict(global_timer.totals())
        t0 = time.time()
        dtrain = self.lgb.Dataset(self.X, label=self.y,
                                  params={"max_bin": MAX_BIN})
        dtrain.construct()
        self.bin_time = time.time() - t0
        # decomposition of the recorded binning time:
        # sample+transpose / native bounds / native quantize / remainder
        after = global_timer.totals()
        parts = {k.replace("dataset_", ""): after.get(k, 0.0)
                 - before.get(k, 0.0)
                 for k in ("dataset_sample", "dataset_bounds",
                           "dataset_quantize")}
        parts["other"] = self.bin_time - sum(parts.values())
        self.bin_parts = parts
        self.booster = self.lgb.Booster(params=PARAMS, train_set=dtrain)

    def train_block(self, n_trees):
        """Train n_trees (one fused dispatch when eligible) and return
        the wall seconds, device work included."""
        t1 = time.time()
        self.booster.update_batch(n_trees)
        _drain(self.booster)
        return time.time() - t1


def _pipeline_bench(bench, result):
    """Pipelined-executor record (pipeline/executor.py): train extra
    trees on the already-compiled bench booster through run_pipelined
    (no valid sets, so the host runs a block ahead) and merge the share
    of blocks enqueued behind a running one (`overlap_frac`) plus
    per-block host/device wall columns into the JSON record. Keys
    MERGE like _serve_bench. BENCH_PIPELINE_TREES=0
    skips (the training headline is unaffected)."""
    n_trees = int(os.environ.get("BENCH_PIPELINE_TREES", 2 * BLOCK_TREES))
    if n_trees <= 0:
        return
    from lightgbm_tpu.pipeline import run_pipelined
    bst = bench.booster
    start = int(bst.current_iteration())
    run_pipelined(bst, start_iter=start,
                  num_boost_round=start + n_trees,
                  base_block=min(BLOCK_TREES, n_trees),
                  run_callbacks=lambda i, ev: None, has_valid=False)
    _drain(bst)
    d = bst.gbdt._pipeline_stats.as_dict()
    result["pipeline_overlap_frac"] = d["overlap_frac"]
    result["pipeline_blocks"] = d["blocks"]
    result["pipeline_block_host_ms"] = d["host_ms"]
    result["pipeline_block_device_ms"] = d["device_ms"]
    print(f"# pipeline detail: {d['blocks']} blocks / "
          f"{d['iterations']} trees, sizes {d['block_sizes']}, "
          f"host ms {d['host_ms']}, device ms {d['device_ms']}, "
          f"enqueued behind a running block "
          f"{100.0 * d['overlap_frac']:.1f}%",
          file=sys.stderr)


def _serve_bench(bench, result):
    """Serve-path record: a mixed-size request stream (1..1000 rows)
    through serving.Server on the just-trained booster. Keys MERGE into
    the single JSON record — never a second JSON line (the round
    tooling parses exactly one). BENCH_SERVE_REQUESTS=0 skips."""
    n_req = int(os.environ.get("BENCH_SERVE_REQUESTS", 48))
    if n_req <= 0:
        return
    from lightgbm_tpu.serving import Server
    rng = np.random.RandomState(5)
    Xq, _ = make_higgs_like(4096, N_FEATURES, seed=23)
    sizes = [int(rng.choice([1, 4, 16, 64, 256, 1000]))
             for _ in range(n_req)]
    with Server(min_bucket=16, max_bucket=1024,
                max_wait_ms=0.5) as srv:
        srv.load_model("bench", booster=bench.booster)
        for s in sizes:
            lo = int(rng.randint(0, 4096 - s)) if s < 4096 else 0
            srv.predict("bench", Xq[lo:lo + s])
        snap = srv.metrics_snapshot("bench")["models"]["bench"]
    for src, dst in (("qps", "serve_qps"),
                     ("rows_per_sec", "serve_rows_per_sec"),
                     ("p50_ms", "serve_p50_ms"),
                     ("p95_ms", "serve_p95_ms"),
                     ("p99_ms", "serve_p99_ms"),
                     ("buckets_compiled", "serve_buckets_compiled"),
                     ("bucket_cache_hits", "serve_bucket_hits")):
        result[dst] = snap[src]
    print(f"# serve detail: {snap['requests']} requests "
          f"({snap['rows']} rows), {snap['buckets_compiled']} "
          f"buckets compiled (bound {snap['max_compilations']}), "
          f"p50/p95/p99 {snap['p50_ms']}/{snap['p95_ms']}/"
          f"{snap['p99_ms']} ms, {snap['qps']} req/s",
          file=sys.stderr)


def _task_bench(result):
    """Task-matrix rows (VERDICT r4 item 2, promoted into the official
    record): regression / multiclass / lambdarank through
    helpers/bench_tasks.py at the bench posture, one dict per task
    appended to result["tasks"] — {"task", "value" (trees/sec),
    "unit", "metric", "metric_value", "vs_single_core"}. Keys MERGE
    into the single JSON record, like _serve_bench. BENCH_TASKS=""
    skips (the binary headline is unaffected), BENCH_TASK_TREES scales
    depth."""
    spec = os.environ.get("BENCH_TASKS",
                          "regression,multiclass,lambdarank")
    names = [t.strip() for t in spec.split(",") if t.strip()]
    if not names:
        return
    n_trees = int(os.environ.get("BENCH_TASK_TREES", 60))
    from helpers.bench_tasks import SINGLE_CORE_RATES, TASKS, run_ours
    for name in names:
        if name not in TASKS:
            raise SystemExit(f"BENCH_TASKS: unknown task {name!r} "
                             f"(known: {', '.join(sorted(TASKS))})")
        rate, metric_value = run_ours(name, n_trees)
        anchor = SINGLE_CORE_RATES.get(name, 0.0)
        result["tasks"].append({
            "task": name, "value": round(float(rate), 3),
            "unit": "trees/sec", "metric": TASKS[name]["metric"],
            "metric_value": round(float(metric_value), 6),
            "vs_single_core": round(float(rate) / anchor, 3)
            if anchor else 0.0})


def _parse_synth_argv(argv=None):
    """`--synth rows=10000000,cols=28[,chunk=262144][,seed=17]` (or the
    `--synth=...` form) -> spec dict, None when the flag is absent.
    Malformed specs raise SystemExit with a usage line rather than
    silently benching the wrong shape."""
    argv = sys.argv[1:] if argv is None else argv
    raw = None
    for i, a in enumerate(argv):
        if a == "--synth":
            if i + 1 >= len(argv):
                raise SystemExit("--synth needs rows=...,cols=...")
            raw = argv[i + 1]
            break
        if a.startswith("--synth="):
            raw = a[len("--synth="):]
            break
    if raw is None:
        return None
    spec = {"rows": 0, "cols": 0, "chunk": 262144, "seed": 17}
    for part in raw.split(","):
        k, _, v = part.partition("=")
        if k not in spec or not v:
            raise SystemExit(f"--synth: bad field {part!r} "
                             "(want rows=...,cols=...[,chunk=...][,seed=...])")
        spec[k] = int(v)
    if spec["rows"] < 1 or spec["cols"] < 1:
        raise SystemExit("--synth: rows and cols must be >= 1")
    return spec


def _stream_bench(result, spec):
    """Out-of-core ingest bench: stream `spec` rows of synthetic data
    (helpers/synth.py — generated chunk-by-chunk, never materialized)
    through the two-pass sketch+bin loader, then train a short booster
    on the binned result. Records stream_* keys — chunk count, parse/
    bin overlap fraction, end-to-end ingest rows/sec — in the same
    JSON record. Runs only when --synth is given; the 1M-row in-memory
    headline above is untouched."""
    if spec is None:
        return
    import lightgbm_tpu as lgb
    from helpers.synth import SynthSource
    src = SynthSource(rows=spec["rows"], cols=spec["cols"],
                      chunk_rows=spec["chunk"], seed=spec["seed"])
    t0 = time.perf_counter()
    ds = lgb.Dataset(src, params={"max_bin": MAX_BIN}).construct()
    ingest_s = time.perf_counter() - t0
    st = ds._binned.stream_stats
    result["stream_chunks"] = st.chunks
    result["stream_rows"] = st.rows
    result["stream_overlap_frac"] = round(st.overlap_frac, 4)
    result["stream_rows_per_sec"] = round(st.rows_per_sec, 1)
    result["stream_sample_rows"] = st.sample_rows
    result["stream_exact"] = int(st.exact)
    result["stream_ingest_s"] = round(ingest_s, 3)
    n_trees = int(os.environ.get("BENCH_STREAM_TREES", 20))
    t0 = time.perf_counter()
    lgb.train(dict(PARAMS, objective="binary"), ds,
              num_boost_round=n_trees)
    train_s = time.perf_counter() - t0
    if train_s > 0:
        result["stream_trees_per_sec"] = round(n_trees / train_s, 3)
    print(f"# stream bench: {st.rows} rows / {st.chunks} chunks in "
          f"{ingest_s:.1f}s ({st.rows_per_sec:.0f} rows/s, "
          f"{st.overlap_frac:.0%} parse/bin overlap), "
          f"{n_trees} trees in {train_s:.1f}s", file=sys.stderr)


def _multichip_worker_main(argv):
    """``bench.py --multichip-worker`` (spawned by --multichip with the
    device count forced in XLA_FLAGS): stream the --synth dataset
    through the two-pass loader, train tree_learner=data through the
    fused+pipelined executor over every visible device, and print ONE
    JSON line with the measured steady-state trees/sec and the
    platform it was measured on (--cpu, passed through by the parent,
    is the only way onto a platform that is not a TPU)."""
    from lightgbm_tpu.utils.compile_cache import configure_compile_cache
    configure_compile_cache()
    device = device_record(allow_cpu="--cpu" in argv)
    import lightgbm_tpu as lgb
    from helpers.synth import SynthSource
    from lightgbm_tpu.observability import registry as _obs

    spec = _parse_synth_argv(argv) or \
        {"rows": 1_000_000, "cols": 28, "chunk": 262144, "seed": 17}
    n_leaves = int(os.environ.get("BENCH_MC_LEAVES", 63))
    n_trees = int(os.environ.get("BENCH_MC_TREES", 30))
    warmup = int(os.environ.get("BENCH_MC_WARMUP", 12))
    src = SynthSource(rows=spec["rows"], cols=spec["cols"],
                      chunk_rows=spec["chunk"], seed=spec["seed"])
    t0 = time.perf_counter()
    ds = lgb.Dataset(src, params={"max_bin": MAX_BIN}).construct()
    ingest_s = time.perf_counter() - t0
    params = dict(PARAMS, num_leaves=n_leaves, tree_learner="data",
                  pipeline=True, use_quantized_grad=False)
    _obs.enable()
    # warmup compiles every dispatch shape (iteration-0 per-iteration
    # path + the fused sharded block); the timed run below re-hits the
    # process-global jit cache, so it measures steady-state training
    lgb.train(params, ds, num_boost_round=warmup)
    t0 = time.perf_counter()
    lgb.train(params, ds, num_boost_round=n_trees)
    train_s = time.perf_counter() - t0
    dist = _obs.distributed_snapshot()
    rate = n_trees / train_s if train_s > 0 else 0.0
    rec = {
        **device,
        "n_devices": device["device_count"], "tree_learner": "data",
        "trees_per_sec": round(rate, 3),
        "vs_baseline": round(rate / BASELINE_TREES_PER_SEC, 3),
        "num_leaves": n_leaves, "trees": n_trees,
        "rows": spec["rows"], "cols": spec["cols"],
        "ingest_s": round(ingest_s, 3),
        "train_s": round(train_s, 3),
        "world": dist["world"],
        "feature_shard_width": dist["feature_shard_width"]}
    # elasticity cost (docs/Distributed.md "Elasticity"): when this
    # round resized mid-run, the sentinel tracks the post-resize
    # throughput and reshard wall alongside the main series
    mem = _obs.membership_snapshot()
    if mem.get("resizes", 0):
        rec["chaos_resize"] = {
            "resizes": int(mem["resizes"]),
            "reshard_wall_s": float(mem["reshard_wall_s"]),
            "post_resize_trees_per_sec": round(rate, 3)}
    print(json.dumps(rec))
    sys.stdout.flush()
    return 0


def _multichip_main(argv):
    """``bench.py --multichip [--devices N] [--out PATH] [--synth ...]
    [--cpu]``: multi-device training benchmark. This parent never
    imports JAX, so the worker it spawns is the one process that holds
    the chips. ``--xla_force_host_platform_device_count=N`` is appended
    to the worker's XLA_FLAGS (it only shapes the host platform: real
    chips are untouched, a --cpu run gets N virtual devices whose
    timing is a count of dispatches, not a speed) and the worker's JSON
    line is wrapped into
    the MULTICHIP_r*.json record shape the regression sentinel tracks
    (observability/regress.py): n_devices/rc/ok/skipped/tail plus the
    measured trees_per_sec, vs_baseline and tree_learner."""
    import subprocess
    ndev, out = 8, "MULTICHIP_r06.json"
    for i, a in enumerate(argv):
        if a == "--devices" and i + 1 < len(argv):
            ndev = int(argv[i + 1])
        elif a.startswith("--devices="):
            ndev = int(a[len("--devices="):])
        elif a == "--out" and i + 1 < len(argv):
            out = argv[i + 1]
        elif a.startswith("--out="):
            out = a[len("--out="):]
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count={ndev}")
    spec = _parse_synth_argv(argv) or \
        {"rows": 1_000_000, "cols": 28, "chunk": 262144, "seed": 17}
    cmd = [sys.executable, os.path.abspath(__file__),
           "--multichip-worker",
           "--synth=" + ",".join(f"{k}={v}" for k, v in spec.items())]
    if "--cpu" in argv:
        cmd.append("--cpu")
    timeout_s = int(os.environ.get("BENCH_MC_TIMEOUT", 3600))
    try:
        proc = subprocess.run(cmd, env=env, capture_output=True,
                              text=True, timeout=timeout_s)
        rc, out_txt, err_txt = proc.returncode, proc.stdout, proc.stderr
    except subprocess.TimeoutExpired as exc:
        rc = -1
        out_txt = (exc.stdout or b"").decode() \
            if isinstance(exc.stdout, bytes) else (exc.stdout or "")
        err_txt = f"worker timed out after {timeout_s}s"
    parsed = None
    for line in reversed(out_txt.strip().splitlines()):
        try:
            parsed = json.loads(line)
            break
        except ValueError:
            continue
    record = {"n_devices": ndev, "rc": rc,
              "ok": bool(rc == 0 and parsed
                         and parsed.get("trees_per_sec", 0) > 0),
              "skipped": False,
              "tail": (err_txt or "")[-2000:] + (out_txt or "")[-500:]}
    if parsed:
        record.update(parsed)
    with open(out, "w") as fh:
        json.dump(record, fh, indent=2)
        fh.write("\n")
    print(json.dumps(record))
    sys.stdout.flush()
    return 0 if record["ok"] else 1


def _compare_main(argv):
    """``bench.py --compare [--strict] [--trajectory-dir D]``: the bench
    regression sentinel (lightgbm_tpu/observability/regress.py) — check
    the BENCH_r*/MULTICHIP_r* trajectory for per-metric drops beyond
    the threshold. Pure record reading: no dataset, no accelerator, no
    probe — safe to run anywhere, including the `make bench` tail.
    With --strict, regressions exit nonzero."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from lightgbm_tpu.observability import regress
    root = None
    for i, a in enumerate(argv):
        if a == "--trajectory-dir":
            if i + 1 >= len(argv):
                raise SystemExit("--trajectory-dir needs a path")
            root = argv[i + 1]
        elif a.startswith("--trajectory-dir="):
            root = a[len("--trajectory-dir="):]
    result = regress.compare(root)
    print(json.dumps({"bench_regressions": result}))
    sys.stdout.flush()
    print(regress.render_compare(result), file=sys.stderr)
    return 1 if ("--strict" in argv and result["regressions"]) else 0


def main(argv):
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from lightgbm_tpu.utils.compile_cache import configure_compile_cache
    configure_compile_cache()
    result = device_record(allow_cpu="--cpu" in argv)
    result.update({
        "metric": "higgs1m_trees_per_sec", "value": 0.0,
        "unit": "trees/sec", "vs_baseline": 0.0, "vs_single_core": 0.0,
        # serve-path schema (filled by _serve_bench; zeros when the
        # serve bench is skipped)
        "serve_qps": 0.0, "serve_rows_per_sec": 0.0,
        "serve_p50_ms": 0.0, "serve_p95_ms": 0.0,
        "serve_p99_ms": 0.0, "serve_buckets_compiled": 0,
        "serve_bucket_hits": 0,
        # pipelined-executor schema (filled by _pipeline_bench; zeros
        # when the pipeline bench is skipped)
        "pipeline_overlap_frac": 0.0, "pipeline_blocks": 0,
        "pipeline_block_host_ms": [],
        "pipeline_block_device_ms": [],
        # reliability-counter schema (overwritten from the live
        # counters at the end of the run)
        "device_retries": 0, "fallbacks": 0, "guard_trips": 0,
        "checkpoint_saves": 0, "checkpoint_failures": 0,
        # device-utilization schema (observability/mfu.py): achieved
        # TFLOP/s from the analytic per-tree histogram MAC count x the
        # measured trees/sec; mfu_per_tree = that over the device's
        # bf16 peak (0.0 when the peak is unknown, e.g. --cpu)
        "achieved_tflops": 0.0, "mfu_per_tree": 0.0,
        "device_peak_tflops": 0.0,
        # attribution side channels (never sentinel metrics): which
        # partition impl ran and — under BENCH_PROFILE_SPANS=1 —
        # per-span wall totals from the observability trace
        "partition_impl": "", "profile_spans": {},
        # per-task rows (regression/multiclass/lambdarank) from
        # helpers/bench_tasks.py, filled by _task_bench
        "tasks": [],
        # out-of-core ingest schema (filled by _stream_bench when
        # --synth rows=...,cols=... is given; zeros otherwise)
        "stream_chunks": 0, "stream_rows": 0,
        "stream_overlap_frac": 0.0, "stream_rows_per_sec": 0.0,
        "stream_sample_rows": 0, "stream_exact": 0,
        "stream_ingest_s": 0.0, "stream_trees_per_sec": 0.0})
    block_trees = min(BLOCK_TREES, BENCH_TREES)
    import lightgbm_tpu as lgb
    from lightgbm_tpu import cext
    from lightgbm_tpu.observability import mfu as _mfu
    from lightgbm_tpu.observability import registry as _obs
    from lightgbm_tpu.reliability import counters
    cext.available()  # lazy g++ build happens here, not in bin_time
    profile_spans = bool(int(os.environ.get("BENCH_PROFILE_SPANS", "0")))
    if profile_spans:
        # totals per span name ride the record. Opt-in — the ring
        # appends cost real wall in the measured blocks, so headline
        # runs leave it off
        _obs.enable(ring=65536)
    X, y = make_higgs_like(N_ROWS, N_FEATURES)
    bench = _Bench(lgb, X, y)
    bench.build()
    # warmup: compile all jitted phases (incl. the fused multi-tree
    # scan, boosting/fused.py — one device dispatch per block)
    bench.train_block(max(1, WARMUP_TREES - 1))
    bench.train_block(block_trees)  # compile the bench-block shape

    # time several blocks, report the MEDIAN (best in the detail line)
    n_blocks = max(1, round(BENCH_TREES / block_trees))
    block_times = [bench.train_block(block_trees)
                   for _ in range(n_blocks)]
    rates = sorted(block_trees / b for b in block_times)
    median_rate = rates[len(rates) // 2] if len(rates) % 2 else \
        0.5 * (rates[len(rates) // 2 - 1] + rates[len(rates) // 2])
    result["value"] = round(median_rate, 3)
    result["vs_baseline"] = round(median_rate / BASELINE_TREES_PER_SEC, 3)
    result["vs_single_core"] = round(
        median_rate / SINGLE_CORE_TREES_PER_SEC, 3)
    # which histogram backend ran and its per-pass plan: pinned once
    # per process by GBDT._resolved_hist_backend and recorded
    # regardless of the observability enable flag
    result["hist_backend"] = _obs.hist_backend_snapshot()
    plan = result["hist_backend"]["plan"]
    if plan and all(p["formulation"] == "onehot" for p in plan):
        # device utilization: analytic MACs of one tree at the bench
        # posture (quantized grads -> 3 histogram channels; binary
        # log-loss has non-constant hessians, so the const-hessian
        # channel drop never applies) x measured rate. The MAC form
        # models the one-hot matmul kernel only; the scatter kernels
        # are partition-shaped and have none, so they report 0.0
        tmacs = _mfu.tree_macs(
            num_leaves=NUM_LEAVES, num_rows=N_ROWS,
            num_features=N_FEATURES, bmax=MAX_BIN,
            quantized=True, const_hess=False, hist_subtraction=True,
            overshoot=PARAMS["growth_overshoot"],
            bridge_gate=PARAMS["growth_bridge_gate"])
        tflops = _mfu.achieved_tflops(tmacs * median_rate)
        peak = _mfu.device_peak_tflops()
        result["achieved_tflops"] = round(tflops, 4)
        result["device_peak_tflops"] = peak
        if peak:
            result["mfu_per_tree"] = round(tflops / peak, 6)
    result["partition_impl"] = str(PARAMS.get("partition_impl", "auto"))
    if profile_spans:
        agg = {}
        for sp in _obs.trace.spans():
            a = agg.setdefault(sp["name"], [0, 0.0])
            a[0] += 1
            a[1] += sp["dur"]
        result["profile_spans"] = {
            name: {"count": c, "total_s": round(t, 4)}
            for name, (c, t) in sorted(
                agg.items(), key=lambda kv: -kv[1][1])[:16]}
    _pipeline_bench(bench, result)
    _serve_bench(bench, result)
    _task_bench(result)
    _stream_bench(result, _parse_synth_argv(argv))
    # reliability counters (lightgbm_tpu/reliability/): retries,
    # fused->per-iter / device->host fallbacks, guard trips
    result.update(counters.snapshot())
    return result, block_times, block_trees, bench


def _report(result, block_times, block_trees, bench):
    """Detail lines on stderr, after the JSON record is out."""
    rates = sorted(block_trees / b for b in block_times)
    blocks = ", ".join(f"{block_trees / b:.2f}" for b in block_times)
    decomp = " (" + " + ".join(
        f"{k} {v:.2f}" for k, v in bench.bin_parts.items()) + ")"
    print(f"# bench detail: {len(block_times)} blocks x "
          f"{block_trees} trees, median {result['value']:.2f} best "
          f"{rates[-1]:.2f} trees/sec, per block: [{blocks}], "
          f"binning {bench.bin_time:.1f}s{decomp}, "
          f"platform={result['platform']} "
          f"device={result['device_kind']} x{result['device_count']}",
          file=sys.stderr)
    Xva, yva = make_higgs_like(40_000, N_FEATURES, seed=99)
    sc = bench.booster.predict(Xva, raw_score=True)
    from lightgbm_tpu.metrics import AUCMetric  # tie-corrected
    auc = AUCMetric._auc_fast(sc, yva > 0, np.ones_like(yva))
    print(f"# held-out AUC after "
          f"{bench.booster.current_iteration()} trees: {auc:.5f}",
          file=sys.stderr)
    if result["achieved_tflops"]:
        peak = result["device_peak_tflops"]
        mfu_s = (f"MFU {result['mfu_per_tree']:.4f} of "
                 f"{peak:.0f} TFLOP/s bf16 peak") if peak else \
            "MFU n/a (unknown device peak; set LGBM_TPU_PEAK_TFLOPS)"
        print(f"# device utilization: "
              f"{result['achieved_tflops']:.3f} achieved TFLOP/s "
              f"from analytic histogram MACs "
              f"(observability/mfu.py, slight lower bound), {mfu_s}",
              file=sys.stderr)
    hb = result["hist_backend"]
    print(f"# histogram backend: {hb['choice']} (" + ", ".join(
          f"{p['sk']}:{p['formulation']}" for p in hb["plan"]) + ")",
          file=sys.stderr)
    for row in result["tasks"]:
        print(f"# task {row['task']}: {row['value']:.2f} trees/sec "
              f"({row['vs_single_core']:.2f}x single-core ref), "
              f"{row['metric']} = {row['metric_value']:.5f}",
              file=sys.stderr)
    print("# note: vs_baseline uses the reference's published "
          "10.5M-row 28-core Higgs rate; vs_single_core uses the "
          "same-host single-core reference on THIS synthetic "
          "1M-row set (band 2.96-4.33 trees/sec loaded/idle, "
          "latest idle 4.33)", file=sys.stderr)


if __name__ == "__main__":
    _argv = sys.argv[1:]
    if "--compare" in _argv:
        sys.exit(_compare_main(_argv))
    if "--multichip-worker" in _argv:
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        sys.exit(_multichip_worker_main(_argv))
    if "--multichip" in _argv:
        sys.exit(_multichip_main(_argv))
    _result, _blocks, _bt, _bench = main(_argv)
    print(json.dumps(_result))
    sys.stdout.flush()
    _report(_result, _blocks, _bt, _bench)
    if _result["fallbacks"]:
        # a block that degraded to per-iteration, a request answered by
        # the host: the number above is not the device's
        print(f"# bench: {_result['fallbacks']} fallback(s) counted; "
              "the record is not a clean measurement", file=sys.stderr)
        sys.exit(1)
