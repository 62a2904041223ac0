#!/usr/bin/env python
"""Serving load bench: sustained QPS at p99 < 10ms, as a guarded record.

Open-loop QPS ramp (testing.chaos_serve.run_open_loop — arrivals on a
fixed schedule, so the server can't hide slowness by back-pressuring
the generator) with heavy-tailed request sizes against a replicated
`serving.Server`, one stage per target QPS. A mid-ramp chaos stage
injects replica-dispatch faults so the record carries the cost of the
degradation ladder, not just the sunny path. The headline value is the
highest achieved QPS among stages that held p99 < 10ms; shed /
fallback / failover / deadline-miss counts ride as side channels.

Runs on the TPU JAX finds; any other platform is refused unless --cpu
is given. Any exception, and any request answered by the host fallback,
is a non-zero exit.

Output contract (mirrors bench.py):
- one single-line JSON metric record on stdout:
  {"platform": ..., "device_kind": ..., "device_count": ...,
   "metric": "serve_sustained_qps_p99lt10ms", "value": ..., "unit":
   "qps", "p99_ms": ..., "shed": ..., "fallback": ..., "failovers":
   ..., "deadline_misses": ...}
- `# serve detail:` lines on stderr;
- a wrapped SERVE_r<N>.json bench record in the repo root (N from
  SERVE_ROUND or the next free round) that
  `bench.py --compare [--strict]` parses and the regression sentinel
  tracks exactly like BENCH_r*.

A second, multi-model stage (PR 15) loads N small models + 1 large one
— the tenant mix where per-model dispatch serializes — twice: unpacked
(one DeviceForest + one queue each) and packed (`Server.load_pack`, one
fused ForestPack dispatch + one continuous-batching queue). The same
heavy-tailed open-loop schedule hits both; the record carries
mm_packed_qps / mm_unpacked_qps / mm_packed_speedup and the matching
p99s so the regression sentinel tracks the packed win per round.

Env knobs: SERVE_BENCH_STAGES="qps:sec,qps:sec,..." (default ramp),
SERVE_BENCH_REPLICAS (default 2), SERVE_BENCH_TREES /
SERVE_BENCH_ROWS (model/pool size), SERVE_ROUND (record number),
SERVE_BENCH_CHAOS=0 to disable fault injection,
SERVE_MM_STAGES / SERVE_MM_SMALL (multi-model stage ramp / small-model
count), SERVE_MM=0 to skip the multi-model stage.
"""

import glob
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

P99_SLO_MS = 10.0


def _parse_stages(spec):
    stages = []
    for part in spec.split(","):
        qps, _, dur = part.strip().partition(":")
        stages.append((float(qps), float(dur or "2.0")))
    return stages


def _next_round():
    env = os.environ.get("SERVE_ROUND", "")
    if env:
        return int(env)
    rounds = [int(m.group(1)) for p in glob.glob(
        os.path.join(REPO, "SERVE_r*.json"))
        if (m := re.search(r"_r(\d+)\.json$", p))]
    return max(rounds, default=0) + 1


def run_bench():
    from lightgbm_tpu.reliability import faults
    from lightgbm_tpu.serving import Server
    from lightgbm_tpu.testing.chaos_serve import (dyadic_booster,
                                                  run_open_loop)

    trees = int(os.environ.get("SERVE_BENCH_TREES", 48))
    rows = int(os.environ.get("SERVE_BENCH_ROWS", 8192))
    replicas = int(os.environ.get("SERVE_BENCH_REPLICAS", 2))
    chaos = os.environ.get("SERVE_BENCH_CHAOS", "1") != "0"
    stages = _parse_stages(os.environ.get(
        "SERVE_BENCH_STAGES", "100:2,200:2,400:2,800:2"))

    bst, X = dyadic_booster(n=rows, f=16, trees=trees, num_leaves=31,
                            seed=7)
    per_stage = []
    with Server(min_bucket=16, max_bucket=1024, max_wait_ms=0.5,
                max_queue=4096, n_replicas=replicas, retry_attempts=2,
                breaker_threshold=3, breaker_cooldown_ms=100.0) as srv:
        srv.load_model("bench", booster=bst)
        # warm the bucket cache so stage 1 doesn't pay compile time
        for s in (1, 4, 16, 64):
            srv.predict("bench", X[:s], raw_score=True)

        def _mid(stage):
            # chaos stage: a burst of replica-dispatch faults mid-ramp
            if chaos and stage == max(len(stages) - 2, 1):
                faults.schedule("serving_replica_predict", fail=3)
                print(f"# serve chaos: armed 3 replica faults at stage "
                      f"{stage}", file=sys.stderr)

        for si, (qps, dur) in enumerate(stages):
            if si:
                _mid(si)
            res = run_open_loop(srv, "bench", X, stages=[(qps, dur)],
                                max_rows=64, raw_score=True,
                                timeout_s=60.0, seed=100 + si)
            pct = res.latency_percentiles()
            per_stage.append({
                "target_qps": qps, "achieved_qps": round(res.qps(), 3),
                "issued": res.issued, "dropped": res.dropped,
                **pct, **res.by_outcome()})
            print(f"# serve detail: stage {si} target {qps:g} qps -> "
                  f"achieved {res.qps():.1f} qps, p50/p95/p99 "
                  f"{pct['p50_ms']}/{pct['p95_ms']}/{pct['p99_ms']} ms,"
                  f" outcomes {res.by_outcome()}", file=sys.stderr)

        snap = srv.metrics_snapshot("bench")["models"]["bench"]
        faults.clear()

    within = [s for s in per_stage if s["p99_ms"] < P99_SLO_MS
              and s["dropped"] == 0]
    if within:
        best = max(within, key=lambda s: s["achieved_qps"])
    else:   # nothing held the SLO: report the least-bad stage honestly
        best = min(per_stage, key=lambda s: s["p99_ms"])
    record = {
        "metric": "serve_sustained_qps_p99lt10ms",
        "value": best["achieved_qps"], "unit": "qps",
        "p99_ms": best["p99_ms"], "p50_ms": best["p50_ms"],
        "slo_held": bool(within),
        "replicas": replicas, "trees": trees,
        "shed": snap["shed_count"],
        "fallback": snap["fallback_count"],
        "failovers": snap["failovers"],
        "deadline_misses": snap["deadline_misses"],
        "device_retries": snap["device_retries"],
        "swap_drains": snap["swap_drains"],
        "stages": per_stage,
    }
    total_dropped = sum(s["dropped"] for s in per_stage)
    if total_dropped:
        raise RuntimeError(
            f"{total_dropped} requests dropped/hung during the ramp")
    return record


def run_multimodel_bench():
    """Packed vs unpacked serving of N small + 1 large model under one
    heavy-tailed open-loop schedule. Returns the mm_* record fields."""
    from lightgbm_tpu.serving import Server
    from lightgbm_tpu.testing.chaos_serve import (dyadic_booster,
                                                  run_open_loop)

    n_small = int(os.environ.get("SERVE_MM_SMALL", 4))
    replicas = int(os.environ.get("SERVE_BENCH_REPLICAS", 2))
    stages = _parse_stages(os.environ.get(
        "SERVE_MM_STAGES", "300:2,600:2,900:2"))
    models = []
    for i in range(n_small):
        bst, _ = dyadic_booster(n=2048, f=16, trees=12, num_leaves=15,
                                seed=20 + i)
        models.append((f"small{i}", bst))
    big, X = dyadic_booster(n=8192, f=16, trees=48, num_leaves=31,
                            seed=7)
    models.append(("large", big))
    names = [nm for nm, _ in models]

    def _run(packed):
        # max_bucket 256: requests are tiny (the launch-bound tenant
        # mix), so coalesced blocks never need the top of the ladder —
        # and the warm loop below can afford to cover EVERY bucket,
        # keeping compile time out of the measured window
        with Server(min_bucket=16, max_bucket=256, max_wait_ms=0.5,
                    max_queue=4096, n_replicas=replicas,
                    retry_attempts=2, slo_ms=0.0,
                    scheduler="slo") as srv:
            if packed:
                srv.load_pack("bench_pack", models)
            else:
                for nm, bst in models:
                    srv.load_model(nm, booster=bst)
            for s in (16, 32, 64, 128, 256):
                for nm in names:
                    srv.predict(nm, X[:s], raw_score=True)
            per_stage = []
            for si, (qps, dur) in enumerate(stages):
                res = run_open_loop(srv, names[0], X, stages=[(qps, dur)],
                                    max_rows=8, raw_score=True,
                                    timeout_s=60.0, seed=300 + si,
                                    names=names)
                pct = res.latency_percentiles()
                per_stage.append({
                    "target_qps": qps,
                    "achieved_qps": round(res.qps(), 3),
                    "issued": res.issued, "dropped": res.dropped,
                    **pct, **res.by_outcome()})
                print(f"# serve mm detail: {'packed' if packed else 'unpacked'}"
                      f" stage {si} target {qps:g} -> "
                      f"{res.qps():.1f} qps, p99 {pct['p99_ms']} ms",
                      file=sys.stderr)
            extra = {}
            if packed:
                psnap = srv.metrics_snapshot()["packs"].get(
                    "bench_pack", {})
                extra = {k: psnap.get(k) for k in
                         ("fused_dispatches", "occupancy",
                          "avg_slots_active", "interleaves",
                          "compile_count")}
        within = [s for s in per_stage
                  if s["p99_ms"] < P99_SLO_MS and s["dropped"] == 0]
        best = max(within, key=lambda s: s["achieved_qps"]) if within \
            else min(per_stage, key=lambda s: s["p99_ms"])
        return {"best": best, "slo_held": bool(within),
                "stages": per_stage, **extra}

    unpacked = _run(packed=False)
    packed = _run(packed=True)
    speedup = packed["best"]["achieved_qps"] / \
        max(unpacked["best"]["achieved_qps"], 1e-9)
    return {
        "mm_packed_qps": packed["best"]["achieved_qps"],
        "mm_packed_p99_ms": packed["best"]["p99_ms"],
        "mm_unpacked_qps": unpacked["best"]["achieved_qps"],
        "mm_unpacked_p99_ms": unpacked["best"]["p99_ms"],
        "mm_packed_speedup": round(speedup, 3),
        "multimodel": {
            "n_small": n_small, "large_trees": 48,
            "packed": packed, "unpacked": unpacked},
    }


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    from bench import device_record
    from lightgbm_tpu.utils.compile_cache import configure_compile_cache
    configure_compile_cache()
    record = device_record(allow_cpu="--cpu" in argv)
    rnd = _next_round()
    record.update(run_bench())
    if os.environ.get("SERVE_MM", "1") != "0":
        record.update(run_multimodel_bench())
    line = json.dumps(record)
    print(line)
    # a sample answered by the host is not a device measurement
    rc = 1 if record["fallback"] else 0
    wrapped = {"n": rnd, "cmd": "python bench_serve.py " + " ".join(argv),
               "rc": rc, "tail": line, "parsed": record}
    out = os.path.join(REPO, f"SERVE_r{rnd:02d}.json")
    with open(out, "w") as fh:
        json.dump(wrapped, fh, indent=1)
        fh.write("\n")
    print(f"# serve record -> {out}", file=sys.stderr)
    if rc:
        print(f"# serve bench: {record['fallback']} request(s) fell back "
              f"to the host", file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main())
