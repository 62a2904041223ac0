#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that the main path starts on the chip.

One process, through the entry points a user calls (lgb.Dataset,
lgb.train, serving.Server), at the full width of the Higgs-shaped
configuration: 1,000,000 x 28, 255 bins, 255 leaves. Depth is cut to a
few fused blocks per leg. It trains under the library defaults, under
the bench posture (hist_backend=auto: a formulation per pass, from
static shapes) and once more with every pass on the formulation auto
used least, serves the
second booster from every local device, and, where four devices are
visible, trains data-parallel over them. After every leg it checks that
the run took the device path it was meant to take: no fallback, no
retry, no degraded block, trees with splits, a held-out AUC above a
floor, device answers that agree with the host's.

Anything but a TPU is a failure, decided before any data is built.
`--rehearse-cpu` (never automatic) runs the same control flow at a tiny
size on the portable path, to be used before chip time is spent; its
report says "platform": "cpu", "rehearsal": true. Any exception or
failed check ends the run with a traceback and a non-zero exit, and
neither report nor verdict.

The last line of stdout is the verdict, one JSON object with exactly
these keys, the device as JAX reports it:
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}
The line before it is the report, one JSON object too: versions, shape,
cache entries, hits and misses, and per leg the backend's compile
seconds and wall seconds named as set-up and wall, and the wall of each
fused block (none of them is a training rate: a leg is mostly
compilation, and a block is ten trees timed once).
"""

import argparse
import json
import math
import os
import sys
import threading
import time

#: held-out AUC a leg must clear after its 10 to 20 trees (learning_rate 0.1)
AUC_FLOOR = 0.9
#: the four-device leg against leg (a) at the same tree count
MULTICHIP_AUC_TOL = 1e-3
#: device answers against Booster.predict (f32 device, f64 host)
SERVE_TOL = 1e-5

_T0 = time.perf_counter()
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class SmokeFailure(AssertionError):
    """A check failed: the run did not do what it was meant to."""


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print("# [%7.1fs] %s" % (time.perf_counter() - _T0, msg), flush=True)


class CompileClock:
    """Seconds the backend spent compiling (XLA and Mosaic; for a
    persistent-cache hit, the retrieval instead), and the cache's hits
    and misses, from JAX's own monitoring events. Tracing and lowering
    are not in it: their events nest and would count twice."""

    def __init__(self):
        import jax
        self._lock = threading.Lock()
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_):
        if event == _BACKEND_COMPILE:
            with self._lock:
                self.seconds += secs

    def _event(self, event, **_):
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1


def cache_entries(path) -> int:
    if not path or not os.path.isdir(path):
        return 0
    return sum(1 for name in os.listdir(path) if name.endswith("-cache"))


def peak_device_bytes():
    import jax
    stats = jax.local_devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def train_leg(name, params, rounds, dtrain, dvalid, clock, *, on_tpu,
              expect_backend=None):
    """lgb.train for `rounds` trees, then every check a leg owes.
    Returns (booster, record)."""
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.reliability import counters
    say("leg %s: lgb.train, %d rounds, %s" % (name, rounds, {
        k: v for k, v in params.items() if k not in ("verbosity",)}))
    evals = {}
    c0, t0 = clock.seconds, time.perf_counter()
    bst = lgb.train(dict(params), dtrain, num_boost_round=rounds,
                    valid_sets=[dvalid], valid_names=["held_out"],
                    callbacks=[lgb.record_evaluation(evals)])
    jax.block_until_ready(bst.gbdt.train_score)
    wall, compile_s = time.perf_counter() - t0, clock.seconds - c0
    gb = bst.gbdt
    auc = [float(v) for v in evals["held_out"]["auc"]]
    leaves = [int(t.num_leaves) for t in gb.trees]
    plan = gb._hist_plan_attrs()
    stats = getattr(gb, "_pipeline_stats", None)
    learner = gb._learner       # the resolved crossbar cell
    rec = {
        "trees": len(gb.trees), "min_leaves": min(leaves),
        "hist_impl": gb._hist_impl,
        "sharded_mxu": learner.is_parallel and learner.device == "mxu",
        "hist_backend": getattr(gb, "_hist_backend", None) or "",
        # the growth program's histogram passes, kernel slots and
        # formulation each (static: grower_mxu.hist_pass_plan)
        "hist_plan": plan.get("hist_plan", ""),
        "grouped_passes_per_tree": plan.get("grouped_passes_per_tree", 0),
        "fused_blocks": stats.block_sizes if stats else [],
        # dispatch-to-results wall of each block: the first one carries
        # the compile, a later one of the same length does not
        "block_wall_s": [round(ms / 1e3, 2) for ms in stats.device_ms]
        if stats else [],
        "setup_compile_s": round(compile_s, 1),
        "wall_s": round(wall, 1),
        "auc": round(auc[-1], 5), "auc_by_tree": auc,
        "peak_device_bytes": peak_device_bytes(),
    }
    say("leg %s: %s" % (name, {k: v for k, v in rec.items()
                                if k != "auc_by_tree"}))
    check(len(gb.trees) == rounds == len(auc),
          "leg %s holds %d trees and %d evaluations, asked for %d"
          % (name, len(gb.trees), len(auc), rounds))
    check(min(leaves) > 1, "leg %s grew a tree without a split: %s"
          % (name, leaves))
    check(math.isfinite(auc[-1]) and auc[-1] > AUC_FLOOR,
          "leg %s held-out AUC %r is not above %s"
          % (name, auc[-1], AUC_FLOOR))
    check(getattr(gb, "_fused_failures", 0) == 0
          and not getattr(gb, "_fused_disabled", False),
          "leg %s: a fused block failed and was degraded" % name)
    snap = counters.snapshot()
    check(snap["fallbacks"] == 0 and snap["device_retries"] == 0,
          "leg %s: reliability counters %s" % (name, snap))
    if on_tpu:
        check(learner.device == "mxu",
              "leg %s ran the %s grower, not the MXU one"
              % (name, learner.device))
        # (the data-parallel MXU learner too, since PR 36)
        check(stats is not None and stats.blocks > 0,
              "leg %s did not go through the fused, pipelined "
              "executor" % name)
    if expect_backend is not None and on_tpu:
        check(rec["hist_backend"] == expect_backend,
              "leg %s ran hist_backend %r, not the pinned %r"
              % (name, rec["hist_backend"], expect_backend))
    return bst, rec


def serve_leg(bst, Xq, *, on_tpu, requests=32):
    """One Server, one replica per local device, `requests` predicts of
    1..1000 rows checked against Booster.predict."""
    import jax
    import numpy as np
    from lightgbm_tpu.serving import Server
    rng = np.random.RandomState(5)
    sizes = [1, 1000] + [int(rng.randint(1, 1001))
                         for _ in range(requests - 2)]
    worst = 0.0
    with Server(n_replicas=0) as srv:
        srv.load_model("smoke", booster=bst)
        for s in sizes:
            lo = int(rng.randint(0, len(Xq) - s + 1))
            got = np.asarray(srv.predict("smoke", Xq[lo:lo + s]))
            want = np.asarray(bst.predict(Xq[lo:lo + s]))
            check(got.shape == want.shape and np.all(np.isfinite(got)),
                  "serving answered shape %s (host %s) or a non-finite "
                  "value" % (got.shape, want.shape))
            worst = max(worst, float(np.max(np.abs(got - want))))
        snap = srv.metrics_snapshot("smoke")["models"]["smoke"]
        devices = [r.device for r in srv.replicas("smoke").replicas()]
    rec = {"requests": snap["requests"], "rows": snap["rows"],
           "max_abs_err_vs_host": worst,
           "replicas": [str(d) for d in devices],
           "buckets_compiled": snap["buckets_compiled"],
           "max_compilations": snap["max_compilations"],
           "fallbacks": snap["fallbacks"], "errors": snap["errors"],
           "device_retries": snap["device_retries"],
           "failovers": snap["failovers"]}
    say("serving: %s" % rec)
    check(worst <= SERVE_TOL, "device and host predictions differ by %g"
          % worst)
    check(snap["requests"] == len(sizes), "served %d of %d requests"
          % (snap["requests"], len(sizes)))
    check(snap["device_resident"] and not snap["degraded"],
          "the model is not served from the device: %s" % snap)
    check(snap["fallbacks"] == 0 and snap["errors"] == 0
          and snap["device_retries"] == 0 and snap["failovers"] == 0,
          "serving counters: %s" % rec)
    check(snap["buckets_compiled"] <= snap["max_compilations"],
          "compiled %d buckets, bound %d"
          % (snap["buckets_compiled"], snap["max_compilations"]))
    check(len(devices) == jax.local_device_count(),
          "%d replicas for %d local devices"
          % (len(devices), jax.local_device_count()))
    if on_tpu:
        check(all(d.platform == "tpu" for d in devices),
              "a replica is not on a TPU device: %s" % rec["replicas"])
    return rec


def multichip_leg(params, dtrain, dvalid, clock, auc_a, *, on_tpu,
                  ndev=4, rounds=20):
    """tree_learner=data over `ndev` devices; the rows must really be
    spread, and the model must agree with the one-device leg. Two
    blocks like the other legs: on the chip the sharded MXU learner
    compiles one growth program and the second block takes the first's
    score as it lies on the mesh."""
    bst, rec = train_leg(
        "multichip", dict(params, tree_learner="data", num_devices=ndev),
        rounds, dtrain, dvalid, clock, on_tpu=on_tpu)
    gb = bst.gbdt
    check(gb.mesh is not None and gb.mesh.devices.size == ndev,
          "the mesh spans %s devices, not %d"
          % (None if gb.mesh is None else gb.mesh.devices.size, ndev))
    blocks = {(s.device.id, s.index[0].start, s.index[0].stop)
              for s in gb.bins.addressable_shards}
    rec["row_blocks"] = sorted(blocks)
    check(len({b[0] for b in blocks}) == ndev
          and len({b[1:] for b in blocks}) == ndev,
          "rows are not spread over %d devices: %s"
          % (ndev, rec["row_blocks"]))
    if on_tpu:
        check(rec["sharded_mxu"],
              "the sharded learner did not take the MXU grower")
    diff = abs(rec["auc"] - auc_a[rounds - 1])
    rec["auc_vs_leg_a"] = round(diff, 6)
    check(diff <= MULTICHIP_AUC_TOL,
          "AUC after %d trees: %r over %d devices, %r on one"
          % (rounds, rec["auc"], ndev, auc_a[rounds - 1]))
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny size, CPU, portable path: checks this "
                         "script's control flow, proves nothing about "
                         "the chip")
    args = ap.parse_args(argv)
    rehearsal = args.rehearse_cpu

    import jax
    import jaxlib
    if rehearsal:
        jax.config.update("jax_platforms", "cpu")
    from lightgbm_tpu.utils.compile_cache import configure_compile_cache
    cache_dir = configure_compile_cache()
    from importlib import metadata
    versions = {"python": sys.version.split()[0], "jax": jax.__version__,
                "jaxlib": jaxlib.__version__}
    try:
        versions["libtpu"] = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        versions["libtpu"] = None
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    say("versions %s" % versions)
    say("jax.devices() = %s" % (devices,))
    say("compile cache: %s (%d entries)"
        % (cache_dir, cache_entries(cache_dir)))
    on_tpu = device["platform"] == "tpu"
    if not on_tpu and not rehearsal:
        print("chip_smoke.py: JAX found platform %r (%s x%d), not a TPU; "
              "nothing was run. --rehearse-cpu checks the script's "
              "control flow on a CPU." % (device["platform"],
                                          device["kind"], device["count"]),
              file=sys.stderr)
        return 2
    if on_tpu:
        from lightgbm_tpu.observability.mfu import peak_tflops_of_kind
        peak = peak_tflops_of_kind(device["kind"])
        check(peak is not None, "device_kind %r is not in the peak table "
              "(observability/mfu.py)" % device["kind"])

    import lightgbm_tpu as lgb
    from bench import make_higgs_like
    from lightgbm_tpu import cext
    from lightgbm_tpu.config import Config
    clock = CompileClock()
    entries_before = cache_entries(cache_dir)

    rows, valid_rows, leaves, max_bin = (
        (20_000, 2_000, 15, 63) if rehearsal
        else (1_000_000, 40_000, 255, 255))
    block = int(Config({}).fused_block_size)
    # two fused blocks of the default length: a leg compiles ONE growth
    # program (a block that starts at iteration 0 runs whole in the
    # fused scan), and the second block finds it compiled
    rounds = 2 * block
    X, y = make_higgs_like(rows, 28, seed=17)
    Xva, yva = make_higgs_like(valid_rows, 28, seed=99)
    t0 = time.perf_counter()
    dtrain = lgb.Dataset(X, label=y, params={"max_bin": max_bin})
    dvalid = lgb.Dataset(Xva, label=yva, reference=dtrain)
    dtrain.construct()
    dvalid.construct()
    binning_s = time.perf_counter() - t0
    check(cext.available(), "the native binning library did not build "
          "or load: binning took the NumPy path")
    say("binned %d x 28 (+%d held out) in %.1fs, native library"
        % (rows, valid_rows, binning_s))

    base = {"objective": "binary", "metric": "auc", "num_leaves": leaves,
            "max_bin": max_bin, "min_data_in_leaf": 20,
            "learning_rate": 0.1, "verbosity": -1}
    bench_posture = dict(base, use_quantized_grad=True,
                         growth_overshoot=1.75, growth_bridge_gate=0.93)
    legs = {}
    _, legs["a_defaults"] = train_leg(
        "a_defaults", base, rounds, dtrain, dvalid, clock, on_tpu=on_tpu)
    bst_b, legs["b_bench_auto"] = train_leg(
        "b_bench_auto", dict(bench_posture, hist_backend="auto"), rounds,
        dtrain, dvalid, clock, on_tpu=on_tpu)
    # the formulation `auto` uses least in this posture runs every pass
    # of a third leg, so both kernels are proven to build and train
    other = "mxu" if legs["b_bench_auto"]["grouped_passes_per_tree"] \
        else "pallas"
    _, legs["c_bench_" + other] = train_leg(
        "c_bench_" + other, dict(bench_posture, hist_backend=other),
        block, dtrain, dvalid, clock, on_tpu=on_tpu, expect_backend=other)

    serving = serve_leg(bst_b, Xva, on_tpu=on_tpu)

    if device["count"] >= 4:
        legs["multichip"] = multichip_leg(
            base, dtrain, dvalid, clock,
            legs["a_defaults"]["auc_by_tree"], on_tpu=on_tpu,
            rounds=rounds)
    else:
        say("multichip leg not run: %d device(s) visible"
            % device["count"])

    report = {
        "platform": device["platform"], "device_kind": device["kind"],
        "device_count": device["count"],
        "peak_bf16_tflops": peak if on_tpu else None,
        "rehearsal": rehearsal, "versions": versions,
        "shape": {"rows": rows, "features": 28, "max_bin": max_bin,
                  "num_leaves": leaves, "rounds": rounds},
        "binning": {"seconds": round(binning_s, 2), "native": True},
        "cache": {"dir": cache_dir, "entries_before": entries_before,
                  "entries_after": cache_entries(cache_dir),
                  "hits": clock.hits, "misses": clock.misses},
        "setup_compile_s": round(clock.seconds, 1),
        "wall_s": round(time.perf_counter() - _T0, 1),
        "legs": legs, "serving": serving,
        "multichip_leg_ran": "multichip" in legs,
    }
    for rec in legs.values():
        del rec["auc_by_tree"]
    print(json.dumps(report), flush=True)
    # the verdict: these keys and no others, last on stdout
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    sys.exit(main())
