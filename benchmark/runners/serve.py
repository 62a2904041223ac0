"""Runner for configurations of kind `serve`: a forest from the seed
behind `serving.Server`, under the streams of a traffic file.

The forest is drawn (benchmark/generators/forest.py), written as model
text and loaded through `Server.load_model` as a user's would be; the
server is built with the keyword arguments the configuration states
(none: the library's defaults). Requests are rows of a seeded pool.

One thread, this one, paces every open-loop stream against the wall
clock from a schedule drawn before the window and sends with
`predict_async`, so a request costs the generator its binning and an
enqueue; the answer's arrival is stamped by a done-callback. Latency is
answer received minus the time the request was DUE, so a stall charges
every request it delays, and how late the generator itself ran is
reported beside it. A closed-loop stream gets a thread per client, each
sending its next request when the last is answered.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List

import numpy as np

from .. import harness
from ..generators import requests as gen
from ..generators.forest import make_forest_text, quantile_grid
from ..generators.higgs import make_higgs_like
from ..harness import say
from ..reference import forest_numpy

MODEL = "forest"
_POOL, _CHECK = 3, 4               # streams of the seed
#: spin, do not sleep, through the last stretch before a due time
_SPIN_S = 2e-4


def build(cfg: dict, seed: int):
    """(server, pool of rows, model text): forest and pool from the
    seed, the model loaded, every bucket the engine can pad to warmed
    with one request of exactly that many rows."""
    from lightgbm_tpu.serving import Server
    nf = int(cfg["num_features"])
    t0 = time.perf_counter()
    X, _, _ = make_higgs_like(int(cfg["pool_rows"]), nf, seed, stream=_POOL)
    grid = quantile_grid(X, int(cfg["max_bin"]))
    text, stats = make_forest_text(
        seed, trees=int(cfg["num_trees"]), leaves=int(cfg["num_leaves"]),
        grid=grid, beta=float(cfg["split_share_beta"]))
    say("forest: %s, drawn with its pool of %d rows in %.2fs"
        % (stats, len(X), time.perf_counter() - t0))
    t0 = time.perf_counter()
    srv = Server(**cfg.get("server", {}))
    srv.load_model(MODEL, model_str=text)
    load_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    bucket = srv.engine.min_bucket
    buckets = []
    while bucket <= srv.engine.max_bucket:
        srv.predict(MODEL, X[:bucket])
        buckets.append(bucket)
        bucket *= 2
    say("server: model loaded in %.2fs, buckets %s warmed in %.2fs"
        % (load_s, buckets, time.perf_counter() - t0))
    return srv, X, text


class _Log:
    """What happened to every request of a run, by index."""

    OK, SHED, ERROR = 1, 2, 3          # 0: pending, or never sent

    def __init__(self, n: int):
        self.due = np.zeros(n)
        self.sent = np.full(n, np.nan)
        self.done = np.full(n, np.nan)
        self.rows = np.zeros(n, np.int64)
        self.stream = np.zeros(n, np.int64)
        self.outcome = np.zeros(n, np.int8)

    def finish(self, k: int, fut) -> None:
        self.done[k] = time.perf_counter()
        self.outcome[k] = self.OK if fut.exception() is None else self.ERROR


def drive(srv, X: np.ndarray, streams: List[Dict], seed: int,
          seconds: float, tracer, drain_timeout_s: float = 15.0) -> dict:
    """Offer `streams` for `seconds`; returns the request log (times
    relative to the window's start) and the window's length."""
    from lightgbm_tpu.serving import OverloadError
    gen.check_streams(streams)
    pool = len(X)
    opened = gen.merged_open_schedule(streams, seed, seconds, pool)
    n_open = len(opened["due_s"])
    rows, lo = opened["rows"], opened["lo"]
    closed = [(i, s) for i, s in enumerate(streams) if s["loop"] == "closed"]
    # a closed-loop client gets room for more requests than it can send
    # (one that sent them all stops)
    per_client = 16384
    clients = [(i, c) for i, s in closed for c in range(int(s["clients"]))]
    log = _Log(n_open + per_client * len(clients))
    log.rows[:n_open], log.stream[:n_open] = rows, opened["stream"]

    stop = threading.Event()
    t0 = time.perf_counter() + 0.05          # the window's start
    t_end = t0 + seconds
    log.due[:n_open] = t0 + opened["due_s"]  # absolute until the end

    def client(slot: int, index: int, number: int) -> None:
        sched = gen.closed_schedule(streams[index], seed, index, number,
                                    per_client, pool)
        base = n_open + slot * per_client
        for j in range(per_client):
            if stop.is_set() or time.perf_counter() >= t_end:
                return
            k = base + j
            a, r = int(sched["lo"][j]), int(sched["rows"][j])
            log.rows[k], log.stream[k] = r, index
            log.due[k] = log.sent[k] = time.perf_counter()
            try:
                srv.predict(MODEL, X[a:a + r], timeout=drain_timeout_s)
                log.outcome[k] = log.OK
            except OverloadError:
                log.outcome[k] = log.SHED
            except TimeoutError:
                return                   # left pending: counted timed out
            except Exception:
                log.outcome[k] = log.ERROR
            log.done[k] = time.perf_counter()

    threads = [threading.Thread(target=client, args=(slot, i, c),
                                name="bench-client-%d-%d" % (i, c),
                                daemon=True)
               for slot, (i, c) in enumerate(clients)]
    while time.perf_counter() < t0:
        pass
    for th in threads:
        th.start()
    for k in range(n_open):
        target = log.due[k]
        with tracer.span("bench.loadgen.wait"):
            while True:
                left = target - time.perf_counter()
                if left <= 0:
                    break
                if left > _SPIN_S:
                    time.sleep(left - _SPIN_S)
        log.sent[k] = time.perf_counter()
        a, r = int(lo[k]), int(rows[k])
        with tracer.span("bench.loadgen.submit"):
            try:
                fut = srv.predict_async(MODEL, X[a:a + r])
            except OverloadError:
                log.outcome[k] = log.SHED
                log.done[k] = time.perf_counter()
                continue
            fut.add_done_callback(
                lambda f, k=k: log.finish(k, f))
    with tracer.span("bench.loadgen.wait"):
        left = t_end - time.perf_counter()
        if left > 0:
            time.sleep(left)
    # the window is over: let what is in flight land, and no more
    deadline = time.perf_counter() + drain_timeout_s
    for th in threads:
        th.join(timeout=max(0.0, deadline - time.perf_counter()))
    while time.perf_counter() < deadline and \
            np.any(log.outcome[:n_open] == 0):
        time.sleep(0.005)
    stop.set()
    for arr in (log.due, log.sent, log.done):
        arr -= t0
    used = (log.rows > 0)
    return {"log": log, "used": used, "n_open": n_open, "seconds": seconds,
            "hung_clients": sum(th.is_alive() for th in threads)}


def summarize(result: dict, streams: List[Dict]) -> dict:
    """Counts, latencies and rows of a driven window, over the streams
    that are `measured`."""
    log, used, n_open = result["log"], result["used"], result["n_open"]
    seconds = result["seconds"]
    measured = np.isin(log.stream, [i for i, s in enumerate(streams)
                                    if s.get("measured", True)]) & used
    ok = measured & (log.outcome == log.OK)
    is_open = np.arange(len(used)) < n_open
    lat_ms = (log.done - log.due)[ok & is_open] * 1e3
    late_ms = (log.sent - log.due)[measured & is_open] * 1e3
    in_window = ok & (log.done <= seconds)
    return {
        "attempted": int(measured.sum()),
        "ok": int(ok.sum()),
        "shed": int((measured & (log.outcome == log.SHED)).sum()),
        "errored": int((measured & (log.outcome == log.ERROR)).sum()),
        "timed_out": int((measured & (log.outcome == 0)).sum())
        + int(result["hung_clients"]),
        "rows_answered_in_window": int(log.rows[in_window].sum()),
        "requests_answered_in_window": int(in_window.sum()),
        "latency_ms": lat_ms, "late_ms": late_ms,
        "done_s": log.done[ok & is_open],
    }


def run(cell: dict, *, seed: int, seconds: float, trace: bool,
        rehearsal: bool) -> dict:
    cfg, traffic = cell["config"], cell["traffic"]
    if rehearsal:
        cfg, traffic = harness.rehearsal_overlay(cfg, traffic)
    streams = traffic["streams"]
    expect = cfg["expect"]
    clock = harness.start_clocks(rehearsal)
    tracer = harness.TracedWindow(trace, cpu_rehearsal=rehearsal)
    from lightgbm_tpu.reliability import counters
    from lightgbm_tpu.utils.timer import global_timer
    say("imports done")

    srv, X, text = build(cfg, seed)
    try:
        if trace:
            seconds = min(seconds, float(traffic["trace_seconds"]))
        snap0 = srv.metrics_snapshot(MODEL)["models"][MODEL]
        timers0 = global_timer.totals()
        compiles0 = clock.read()
        setup_s = time.perf_counter() - harness.T0
        tracer.start()
        result = drive(srv, X, streams, seed, seconds, tracer,
                       float(traffic.get("drain_timeout_s", 15.0)))
        tracer.stop()
        compiles1 = clock.read()
        snap1 = srv.metrics_snapshot(MODEL)["models"][MODEL]
        timers1 = global_timer.totals()
        got = summarize(result, streams)

        # ---- outside the window: agreement with the plain reference
        rng = np.random.Generator(np.random.PCG64(
            np.random.SeedSequence([int(seed), _CHECK])))
        spec = next(s for s in streams if s.get("measured", True))["sizes"]
        sizes = gen.draw_sizes(rng, spec, int(expect["agreement_requests"]))
        model = forest_numpy.parse_model_text(text)
        worst = 0.0
        for r in sizes:
            a = int(rng.integers(0, len(X) - int(r) + 1))
            have = np.asarray(srv.predict(MODEL, X[a:a + int(r)]),
                              np.float64).reshape(-1)
            want = forest_numpy.predict_proba(model, X[a:a + int(r)])
            worst = max(worst, float(np.max(np.abs(have - want))))
        snap2 = srv.metrics_snapshot(MODEL)["models"][MODEL]
    finally:
        srv.close()

    def delta(key):
        return snap1[key] - snap0[key]

    fallbacks = delta("fallbacks")
    failed = got["shed"] + got["errored"] + got["timed_out"] + fallbacks
    lat, late = got["latency_ms"], got["late_ms"]
    say("window %.1fs: attempted %d, answered %d (%d in the window, %d "
        "rows), shed %d, errored %d, timed out %d, host fallbacks %d, "
        "deadline misses %d"
        % (seconds, got["attempted"], got["ok"],
           got["requests_answered_in_window"],
           got["rows_answered_in_window"], got["shed"], got["errored"],
           got["timed_out"], fallbacks, delta("deadline_misses")))
    say("server: %d device batches for %d requests, %d rows; buckets "
        "compiled inside the window %d, programs built %d; counters %s"
        % (delta("batches"), delta("requests"), delta("rows"),
           delta("buckets_compiled"),
           compiles1["programs"] - compiles0["programs"],
           counters.snapshot()))
    end_to_end = {"setup_s": setup_s,
                  "serve_rows_per_s":
                      got["rows_answered_in_window"] / seconds}
    if len(lat):
        beyond = len(lat) - int(np.ceil(0.99 * len(lat)))
        end_to_end.update({
            "serve_p50_ms": harness.percentile(lat, 50),
            "serve_p90_ms": harness.percentile(lat, 90),
            "serve_p99_ms": harness.percentile(lat, 99)})
        say("latency from the due time over %d answers: p50 %.3f p90 %.3f "
            "p99 %.3f ms (%d samples beyond the 99th), max %.3f; generator "
            "late by p50 %.3f p99 %.3f max %.3f ms"
            % (len(lat), end_to_end["serve_p50_ms"],
               end_to_end["serve_p90_ms"], end_to_end["serve_p99_ms"],
               beyond, lat.max(), harness.percentile(late, 50),
               harness.percentile(late, 99), late.max()))
    say("agreement with forest_numpy over %d requests: max |p - p_ref| "
        "%.3g (limit %g); fallbacks meanwhile %d"
        % (len(sizes), worst, expect["agreement_atol"],
           snap2["fallbacks"] - snap1["fallbacks"]))

    problems = []
    if not worst <= expect["agreement_atol"]:
        problems.append("answers differ from forest_numpy by %g" % worst)
    if snap2["fallbacks"] != snap1["fallbacks"]:
        problems.append("the agreement requests were answered by the host")
    if delta("buckets_compiled") or \
            compiles1["programs"] != compiles0["programs"]:
        problems.append("%d buckets, %d programs were built inside the "
                        "window" % (delta("buckets_compiled"),
                                    compiles1["programs"]
                                    - compiles0["programs"]))
    if not snap1["device_resident"] or snap1["degraded"]:
        problems.append("the model is not served from the device")
    for p in problems:
        say("NOT CORRECT: " + p)

    readings = {
        "kind": "serve", "window_s": seconds,
        "timers_window": {k: timers1.get(k, 0.0) - timers0.get(k, 0.0)
                          for k in timers1},
        "batches": delta("batches"), "rows": delta("rows"),
        "requests": delta("requests"),
        "late_ms": late, "latency_ms": lat,
        "trace": tracer.reduced,
        "memory_peak_bytes": harness.memory_peak_bytes(),
    }
    compared = [("agreement_max_abs", worst, "max",
                 expect["agreement_atol"]),
                ("buckets_compiled_in_window", delta("buckets_compiled"),
                 "max", 0),
                ("programs_in_window",
                 compiles1["programs"] - compiles0["programs"], "max", 0)]
    return {"correct": not problems, "attempted": got["attempted"],
            "failed": failed, "end_to_end": end_to_end,
            "readings": readings, "compared": compared}


def rehearsal_says(cell: dict) -> tuple:
    """Words of a rehearsal's earlier lines: an open-loop stream reports
    its tail's sample count, a closed loop its device batches."""
    tail = "samples beyond the 99th" if any(
        s["loop"] == "open" for s in cell["traffic"]["streams"]) \
        else "device batches"
    return ("leaf_depth_median", tail, "buckets compiled inside the window 0",
            "agreement with forest_numpy")


def rehearsal_reads(cell: dict) -> dict:
    """Per-layer metrics a traced rehearsal reports, with their ranges:
    none in particular (a short window of a CPU may hold no batch of one
    stream)."""
    return {}
