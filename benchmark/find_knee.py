"""Find an open-loop serving cell's knee, once, on the chip.

    python3 -m benchmark.find_knee --workload serve_online --seed 1 \
        --rates 20,40,80,160,320 --step-seconds 8

Builds the cell's server as the runner does (one process, one set-up),
then offers the cell's own streams at each rate in turn, lowest first,
and prints one JSON line a rate: p50 and p99 from the due time, the p99
of each half of the step (a p99 that grows over the window is a queue
that grows), shed and failed requests, and how late the generator ran.
The knee is the highest rate with nothing shed or failed and a second
half no worse than 1.5 x the first; the sweep stops at the first rate
past it. Write 0.8 x the knee into the traffic file as `rate_per_s`, as
a number: a cell offers a fixed load and never searches for one.
"""

from __future__ import annotations

from . import harness  # first: it reads the process's start time

import argparse
import json
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", required=True,
                    help="requests per second, comma-separated, ascending")
    ap.add_argument("--step-seconds", type=float, default=8.0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    cfg, traffic = cell["config"], cell["traffic"]
    if cfg["kind"] != "serve":
        raise SystemExit("only a serving cell has a knee")
    harness.claim_device(cell["chips"], args.rehearse_cpu)
    if args.rehearse_cpu:
        cfg, traffic = harness.rehearsal_overlay(cfg, traffic)
    harness.start_clocks(args.rehearse_cpu)
    from .runners import serve
    srv, X, _ = serve.build(cfg, args.seed)
    tracer = harness.TracedWindow(False)
    knee = None
    try:
        for step, rate in enumerate(float(r) for r in args.rates.split(",")):
            streams = [dict(s, rate_per_s=rate) if s["loop"] == "open"
                       else s for s in traffic["streams"]]
            got = serve.summarize(
                serve.drive(srv, X, streams, args.seed + step,
                            args.step_seconds, tracer), streams)
            lat, done = got["latency_ms"], got["done_s"]
            half = done < args.step_seconds / 2
            line = {"rate_per_s": rate, "attempted": got["attempted"],
                    "answered": got["ok"], "shed": got["shed"],
                    "errored": got["errored"],
                    "timed_out": got["timed_out"]}
            if len(lat):
                line.update({
                    "p50_ms": harness.percentile(lat, 50),
                    "p99_ms": harness.percentile(lat, 99),
                    "p99_first_half_ms": harness.percentile(lat[half], 99)
                    if half.any() else None,
                    "p99_second_half_ms":
                        harness.percentile(lat[~half], 99)
                        if (~half).any() else None,
                    "late_p99_ms": harness.percentile(got["late_ms"], 99)})
            grows = bool(len(lat)) and half.any() and (~half).any() and \
                line["p99_second_half_ms"] > 1.5 * line["p99_first_half_ms"]
            line["sustained"] = bool(
                len(lat) and not grows and not got["shed"]
                and not got["errored"] and not got["timed_out"])
            print(json.dumps(line), flush=True)
            if not line["sustained"]:
                break
            knee = rate
    finally:
        srv.close()
    print(json.dumps({"knee_rate_per_s": knee,
                      "rate_at_0.8": None if knee is None else 0.8 * knee}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
