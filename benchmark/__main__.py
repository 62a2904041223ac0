"""python3 -m benchmark --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs one cell once: refuse anything but a TPU that peaks.json knows,
set up (data or forest from the seed, warm every shape the window
uses), measure for --seconds, check the outputs, print the result as
the last line of stdout, exit 0. A run that cannot give a result prints
none and exits non-zero.
"""

from . import harness  # first: it reads the process's start time

import argparse
import json
import sys
import traceback


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python3 -m benchmark",
                                 description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="the cell's control flow at the tiny size its "
                         "configuration states, on the CPU: proves "
                         "nothing about the chip, and its record says "
                         '"platform": "cpu"')
    args = ap.parse_args(argv)
    try:
        cell = harness.load_cell(args.workload)
        if cell.get("held_back"):
            harness.say("cell %s is HELD BACK (benchmark/held_back.json): "
                        "the driver does not run it" % cell["name"])
        runner = harness.load_runner(cell["config"]["kind"])
        device = harness.claim_device(cell["chips"], args.rehearse_cpu)
        harness.say("cell %s = %s x %s on %s" % (
            cell["name"], cell["config_entry"]["name"],
            cell["traffic_name"], device))
        run = runner.run(cell, seed=args.seed, seconds=args.seconds,
                         trace=bool(args.trace),
                         rehearsal=args.rehearse_cpu)
        line = harness.result_line(cell, run, device, bool(args.trace))
    except harness.BenchmarkError as exc:
        print("benchmark: %s" % exc, file=sys.stderr)
        return 2
    except Exception:
        traceback.print_exc()
        return 1
    # the numbers `correct` compared are the last lines of stderr too
    for name, got in line.get("compared", {}).items():
        bound = "max" if "max" in got else "min"
        print("compared: %s %r (%s %r)" % (name, got["value"], bound,
                                           got[bound]),
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
