"""A traced run of a benchmark cell, with the device operations of its
capture COUNTED by opcode and shape beside what the growth counters say
the same trees ran (PR 37's cross-check).

The benchmark's reduction keeps self time by operation name and throws
the capture away; a count needs the events. This runs the cell's own
`python3 -m benchmark ... --trace 1` in this process, keeps the capture
`trace_reduce.load_xplane` read, and prints after the run's own last
line one more JSON line: chip 0's operations of the asked opcode, how
many of each result shape, and the `entry.unpack_block` attributes of
every fused block of the run (the traced window is the blocks from
`warm_trees` on, `trace_blocks` of them: the runner's `window:` line).

On `expo_categorical_train` XLA lowers the scatter that inverts a
grouped pass's rank as ONE sort of `(s32[11000000], s32[11000000])`
(PERF.md section 5), so the sorts of that shape in the capture must
equal the window's `grouped_passes` exactly.

    python helpers/capture_op_counts.py --opcode sort -- \\
        --workload expo_categorical_train --seed 2147487707 --seconds 38

`--untraced` runs the cell with `--trace 0` and prints the blocks alone:
every block of a whole window with what it ran, to hold beside the
block walls of the runner's `window:` line.
"""

import argparse
import collections
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--opcode", default="sort")
    ap.add_argument("--untraced", action="store_true")
    ap.add_argument("cell", nargs=argparse.REMAINDER,
                    help="-- then the benchmark's own arguments "
                         "(--trace 1 is added)")
    args = ap.parse_args(argv)
    from benchmark import __main__ as bench  # first: the process's T0
    from benchmark import trace_reduce
    kept = {}
    load = trace_reduce.load_xplane

    def keeping(path, cpu_rehearsal=False):
        kept["trace"] = load(path, cpu_rehearsal)
        return kept["trace"]

    trace_reduce.load_xplane = keeping
    rc = bench.main([a for a in args.cell if a != "--"] +
                    ["--trace", "0" if args.untraced else "1"])
    if rc or not (args.untraced or "trace" in kept):
        return rc or 1
    shapes = collections.Counter()
    for text, _, _ in kept.get("trace", {"devices": [{"ops": []}]})[
            "devices"][0]["ops"]:
        if trace_reduce.opcode(text) == args.opcode:
            shape = text.partition(" = ")[2]
            shapes[shape.partition(" " + args.opcode + "(")[0]] += 1
    from lightgbm_tpu.observability import registry
    blocks = [s["attrs"] for s in registry.trace.spans()
              if s["name"] == "entry.unpack_block"]
    print(json.dumps({"opcode": args.opcode, "chip0": dict(shapes),
                      "blocks": blocks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
