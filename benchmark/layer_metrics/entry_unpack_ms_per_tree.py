"""Milliseconds the host spends unpacking one tree of a fused block:
the median `entry.unpack_tree` span over the window's trees. The median
and not the mean or the sum: the first unpack_tree of a block waits out
the block still in flight (seconds), the rest run with the device idle,
and those are what entry.gap_ms_per_tree sees from outside."""

from benchmark import program_readings as pr

NAME = "entry.unpack_ms_per_tree"
UNIT = "ms"
BETTER = "lower"
LAYER = "entry"
SOURCE = "program_span"
MOVES = "trees_per_s"
WORKLOADS = ["higgs_train"]


def read(r):
    recs = pr.spans(r)
    if recs is None:
        return None
    return pr.median_ms([s["dur"] for s in
                         pr.in_window(r, recs, "entry.unpack_tree")])
