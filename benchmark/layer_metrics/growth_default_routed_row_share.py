"""Share of the rows routed at the window's splits that went by their
node's default direction: `default_rows` over `routed_rows`, attributes
of the program's `entry.unpack_block` spans, summed over the blocks of
the window. Both are counted inside the growth program
(grower_mxu.GROWTH_COUNTERS): the rows of every node that split, and of
them the rows in the NaN bin of its split column (the count channel of
the node's histogram), before the prune to best-first. It says how much
of the routing the learned default direction decides, as
`growth.categorical_split_share` says how much the category sets do. A
program without the attributes, or a window with no split, gives
nothing."""

from benchmark import program_readings as pr

NAME = "growth.default_routed_row_share"
UNIT = "%"
BETTER = "higher"
LAYER = "growth"
SOURCE = "program_counter"
MOVES = "trees_per_s"
WORKLOADS = ["bosch_missing_train"]


def read(r):
    recs = pr.spans(r)
    if recs is None:
        return None
    attrs = [s.get("attrs") or {}
             for s in pr.in_window(r, recs, "entry.unpack_block")]
    if not attrs or any("routed_rows" not in a for a in attrs):
        return None
    routed = sum(a["routed_rows"] for a in attrs)
    if not routed:
        return None
    return 100.0 * sum(a["default_rows"] for a in attrs) / routed
