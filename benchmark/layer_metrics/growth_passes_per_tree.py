"""Histogram passes a tree of the window ran: `passes` over `trees`,
both attributes of the program's `entry.unpack_block` spans, summed over
the blocks of the window. The growth program counts them itself, inside
the pass that runs (a pass its schedule skips adds nothing, every
iteration of the fixup loop adds one), the fused scan stacks the counts
with every tree and the unpack reads them after its own wait: no
program, no wait of their own. A tree on its schedule runs a fixed
number (nine at 255 leaves); what lies above is the bridge and the
fixup loop, as often as the tree's shape asks, and each costs a sweep
over every row. A program without the attributes gives nothing.

`window_attrs` and `live_row_share` serve the other three readers of
the growth counters too."""

from benchmark import program_readings as pr

NAME = "growth.passes_per_tree"
UNIT = "count"
BETTER = "lower"
LAYER = "growth"
SOURCE = "program_counter"
MOVES = "trees_per_s"
WORKLOADS = None


def window_attrs(r, *names):
    """The attributes of the window's `entry.unpack_block` spans, one
    dict a block; None where the window has no such span or one of them
    lacks one of `names` (the parent's program)."""
    recs = pr.spans(r)
    if recs is None:
        return None
    attrs = [s.get("attrs") or {}
             for s in pr.in_window(r, recs, "entry.unpack_block")]
    if not attrs or any(n not in a for a in attrs for n in names):
        return None
    return attrs


def per_tree(r, *names):
    """The window's sum of the attributes `names` over its trees."""
    attrs = window_attrs(r, "trees", *names)
    trees = sum(a["trees"] for a in attrs) if attrs else 0
    if not trees:
        return None
    return sum(a[n] for a in attrs for n in names) / trees


def live_row_share(r, live, passes):
    """In percent, the rows `live` in the window's passes of one
    formulation over the rows those passes swept (`rows` each: every
    row of the data, over the whole mesh); None where none ran."""
    attrs = window_attrs(r, live, passes, "rows")
    swept = sum(a[passes] * a["rows"] for a in attrs) if attrs else 0
    if not swept:
        return None
    return 100.0 * sum(a[live] for a in attrs) / swept


def read(r):
    return per_tree(r, "passes")
