"""Share of the window's tree nodes that decide on a categorical
column: `cat_nodes` over `nodes`, both attributes of the program's
`entry.unpack_block` spans (counted on the device by the one program
that splits a block into its trees, read after the unpack's own wait:
no program, no wait of their own), summed over the blocks of the
window. Says that the many-against-many search and the membership
routing do the work in this cell; near zero would mean the trees grow
on the numerical columns and the cell measures what `higgs_train` does.
A program without the attributes gives nothing."""

from benchmark import program_readings as pr

NAME = "growth.categorical_split_share"
UNIT = "%"
BETTER = "higher"
LAYER = "growth"
SOURCE = "program_counter"
MOVES = "trees_per_s"
WORKLOADS = ["expo_categorical_train"]


def read(r):
    recs = pr.spans(r)
    if recs is None:
        return None
    attrs = [s.get("attrs") or {}
             for s in pr.in_window(r, recs, "entry.unpack_block")]
    nodes = sum(a.get("nodes") or 0 for a in attrs)
    if not nodes or any("cat_nodes" not in a for a in attrs):
        return None
    return 100.0 * sum(a["cat_nodes"] for a in attrs) / nodes
