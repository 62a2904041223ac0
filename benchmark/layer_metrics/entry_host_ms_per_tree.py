"""Milliseconds of host work per tree between two dispatches, from the
program's spans: per `entry.tree` of the window, the children named in
HOST_SPANS (every phase but `entry.dispatch`, which enqueues the tree,
and `entry.wait_device`, where the host waits for the device), summed,
then the median over the trees.

Where the window ran fused blocks instead (the CPU rehearsal of a
sharded cell does), a tree's host work is its `entry.unpack_tree` (the
median over the window's trees: the first of a block waits out the
block in flight) plus its share of the block's metric sync and
callbacks."""

from statistics import median

from benchmark import program_readings as pr

NAME = "entry.host_ms_per_tree"
UNIT = "ms"
BETTER = "lower"
LAYER = "entry"
SOURCE = "program_span"
MOVES = "trees_per_s"
WORKLOADS = ["higgs_dp4_train"]

#: the children of entry.tree that are host work
HOST_SPANS = ("boosting.gradients", "boosting.bagging", "boosting.shrink",
              "boosting.update_score", "entry.append_tree",
              "entry.callbacks")
#: and of a fused entry.block, beside its trees' unpacking
BLOCK_HOST_SPANS = ("entry.sync_metrics", "entry.callbacks")


def read(r):
    recs = pr.spans(r)
    if recs is None:
        return None
    trees = pr.in_window(r, recs, "entry.tree")
    if trees:
        return median(
            sum(c["dur"] for c in pr.children(recs, t)
                if c["name"] in HOST_SPANS) for t in trees) * 1e3
    unpack = pr.median_ms([s["dur"] for s in
                           pr.in_window(r, recs, "entry.unpack_tree")])
    blocks = pr.in_window(r, recs, "entry.block")
    if unpack is None or not blocks:
        return None
    per_tree = [sum(c["dur"] for c in pr.children(recs, b)
                    if c["name"] in BLOCK_HOST_SPANS)
                / max(1, (b.get("attrs") or {}).get("k", 1))
                for b in blocks]
    return unpack + median(per_tree) * 1e3
