"""Passes past the schedule a tree of the window ran: `bridge_passes`
plus `fixup_iters` over `trees`, attributes of the program's
`entry.unpack_block` spans (counted inside the growth program:
`growth_passes_per_tree`), summed over the blocks of the window. The
schedule's passes are the same for every tree; these are the tree's
own, data-dependent, and each is a sweep over every row whatever few
rows it builds: what moves a cell's rate between seeds when the program
and the machine are the same. A program without the attributes gives
nothing."""

from benchmark.layer_metrics.growth_passes_per_tree import per_tree

NAME = "growth.offschedule_passes_per_tree"
UNIT = "count"
BETTER = "lower"
LAYER = "growth"
SOURCE = "program_counter"
MOVES = "trees_per_s"
WORKLOADS = None


def read(r):
    return per_tree(r, "bridge_passes", "fixup_iters")
