"""Share of the rows the window's one-hot passes swept that stood in a
slot the pass built: `onehot_rows` over `onehot_passes` x `rows`,
attributes of the program's `entry.unpack_block` spans (counted inside
the growth program: `growth_passes_per_tree`), summed over the blocks
of the window. A one-hot pass multiplies EVERY row against every slot
and sits on the MXU's weight-tile floor, so its cost goes with the rows
swept; the share says how much of that sweep was useful work, which is
the most a program that sweeps live rows only could take out of it. It
describes the work (the root pass builds every row, a later pass the
smaller sibling of each split), as `growth.categorical_split_share`
does. A program without the attributes, or a window with no one-hot
pass, gives nothing."""

from benchmark.layer_metrics.growth_passes_per_tree import live_row_share

NAME = "growth.onehot_live_row_share"
UNIT = "%"
BETTER = "higher"
LAYER = "growth"
SOURCE = "program_counter"
MOVES = "trees_per_s"
WORKLOADS = None


def read(r):
    return live_row_share(r, "onehot_rows", "onehot_passes")
