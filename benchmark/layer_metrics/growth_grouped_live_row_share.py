"""Share of the rows the window's grouped passes swept that stood in a
slot the pass built: `grouped_rows` over `grouped_passes` x `rows`,
attributes of the program's `entry.unpack_block` spans (counted inside
the growth program: `growth_passes_per_tree`), summed over the blocks
of the window. A grouped pass routes, ranks, scatters and sorts ALL
rows to gather the live ones by slot group, and only its gather and its
kernel go with the live rows; the share says how much of the row-sized
work builds anything, which is the most a program that keeps rows in
slot order across passes could take out of it (a pass past the schedule
costs its whole sweep for a share of a few percent). It describes the
work, as `growth.categorical_split_share` does. A program without the
attributes, or a window with no grouped pass, gives nothing."""

from benchmark.layer_metrics.growth_passes_per_tree import live_row_share

NAME = "growth.grouped_live_row_share"
UNIT = "%"
BETTER = "higher"
LAYER = "growth"
SOURCE = "program_counter"
MOVES = "trees_per_s"
WORKLOADS = None


def read(r):
    return live_row_share(r, "grouped_rows", "grouped_passes")
