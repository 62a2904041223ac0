"""Device milliseconds per tree of the passes built over the feature
axis in tiles, in the traced window: the self time of the kernels
`fused_route_hist_tiled` (a one-hot pass's route and build) and
`grouped_hist_tiled` (a slot-grouped pass's build), by their names in
the capture, averaged over the chips (`runners/train_missing.py` reads
them off the capture as `wide_pass_s`). A program that builds no pass
in tiles gives nothing."""

NAME = "growth.wide_pass_ms_per_tree"
UNIT = "ms"
BETTER = "lower"
LAYER = "growth"
SOURCE = "device_trace"
MOVES = "trees_per_s"
WORKLOADS = ["bosch_missing_train"]


def read(r):
    busy = r.get("wide_pass_s")
    if busy is None or not r.get("window_trees"):
        return None
    return busy * 1e3 / r["window_trees"]
