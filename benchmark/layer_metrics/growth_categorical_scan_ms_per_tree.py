"""Device milliseconds per tree in which the categorical half of the
split search ran, in the traced window: the union of the intervals of
the device operations the program names `split.categorical`
(`jax.named_scope` in learner/split.py: the one-against-rest gains, the
sort by g / (h + cat_smooth), the gathers of the first
max_cat_threshold bins of either order, the batching scan, the gains),
averaged over the chips. The runner of a job with categorical columns
takes it from the capture itself (`runners/train_cat.py`), as
`objective.device_ms_per_tree` is taken. A fusion that mixes these
operations with others goes by one `op_name` and is counted whole or
not at all. A program that names no such scope gives nothing."""

NAME = "growth.categorical_scan_ms_per_tree"
UNIT = "ms"
BETTER = "lower"
LAYER = "growth"
SOURCE = "device_trace"
MOVES = "trees_per_s"
WORKLOADS = ["expo_categorical_train"]


def read(r):
    busy = r.get("categorical_busy_s")
    if busy is None or not r.get("window_trees"):
        return None
    return busy * 1e3 / r["window_trees"]
