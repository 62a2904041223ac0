"""Device milliseconds per tree in which an operation of the objective
ran, in the traced window: the union of the intervals of the device
operations the program names `objective.<name>` (`jax.named_scope`
around `get_gradients` in the fused scan), averaged over the chips. The
runner of a ranking job takes it from the capture itself
(`runners/rank.py`, by `training.scope_busy_s`): the ten names a
breakdown keeps cannot carry it. A program that names no such scope gives nothing."""

NAME = "objective.device_ms_per_tree"
UNIT = "ms"
BETTER = "lower"
LAYER = "objective"
SOURCE = "device_trace"
MOVES = "trees_per_s"
WORKLOADS = ["mslr_rank_train"]


def read(r):
    busy = r.get("objective_busy_s")
    if busy is None or not r.get("window_trees"):
        return None
    return busy * 1e3 / r["window_trees"]
