"""Device-busy milliseconds per tree in the traced window (union of the
intervals in which an operation ran, averaged over the chips)."""

NAME = "growth.device_ms_per_tree"
UNIT = "ms"
BETTER = "lower"
LAYER = "growth"
SOURCE = "device_trace"
MOVES = "trees_per_s"
WORKLOADS = None


def read(r):
    t = r.get("trace")
    if not t or not r.get("window_trees"):
        return None
    return t["busy_s"] * 1e3 / r["window_trees"]
