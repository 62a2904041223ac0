"""Milliseconds per tree in which the device ran nothing during the
traced window: what the entry layer (engine.train and the pipelined
executor: dispatching a block, unpacking the last one) costs that the
device does NOT hide. PipelineStats.host_ms was meant to be this and is
not: on the chip finalize_block's slice programs queue behind the
running block, so it reads the block's wall (12.56 s a block, my chip
run, PR 22)."""

NAME = "entry.gap_ms_per_tree"
UNIT = "ms"
BETTER = "lower"
LAYER = "entry"
SOURCE = "device_trace"
MOVES = "trees_per_s"
WORKLOADS = None


def read(r):
    t = r.get("trace")
    if not t or not t.get("window_s") or not r.get("window_trees"):
        return None
    return (t["window_s"] - t["busy_s"]) * 1e3 / r["window_trees"]
