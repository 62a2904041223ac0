"""Milliseconds per tree in which the device ran nothing during the
traced window: what the entry layer (engine.train and the pipelined
executor: dispatching a block, unpacking the last one) costs that the
device does NOT hide. With device.idle_share it is the measure of the
host's hold on the chip; the program's own spans around the host's work
say where, not how much of it the device waited for."""

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
