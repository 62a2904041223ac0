"""Share of the traced window in which no operation ran on the device
(1 - busy / window, averaged over the chips)."""

NAME = "device.idle_share"
UNIT = "%"
BETTER = "lower"
LAYER = "device"
SOURCE = "device_trace"
MOVES = "trees_per_s"
WORKLOADS = None


def read(r):
    t = r.get("trace")
    if not t or not t.get("window_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
