"""Share of device-busy time inside Mosaic custom calls (the Pallas
histogram, route and node kernels), from the trace. It moves
growth.device_ms_per_tree and through it trees_per_s."""

NAME = "kernels.custom_call_share"
UNIT = "%"
BETTER = "higher"
LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "trees_per_s"
WORKLOADS = None


def read(r):
    t = r.get("trace")
    if not t or not t["busy_s"] > 0:
        return None
    return 100.0 * t["mosaic_s"] / t["busy_s"]
