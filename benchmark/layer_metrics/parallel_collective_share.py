"""Share of the traced window that chip 0 spent in collective operations
(all-reduce and its kin, synchronous or asynchronous)."""

NAME = "parallel.collective_share"
UNIT = "%"
BETTER = "lower"
LAYER = "parallel"
SOURCE = "device_trace"
MOVES = "trees_per_s"
WORKLOADS = ["higgs_dp4_train"]


def read(r):
    t = r.get("trace")
    if not t or not t.get("window_s") or t["devices"] < 2:
        return None
    return 100.0 * t["collective_s"] / t["window_s"]
