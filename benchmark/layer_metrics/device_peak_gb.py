"""Peak device memory on the fullest chip, in GB (memory_stats: live
buffers at their peak plus the largest program scratch reserved)."""

NAME = "device.peak_gb"
UNIT = "GB"
BETTER = "lower"
LAYER = "device"
SOURCE = "program_counter"
MOVES = "trees_per_s"
WORKLOADS = None


def read(r):
    b = r.get("memory_peak_bytes")
    return None if b is None else b / 1e9
