"""99th percentile of (time sent - time due) over the open-loop requests:
how late the generator itself ran. A starved generator must not read as
a fast server, so a run whose lateness nears its latencies is void."""

NAME = "loadgen.late_ms_p99"
UNIT = "ms"
BETTER = "lower"
LAYER = "load generator"
SOURCE = "host_clock"
MOVES = "serve_p99_ms"
WORKLOADS = None


def read(r):
    late = r.get("late_ms")
    if late is None or not len(late):
        return None
    from ..harness import percentile
    return percentile(late, 99)
