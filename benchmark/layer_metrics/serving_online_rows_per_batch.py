"""serving.rows_per_batch for cells that report latency: how many rows
the coalescing window gathered per dispatch."""

NAME = "serving.online_rows_per_batch"
UNIT = "rows"
BETTER = "higher"
LAYER = "serving"
SOURCE = "program_counter"
MOVES = "serve_p50_ms"
WORKLOADS = None


def read(r):
    from . import serving_rows_per_batch as same
    return same.read(r)
