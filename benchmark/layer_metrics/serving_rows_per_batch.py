"""Rows per coalesced device batch in the window (rows / batches of the
model's metrics snapshot): how full the batcher got its buckets."""

NAME = "serving.rows_per_batch"
UNIT = "rows"
BETTER = "higher"
LAYER = "serving"
SOURCE = "program_counter"
MOVES = "serve_rows_per_s"
WORKLOADS = None


def read(r):
    if r.get("kind") != "serve" or not r.get("batches"):
        return None
    return r["rows"] / r["batches"]
