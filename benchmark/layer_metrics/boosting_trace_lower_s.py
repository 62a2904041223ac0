"""Seconds of Python tracing and lowering before the window: the wall
from the lgb.train call to the window, less the backend compile seconds
in it, less the warm-up trees at the steady rate. What is left is the
program preparing its programs, cache or no cache."""

NAME = "boosting.trace_lower_s"
UNIT = "s"
BETTER = "lower"
LAYER = "boosting"
SOURCE = "host_clock"
MOVES = "setup_s"
WORKLOADS = None


def read(r):
    if r.get("kind") != "train" or not r.get("window_trees"):
        return None
    steady = r["window_s"] / r["window_trees"]
    return max(0.0, r["train_call_to_window_s"] - r["compile_setup_s"]
               - r["warm_trees"] * steady)
