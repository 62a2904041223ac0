"""Seconds JAX spent tracing the programs built before the window: the
compile ledger's trace events (lightgbm_tpu/observability/compiles.py,
fed by /jax/core/compile/jaxpr_trace_duration), each with what nested
in it. With boosting.lower_s, what the program spends preparing its
programs before the window, cache or no cache."""

from benchmark import program_readings as pr

NAME = "boosting.trace_s"
UNIT = "s"
BETTER = "lower"
LAYER = "boosting"
SOURCE = "program_counter"
MOVES = "setup_s"
WORKLOADS = None


def read(r):
    events = pr.built_before_window(r, "trace")
    if events is None:
        return None
    return sum(e["seconds"] for e in events)
