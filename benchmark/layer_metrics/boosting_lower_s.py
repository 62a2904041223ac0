"""Seconds JAX spent lowering to MLIR the programs built before the
window: the compile ledger's lowering events
(/jax/core/compile/jaxpr_to_mlir_module_duration)."""

from benchmark import program_readings as pr

NAME = "boosting.lower_s"
UNIT = "s"
BETTER = "lower"
LAYER = "boosting"
SOURCE = "program_counter"
MOVES = "setup_s"
WORKLOADS = None


def read(r):
    events = pr.built_before_window(r, "lower")
    if events is None:
        return None
    return sum(e["seconds"] for e in events)
