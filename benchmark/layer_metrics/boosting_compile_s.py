"""Seconds the backend spent building programs, or fetching them from the
persistent cache, before the window opened (JAX monitoring events; a
copy of chip_smoke.py's CompileClock)."""

NAME = "boosting.compile_s"
UNIT = "s"
BETTER = "lower"
LAYER = "boosting"
SOURCE = "program_counter"
MOVES = "setup_s"
WORKLOADS = None


def read(r):
    return r.get("compile_setup_s") if r.get("kind") == "train" else None
