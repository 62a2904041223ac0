"""How many programs the backend built or fetched from the persistent
cache before the window: the compile ledger's backend events. Each was
traced and lowered in this process whether or not the cache had it."""

from benchmark import program_readings as pr

NAME = "boosting.programs_built"
UNIT = "count"
BETTER = "lower"
LAYER = "boosting"
SOURCE = "program_counter"
MOVES = "setup_s"
WORKLOADS = None


def read(r):
    events = pr.built_before_window(r, "backend")
    if events is None:
        return None
    return len(events)
