"""Seconds from the lgb.train call to the first dispatch: Booster and
GBDT construction, placing the bins on the device, the objective's
set-up. The total of the program's `boosting.init` span, from the timer
totals (never evicted; the ring may be)."""

NAME = "boosting.init_s"
UNIT = "s"
BETTER = "lower"
LAYER = "boosting"
SOURCE = "program_span"
MOVES = "setup_s"
WORKLOADS = None


def read(r):
    if r.get("kind") != "train":
        return None
    return (r.get("timers") or {}).get("boosting.init")
