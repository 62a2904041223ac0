"""Share of the pair slots a ranking objective computes a tree that the
reference's two loops would visit: `pairs` (the sum over queries of the
(i, j) with i < min(cnt - 1, truncation level) and i < j < cnt, from the
query lengths alone) over `pair_slots` (rows x columns of every pair
tensor the program builds, padding included), both attributes of the
program's `objective.init` span. Static: set once at `init`, no sync.
A layout padded to the longest query reads a fraction of a percent on
heavy-tailed queries. A program without the span gives nothing."""

from benchmark import program_readings as pr

NAME = "objective.pair_fill"
UNIT = "%"
BETTER = "higher"
LAYER = "objective"
SOURCE = "program_counter"
MOVES = "trees_per_s"
WORKLOADS = ["mslr_rank_train"]


def read(r):
    recs = pr.spans(r)
    if recs is None:
        return None
    attrs = [s.get("attrs") or {} for s in recs
             if s["name"] == "objective.init"]
    if not attrs or not attrs[-1].get("pair_slots"):
        return None
    return 100.0 * attrs[-1]["pairs"] / attrs[-1]["pair_slots"]
