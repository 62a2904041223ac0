"""What the program measured of itself, for the per-layer readers that
take a number from inside it: the span ring and the compile ledger of
`lightgbm_tpu.observability`, in the process the runner ran in.

A span record is {"name", "id", "parent_id", "ts", "dur", "attrs"}
(`ts` and `dur` in seconds on the ring's clock); spans of one block or
one tree carry `iter` (the first boosting iteration they cover) and `k`
(how many). A ledger event is {"kind": "trace" | "lower" | "backend" |
"hits" | "misses", "fun", "seconds", "ts"} with `ts` on the same clock.
A reader selects the window by `iter` (warm_trees <= iter < warm_trees +
window_trees) and set-up as everything that ended before the window's
first span began.

Readings may bring both with them (`spans`, `compile_events`: a test's
hand-made ones, or a runner that snapshots them at the window's end);
otherwise they are read from the live process. A program that has no
such ring or ledger (the parent of the PR that added them) gives None,
and the reader leaves its metric out.
"""

from __future__ import annotations

from statistics import median
from typing import Dict, List, Optional


def _registry():
    try:
        from lightgbm_tpu.observability import registry
    except Exception:
        return None
    if not hasattr(registry.compiles, "events") or \
            not hasattr(registry.trace, "totals"):
        return None
    return registry


def spans(r: dict) -> Optional[List[Dict]]:
    if r.get("kind") != "train":
        return None
    if "spans" in r:
        return r["spans"]
    reg = _registry()
    return reg.trace.spans() if reg is not None else None


def compile_events(r: dict) -> Optional[List[Dict]]:
    if r.get("kind") != "train":
        return None
    if "compile_events" in r:
        return r["compile_events"]
    reg = _registry()
    return reg.compiles.events() if reg is not None else None


def _iter(rec: Dict):
    return (rec.get("attrs") or {}).get("iter")


def in_window(r: dict, recs: List[Dict], name: str) -> List[Dict]:
    """The spans called `name` whose `iter` lies in the window."""
    lo = r.get("warm_trees")
    n = r.get("window_trees")
    if lo is None or not n:
        return []
    return [s for s in recs if s["name"] == name and _iter(s) is not None
            and lo <= _iter(s) < lo + n]


def window_start_ts(r: dict, recs: List[Dict]) -> Optional[float]:
    """When the first span of the window's first tree began."""
    lo = r.get("warm_trees")
    if lo is None:
        return None
    ts = [s["ts"] for s in recs
          if s["name"] in ("entry.block", "entry.tree")
          and _iter(s) is not None and _iter(s) >= lo]
    return min(ts) if ts else None


def built_before_window(r: dict, kind: str) -> Optional[List[Dict]]:
    """The ledger's events of one kind that ended before the window."""
    recs, events = spans(r), compile_events(r)
    if recs is None or events is None:
        return None
    cut = window_start_ts(r, recs)
    if cut is None:
        return None
    return [e for e in events if e["kind"] == kind and e["ts"] < cut]


def children(recs: List[Dict], parent: Dict) -> List[Dict]:
    return [s for s in recs if s.get("parent_id") == parent["id"]]


def median_ms(values: List[float]) -> Optional[float]:
    return median(values) * 1e3 if values else None
