"""Bench regression sentinel: the perf trajectory as a checked artifact.

Every round leaves a ``BENCH_r<N>.json`` (wrapped single-line bench
record: {"n", "cmd", "rc", "tail", "parsed": {metric record}}), a
``MULTICHIP_r<N>.json`` ({"n_devices", "rc", "ok", "skipped", "tail"};
real-training rounds add {"trees_per_sec", "vs_baseline",
"tree_learner"} — bench.py --multichip)
and — since the serving chaos PR — a ``SERVE_r<N>.json``
(bench-record shape, emitted by bench_serve.py: sustained QPS at
p99<10ms plus shed/fallback/failover side channels) in the repo root. Nothing ever read them back — a silent perf
regression would ride along unnoticed until someone eyeballed the
numbers. This module parses the whole trajectory, computes per-metric
best-so-far, and flags the latest round when it drops more than
``REGRESSION_THRESHOLD`` below the best earlier round.

The trajectory is imperfect by construction (rounds where the
accelerator was unavailable have ``rc != 0`` / ``parsed: null`` /
``value: 0``): such records are *unusable samples*, excluded from
best-so-far — but an unusable LATEST round after any usable one is
itself reported as a regression (the bench stopped working).

A round may instead DECLARE denial: a bench/serve record with
``"skipped": true`` and a ``"skip_reason"`` string (the multichip
series has carried the same flag since r01). Skipped rounds are not
samples and do not trip the unusable-latest rule — the distinction is
intent: an rc=0/value=0 record says "the bench ran and measured
nothing" (that IS a regression), a skipped record says "the operator
established the hardware was unreachable and recorded why".

Wired into ``bench.py --compare [--strict]`` (strict: exit nonzero on
regressions) and the ``make bench`` tail; tier-1 tests schema-validate
the real records (tests/test_regress.py).
"""

from __future__ import annotations

import glob
import json
import os
import re
from typing import Dict, List, Optional, Tuple

__all__ = ["REGRESSION_THRESHOLD", "load_trajectory", "validate_record",
           "compare"]

#: fractional drop vs best-so-far that counts as a regression
REGRESSION_THRESHOLD = 0.10

_ROUND_RE = re.compile(r"_r(\d+)\.json$")


def _load_series(root: str, pattern: str) -> List[Tuple[int, str, Dict]]:
    """[(round, filename, record)] sorted by round number."""
    out = []
    for path in glob.glob(os.path.join(root, pattern)):
        m = _ROUND_RE.search(os.path.basename(path))
        if not m:
            continue
        with open(path) as fh:
            out.append((int(m.group(1)), os.path.basename(path),
                        json.load(fh)))
    out.sort()
    return out


def load_trajectory(root: str) -> Dict[str, List[Tuple[int, str, Dict]]]:
    """{"bench": [...], "multichip": [...], "serve": [...]}
    round-ordered records."""
    return {"bench": _load_series(root, "BENCH_r*.json"),
            "multichip": _load_series(root, "MULTICHIP_r*.json"),
            "serve": _load_series(root, "SERVE_r*.json")}


def validate_record(kind: str, name: str, rec) -> List[str]:
    """Schema problems with one on-disk record ([] when clean)."""
    problems: List[str] = []

    def _need(key, types):
        if key not in rec:
            problems.append(f"{name}: missing key {key!r}")
        elif not isinstance(rec[key], types):
            problems.append(f"{name}: {key!r} has type "
                            f"{type(rec[key]).__name__}")

    if not isinstance(rec, dict):
        return [f"{name}: record is {type(rec).__name__}, not an object"]
    if kind in ("bench", "serve"):
        # SERVE_r*.json (bench_serve.py) uses the bench record shape,
        # so serving rides the same sentinel machinery as training
        _need("n", int)
        _need("rc", int)
        _need("cmd", str)
        if "skipped" in rec:
            _need("skipped", bool)
            if rec.get("skipped") is True and not isinstance(
                    rec.get("skip_reason"), str):
                problems.append(f"{name}: skipped record needs a "
                                f"'skip_reason' string")
        if "parsed" not in rec:
            problems.append(f"{name}: missing key 'parsed'")
        elif rec["parsed"] is not None:
            p = rec["parsed"]
            if not isinstance(p, dict):
                problems.append(f"{name}: 'parsed' is not an object")
            else:
                for key, types in (("metric", str), ("unit", str),
                                   ("value", (int, float))):
                    if key not in p:
                        problems.append(f"{name}: parsed missing {key!r}")
                    elif not isinstance(p[key], types):
                        problems.append(f"{name}: parsed[{key!r}] has "
                                        f"type {type(p[key]).__name__}")
    elif kind == "multichip":
        _need("n_devices", int)
        _need("rc", int)
        _need("ok", bool)
        _need("skipped", bool)
        # real-training fields (bench.py --multichip, r06+): optional —
        # dry-run rounds predate them — but typed when present
        for key, types in (("trees_per_sec", (int, float)),
                           ("vs_baseline", (int, float)),
                           ("tree_learner", str)):
            if key in rec and not isinstance(rec[key], types):
                problems.append(f"{name}: {key!r} has type "
                                f"{type(rec[key]).__name__}")
        # elasticity-cost block (rounds that exercised a mid-run
        # resize): optional, but when present it is a typed object so
        # the sentinel can trust its series
        if "chaos_resize" in rec:
            cr = rec["chaos_resize"]
            if not isinstance(cr, dict):
                problems.append(f"{name}: 'chaos_resize' is not an "
                                f"object")
            else:
                for key, types in (
                        ("resizes", int),
                        ("reshard_wall_s", (int, float)),
                        ("post_resize_trees_per_sec", (int, float))):
                    if key in cr and not isinstance(cr[key], types):
                        problems.append(
                            f"{name}: chaos_resize[{key!r}] has type "
                            f"{type(cr[key]).__name__}")
    else:
        problems.append(f"{name}: unknown record kind {kind!r}")
    return problems


def _bench_points(records) -> Dict[str, List[Tuple[int, float]]]:
    """metric name -> [(round, value)] usable samples only. The
    primary per-round value lands under the parsed 'metric' name;
    ratio side-channels (vs_baseline, ...) become '<metric>:<key>'."""
    series: Dict[str, List[Tuple[int, float]]] = {}
    for rnd, _, rec in records:
        parsed = rec.get("parsed")
        if rec.get("skipped", False) or rec.get("rc", 1) != 0 or \
                not isinstance(parsed, dict):
            continue
        metric = str(parsed.get("metric", "bench"))
        value = parsed.get("value")
        if isinstance(value, (int, float)) and value > 0:
            series.setdefault(metric, []).append((rnd, float(value)))
            # ratio/aux side-channels tracked with the same drop
            # detector: multichip ratios, and the serve bench's
            # packed-vs-unpacked multi-model columns (PR 15)
            for key in ("vs_baseline", "vs_single_core",
                        "mm_packed_qps", "mm_unpacked_qps",
                        "mm_packed_speedup"):
                v = parsed.get(key)
                if isinstance(v, (int, float)) and v > 0:
                    series.setdefault(f"{metric}:{key}", []) \
                        .append((rnd, float(v)))
    return series


def _multichip_points(records) -> Dict[str, List[Tuple[int, float]]]:
    """multichip metric series: rounds that measured real training
    (bench.py --multichip writes trees_per_sec; dry-run rounds don't)
    feed the same drop detector the bench series uses."""
    series: Dict[str, List[Tuple[int, float]]] = {}
    for rnd, _, rec in records:
        if rec.get("rc", 1) != 0 or rec.get("skipped", False):
            continue
        for key in ("trees_per_sec", "vs_baseline"):
            v = rec.get(key)
            if isinstance(v, (int, float)) and v > 0:
                series.setdefault(f"multichip_{key}", []) \
                    .append((rnd, float(v)))
        # elasticity cost (rounds that resized mid-run): post-resize
        # throughput rides the drop detector like the main series;
        # reshard wall is tracked inverted (1/wall) so a slower reshard
        # registers as the drop it is
        cr = rec.get("chaos_resize")
        if isinstance(cr, dict) and cr.get("resizes", 0):
            v = cr.get("post_resize_trees_per_sec")
            if isinstance(v, (int, float)) and v > 0:
                series.setdefault("multichip_post_resize_trees_per_sec",
                                  []).append((rnd, float(v)))
            w = cr.get("reshard_wall_s")
            if isinstance(w, (int, float)) and w > 0:
                series.setdefault("multichip_reshard_inv_wall", []) \
                    .append((rnd, 1.0 / float(w)))
    return series


def compare(root: Optional[str] = None,
            threshold: float = REGRESSION_THRESHOLD) -> Dict:
    """The ``bench_regressions`` section: per-metric latest vs
    best-so-far over the BENCH_r*/MULTICHIP_r* trajectory under
    `root` (default: repo root = this package's parent)."""
    if root is None:
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
    traj = load_trajectory(root)
    metrics: Dict[str, Dict] = {}
    regressions: List[Dict] = []

    all_points = dict(_bench_points(traj["bench"]))
    for metric, pts in _bench_points(traj["serve"]).items():
        all_points[f"serve:{metric}"] = pts
    all_points.update(_multichip_points(traj["multichip"]))
    for metric, points in sorted(all_points.items()):
        latest_rnd, latest = points[-1]
        earlier = points[:-1]
        entry: Dict = {"latest": latest, "latest_round": latest_rnd,
                       "samples": len(points)}
        if earlier:
            best_rnd, best = max(earlier, key=lambda p: p[1])
            entry.update(best=best, best_round=best_rnd,
                         delta_frac=round((latest - best) / best, 4))
            if latest < best * (1.0 - threshold):
                regressions.append({
                    "metric": metric, "latest": latest,
                    "latest_round": latest_rnd, "best": best,
                    "best_round": best_rnd,
                    "drop_frac": round(1.0 - latest / best, 4)})
        metrics[metric] = entry

    # an unusable latest bench/serve round after any usable one: the
    # bench itself regressed, whatever the numbers used to say
    for series_name, series in (("bench_record", traj["bench"]),
                                ("serve_record", traj["serve"])):
        if series and _bench_points(series):
            last_rnd, last_name, last = series[-1]
            usable_rounds = {r for pts in _bench_points(series).values()
                             for r, _ in pts}
            if last.get("skipped", False):
                # declared denial: not a sample, not a bench failure
                continue
            if last_rnd not in usable_rounds:
                regressions.append({
                    "metric": series_name, "latest_round": last_rnd,
                    "record": last_name,
                    "drop_frac": 1.0,
                    "detail": f"rc={last.get('rc')!r} "
                              f"parsed={last.get('parsed')!r}"})

    mc = [(rnd, rec) for rnd, _, rec in traj["multichip"]
          if not rec.get("skipped", False)]
    if mc:
        oks = [(rnd, bool(rec.get("ok", False))) for rnd, rec in mc]
        latest_rnd, latest_ok = oks[-1]
        metrics["multichip_ok"] = {"latest": int(latest_ok),
                                   "latest_round": latest_rnd,
                                   "samples": len(oks)}
        if not latest_ok and any(ok for _, ok in oks[:-1]):
            regressions.append({
                "metric": "multichip_ok", "latest": 0,
                "latest_round": latest_rnd, "best": 1,
                "drop_frac": 1.0})

    return {"root": root, "threshold": threshold,
            "bench_records": len(traj["bench"]),
            "multichip_records": len(traj["multichip"]),
            "serve_records": len(traj["serve"]),
            "metrics": metrics, "regressions": regressions}


def render_compare(result: Dict) -> str:
    """Human tail for ``bench.py --compare`` (stderr)."""
    lines = [f"bench trajectory: {result['bench_records']} bench + "
             f"{result['multichip_records']} multichip + "
             f"{result.get('serve_records', 0)} serve records "
             f"(threshold {result['threshold']:.0%})"]
    for metric, e in sorted(result["metrics"].items()):
        if "best" in e:
            lines.append(
                f"  {metric}: latest {e['latest']:g} (r{e['latest_round']:02d})"
                f" vs best {e['best']:g} (r{e['best_round']:02d}), "
                f"delta {e['delta_frac']:+.1%}")
        else:
            lines.append(f"  {metric}: latest {e['latest']:g} "
                         f"(r{e['latest_round']:02d}), no earlier sample")
    if result["regressions"]:
        for r in result["regressions"]:
            lines.append(f"  REGRESSION {r['metric']}: "
                         f"-{r['drop_frac']:.1%} at "
                         f"r{r['latest_round']:02d}")
    else:
        lines.append("  no regressions")
    return "\n".join(lines)
