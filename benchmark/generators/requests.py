"""The one traffic generator for serving cells: a traffic file's
parameters and a seed in, a fixed schedule of requests out.

A traffic file (benchmark/traffic/<name>.json) of a serving cell lists
`streams`. Each stream is an open loop (`rate_per_s`, Poisson arrivals,
optionally with `burst`: {"factor", "on_ms", "period_ms"} multiplying
the rate for the first `on_ms` of every period) or a closed loop
(`clients`, each sending its next request when the last is answered),
with request sizes from `sizes`:

    {"dist": "bounded_pareto", "shape": 1.3, "scale": 2, "min": 1, "max": 64}
    {"dist": "log_uniform", "min": 512, "max": 8192}
    {"dist": "fixed", "rows": 256}

`bounded_pareto` is bench_serve's heavy_tailed_sizes (1 + floor(Pareto
* scale), clipped) with its constants as parameters. Everything is
drawn before the window opens, so the generator's work inside it is
pacing and nothing else.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np


def _rng(seed: int, *path: int) -> np.random.Generator:
    return np.random.Generator(np.random.PCG64(
        np.random.SeedSequence([int(seed), 0x7AFF1C, *map(int, path)])))


def draw_sizes(rng: np.random.Generator, spec: Dict, count: int
               ) -> np.ndarray:
    dist = spec["dist"]
    if dist == "bounded_pareto":
        raw = 1 + (rng.pareto(spec["shape"], size=count)
                   * spec["scale"]).astype(np.int64)
        return np.clip(raw, spec["min"], spec["max"])
    if dist == "log_uniform":
        lo, hi = np.log(spec["min"]), np.log(spec["max"])
        return np.clip(np.exp(rng.uniform(lo, hi, size=count)).astype(
            np.int64), spec["min"], spec["max"])
    if dist == "fixed":
        return np.full(count, int(spec["rows"]), np.int64)
    raise ValueError("unknown size distribution %r" % dist)


def open_schedule(stream: Dict, seed: int, index: int, horizon_s: float,
                  pool_rows: int) -> Dict[str, np.ndarray]:
    """Due times (seconds from the window's start, ascending, all below
    `horizon_s`), sizes and first pool rows of one open-loop stream."""
    rng = _rng(seed, index)
    rate = float(stream["rate_per_s"])
    burst = stream.get("burst")
    if burst:
        # thinning: draw at the peak rate, keep a 1/factor share of the
        # arrivals that fall outside a burst
        peak = rate * float(burst["factor"])
        n = int(peak * horizon_s * 1.2) + 64
        due = np.cumsum(rng.exponential(1.0 / peak, size=n))
        phase = np.mod(due * 1e3, float(burst["period_ms"]))
        keep = (phase < float(burst["on_ms"])) | \
            (rng.uniform(size=n) < 1.0 / float(burst["factor"]))
        due = due[keep]
    else:
        n = int(rate * horizon_s * 1.2) + 64
        due = np.cumsum(rng.exponential(1.0 / rate, size=n))
    due = due[due < horizon_s]
    sizes = draw_sizes(rng, stream["sizes"], len(due))
    starts = rng.integers(0, pool_rows - int(sizes.max()) + 1,
                          size=len(due))
    return {"due_s": due, "rows": sizes, "lo": starts}


def merged_open_schedule(streams: List[Dict], seed: int, horizon_s: float,
                         pool_rows: int) -> Dict[str, np.ndarray]:
    """Every open-loop stream's schedule in one, ascending by due time,
    with `stream` the index of the stream each request belongs to."""
    parts = [dict(open_schedule(s, seed, i, horizon_s, pool_rows),
                  stream=i)
             for i, s in enumerate(streams) if s["loop"] == "open"]
    if not parts:
        return {"due_s": np.zeros(0), "rows": np.zeros(0, np.int64),
                "lo": np.zeros(0, np.int64), "stream": np.zeros(0, np.int64)}
    due = np.concatenate([p["due_s"] for p in parts])
    order = np.argsort(due, kind="mergesort")
    return {"due_s": due[order],
            "rows": np.concatenate([p["rows"] for p in parts])[order],
            "lo": np.concatenate([p["lo"] for p in parts])[order],
            "stream": np.concatenate([np.full(len(p["due_s"]), p["stream"])
                                      for p in parts])[order]}


def closed_schedule(stream: Dict, seed: int, index: int, client: int,
                    count: int, pool_rows: int) -> Dict[str, np.ndarray]:
    """The first `count` requests of one client of a closed-loop
    stream."""
    rng = _rng(seed, index, client)
    sizes = draw_sizes(rng, stream["sizes"], count)
    starts = rng.integers(0, pool_rows - int(sizes.max()) + 1, size=count)
    return {"rows": sizes, "lo": starts}


def check_streams(streams: List[Dict]) -> None:
    for s in streams:
        if s["loop"] == "open":
            if not s.get("rate_per_s", 0) > 0:
                raise ValueError("open stream %r needs rate_per_s > 0 (the "
                                 "knee is found once, by "
                                 "benchmark/find_knee.py, and written into "
                                 "the traffic file as a number)"
                                 % s.get("name"))
        elif s["loop"] == "closed":
            if not int(s.get("clients", 0)) > 0:
                raise ValueError("closed stream %r needs clients > 0"
                                 % s.get("name"))
        else:
            raise ValueError("stream %r: loop %r is neither open nor closed"
                             % (s.get("name"), s["loop"]))
