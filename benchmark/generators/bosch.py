"""Bosch-shaped data: the numeric table of the Kaggle "Bosch Production
Line Performance" set, as the reference's GPU experiment trains it.

The published set (1,183,747 parts x 968 numeric columns; 1,000,000
training rows) cannot be fetched: there is no network. This draws rows
of its shape. A part passes some of the 52 stations of 4 production
lines, and a station's columns hold measurements only for the parts
that passed it: NaN elsewhere, so most of a row is missing and WHICH
part is missing follows the part's route.

What every seed shares, so that every seed gives a run the same work:

- the layout (`STATION_COLUMNS`: 4 lines, 52 stations, 968 columns; the
  per-station counts are recalled, not read from the set);
- the route patterns (`route_patterns`: a fixed table of sets of
  stations, drawn once from `_TABLES_SEED`) and their frequencies, so
  that every seed's rows hold the same number of parts on each route,
  the same missing cells per column and a missing share of 0.81;
- each column's value law (a centre and a spread drawn once), the
  values rounded to thousandths as the published measurements are;
- the label model: a latent of twelve present-or-missing columns, each
  with its own effect for a missing value (on the low side for some
  columns and the high side for others, so that a split has to pick
  the side the missing rows go to), plus a fixed effect per route,
  plus logistic noise, cut so that 0.58% of parts fail.

What the seed decides: the rows, which part takes which route, its
measurements and its label, for every set but the cell's training
rows. Those are drawn from `TRAIN_ROWS_SEED` under every seed: one
fixed table, so that every seed grows the same trees and reads the
same rate, while the held-out rows (a second stream) and their AUC are
the seed's. The reason is the new-cell rule on the spread of
`trees_per_s` (half its bound): with the training rows drawn from the
seed, two seeds read 0.6678 and 0.6587 trees/s on a TPU v5e, 1.4%
apart, because the seed re-rolls how many passes past the schedule a
tree runs (11 to 24 fixup iterations a block of ten trees) and the
split search at 512 slots x 968 columns makes each about 50 ms.
`helpers/bosch_controls.py --table SEED` runs the cell's checks on a
training table drawn from any other seed.

The constants are sized so that what can be learned is about the
published AUC, 0.7248: the latent without its noise orders the parts
with an AUC of about 0.78 against their labels
(`tests/benchmark_harness/test_bosch_cell.py` holds the range).
"""

from __future__ import annotations

import functools
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Optional, Tuple

import numpy as np

#: rows per independent stream; part of the data's definition
CHUNK_ROWS = 1 << 17

#: columns of each station, line by line (L0: S0-S23, 168 columns; L1:
#: S24-S25, 513; L2: S26-S28, 42; L3: S29-S51, 245): 968 in all
STATION_COLUMNS = (
    (12, 2, 2, 2, 4, 4, 6, 6, 7, 7, 7, 7, 12, 12, 9, 9, 9, 9, 8, 8, 8,
     8, 5, 5),
    (460, 53),
    (14, 14, 14),
    (8, 8, 10, 10, 12, 12, 8, 8, 11, 11, 13, 13, 14, 14, 9, 9, 10, 10,
     12, 12, 14, 13, 4),
)
LINE_STATIONS = tuple(len(line) for line in STATION_COLUMNS)
NUM_FEATURES = sum(sum(line) for line in STATION_COLUMNS)
#: the share of parts that fail, as in the published training set
FAILURE_RATE = 0.0058
#: the route patterns (route_patterns) and their frequency law
NUM_ROUTES = 40

_TABLES_SEED = 20160          # the fixed tables' own stream
#: the seed of the cell's training rows, under every seed (module
#: docstring)
TRAIN_ROWS_SEED = 20161
#: scale of the label's logistic noise (module docstring)
NOISE = 1.3


def _station_spans():
    """[(first column, columns)] of every station, in order."""
    spans, at = [], 0
    for line in STATION_COLUMNS:
        for n in line:
            spans.append((at, n))
            at += n
    return spans


@functools.lru_cache(maxsize=None)
def route_patterns() -> Tuple[np.ndarray, np.ndarray]:
    """The fixed routes: (present [NUM_ROUTES, NUM_FEATURES] bool: the
    columns a part on the route has, frequency [NUM_ROUTES] float64,
    summing to 1). A route runs along the lines in order: line 0 on
    three parts in five, through a run of its stations with some
    skipped; line 1 on one part in five, at one of its two stations;
    line 2 on one in six; line 3 on nearly all, through about a third
    of its stations; a station no route passes is added to one."""
    rng = np.random.Generator(np.random.PCG64(_TABLES_SEED))
    spans = _station_spans()
    first = np.cumsum((0,) + LINE_STATIONS[:-1])
    present = np.zeros((NUM_ROUTES, NUM_FEATURES), bool)

    def visit(r, station):
        lo, n = spans[station]
        present[r, lo:lo + n] = True

    for r in range(NUM_ROUTES):
        if rng.random() < 0.6:
            start = int(rng.integers(0, 6))
            stop = int(rng.integers(start + 8, LINE_STATIONS[0] + 1))
            for s in range(start, stop):
                if rng.random() < 0.7:
                    visit(r, first[0] + s)
        if rng.random() < 0.2:
            visit(r, first[1] + int(rng.random() < 0.3))
        if rng.random() < 1 / 6:
            for s in range(LINE_STATIONS[2]):
                if rng.random() < 0.6:
                    visit(r, first[2] + s)
        if rng.random() < 0.97:
            for s in range(LINE_STATIONS[3]):
                if rng.random() < 0.33:
                    visit(r, first[3] + s)
        if not present[r].any():
            visit(r, first[3])
    # every station is on some route, so that no column is all missing
    for station in range(len(spans)):
        lo, _ = spans[station]
        if not present[:, lo].any():
            visit(station % NUM_ROUTES, station)
    # a bounded Zipf law over the routes, in their drawn order
    freq = 1.0 / (np.arange(NUM_ROUTES) + 4.0)
    return present, freq / freq.sum()


@functools.lru_cache(maxsize=None)
def _tables():
    """The fixed value laws and label model (module docstring)."""
    rng = np.random.Generator(np.random.PCG64([_TABLES_SEED, 1]))
    centre = rng.normal(0.0, 0.1, NUM_FEATURES).astype(np.float32)
    spread = rng.uniform(0.02, 0.2, NUM_FEATURES).astype(np.float32)
    present, _ = route_patterns()
    # the label reads twelve columns that are present on a tenth to
    # three quarters of the routes' parts
    share = present.mean(axis=0)
    candidates = np.flatnonzero((share > 0.1) & (share < 0.75))
    key = np.sort(rng.choice(candidates, 12, replace=False))
    weight = rng.choice([-1.0, 1.0], 12) * rng.uniform(0.6, 1.2, 12)
    # a missing value reads like a value this many spreads from the
    # centre: low for half the columns, high for the rest
    missing_at = np.where(np.arange(12) % 2 == 0, -1.5, 1.5)
    route_effect = rng.normal(0.0, 0.5, NUM_ROUTES)
    return centre, spread, key, weight, missing_at, route_effect


def _route_of_rows(rows: int, seed: int, stream: int) -> np.ndarray:
    """[rows] route of every row: each route's count is its frequency's
    share of the rows (largest remainders), the order the seed's."""
    _, freq = route_patterns()
    want = freq * rows
    count = np.floor(want).astype(np.int64)
    count[np.argsort(-(want - count), kind="stable")[:rows - count.sum()]] \
        += 1
    route = np.repeat(np.arange(NUM_ROUTES, dtype=np.int16), count)
    rng = np.random.Generator(np.random.PCG64(
        np.random.SeedSequence([int(seed), int(stream), 0xB05C])))
    return rng.permutation(route)


def _chunk(seed: int, stream: int, index: int, route: np.ndarray):
    """One chunk's rows (float32, NaN where the route skips) and latent."""
    present, _ = route_patterns()
    centre, spread, key, weight, missing_at, route_effect = _tables()
    rng = np.random.Generator(np.random.PCG64(
        np.random.SeedSequence([int(seed), int(stream), int(index) + 1])))
    rows = len(route)
    X = rng.standard_normal((rows, NUM_FEATURES), dtype=np.float32)
    X *= spread
    X += centre
    np.round(X, 3, out=X)
    np.copyto(X, np.float32(np.nan), where=~present[route])
    # the label's columns in units of their spread; a missing value at
    # its column's fixed place
    z = (X[:, key] - centre[key]) / spread[key]
    z = np.where(np.isnan(z), missing_at, z)
    latent = np.tanh(z) @ weight + route_effect[route]
    noise = rng.logistic(0.0, NOISE, rows)
    return X, latent, latent + noise


def make_bosch_like(rows: int, seed: int, *, stream: int = 0,
                    threshold: Optional[float] = None
                    ) -> Tuple[np.ndarray, np.ndarray, float]:
    """(X float32 [rows, 968] with NaN, y float32 [rows], threshold).
    `stream` tells sets of one seed apart (training, held out). The
    label is `latent + noise > threshold`; with no threshold given it
    is these rows' (1 - FAILURE_RATE) quantile, and a held-out set
    passes the training set's so that both follow one rule."""
    X, _, score = make_bosch_rows(rows, seed, stream=stream)
    if threshold is None:
        threshold = float(np.quantile(score, 1.0 - FAILURE_RATE))
    return X, (score > threshold).astype(np.float32), threshold


def make_bosch_rows(rows: int, seed: int, *, stream: int = 0
                    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(X, latent without noise, latent with noise), each row's route
    from `_route_of_rows`, in chunks of CHUNK_ROWS drawn on threads."""
    route = _route_of_rows(rows, seed, stream)
    starts = list(range(0, rows, CHUNK_ROWS))
    X = np.empty((rows, NUM_FEATURES), np.float32)
    latent = np.empty(rows, np.float64)
    score = np.empty(rows, np.float64)

    def fill(k):
        lo = starts[k]
        hi = min(lo + CHUNK_ROWS, rows)
        X[lo:hi], latent[lo:hi], score[lo:hi] = _chunk(
            seed, stream, k, route[lo:hi])

    workers = max(1, min(8, len(starts), (os.cpu_count() or 1) - 1))
    with ThreadPoolExecutor(workers) as pool:
        list(pool.map(fill, range(len(starts))))
    return X, latent, score
