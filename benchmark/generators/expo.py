"""Expo-shaped data: the airline on-time records (Data Expo 2009) with
their categorical columns kept as categories.

The published set (11,000,000 rows; LightGBM docs/Experiments.rst lists
it with 700 columns, the one-hot codes of its categorical columns)
cannot be fetched: there is no network. This draws rows of the public
schema's shape: six integer-coded categorical columns (month 12 levels,
day of month 31, day of week 7, carrier 29, origin 305, destination
305: 689 levels, which one-hot coded and beside the eleven numerical
columns make the published 700) and eleven numerical ones, float32.

What every seed shares, so that every seed gives the same work:

- the level frequencies: calendar columns uniform; carrier, origin and
  destination a bounded Zipf law, p(k) proportional to 1 / (k + 8) over
  frequency ranks k = 0.., with ONE fixed assignment of levels to ranks.
  The commonest airport holds 3.4% of the rows and the rarest 0.09%;
  the commonest 254 cover 95.2%, so at 255 bins the rest share bin 0;
- the label model: fixed effects per level (carrier, origin,
  destination, month, day of week), a fixed origin x carrier
  interaction, a smooth function of the numerical columns, Gaussian
  noise; the label is `latent > threshold`, the threshold the set's own
  median (a held-out set passes the training set's).

What the seed decides: the integer code of every carrier and airport
(`level_codes`: 29, 305 and 305 distinct codes out of twice as many,
increasing in the tables' own level numbers, so a table of frequencies
or a tie between two levels' counts reads the same under every seed),
and the rows of every set drawn without a `rows_seed`. The cell's
TRAINING rows are drawn with `rows_seed=TRAIN_ROWS_SEED`: one fixed
table of flights, as the level tables are fixed, under the seed's
codes. The reason is the driver's check of this cell (PR 35): with the
training rows drawn from the seed, `trees_per_s` spread by 0.68% of its
median between seeds against the 0.5% a new cell may, with the two runs
of one seed alike to 6e-6. A tree of this program runs nine passes and
as many more as its shape asks for (grower_mxu.py's bridge and fixup
loop), a pass more costs 200-220 ms of a tree's 1,120-1,170 at 11M rows
(three traced chip runs, PR 35), and which trees take one follows from
which leaves fall under 40 rows: any other draw of the rows re-rolls
it, and so does another ORDER of the same rows (float32 sums tip
near-tied splits: measured on `mslr_rank_train`, PR 32). Codes that
keep their order move no bin and no sum: the binned matrix, the trees
and the work are the same under every seed, while the values in the
model text, the bitsets' widths and the held-out rows are the seed's.
PERF.md section 7 says what the program has to do before the training
rows can be the seed's again.

The constants below are sized so that the published AUC, 0.777, is
about what can be learned:
the latent without its noise reads 0.786 on its own rows, its
categorical part alone 0.776, its smooth part alone 0.573 (a million
rows), so a model that reads every column lands near the published
figure and one that reads the numerical columns alone stays under 0.60
(`tests/benchmark_harness/test_expo_categorical_cell.py` holds both).
"""

from __future__ import annotations

import functools
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Optional, Tuple

import numpy as np

#: rows per independent stream; part of the data's definition
CHUNK_ROWS = 1 << 18

COLUMNS = ("month", "day_of_month", "day_of_week", "carrier", "origin",
           "dest", "year", "crs_dep_time", "crs_arr_time",
           "crs_elapsed_time", "distance", "flight_num", "dep_time",
           "arr_time", "actual_elapsed_time", "air_time", "taxi_out")
CATEGORICAL = (0, 1, 2, 3, 4, 5)
#: levels of the categorical columns, in COLUMNS' order
LEVELS = (12, 31, 7, 29, 305, 305)

_TABLES_SEED = 20091                      # the fixed tables' own stream
#: `rows_seed` of the cell's training rows (module docstring)
TRAIN_ROWS_SEED = 20092
#: the columns whose codes the seed assigns: name, column, levels
_ID_COLUMNS = (("carrier", 3, 29), ("origin", 4, 305), ("dest", 5, 305))
_CODES_STREAM = 1009                      # beside the row streams 0, 1, ..
# standard deviations of the fixed effects over LEVELS (not over rows)
_SD = {"month": 0.25, "day_of_week": 0.15, "carrier": 0.45, "origin": 0.55,
       "dest": 0.40, "interaction": 0.45}
_NOISE_SD = 1.35


def zipf(levels: int) -> np.ndarray:
    """p(k) proportional to 1 / (k + 8), k = 0 .. levels - 1."""
    p = 1.0 / (np.arange(levels) + 8.0)
    return p / p.sum()


class _Tables:
    """The fixed tables: level of each frequency rank, effect of each
    level (`code_of_rank` gives the level's own number, which
    `level_codes` turns into the seed's code). Built once a process
    from _TABLES_SEED."""

    def __init__(self):
        rng = np.random.Generator(np.random.PCG64(_TABLES_SEED))
        self.code_of_rank = {}
        self.cdf = {}
        for name, levels in (("carrier", 29), ("origin", 305),
                             ("dest", 305)):
            self.code_of_rank[name] = rng.permutation(levels).astype(
                np.int64)
            self.cdf[name] = np.cumsum(zipf(levels))
        self.effect = {
            "month": rng.standard_normal(13) * _SD["month"],
            "day_of_week": rng.standard_normal(8) * _SD["day_of_week"],
            "carrier": rng.standard_normal(29) * _SD["carrier"],
            "origin": rng.standard_normal(305) * _SD["origin"],
            "dest": rng.standard_normal(305) * _SD["dest"],
            "interaction": rng.standard_normal((305, 29))
            * _SD["interaction"]}


@functools.lru_cache(maxsize=1)
def tables() -> _Tables:
    return _Tables()


def level_codes(seed: int) -> dict:
    """The seed's integer code of each carrier, origin and destination,
    indexed by the tables' own level number and increasing in it:
    `levels` distinct codes out of 1 .. 2 * levels (never 0: the
    program's binning counts a column's zeros apart and places that
    level last among levels of equal count, which would move a bin)."""
    rng = np.random.Generator(np.random.PCG64(
        np.random.SeedSequence([int(seed), _CODES_STREAM])))
    return {name: 1 + np.sort(rng.choice(2 * levels, levels, replace=False))
            .astype(np.int64) for name, _, levels in _ID_COLUMNS}


def _hhmm(minutes: np.ndarray) -> np.ndarray:
    m = np.mod(minutes, 1440.0)
    return np.floor(m / 60.0) * 100.0 + np.floor(np.mod(m, 60.0))


def _chunk(seed: int, stream: int, index: int, rows: int, codes: dict):
    t = tables()
    rng = np.random.Generator(np.random.PCG64(
        np.random.SeedSequence([int(seed), int(stream), int(index)])))
    X = np.empty((rows, len(COLUMNS)), np.float32)
    month = rng.integers(1, 13, rows)
    dow = rng.integers(1, 8, rows)
    X[:, 0] = month
    X[:, 1] = rng.integers(1, 32, rows)
    X[:, 2] = dow
    code = {}
    for col, name in ((3, "carrier"), (4, "origin"), (5, "dest")):
        rank = np.searchsorted(t.cdf[name], rng.random(rows))
        code[name] = t.code_of_rank[name][
            np.minimum(rank, len(t.cdf[name]) - 1)]
        X[:, col] = codes[name][code[name]]
    z = rng.standard_normal((rows, 8), dtype=np.float32)
    dep = 300.0 + 1080.0 * rng.random(rows, dtype=np.float32)    # minutes
    distance = np.clip(np.exp(6.3 + 0.7 * z[:, 0]), 30.0, 5000.0)
    elapsed = distance / 7.5 + 30.0 + 8.0 * z[:, 1]
    late = 12.0 * np.abs(z[:, 2])
    taxi = 8.0 + 6.0 * np.abs(z[:, 3])
    actual = elapsed + 10.0 * z[:, 4]
    X[:, 6] = rng.integers(1987, 2009, rows)
    X[:, 7] = _hhmm(dep)
    X[:, 8] = _hhmm(dep + elapsed)
    X[:, 9] = np.round(elapsed)
    X[:, 10] = np.round(distance)
    X[:, 11] = rng.integers(1, 7000, rows)
    X[:, 12] = _hhmm(dep + late)
    X[:, 13] = _hhmm(dep + late + actual)
    X[:, 14] = np.round(actual)
    X[:, 15] = np.round(actual - taxi - 5.0)
    X[:, 16] = np.round(taxi)
    # the smooth part: later departures, longer taxi, recent years
    smooth = (0.30 * np.sin((dep - 300.0) / 1080.0 * np.pi - np.pi / 2)
              + 0.20 * (taxi - 12.8) / 4.0 * 0.5
              + 0.10 * (X[:, 6] - 1997.5) / 6.3
              - 0.10 * z[:, 0])
    latent = (t.effect["month"][month] + t.effect["day_of_week"][dow]
              + t.effect["carrier"][code["carrier"]]
              + t.effect["origin"][code["origin"]]
              + t.effect["dest"][code["dest"]]
              + t.effect["interaction"][code["origin"], code["carrier"]]
              + smooth + _NOISE_SD * z[:, 5])
    return X, latent.astype(np.float32)


def make_expo_like(rows: int, seed: int, *, stream: int = 0,
                   threshold: Optional[float] = None,
                   rows_seed: Optional[int] = None
                   ) -> Tuple[np.ndarray, np.ndarray, float]:
    """(X float32 [rows, 17], y float32 [rows], threshold). The codes
    of the carriers and airports are the seed's (`level_codes`); the
    rows are drawn from `rows_seed`, the seed where none is given, and
    `stream` tells sets of one such seed apart (training, held out).
    The label is `latent > threshold`; with no threshold given it is
    the median of these rows' latents (balanced classes), and a
    held-out set passes the training set's so that both follow one
    rule."""
    codes = level_codes(seed)
    if rows_seed is None:
        rows_seed = seed
    starts = list(range(0, rows, CHUNK_ROWS))
    X = np.empty((rows, len(COLUMNS)), np.float32)
    latent = np.empty(rows, np.float32)
    tables()

    def fill(k):
        lo = starts[k]
        hi = min(lo + CHUNK_ROWS, rows)
        X[lo:hi], latent[lo:hi] = _chunk(rows_seed, stream, k, hi - lo,
                                         codes)

    workers = max(1, min(8, len(starts), (os.cpu_count() or 1) - 1))
    with ThreadPoolExecutor(workers) as pool:
        list(pool.map(fill, range(len(starts))))
    if threshold is None:
        threshold = float(np.median(latent))
    return X, (latent > threshold).astype(np.float32), threshold
