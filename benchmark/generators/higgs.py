"""Higgs-shaped data from a seed.

The published set (10.5M x 28, UCI HIGGS) cannot be fetched: there is
no network. This is bench.py's `make_higgs_like` (dense standard-normal
features, a nonlinear rule on seven of them plus noise, balanced labels)
drawn as float32 and in fixed chunks, so that 10.5M rows take seconds
and the result does not depend on how many threads drew them.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import Optional, Tuple

import numpy as np

#: rows per independent stream; part of the data's definition
CHUNK_ROWS = 1 << 18


def _chunk(seed: int, stream: int, index: int, rows: int, features: int):
    rng = np.random.Generator(np.random.PCG64(
        np.random.SeedSequence([int(seed), int(stream), int(index)])))
    X = rng.standard_normal((rows, features), dtype=np.float32)
    noise = rng.standard_normal(rows, dtype=np.float32)
    logit = (1.2 * X[:, 0] - 0.8 * X[:, 1] + 0.6 * X[:, 2] * X[:, 3] +
             0.5 * np.abs(X[:, 4]) - 0.4 * X[:, 5] ** 2 +
             0.3 * X[:, 6] * X[:, 0] + 0.35 * noise)
    return X, logit


def make_higgs_like(rows: int, features: int, seed: int, *,
                    stream: int = 0, threshold: Optional[float] = None
                    ) -> Tuple[np.ndarray, np.ndarray, float]:
    """(X float32 [rows, features], y float32 [rows], threshold).
    `stream` tells sets of one seed apart (training, held out). The
    label is `logit > threshold`; with no threshold given it is the
    median of these rows' logits (balanced classes), and a held-out set
    passes the training set's so that both follow one rule."""
    if features < 7:
        raise ValueError("the labelling rule reads seven features")
    starts = list(range(0, rows, CHUNK_ROWS))
    X = np.empty((rows, features), np.float32)
    logit = np.empty(rows, np.float32)

    def fill(k):
        lo = starts[k]
        hi = min(lo + CHUNK_ROWS, rows)
        X[lo:hi], logit[lo:hi] = _chunk(seed, stream, k, hi - lo, features)

    workers = max(1, min(8, len(starts), (os.cpu_count() or 1) - 1))
    with ThreadPoolExecutor(workers) as pool:
        list(pool.map(fill, range(len(starts))))
    if threshold is None:
        threshold = float(np.median(logit))
    return X, (logit > threshold).astype(np.float32), threshold
