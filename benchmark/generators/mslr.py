"""MSLR-shaped ranking data from a seed.

The published set (MSLR-WEB30K fold 1's training split: 2,270,296
documents x 137 features under 18,919 queries, relevance 0-4) cannot be
fetched: there is no network. This draws data of that shape from
`--seed`, so that every seed gives the same amount of work and none the
same rows:

- The multiset of query lengths is ONE fixed list (`query_lengths`): the
  quantiles of a log-normal law (`SIGMA`), scaled so that after rounding
  and clipping to 1..longest they sum to the documents exactly, the
  first query shortened to one document. It is part of the data's
  definition, like `CHUNK_ROWS`; the seed only permutes its order. So
  the objective's length buckets, and with them every shape of the
  program, are the same in every seed.
- Features are dense float32 standard normals drawn from the seed in
  fixed chunks (like generators/higgs.py), so 311M values take seconds
  and the result does not depend on how many threads drew them.
- A document's label is a fixed-quantile cut (`LABEL_SHARES`) of a
  latent relevance: a nonlinear rule on ten features, an offset of its
  query, noise. The cuts are the set's own quantiles, so the label
  shares are the same in every seed.
- A held-out set is a second stream of the same seed under the same
  rule and the training set's label cuts.

What the seed still moves is the trees' shape, and with it the rows a
tree's grouped passes build: PERF.md section 6 has the spread that
leaves in `trees_per_s`.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from statistics import NormalDist
from typing import Optional, Tuple

import numpy as np

#: rows per independent stream; part of the data's definition
CHUNK_ROWS = 1 << 18
#: the log-normal law's shape: at 18,919 queries of mean 120 its
#: quantiles run from 3 to beyond 1,251, where a handful are clipped
SIGMA = 0.8
#: share of the documents at relevance 0..4 (MSLR-WEB30K's, recalled:
#: about 51.5 / 32.5 / 13.4 / 1.9 / 0.7 percent)
LABEL_SHARES = (0.515, 0.325, 0.134, 0.019, 0.007)
#: how much of the latent relevance is the query's and how much noise
QUERY_SCALE, NOISE_SCALE = 0.6, 0.5
#: the stream of the per-query draws, beside the chunks' 0, 1, 2, ...
_QUERY_INDEX = (1 << 31) - 1


def query_lengths(queries: int, documents: int, longest: int) -> np.ndarray:
    """The fixed multiset of lengths, ascending: int64 [queries], each in
    1..longest, summing to `documents`."""
    if not queries <= documents <= queries * longest:
        raise ValueError("%d queries of 1..%d documents cannot hold %d"
                         % (queries, longest, documents))
    nd = NormalDist()
    z = np.asarray([nd.inv_cdf((k + 0.5) / queries) for k in range(queries)])
    shape = np.exp(SIGMA * z)

    def at(scale):
        return np.clip(np.rint(scale * shape), 1, longest).astype(np.int64)

    lo, hi = 0.0, float(longest) * 4
    for _ in range(200):          # the largest scale whose sum fits
        mid = (lo + hi) / 2
        if at(mid).sum() <= documents:
            lo = mid
        else:
            hi = mid
    lengths = at(lo)
    if documents <= (queries - 1) * longest + 1:
        lengths[0] = 1
    # what rounding left over goes one document each to the queries
    # nearest the middle of the list, which are neither clipped nor short
    short = int(documents - lengths.sum())
    while short > 0:
        room = np.flatnonzero(lengths < longest)
        take = room[np.argsort(np.abs(room - queries // 2),
                               kind="stable")][:short]
        lengths[take] += 1
        short -= len(take)
    lengths.sort()
    assert lengths.sum() == documents and lengths[0] >= 1 \
        and lengths[-1] <= longest
    return lengths


def _chunk(seed: int, stream: int, index: int, rows: int, features: int):
    rng = np.random.Generator(np.random.PCG64(
        np.random.SeedSequence([int(seed), int(stream), int(index)])))
    X = rng.standard_normal((rows, features), dtype=np.float32)
    noise = rng.standard_normal(rows, dtype=np.float32)
    latent = (1.0 * X[:, 0] - 0.8 * X[:, 1] + 0.6 * X[:, 2] * X[:, 3] +
              0.5 * np.abs(X[:, 4]) - 0.4 * X[:, 5] ** 2 +
              0.5 * np.tanh(2.0 * X[:, 6]) + 0.3 * X[:, 7] * X[:, 0] +
              0.4 * np.maximum(X[:, 8], 0.0) - 0.3 * X[:, 9] +
              NOISE_SCALE * noise)
    return X, latent


def make_mslr_like(lengths: np.ndarray, features: int, seed: int, *,
                   stream: int = 0, cuts: Optional[np.ndarray] = None
                   ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """(X float32 [rows, features], y float32 [rows] in 0..4, sizes int64
    [queries], cuts float32 [4]) from (`seed`, `stream`). `lengths` is
    the multiset (any order); `sizes` is the seed's order of it and the
    `group=` of the set. `stream` tells the sets of one seed apart
    (training, held out). With no `cuts` given the label boundaries are
    the set's own quantiles at LABEL_SHARES; a held-out set passes the
    training set's."""
    if features < 10:
        raise ValueError("the relevance rule reads ten features")
    per_query = np.random.Generator(np.random.PCG64(
        np.random.SeedSequence([int(seed), int(stream), _QUERY_INDEX])))
    sizes = per_query.permutation(np.sort(np.asarray(lengths, np.int64)))
    offset = QUERY_SCALE * per_query.standard_normal(len(sizes),
                                                     dtype=np.float32)
    rows = int(sizes.sum())
    starts = list(range(0, rows, CHUNK_ROWS))
    X = np.empty((rows, features), np.float32)
    latent = np.empty(rows, np.float32)

    def fill(k):
        lo = starts[k]
        hi = min(lo + CHUNK_ROWS, rows)
        X[lo:hi], latent[lo:hi] = _chunk(seed, stream, k, hi - lo, features)

    workers = max(1, min(8, len(starts), (os.cpu_count() or 1) - 1))
    with ThreadPoolExecutor(workers) as threads:
        list(threads.map(fill, range(len(starts))))
    latent += np.repeat(offset, sizes)
    if cuts is None:
        cuts = np.quantile(latent, np.cumsum(LABEL_SHARES)[:-1]).astype(
            np.float32)
    y = np.searchsorted(cuts, latent, side="right").astype(np.float32)
    return X, y, sizes, cuts
