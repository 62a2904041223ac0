"""Ranking objectives: LambdaRank-NDCG and XE-NDCG over length buckets.

Redesign of the reference rank objectives (src/objective/rank_objective.hpp:
LambdarankNDCG :95-281, RankXENDCG :283-365). The reference parallelizes an
OMP loop over queries, each doing an O(truncation x cnt) pairwise scan with
a cached sigmoid table. Here queries are laid out ONCE, at `init`, in a few
buckets of padded length (powers of two from 8 up): a bucket is a dense
[queries, length] table of document indices, so a query costs its own
bucket's length and not the longest query's (MSLR's queries run from 1 to
1,251 documents around a mean of 120). A tree gathers the scores into the
tables once, sorts each bucket's rows (labels, gains and document indices
ride the sort as payloads, so nothing is gathered by rank), forms the pair
tensor [queries, T, length] with T = min(truncation, length) (the
reference's outer loop stops at the truncation level, :140), and writes
every document's gradient once, by its index: a document belongs to one
query. The sigmoid lookup table (:229-256) is pointless on TPU: `jnp.exp`
is vectorized; clamping to [-50/sigma, 50/sigma] matches the table's domain.

DCG pieces follow src/metric/dcg_calculator.cpp: label_gain[i] = 2^i - 1,
discount[rank] = 1/log2(rank + 2), CalMaxDCGAtK over labels sorted desc.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .config import Config
from .objectives import ObjectiveFunction
from .observability import span
from .utils.log import Log

__all__ = ["LambdarankNDCG", "RankXENDCG", "bucket_queries",
           "pair_slots", "reference_pairs"]

_K_MIN_SCORE = -1e30
#: the shortest padded length; below it a bucket would hold a handful of
#: lanes and cost a set of operations of its own for nothing
_MIN_BUCKET_LEN = 8
#: a bucket's queries go through in batches whose [batch, T, length] pair
#: tensor holds at most this many elements (128 MB of float32)
_PAIR_ELEMS = 32 * 1024 * 1024


def default_label_gain(max_label: int = 31) -> np.ndarray:
    return (2.0 ** np.arange(max_label + 1)) - 1.0


class Bucket(NamedTuple):
    """Where one padded length's queries sit in the flat tables: slots
    [slot0, slot0 + batches * batch * length), and the rows T of a
    query's pair tensor there. Python integers: part of the traced
    program's structure."""
    length: int
    pair_rows: int
    batch: int
    batches: int
    slot0: int

    @property
    def rows(self) -> int:
        return self.batches * self.batch

    @property
    def pair_slots(self) -> int:
        return self.rows * self.pair_rows * self.length


def bucket_queries(query_boundaries: np.ndarray, pair_rows: int
                   ) -> Tuple[Tuple[Bucket, ...], np.ndarray]:
    """(buckets, slot_doc): queries grouped by the power of two at or
    above their length. `slot_doc` [slots] int32 is the document each
    slot of the flat layout holds (num_data for padding). `pair_rows` is
    T, the pair tensor's rows a query, which sizes a bucket's batch. One
    iteration a bucket, none over queries."""
    starts = np.asarray(query_boundaries[:-1], np.int64)
    sizes = np.diff(np.asarray(query_boundaries, np.int64))
    n = int(query_boundaries[-1])
    padded = np.maximum(
        _MIN_BUCKET_LEN,
        1 << np.ceil(np.log2(np.maximum(sizes, 1))).astype(np.int64))
    buckets, docs = [], []
    slot0 = 0
    for length in np.unique(padded).tolist():
        qs = np.flatnonzero(padded == length)
        t = min(max(int(pair_rows), 1), length)
        batch = max(1, min(len(qs), _PAIR_ELEMS // (t * length)))
        batches = -(-len(qs) // batch)
        q_pad = np.full(batches * batch, -1, np.int64)
        q_pad[:len(qs)] = qs
        pos = np.arange(length, dtype=np.int64)[None, :]
        # a padding row reads query -1, the last one: masked by `live`
        live = (q_pad >= 0)[:, None] & (pos < sizes[q_pad][:, None])
        docs.append(np.where(live, starts[q_pad][:, None] + pos,
                             n).reshape(-1))
        buckets.append(Bucket(length, t, batch, batches, slot0))
        slot0 += len(q_pad) * length
    return tuple(buckets), np.concatenate(docs).astype(np.int32)


def reference_pairs(sizes: np.ndarray, truncation_level: int) -> int:
    """Pairs (i, j) the reference's two loops visit over queries of these
    lengths, whatever the labels: i < min(cnt - 1, truncation), i < j <
    cnt (rank_objective.hpp:140-142)."""
    cnt = np.asarray(sizes, np.int64)
    m = np.minimum(np.maximum(cnt - 1, 0), int(truncation_level))
    return int((m * (cnt - 1) - m * (m - 1) // 2).sum())


def pair_slots(sizes: np.ndarray, pair_rows: int) -> int:
    """Elements of the pair tensors a tree forms over queries of these
    lengths under this module's layout, padding included: what the
    `objective.init` span states, for a caller that has no objective
    yet (the benchmark's ranking runner asks before it trains)."""
    boundaries = np.concatenate([[0], np.cumsum(np.asarray(sizes, np.int64))])
    return sum(b.pair_slots for b in bucket_queries(boundaries, pair_rows)[0])


@dataclasses.dataclass(frozen=True)
class _Plan:
    """What of a ranking objective is static: the bucket layout and the
    objective's scalars. Hashable by value, so it is the static argument
    of the one jitted gradient function, and two objectives over the
    same layout share its compiled program. The arrays (the objective's
    `table_state`, the scores) are that function's other arguments."""
    buckets: Tuple[Bucket, ...]
    num_data: int

    def slot_inputs(self, tables, score_pad, key) -> Tuple[jax.Array, ...]:
        """The per-slot arrays a bucket's gradients read, each [slots];
        the first is the document of the slot. `score_pad` is the scores
        with the least score at row num_data, for the padding slots."""
        raise NotImplementedError

    def bucket_grads(self, bucket: Bucket, doc, *inputs):
        """(lambdas, hessians, documents) of one batch of one bucket's
        queries. `doc` and every input are [batch, length]; so are the
        three returned, in any order along a row that they share."""
        raise NotImplementedError


def slot_gradients(plan: _Plan, score, tables, key):
    """(lambdas, hessians, documents), each [slots]: every bucket's
    gradients, flat, beside the document each belongs to (num_data for a
    padding slot)."""
    inputs = plan.slot_inputs(tables, jnp.concatenate(
        [score, jnp.full(1, _K_MIN_SCORE, score.dtype)]), key)
    parts = []
    for b in plan.buckets:
        batched = tuple(
            jax.lax.slice_in_dim(a, b.slot0, b.slot0 + b.rows * b.length)
            .reshape(b.batches, b.batch, b.length) for a in inputs)
        fn = functools.partial(plan.bucket_grads, b)
        if b.batches == 1:
            out = fn(*(a[0] for a in batched))
        else:
            out = jax.lax.map(lambda xs: fn(*xs), batched)
        parts.append([a.reshape(-1) for a in out])
    return tuple(jnp.concatenate(v) for v in zip(*parts))


def to_documents(num_data: int, values, doc):
    """[slots] values in document order, [num_data]: a document sits in
    one slot, and every padding slot names row num_data, which does not
    exist and is dropped, so this is one write a document."""
    return jnp.zeros(num_data, values.dtype).at[doc].set(values, mode="drop")


@functools.partial(jax.jit, static_argnums=0)
def objective_gradients(plan: _Plan, score, tables, weight, key):
    """The one gradient program of the ranking objectives. Its name is
    what a capture shows where a tree is a dispatch of its own."""
    lam, hes, doc = slot_gradients(plan, score, tables, key)
    g = to_documents(plan.num_data, lam, doc)
    h = to_documents(plan.num_data, hes, doc)
    if weight is not None:
        g, h = g * weight, h * weight
    return g, h


class _RankingBase(ObjectiveFunction):
    """Bucketed ranking base (RankingObjective, rank_objective.hpp:25)."""

    #: rows of a query's pair tensor: the subclass's truncation level, 1
    #: for an objective that forms no pairs
    pair_rows = 1
    #: the layout's tables, one value a slot. They are not one value a
    #: data row, so a fused program takes them as arguments by these
    #: names (boosting/fused.py) and does not close over them
    table_state: Tuple[str, ...] = ("slot_doc", "slot_label")

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if metadata.query_boundaries is None:
            Log.fatal("Ranking tasks require query information")
        self.query_boundaries = np.asarray(metadata.query_boundaries)
        self.num_queries = len(self.query_boundaries) - 1
        sizes = np.diff(self.query_boundaries)
        with span("objective.init", objective=self.name,
                  queries=self.num_queries, longest=int(sizes.max())) as sp:
            buckets, slot_doc = bucket_queries(
                self.query_boundaries, self.pair_rows)
            slot_label = np.concatenate(
                [np.asarray(metadata.label, np.float32), [0.0]])[slot_doc]
            self.slot_doc = jnp.asarray(slot_doc)
            self.slot_label = jnp.asarray(slot_label)
            self.plan = self._init_plan(buckets, slot_doc, slot_label)
            sp.attrs.update(
                buckets=len(buckets),
                pair_slots=sum(b.pair_slots for b in buckets),
                pairs=reference_pairs(sizes, self.pair_rows))

    def _init_plan(self, buckets, slot_doc: np.ndarray,
                   slot_label: np.ndarray) -> _Plan:
        """The subclass's plan, after it has set the tables of its own
        that `table_state` names."""
        raise NotImplementedError

    def _next_key(self) -> Optional[jax.Array]:
        """The random key of this call, for an objective that draws."""
        return None

    def get_gradients(self, score):
        return objective_gradients(
            self.plan, score,
            tuple(getattr(self, name) for name in self.table_state),
            self.weight, self._next_key())

    def boost_from_score(self, class_id: int = 0) -> float:
        return 0.0


@dataclasses.dataclass(frozen=True)
class _LambdarankPlan(_Plan):
    sigmoid: float
    norm: bool

    def slot_inputs(self, tables, score_pad, key):
        slot_doc = tables[0]
        return tables + (score_pad[slot_doc],)

    def bucket_grads(self, bucket, doc, label, gain, inv_max_dcg, score):
        """Pairwise lambdas of a batch of padded queries, in sorted order
        (rank_objective.hpp:140-226). Padding slots read the least score
        and sort last, so `j` valid and `i < j` means `i` valid."""
        length, t = bucket.length, bucket.pair_rows
        sig = self.sigmoid
        # stable, descending by score: equal scores keep document order
        _, s_sc, s_lbl, s_gain, s_doc = jax.lax.sort(
            (-score, score, label, gain, doc), dimension=1,
            is_stable=True, num_keys=1)
        s_valid = s_doc < self.num_data
        rank = jnp.arange(length)
        # float64 on the host, rounded once: the chip's float32 log2 is a
        # few roundings off, and |d_i - d_j| of neighbours magnifies that
        discount = 1.0 / np.log2(np.arange(length) + 2.0)
        best = s_sc[:, 0]
        worst = jnp.min(jnp.where(s_valid, s_sc, jnp.inf), axis=1)

        # pairs [batch, T, length]: i a row among the first T sorted
        # positions, j a column, i < j
        def rows(a):
            return a[:, :t, None]

        def cols(a):
            return a[:, None, :]

        pair_ok = (rank[:t, None] < rank[None, :])[None] & \
            cols(s_valid) & (rows(s_lbl) != cols(s_lbl))
        hi_is_i = rows(s_lbl) > cols(s_lbl)
        delta_score = jnp.where(hi_is_i, rows(s_sc) - cols(s_sc),
                                cols(s_sc) - rows(s_sc))
        dcg_gap = jnp.abs(rows(s_gain) - cols(s_gain))
        paired_disc = jnp.asarray(
            np.abs(discount[:t, None] - discount[None, :]), jnp.float32)[None]
        delta_ndcg = dcg_gap * paired_disc * inv_max_dcg[:, :1, None]
        if self.norm:
            delta_ndcg = jnp.where(
                (best != worst)[:, None, None],
                delta_ndcg / (0.01 + jnp.abs(delta_score)), delta_ndcg)
        ds = jnp.clip(delta_score * sig, -100.0, 100.0)
        p = 1.0 / (1.0 + jnp.exp(ds))                     # GetSigmoid
        p_lambda = jnp.where(pair_ok, -sig * delta_ndcg * p, 0.0)
        p_hess = jnp.where(pair_ok, p * (1.0 - p) * sig * sig * delta_ndcg,
                           0.0)

        # high += p_lambda, low -= p_lambda; both += p_hess
        signed = jnp.where(hi_is_i, p_lambda, -p_lambda)
        lam = -jnp.sum(signed, axis=1)                    # [batch, length]
        hes = jnp.sum(p_hess, axis=1)
        lam = lam.at[:, :t].add(jnp.sum(signed, axis=2))  # [batch, T]
        hes = hes.at[:, :t].add(jnp.sum(p_hess, axis=2))
        if self.norm:
            sum_lambdas = -2.0 * jnp.sum(p_lambda, axis=(1, 2))
            factor = jnp.where(sum_lambdas > 0,
                               jnp.log2(1.0 + sum_lambdas) /
                               jnp.maximum(sum_lambdas, 1e-30), 1.0)
            lam = lam * factor[:, None]
            hes = hes * factor[:, None]
        return lam, hes, s_doc


class LambdarankNDCG(_RankingBase):
    name = "lambdarank"
    table_state = _RankingBase.table_state + ("slot_gain",
                                              "slot_inv_max_dcg")

    def __init__(self, config: Config):
        super().__init__(config)
        self.sigmoid = float(config.sigmoid)
        self.norm = bool(config.lambdarank_norm)
        self.truncation_level = int(config.lambdarank_truncation_level)
        self.pair_rows = self.truncation_level
        if config.label_gain:
            self.label_gain_np = np.asarray(config.label_gain, np.float64)
        else:
            self.label_gain_np = default_label_gain()

    def init(self, metadata, num_data):
        lbl = np.asarray(metadata.label)
        if lbl.min() < 0 or not np.allclose(lbl, np.round(lbl)):
            Log.fatal("Label should be int >= 0 in lambdarank")
        if int(lbl.max()) >= len(self.label_gain_np):
            Log.fatal("Label %d exceeds label_gain size %d",
                      int(lbl.max()), len(self.label_gain_np))
        super().init(metadata, num_data)

    def _init_plan(self, buckets, slot_doc, slot_label):
        """The gain of every slot's label, and at every slot its query's
        inverse maximal DCG at the truncation level
        (rank_objective.hpp:124-135): a query's gains sorted descending
        against the discounts."""
        gain = self.label_gain_np[slot_label.astype(np.int64)]
        self.slot_gain = jnp.asarray(gain, jnp.float32)
        # a padding slot sorts behind every document and adds nothing
        ranked = np.where(slot_doc < self.num_data, gain, -np.inf)
        inv = []
        for b in buckets:
            g = ranked[b.slot0:b.slot0 + b.rows * b.length].reshape(
                b.rows, b.length)
            top = -np.sort(-g, axis=1)[:, :self.truncation_level]
            top = np.where(np.isfinite(top), top, 0.0)
            mdcg = top @ (1.0 / np.log2(np.arange(top.shape[1]) + 2.0))
            inv.append(np.repeat(
                np.where(mdcg > 0, 1.0 / np.maximum(mdcg, 1e-300), 0.0),
                b.length))
        self.slot_inv_max_dcg = jnp.asarray(np.concatenate(inv),
                                            jnp.float32)
        return _LambdarankPlan(buckets, self.num_data, self.sigmoid,
                               self.norm)


@dataclasses.dataclass(frozen=True)
class _XendcgPlan(_Plan):

    def slot_inputs(self, tables, score_pad, key):
        slot_doc = tables[0]
        # a fresh Gumbel draw per call (the reference uses a per-query
        # PRNG stream, rank_objective.hpp:296-299)
        uniform = jax.random.uniform(
            key, slot_doc.shape, jnp.float32, 1e-7, 1.0)
        return tables + (uniform, score_pad[slot_doc])

    def bucket_grads(self, bucket, doc, label, uniform, score):
        """XE-NDCG (rank_objective.hpp:301-355): three-term approximation,
        over a batch of padded queries in document order."""
        valid = doc < self.num_data
        sc = jnp.where(valid, score, -jnp.inf)
        rho = jnp.where(valid, jax.nn.softmax(sc, axis=1), 0.0)
        phi = jnp.where(valid, 2.0 ** label - uniform, 0.0)

        def total(a):
            return jnp.sum(a, axis=1, keepdims=True)

        inv_denom = 1.0 / jnp.maximum(total(phi), 1e-15)
        term1 = -phi * inv_denom + rho
        params = jnp.where(valid, term1 / (1.0 - rho + 1e-15), 0.0)
        term2 = rho * (total(params) - params)
        params2 = jnp.where(valid, term2 / (1.0 - rho + 1e-15), 0.0)
        lam = term1 + term2 + rho * (total(params2) - params2)
        hes = rho * (1.0 - rho)
        keep = valid & (total(valid.astype(jnp.int32)) > 1)
        return jnp.where(keep, lam, 0.0), jnp.where(keep, hes, 0.0), doc


class RankXENDCG(_RankingBase):
    name = "rank_xendcg"

    def __init__(self, config: Config):
        super().__init__(config)
        self.seed = int(config.seed)
        self._iter = 0

    def _init_plan(self, buckets, slot_doc, slot_label):
        return _XendcgPlan(buckets, self.num_data)

    def _next_key(self):
        # one key folded per call: inside a fused scan the call is traced
        # once, so every tree of a block draws the same numbers
        key = jax.random.fold_in(jax.random.PRNGKey(self.seed), self._iter)
        self._iter += 1
        return key
