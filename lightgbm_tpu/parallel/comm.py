"""Communication spec for distributed tree learning.

The reference's whole network layer (src/network/: Bruck allgather,
recursive-halving reduce-scatter, socket/MPI linkers — SURVEY.md §2.4)
collapses to THREE collective call sites expressed with jax.lax ops inside
`shard_map`; XLA picks the wire algorithms (ICI/DCN routing, ring vs
recursive) that src/network/network.cpp:68-301 hand-implements:

- data-parallel  (data_parallel_tree_learner.cpp): histogram merge
  = `psum` / `psum_scatter` over the row-sharded mesh axis.
- feature-parallel (feature_parallel_tree_learner.cpp): best-split sync
  = `all_gather` of per-device SplitInfo + argmax (the max-gain reducer of
  parallel_tree_learner.h:191-214).
- voting-parallel (voting_parallel_tree_learner.cpp, PV-Tree): local top-k
  votes -> `psum` of vote one-hots -> top-2k feature selection -> masked
  histogram `psum`.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

__all__ = ["CommSpec", "check_collective_fault", "guarded_allgather",
           "checkpoint_agree", "checkpoint_coordinator",
           "CheckpointCoordinator"]


def check_collective_fault() -> None:
    """Host-side injection hook for the `collective_psum` fault site.

    The collectives themselves run inside shard_map-traced code where a
    Python raise would bake into the compiled program, so the GBDT
    growth dispatch calls this at the host boundary before every
    sharded-grower launch — the point where a real interconnect failure
    would surface as a dispatch error. Retried/fallback handling lives
    with the caller (reliability/retry.py)."""
    from ..reliability import faults
    faults.inject("collective_psum")


def guarded_allgather(x, label: str = "allgather") -> np.ndarray:
    """THE host-boundary allgather: every cross-process gather in the
    library funnels through here so one choke point carries both the
    `collective_psum` fault site (rank_death chaos schedules included)
    and the collective-watchdog deadline bracket. A peer that died
    before this call leaves us blocked inside `process_allgather`; the
    watchdog deadline turns that into a named "rank k last seen Ns ago"
    abort instead of an eternal hang.

    Each call also piggybacks one wall-clock stamp per rank on the SAME
    pytree allgather (one extra float64 on the wire, zero extra
    collectives): the samples feed the cross-rank clock alignment of
    ``python -m lightgbm_tpu.observability merge`` and the
    lightgbm_tpu_clock_skew metrics. A membership epoch (one int64)
    rides along the same way: a rank resumed from a stale membership
    record would otherwise exchange rows sharded for the WRONG world —
    every gather cross-checks epochs and raises on divergence
    (distributed/elastic.py, stale-epoch rejection).

    Returns ``[nproc, *x.shape]`` on every world size: one process
    stacks a leading axis of 1 exactly like N processes stack N, so
    callers index ranks the same way whether or not peers exist."""
    import time
    from jax.experimental import multihost_utils
    from ..reliability.watchdog import collective_guard
    check_collective_fault()
    arr = np.asarray(x)
    if arr.ndim:        # ascontiguousarray would promote 0-d to 1-d,
        arr = np.ascontiguousarray(arr)   # changing the wire shape

    with collective_guard(label):
        gathered, walls, epochs = multihost_utils.process_allgather(
            (arr, np.float64(time.time()), np.int64(_local_epoch())))
    _record_clock_sample(label, walls)
    _check_epochs(label, epochs)
    return np.asarray(gathered)


def _local_epoch() -> int:
    """This rank's membership epoch, stamped onto every gather."""
    from ..distributed.elastic import current_epoch
    return current_epoch()


def _check_epochs(label: str, epochs) -> None:
    """Stale-epoch rejection: every rank sees every rank's epoch on the
    gather it just completed, so divergence raises on ALL ranks in the
    same bracket (rank-uniform data -> rank-uniform control flow; no
    COLL002 split-brain)."""
    from ..distributed.elastic import check_epoch_agreement
    check_epoch_agreement(np.asarray(epochs).reshape(-1), label)


def _record_clock_sample(label: str, walls) -> None:
    """Feed one piggybacked clock sample (every rank's pre-collective
    wall stamp) to the observability registry; never raises — clock
    forensics must not fail the collective that carried them."""
    try:
        from ..observability.registry import registry
        registry.record_clock_sample(label,
                                     np.asarray(walls).reshape(-1))
    except Exception:       # pragma: no cover - forensics only
        pass


def checkpoint_agree(value: int, label: str = "checkpoint_agree"
                     ) -> np.ndarray:
    """One-int agreement collective (the PR-8 agreement-flag idiom):
    every rank contributes `value`, every rank sees all of them, and
    all can decide identically — used by the coordinated checkpoint
    protocol to agree on the iteration to snapshot and on shard-write
    success before the commit marker is cut. Delegates to
    `guarded_allgather`, inheriting its fault site and watchdog
    bracket."""
    out = guarded_allgather(np.asarray([int(value)], dtype=np.int64),
                            label=label)
    return out.reshape(-1)


@dataclasses.dataclass(frozen=True)
class CheckpointCoordinator:
    """The handle `save_checkpoint` uses to run the multihost commit
    protocol. Exists only when >1 process participates — single-host
    saves keep the original (and cheaper) tmp+rename path."""
    rank: int
    world: int

    def agree(self, value: int, label: str = "checkpoint_agree"):
        return checkpoint_agree(value, label=label)


def checkpoint_coordinator() -> Optional[CheckpointCoordinator]:
    """A `CheckpointCoordinator` for this run, or None on one process
    (coordination degenerates to nothing — no collectives issued)."""
    import jax
    try:
        world = jax.process_count()
    except RuntimeError:
        world = 1
    if world <= 1:
        return None
    return CheckpointCoordinator(rank=jax.process_index(), world=world)


@dataclasses.dataclass(frozen=True)
class CommSpec:
    """Static distributed-training configuration (hashable for jit)."""
    axis: str = "data"            # mesh axis name
    mode: str = "data"            # "data" | "feature" | "voting"
    num_devices: int = 1
    top_k: int = 20               # voting-parallel top-k (config.top_k)
    # histogram merge algorithm for the row-sharded modes:
    # "psum" replicates the full [S, F, B, 3] histogram on every device
    # (the seed behavior); "reduce_scatter" gives each device a
    # contiguous feature shard of the global histogram and merges only
    # [S]-sized split candidates (distributed/hist_agg.py — the
    # reference's ReduceScatter of data_parallel_tree_learner.cpp:184).
    hist_agg: str = "psum"

    def __post_init__(self):
        if self.mode not in ("data", "feature", "voting"):
            raise ValueError(f"unknown parallel mode {self.mode!r}")
        if self.hist_agg not in ("psum", "reduce_scatter"):
            raise ValueError(
                f"unknown histogram aggregation {self.hist_agg!r} "
                f"(expected 'psum' or 'reduce_scatter')")
