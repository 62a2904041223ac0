"""Double-buffered training loop: the host runs one block ahead of the
device.

The non-pipelined block loop in engine.train alternates strictly:
dispatch a fused block, wait, put its trees on the list, evaluate, run
callbacks, repeat. This executor reorders the same steps around JAX's
async dispatch so that block k+1 is enqueued while block k runs, and
the device goes from one block to the next without waiting for the
host:

    entry.block
      entry.dispatch      block k (async; in GBDT.train_many_dispatch),
                          `in_flight`: block k-1 was still running
      entry.unpack_block  finalize block k-1's trees, one
                          entry.unpack_tree each: waits for block k-1
                          and for nothing else (`waited_ms`)
      entry.sync_metrics  block k's metrics: the explicit sync point
                          of a run with valid sets
      entry.callbacks     j = 0..b-1 (early stop may raise)

Between two dispatches the host does nothing that waits for, or queues
behind, the block in flight. A block's per-tree views come from ONE
split program enqueued right behind the block (boosting/fused.py
split_block), so the unpack dispatches nothing and finds them ready
when its own block is; the lagged stop poll reads the newest leaf count
that is already there. The unpack's wait for block k-1 is the loop's
backpressure: the host is one block ahead and never two. A run with
valid sets waits for every block at entry.sync_metrics, because its
callbacks decide on that block's metrics: it gains the cheaper unpack
and not the run-ahead.

Nothing is speculative: block k+1 is never dispatched before block k's
early-stop decisions, so the executor trains the byte-identical model
of the non-pipelined loop — which stays available via pipeline=false as
the parity oracle (tests/test_pipeline.py). Early stop mid-block
replicates the engine's protocol exactly: finalize this block's trees,
restore block-final valid scores, roll back the post-stop trees, pin
valid scores to the stopping iteration's trajectory point, re-raise.

Metric values come from device reductions when every metric supports it
(device_eval.py) — the sync then moves a [b, n_metrics] array instead
of full score matrices — else from the host metrics path, identically
to the engine loop.
"""

from __future__ import annotations

import collections
from typing import Callable, List, Optional

import jax
import numpy as np

from ..callback import EarlyStopException
from ..observability import registry as _obs
from ..observability import span
from ..observability.profile import profiler as _profiler
from .device_eval import build_device_eval
from .scheduler import AdaptiveBlockScheduler

__all__ = ["PipelineStats", "run_pipelined"]


class PipelineStats:
    """Per-run pipeline accounting, attached to the booster's GBDT as
    `_pipeline_stats` unconditionally: a view over the run's
    `entry.block` spans, fed from them block by block (the executor
    reads no clock of its own). `blocks`, `iterations` and `in_flight`
    are whole-run counts; the per-block lists keep the last 4096
    blocks.

    `device_ms` is the host wall from a block's dispatch to the end of
    its metric sync: once the host runs ahead, the time from one
    dispatch to the next, which the unpack's wait for the block before
    holds to what the device takes. `host_ms` is the host's own work in
    the `entry.unpack_block` span inside it, its wait left out.
    `passes` and `fixup_iters` are what the trees of the block unpacked
    inside it ran, counted inside the growth program and read from that
    same span (the histogram passes of either formulation, and those of
    them that were iterations of the fixup loop, summed over the
    block's trees; None where no block was unpacked or its program
    counts nothing). That block is the one BEFORE, the very block whose
    device time `device_ms` reads once the host runs ahead: so a slow
    entry beside more passes is the trees' doing, and beside the same
    passes the machine's. (In a run with valid sets `device_ms` is the
    entry's own block and the counts are still the block before's; a
    run's last block is unpacked after the loop and is on the ring
    alone.)
    `in_flight` counts the blocks enqueued while the block before them
    was still running, and `overlap_frac` is their share: the share of
    block boundaries the device crossed without waiting for the host
    (the first block of a run has no block before it, and a run with
    valid sets waits at every boundary). bench.py and chip_smoke.py
    read the fields under these names."""

    _KEEP = 4096

    def __init__(self):
        self.blocks = 0
        self.iterations = 0
        self.in_flight = 0
        self._recent = collections.deque(maxlen=self._KEEP)

    def add(self, k: int, host_ms: float, device_ms: float,
            in_flight: bool = False, passes: Optional[int] = None,
            fixup_iters: Optional[int] = None) -> None:
        self.blocks += 1
        self.iterations += int(k)
        self.in_flight += bool(in_flight)
        self._recent.append((int(k), float(host_ms), float(device_ms),
                             passes, fixup_iters))

    @property
    def block_sizes(self) -> List[int]:
        return [r[0] for r in self._recent]

    @property
    def host_ms(self) -> List[float]:
        """`entry.unpack_block` wall per block, less its wait."""
        return [r[1] for r in self._recent]

    @property
    def device_ms(self) -> List[float]:
        """Dispatch-to-metric-sync host wall per block."""
        return [r[2] for r in self._recent]

    @property
    def passes(self) -> List[Optional[int]]:
        """Histogram passes the trees of the block unpacked here ran."""
        return [r[3] for r in self._recent]

    @property
    def fixup_iters(self) -> List[Optional[int]]:
        """Fixup-loop iterations among them."""
        return [r[4] for r in self._recent]

    @property
    def overlap_frac(self) -> float:
        """Share of blocks enqueued behind a running one."""
        return self.in_flight / self.blocks if self.blocks else 0.0

    def as_dict(self) -> dict:
        return {
            "blocks": self.blocks,
            "iterations": self.iterations,
            "in_flight": self.in_flight,
            "block_sizes": self.block_sizes,
            "host_ms": [round(v, 3) for v in self.host_ms],
            "device_ms": [round(v, 3) for v in self.device_ms],
            "passes": self.passes,
            "fixup_iters": self.fixup_iters,
            "overlap_frac": round(self.overlap_frac, 4),
        }


def _block_callbacks(booster, dev, handle, traj, mhost, i: int, b: int,
                     has_valid: bool, run_callbacks, evlist: List) -> List:
    """The per-iteration metric/callback protocol of block [i, i + b),
    identical to the engine block loop; returns the last evaluation
    list. On any exit it leaves the booster consistent: an early stop
    mid-block finalizes this block's trees, restores block-final valid
    scores, rolls the post-stop trees back and pins valid scores to the
    stopping iteration's trajectory point before re-raising."""
    gb = booster.gbdt
    finalized = False
    try:
        if traj is not None and has_valid:
            try:
                for j in range(b):
                    if mhost is not None:
                        evlist = dev.evlist_at(mhost, j)
                    else:
                        for vi in range(len(traj)):
                            gb.valid_scores[vi] = traj[vi][j]
                        evlist = booster.eval_valid()
                    run_callbacks(i + j, evlist)
            except EarlyStopException:
                # this block's trees must exist before rollback pops
                # them; then replicate the engine's restore protocol:
                # block-final scores, roll the post-stop trees back, pin
                # valid scores to the stopping iteration's trajectory
                # point
                booster.finalize_block(handle)
                finalized = True
                for vi in range(len(traj)):
                    gb.valid_scores[vi] = traj[vi][b - 1]
                for _ in range(b - 1 - j):
                    booster.rollback_one_iter()
                for vi in range(len(traj)):
                    gb.valid_scores[vi] = traj[vi][j]
                raise
        elif has_valid:
            # belt-and-braces (mirrors engine.train): a missing
            # trajectory degrades to block-end eval cadence
            evlist = booster.eval_valid()
            run_callbacks(i + b - 1, evlist)
        else:
            for j in range(b):
                evlist = []
                run_callbacks(i + j, evlist)
    except BaseException:
        # any other exit: leave the booster consistent — trees hold the
        # full block, so scores must too
        if not finalized:
            booster.finalize_block(handle)
            if traj is not None:
                for vi in range(len(traj)):
                    gb.valid_scores[vi] = traj[vi][b - 1]
        raise
    # in host-eval mode the loop above left valid_scores at traj[b-1],
    # the block-final state; device mode never moved them off it
    return evlist


def run_pipelined(booster, *, start_iter: int, num_boost_round: int,
                  base_block: int, run_callbacks: Callable[[int, List], None],
                  has_valid: bool, stopping_rounds: int = 0) -> List:
    """Train [start_iter, num_boost_round) pipelined; returns the last
    evaluation_result_list. Raises EarlyStopException (and any callback
    exception) with the booster in the exact state the non-pipelined
    block loop would leave it in — engine.train's handlers run
    unchanged."""
    gb = booster.gbdt
    cfg = booster.config
    sched = AdaptiveBlockScheduler(
        base_block, adaptive=bool(cfg.pipeline_adaptive_blocks),
        target_ms=float(cfg.pipeline_target_block_ms),
        max_block=int(cfg.pipeline_max_block),
        stopping_rounds=int(stopping_rounds or 0))
    dev = build_device_eval(booster) \
        if has_valid and cfg.pipeline_device_eval else None
    stats = PipelineStats()
    gb._pipeline_stats = stats
    pending: Optional[dict] = None
    evlist: List = []
    i = start_iter
    try:
        while i < num_boost_round:
            b = sched.next_block(num_boost_round - i)
            was_built = getattr(gb, "_fused_run", None) is None
            with span("entry.block", iter=i, k=b) as blk:
                with _profiler.capture("pipeline_block") as _capturing:
                    # entry.dispatch (and boosting.build_program the
                    # first time) open inside train_many_dispatch
                    handle = booster.update_batch_dispatch(b)
                    traj = getattr(gb, "_fused_valid_traj", None)
                    mx = dev.dispatch(traj) \
                        if dev is not None and traj is not None else None
                    if _capturing:
                        # live device capture: force the async block to
                        # complete inside the trace window (costs the
                        # asynchrony for this one profiled block only)
                        jax.block_until_ready((handle, traj, mx))
                # ---- the previous block's trees go on the list behind
                # this block's dispatch (entry.unpack_block, in
                # finalize_block): it waits for THAT block, which is
                # this loop's backpressure
                host_s, ran = 0.0, {}
                if pending is not None:
                    booster.finalize_block(pending)
                    host_s = pending.get("host_s", 0.0)
                    ran = pending.get("ran", {})
                    pending = None
                # ---- explicit sync, where callbacks decide on this
                # block's metrics: small metric arrays in device-eval
                # mode, the score trajectory itself in host mode
                synced = has_valid and traj is not None
                with span("entry.sync_metrics", iter=i, k=b) as sync:
                    mhost = [None if a is None else np.asarray(a)
                             for a in mx] if mx is not None else None
                    if synced and mx is None:
                        jax.block_until_ready(traj)
                wall_s = sync.end - blk.start
                in_flight = bool(handle.get("in_flight"))
                stats.add(b, host_s * 1e3, wall_s * 1e3, in_flight,
                          ran.get("passes"), ran.get("fixup_iters"))
                if _obs.enabled:
                    _obs.record_pipeline_block(b, wall_s, host_s,
                                               in_flight)
                # the scheduler learns where this loop waited for the
                # block (valid sets; a block that fell back to the
                # per-iteration path and was worked off inside its
                # dispatch): there every block costs the host a round
                # trip that a longer block amortises, and the wall to
                # the end of the sync is what the block took. Where the
                # host runs ahead it is fed NOTHING: the device crosses
                # block boundaries without waiting for the host, so a
                # longer block would amortise nothing and cost a
                # program, and the wall of a loop iteration, which is
                # milliseconds after any sync, says nothing of the
                # device
                if synced or handle["mode"] != "fused":
                    sched.observe(b, wall_s, compiled=was_built)
                # ---- per-iteration metric/callback protocol (identical
                # to the engine block loop; early stop decisions gate the
                # next dispatch, so nothing downstream is speculative)
                with span("entry.callbacks", iter=i, k=b):
                    evlist = _block_callbacks(
                        booster, dev, handle, traj, mhost, i, b,
                        has_valid, run_callbacks, evlist)
            pending = handle
            i += b
    finally:
        if pending is not None:
            booster.finalize_block(pending)
    return evlist
