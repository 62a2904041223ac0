"""Double-buffered training loop: host work deferred behind the next
block's dispatch.

The non-pipelined block loop in engine.train alternates strictly:
dispatch a fused block, sync, unpack its stacked trees, evaluate, run
callbacks, repeat — the device idles through all host work. This
executor reorders the same steps around JAX's async dispatch so the
expensive host step (unpacking K stacked TreeArrays into per-tree
views) is issued after the NEXT block's dispatch:

    entry.block
      entry.dispatch      block k (async; in GBDT.train_many_dispatch)
      entry.unpack_block  finalize block k-1's trees, one
                          entry.unpack_tree each
      entry.sync_metrics  block k's metrics: the explicit sync point
      entry.callbacks     j = 0..b-1 (early stop may raise)

What the chip showed (PERF.md section 6): the unpacking does NOT run
beside the device. Its ~170 slice programs queue behind the block in
flight, so the first entry.unpack_tree of a block waits out that block
and the rest run with the device idle, about 16 ms a tree.

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
    reads no clock of its own). `blocks` and `iterations` are whole-run
    counts; the per-block lists keep the last 4096 blocks.

    `device_ms` is the host wall from a block's dispatch to the end of
    its metric sync, `host_ms` the `entry.unpack_block` span inside it.
    The names date from when the unpacking was thought to overlap the
    device. On the chip it does not (PERF.md section 6): the unpacking
    waits out the block in flight, so `host_ms` reads about the block's
    wall and `overlap_frac` about 1 whatever the device did.
    bench.py and chip_smoke.py still read the fields under these
    names."""

    _KEEP = 4096

    def __init__(self):
        self.blocks = 0
        self.iterations = 0
        self._recent = collections.deque(maxlen=self._KEEP)

    def add(self, k: int, host_ms: float, device_ms: float) -> None:
        self.blocks += 1
        self.iterations += int(k)
        self._recent.append((int(k), float(host_ms), float(device_ms)))

    @property
    def block_sizes(self) -> List[int]:
        return [r[0] for r in self._recent]

    @property
    def host_ms(self) -> List[float]:
        """`entry.unpack_block` wall per block (see the class note)."""
        return [r[1] for r in self._recent]

    @property
    def device_ms(self) -> List[float]:
        """Dispatch-to-metric-sync host wall per block."""
        return [r[2] for r in self._recent]

    @property
    def overlap_frac(self) -> float:
        """Unpacking wall over block wall (see the class note: on the
        chip this is NOT the share of host work the device hid)."""
        wall = sum(self.device_ms)
        if wall <= 0:
            return 0.0
        return min(1.0, sum(self.host_ms) / wall)

    def as_dict(self) -> dict:
        return {
            "blocks": self.blocks,
            "iterations": self.iterations,
            "block_sizes": self.block_sizes,
            "host_ms": [round(v, 3) for v in self.host_ms],
            "device_ms": [round(v, 3) for v in self.device_ms],
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
                        import jax
                        jax.block_until_ready((handle, traj, mx))
                # ---- the previous block's trees unpack behind this
                # block's dispatch (entry.unpack_block, in finalize_block)
                unpack_s = 0.0
                if pending is not None:
                    booster.finalize_block(pending)
                    unpack_s = pending.get("unpack_s", 0.0)
                    pending = None
                # ---- explicit sync: small metric arrays in device-eval
                # mode; in host mode the trajectory syncs lazily when the
                # metrics first touch it below
                with span("entry.sync_metrics", iter=i, k=b) as sync:
                    mhost = [None if a is None else np.asarray(a)
                             for a in mx] if mx is not None else None
                wall_s = sync.end - blk.start
                stats.add(b, unpack_s * 1e3, wall_s * 1e3)
                if _obs.enabled:
                    _obs.record_pipeline_block(b, wall_s, unpack_s)
                # ---- per-iteration metric/callback protocol (identical
                # to the engine block loop; early stop decisions gate the
                # next dispatch, so nothing downstream is speculative)
                with span("entry.callbacks", iter=i, k=b):
                    evlist = _block_callbacks(
                        booster, dev, handle, traj, mhost, i, b,
                        has_valid, run_callbacks, evlist)
            pending = handle
            i += b
            sched.observe(b, wall_s, compiled=was_built)
    finally:
        if pending is not None:
            booster.finalize_block(pending)
    return evlist
