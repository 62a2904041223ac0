"""Reduce-scatter histogram aggregation (distributed/hist_agg.py).

The seed data-parallel learner merged histograms with a full `psum`:
every device materializes the whole [S, F, B, 3] global histogram and
scans every feature — the reference's plain Allreduce fallback. The
reference's real algorithm (data_parallel_tree_learner.cpp:184-233) is
a Reduce-Scatter: device d ends up owning only its feature block of the
global histogram, scans just that block for its best local split, and a
small [S, world] allgather + max-gain merge picks the winners. Memory
per device drops from O(S*F*B) to O(S*F*B / world) and the wire moves
each histogram byte once instead of world times (memory-efficient array
redistribution, arXiv:2112.01075).

Two flavors, both funneled through this module:

- **exact** (`build_feature_shards` + the `bins_ft` argument of
  `learner/grower.py::grow_tree`): a one-time all_to_all transposes the
  row-sharded binned matrix into per-device column blocks
  [N_global, F/world]. Each device then builds the histogram of ALL
  rows for ITS features — the identical scatter-adds the serial learner
  performs, restricted to a column block — so per-feature histograms,
  split gains and therefore the grown tree are byte-identical to the
  serial learner (the parity oracles in
  tests/test_distributed_learner.py). Device memory for the transpose
  equals the row shard it already holds.
- **scatter** (`reduce_scatter_hist`): a `psum_scatter` over per-device
  partial histograms. No transpose and no [N_global] gathers, but the
  blocked summation order differs from the serial accumulation, so it
  is numerically (not bitwise) equivalent — the fallback when the
  transpose is unavailable.

Fault/observability contract: the host entry point
(`build_feature_shards`) carries the `distributed_hist_agg` fault site
and a collective-watchdog bracket; `reduce_scatter_hist` is traced code
whose site fires at the growth dispatch boundary (gbdt._grow), like the
other device collectives (COLL004/FAULT001/OBS001 manifests).
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..parallel.comm import CommSpec

__all__ = ["check_hist_agg_fault", "build_feature_shards",
           "reduce_scatter_hist", "feature_shard_width"]


def check_hist_agg_fault() -> None:
    """Host-side injection hook for the `distributed_hist_agg` fault
    site — fired before the all_to_all feature-shard transpose is
    dispatched (the collective itself is traced; a Python raise inside
    it would bake into the compiled program)."""
    from ..reliability import faults
    faults.inject("distributed_hist_agg")


def feature_shard_width(num_features: int, num_devices: int) -> int:
    """Features per device under the contiguous-block ownership map
    (device d owns [d*Fp, (d+1)*Fp); trailing devices may own only
    padding when F < world * ceil(F/world))."""
    return -(-num_features // max(1, num_devices))


def build_feature_shards(mesh: Mesh, comm: CommSpec,
                         bins: jax.Array) -> jax.Array:
    """One-time all_to_all transpose of the row-sharded binned matrix
    into per-device feature blocks: device d receives [N_global, Fp]
    holding ALL rows of its contiguous feature block (zero-padded to
    Fp * world columns). Runs once at `_setup_parallel`; every tree
    then histograms its own block with the serial scatter-add order,
    which is what makes the reduce-scatter path byte-exact.

    Wrapped in the `distributed_hist_agg` fault site and a
    collective-watchdog bracket, like every other host-boundary
    collective (parallel/comm.py::guarded_allgather)."""
    from ..reliability.watchdog import collective_guard

    check_hist_agg_fault()
    axis = comm.axis
    world = comm.num_devices
    f = bins.shape[1]
    fp = feature_shard_width(f, world)
    fpad = fp * world

    @functools.partial(shard_map, mesh=mesh, in_specs=(P(axis, None),),
                       out_specs=P(None, axis), check_vma=False)
    def _transpose(blk):
        # pad features INSIDE the device fn so the wire moves exactly
        # fp columns per peer; padded columns are all-zero (bin 0) and
        # are masked out of the scan by the padded slot_fmask
        blk = jnp.pad(blk, ((0, 0), (0, fpad - f)))
        return jax.lax.all_to_all(blk, axis, split_axis=1, concat_axis=0,
                                  tiled=True)

    t0 = time.perf_counter()
    with collective_guard("distributed_hist_agg"):
        bins_ft = jax.jit(_transpose)(bins)
        bins_ft.block_until_ready()
    _record_setup(world, fp, time.perf_counter() - t0)
    return bins_ft


def reduce_scatter_hist(hist: jax.Array, axis: str) -> jax.Array:
    """psum_scatter the per-device partial histograms over the feature
    dimension: input [S, Fpad, B, 3] partials, output [S, Fp, B, 3] —
    this device's fully-summed feature block of the global histogram
    (the scatter flavor; blocked sums, numerically-but-not-bitwise
    equal to the serial accumulation). Traced code: its fault site
    (`collective_psum`) fires at the growth dispatch boundary
    (gbdt._grow), like grow_tree's other collectives."""
    return jax.lax.psum_scatter(hist, axis, scatter_dimension=1,
                                tiled=True)


def _record_setup(world: int, fp: int, wall_seconds: float) -> None:
    """Feed the lightgbm_tpu_distributed metric family; never raises —
    telemetry must not fail the setup collective that carried it. When
    this transpose runs in a reincarnated world (membership epoch > 0)
    the wall is ALSO the feature-shard rebuild half of the resize cost,
    so it folds into lightgbm_tpu_membership reshard_wall_s alongside
    the row reshard the checkpoint loader timed."""
    try:
        from ..observability.registry import registry
        registry.record_distributed_setup(world, fp, wall_seconds)
        from .elastic import current_epoch
        if current_epoch() > 0:
            registry.record_membership_reshard(wall_seconds)
    except Exception:       # pragma: no cover - telemetry only
        pass
