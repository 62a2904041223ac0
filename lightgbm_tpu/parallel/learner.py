"""Sharded tree learner: shard_map'ped growth over a device mesh.

The factory role of the reference's CreateTreeLearner crossbar
(tree_learner.cpp:16-64: device x {serial,feature,data,voting}) — here the
"device" dimension is always TPU/XLA and the parallelism dimension picks the
collective pattern (CommSpec). Parallel learners in the reference are
templates OVER the serial learner (parallel_tree_learner.h:26-107); here the
same single `grow_tree` body runs inside `shard_map`, with its collectives
activated by `comm`.

Sharding contract (1-D mesh, axis "data"):
- data/voting: bins/grad/hess/cnt row-sharded; tree replicated out.
- feature: bins replicated (the reference feature-parallel replicates data,
  docs/Features.rst:109); the per-device feature shard is derived from
  axis_index inside the grower.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..learner.grower import grow_tree
from .comm import CommSpec

__all__ = ["make_sharded_grower", "shard_rows", "replicate"]


def shard_rows(mesh: Mesh, *arrays):
    """Place arrays with rows sharded over the mesh axis."""
    axis = mesh.axis_names[0]
    out = []
    for a in arrays:
        spec = P(axis) if a.ndim >= 1 else P()
        out.append(jax.device_put(a, NamedSharding(mesh, spec)))
    return out if len(out) > 1 else out[0]


def replicate(mesh: Mesh, *arrays):
    out = [jax.device_put(a, NamedSharding(mesh, P())) for a in arrays]
    return out if len(out) > 1 else out[0]


def make_sharded_grower(mesh: Mesh, comm: CommSpec, *, num_leaves: int,
                        max_depth: int, hp, leafwise: bool, bmax: int,
                        feature_block: int = 8, use_mxu: bool = False,
                        mxu_kwargs: Optional[dict] = None,
                        interpret: bool = False, monotone=None,
                        monotone_method: str = "basic",
                        interaction_groups: Optional[tuple] = None,
                        feature_fraction_bynode: float = 1.0,
                        with_rng: bool = False, forced=None,
                        cegb_cfg=None, with_cegb_state: bool = False,
                        efb=None, with_bins_ft: bool = False):
    """Build a shard_map'ped grower with the given static config.

    use_mxu (data-parallel only) runs the MXU grower inside shard_map
    with per-pass histogram psum over the mesh axis — the TPU form of
    DataParallelTreeLearner's histogram Reduce-Scatter
    (data_parallel_tree_learner.cpp:184-186). Other modes (and the CPU
    fallback) keep the portable scatter grower, whose collectives live
    inside grow_tree itself.

    with_rng=True adds a replicated rng_key argument (the 9th) so
    per-node feature sampling / extra_trees / quantized rounding take a
    per-iteration key: every shard holds the identical key, samples the
    identical masks, and therefore takes identical split decisions — the
    reference syncs sampling seeds across machines the same way
    (application.cpp:170-175 GlobalSyncUpByMin of seeds).

    with_bins_ft=True adds a trailing feature-sharded argument: the
    [N_global, F/world] transpose from
    distributed/hist_agg.py::build_feature_shards, enabling the exact
    reduce-scatter histogram flavor inside grow_tree."""
    axis = comm.axis
    data_spec = P(axis) if comm.mode in ("data", "voting") else P()

    if use_mxu and comm.mode == "data":
        from ..learner.grower_mxu import grow_tree_mxu
        grower = functools.partial(
            grow_tree_mxu, num_leaves=num_leaves, max_depth=max_depth,
            hp=hp, bmax=bmax, psum_axis=axis, interpret=interpret,
            monotone=monotone, interaction_groups=interaction_groups,
            feature_fraction_bynode=feature_fraction_bynode,
            forced=forced, cegb_cfg=cegb_cfg, efb=efb,
            **(mxu_kwargs or {}))
    else:
        grower = functools.partial(
            grow_tree, num_leaves=num_leaves, max_depth=max_depth, hp=hp,
            leafwise=leafwise, bmax=bmax, feature_block=feature_block,
            comm=comm, monotone=monotone,
            monotone_method=monotone_method,
            interaction_groups=interaction_groups,
            feature_fraction_bynode=feature_fraction_bynode,
            forced=forced, cegb_cfg=cegb_cfg, efb=efb)

    # forced-split spec arrays are baked in as static closures (tree-wide
    # constants); CEGB state travels as a live argument because the
    # row_feat_used flags persist and grow across trees. Its per-row
    # component shards with the rows (reference is_feature_used_ is
    # per-datapoint, cost_effective_gradient_boosting.hpp:56).
    in_specs = (data_spec, data_spec, data_spec, data_spec,
                P(), P(), P(), P())
    if with_rng:
        in_specs += (P(),)
    if with_cegb_state:
        # the per-row flags only exist under the lazy penalty; the (1,1)
        # placeholder otherwise must stay replicated
        rfu_spec = data_spec if (cegb_cfg is not None and
                                 cegb_cfg.has_lazy) else P()
        in_specs += ((P(), P(), P(), rfu_spec),)
    if with_bins_ft:
        in_specs += (P(None, axis),)
    out_specs = (P(), data_spec)
    if with_cegb_state:
        out_specs = (P(), data_spec, (P(), rfu_spec))

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=False)
    def sharded(bins, grad, hess, cnt, feature_mask, num_bins,
                missing_is_nan, is_cat, *rest):
        rest = list(rest)
        kw = {}
        if with_rng:
            kw["rng_key"] = rest.pop(0)
        if with_cegb_state:
            kw["cegb_state"] = tuple(rest.pop(0))
        if with_bins_ft:
            kw["bins_ft"] = rest.pop(0)
        return grower(bins, grad, hess, cnt, feature_mask, num_bins,
                      missing_is_nan, is_cat, **kw)

    return jax.jit(sharded)
