"""Row-sharded fused multi-tree training for the PORTABLE grower: K
sharded boosting iterations per device dispatch.

`boosting/fused.py` keeps the whole boosting loop on device as a
`lax.scan` for the MXU learner, serial or (given a mesh) data-parallel.
This module is the same reformulation for the data-parallel row of the
crossbar's portable device (`grow_tree` with its reduce-scatter/psum
histogram merge): the scan body runs INSIDE `shard_map`, so every
iteration's gradients, bagging mask, sharded tree growth and score
update happen on the row shard, and the host sees one dispatch per K
trees. `GBDT.train_many_dispatch` calls the builder's `run` through
the exact signature the serial fused path uses, so the pipelined
executor double-buffers it unchanged.

Parity contract: gradients are elementwise, the bagging mask is the
identical global draw every shard recomputes and slices, and
`grow_tree` under the exact reduce-scatter flavor is byte-identical to
serial — the per-iteration sharded path (fused_block_size=1)
reproduces serial `train_one_iter` calls bit-for-bit when rows divide
the mesh, and the byte-parity oracles run there. The fused block
itself is DETERMINISTIC (same model for every block size / pipeline
setting — what chaos resume replays), but may differ from the
per-iteration path by 1-ulp score rounding: with the whole loop in one
program, the XLA CPU backend contracts the shrinkage multiply into the
score add (an FMA, one rounding instead of two). `optimization_barrier`
is expanded away before fusion on CPU, and neither bitcast roundtrips,
`reduce_precision`, nor --xla_allow_excess_precision=false defeat the
LLVM-level contraction — so the engine's b=1 bit-parity note
(engine.py) carries this documented exception for the sharded path.

Elasticity note (distributed/elastic.py): this builder closes over a
FIXED world — `comm.num_devices`, the row pad, and the feature-shard
transpose (`bins_ft`) are all sized for the mesh at build time. A
membership resize therefore never mutates a live builder; the
reincarnated process rebuilds the whole stack (crossbar re-resolve →
`build_feature_shards` → this builder) at the new world, and the epoch
stamped on every guarded gather rejects any straggler still running a
builder from the old membership.

Objective handling: the built-in objectives close over [N] row state
(label / weight / trans_label / y_signed / ...). Baking those into the
scan as replicated constants would defeat the sharding, so every 1-D
[num_data] attribute of the objective is collected at build time,
padded, row-sharded, and rebound onto a shallow copy of the objective
inside the device function — `get_gradients` then computes on blocks.
"""

from __future__ import annotations

import copy
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = ["build_sharded_fused_train", "objective_row_state"]


def objective_row_state(objective, num_data: int):
    """(names, arrays): every 1-D [num_data] array attribute of the
    objective — the per-row state `get_gradients` reads (label, weight,
    trans_label, y_signed, label_weight, ...). Sorted by name so the
    argument order is deterministic across builds."""
    names, arrays = [], []
    for name in sorted(vars(objective)):
        val = vars(objective)[name]
        if val is None or not hasattr(val, "ndim"):
            continue
        if getattr(val, "ndim", 0) == 1 and val.shape[0] == num_data:
            names.append(name)
            arrays.append(jnp.asarray(val))
    return names, arrays


def build_sharded_fused_train(*, mesh, comm, objective, bins,
                              bins_ft: Optional[jax.Array], num_data: int,
                              row_pad: int, feature_mask_fn, num_bins,
                              missing_is_nan, is_cat, grow_kwargs: dict,
                              shrinkage: float, extra_seed: int,
                              needs_rng: bool, bagging: Optional[dict]
                              = None):
    """Return run(score, it0, *, k, sample_keys=None) ->
    (score'[:num_data], stacked TreeArrays, None) — the serial
    `build_fused_train` contract, over the row-sharded mesh (the
    portable grower counts nothing of its growth: no counters).

    `bins` is the already-sharded [N_pad, F] binned matrix (P(axis)),
    `bins_ft` the optional feature-shard transpose from
    `hist_agg.build_feature_shards` (P(None, axis)); `grow_kwargs` are
    the static portable-grower settings (the same ones
    `parallel.learner.make_sharded_grower` bakes). `bagging` (None =
    no sampling) carries {freq, seed, fraction, pos_fraction,
    neg_fraction, use_posneg}: the mask is the stateless global draw of
    `gbdt._bagging`, recomputed replicated in-shard and sliced to the
    block, so the fused and per-iteration paths consume identical
    masks. GOSS is not eligible here (its top-k threshold is global;
    the caller gates it out)."""
    from ..learner.grower import grow_tree

    axis = comm.axis
    n_pad = num_data + row_pad
    shrink = jnp.float32(shrinkage)
    row_names, row_arrays = objective_row_state(objective, num_data)
    row_sharded = tuple(jnp.pad(a, (0, row_pad)) for a in row_arrays)
    valid = jnp.pad(jnp.ones(num_data, jnp.float32), (0, row_pad))
    with_ft = bins_ft is not None

    if bagging is not None:
        bag_freq = int(bagging["freq"])
        bag_seed = int(bagging["seed"])
        bag_frac = float(bagging["fraction"])
        bag_pos = float(bagging["pos_fraction"])
        bag_neg = float(bagging["neg_fraction"])
        bag_posneg = bool(bagging["use_posneg"])

    def _bag_mask(it, label_blk, off, nl):
        # the mask the per-iteration path STORED at the last resample
        # boundary (gbdt._bagging), recomputed statelessly: the full
        # [num_data] draw is replicated (every shard draws identically)
        # and sliced to this shard's rows; padded rows draw u=1.0 and
        # can never enter the bag
        it_rs = it - it % bag_freq
        k2 = jax.random.fold_in(jax.random.PRNGKey(bag_seed), it_rs)
        u = jnp.pad(jax.random.uniform(k2, (num_data,)), (0, row_pad),
                    constant_values=1.0)
        u_blk = jax.lax.dynamic_slice_in_dim(u, off, nl)
        if bag_posneg:
            frac = jnp.where(label_blk > 0, bag_pos, bag_neg)
        else:
            frac = bag_frac
        return (u_blk < frac).astype(jnp.float32)

    in_specs = (P(axis), P(), P(axis)) + (P(axis),) * len(row_sharded) \
        + (P(), P(), P())
    if with_ft:
        in_specs += (P(None, axis),)
    in_specs += (P(axis, None),)

    @functools.partial(shard_map, mesh=mesh, in_specs=in_specs,
                       out_specs=(P(axis), P()), check_vma=False)
    def device_run(score, its, valid_blk, *rest):
        rest = list(rest)
        row_blks = [rest.pop(0) for _ in row_names]
        nb, minan, isc = rest.pop(0), rest.pop(0), rest.pop(0)
        bins_ft_blk = rest.pop(0) if with_ft else None
        bins_blk = rest.pop(0)
        nl = score.shape[0]
        off = jax.lax.axis_index(axis) * nl
        obj = copy.copy(objective)
        for name, blk in zip(row_names, row_blks):
            setattr(obj, name, blk)
        label_blk = getattr(obj, "label", None)

        def body(carry, it):
            grad, hess = obj.get_gradients(carry)
            grad = grad * valid_blk
            hess = hess * valid_blk
            if bagging is not None:
                mask = _bag_mask(it, label_blk, off, nl)
                grad, hess, cnt = grad * mask, hess * mask, mask
            else:
                cnt = valid_blk
            fmask = feature_mask_fn(it)
            rng = jax.random.fold_in(
                jax.random.PRNGKey(extra_seed), it) if needs_rng else None
            tree, row_node = grow_tree(
                bins_blk, grad, hess, cnt, fmask, nb, minan, isc,
                rng_key=rng, comm=comm, bins_ft=bins_ft_blk,
                **grow_kwargs)
            # ok-zeroing + shrinkage in-scan (train_one_iter's "no
            # further splits" handling, like the serial fused body).
            # The score add below may round 1 ulp off the per-iteration
            # path: in one program the backend contracts this multiply
            # into the add (FMA) — see the module docstring. The trees
            # themselves (emitted leaf values) are exact; only the
            # in-scan score carry sees the contracted rounding.
            ok = (tree.num_leaves > 1).astype(jnp.float32)
            lv = tree.leaf_value * (shrink * ok)
            tree = tree._replace(leaf_value=lv)
            return carry + lv[row_node], tree

        return jax.lax.scan(body, score, its)

    jit_run = jax.jit(device_run)
    data_sh = NamedSharding(mesh, P(axis))

    def run(score, it0, *, k: int, sample_keys=None):
        # sample_keys belongs to the GOSS contract of the serial fused
        # path; the eligibility gate keeps GOSS off this builder
        del sample_keys
        its = jnp.asarray(it0, jnp.int32) + jnp.arange(k, dtype=jnp.int32)
        if row_pad:
            score = jnp.pad(score, (0, row_pad))
        score = jax.device_put(score, data_sh)
        args = (score, its, jax.device_put(valid, data_sh))
        args += tuple(jax.device_put(a, data_sh) for a in row_sharded)
        args += (num_bins, missing_is_nan, is_cat)
        if with_ft:
            args += (bins_ft,)
        args += (bins,)
        with mesh:
            out_score, stacked = jit_run(*args)
        return out_score[:num_data], stacked, None

    return run
