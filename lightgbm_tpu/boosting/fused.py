"""Fused multi-tree training: K boosting iterations per device dispatch.

The reference's training loop crosses the host boundary every iteration
(gbdt.cpp:371 TrainOneIter, driven from Python via
LGBM_BoosterUpdateOneIter). On an accelerator every crossing is a
dispatch plus, for the stop poll and the metrics, a sync that leaves the
device idle while the host catches up. The TPU-native reformulation:
the boosting loop itself is a `lax.scan` whose body grows one tree (or
one tree per class) — objective gradients, bagging/GOSS sampling,
quantization, growth, prune, exact leaf refit and the score update all
stay on device — so the host sees ONE dispatch per K trees and receives
the K stacked TreeArrays plus the advanced scores.

In-scan sampling (round 4): bagging masks are STATELESS — the mask at
iteration `it` depends only on (bagging_seed, it - it % bagging_freq),
so the scan recomputes exactly what the per-iteration path
(gbdt.py:_bagging, reference gbdt.cpp:183-264) stores; GOSS consumes
per-iteration keys passed as scan inputs (the same _next_key sequence
the per-iteration path draws, goss.hpp:76-95), keeping the two paths
bit-identical. Multiclass grows num_class trees per scan step
(gbdt.cpp:371 TrainOneIter's per-class loop).

Eligibility is decided by the caller (GBDT._fused_eligible): the MXU
growth path, serial or data-parallel (the same scan inside shard_map,
`mesh=`: GBDT._sharded_fused_ok), plain gbdt/goss boosting, no
L1-family leaf renewal — every excluded feature falls back to the
per-iteration path unchanged.
Validation sets DO ride along (round 5): the stacked block is replayed
over each valid set after the dispatch (stacked_score_traj), giving
the exact per-iteration valid-score trajectory for metric evaluation
and early stopping between dispatches.
"""

from __future__ import annotations

import copy
import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = ["build_fused_train", "split_block", "stacked_score_traj"]

# Score carries are donated (jax.jit donate_argnames): XLA reuses the
# input buffer for the output instead of allocating a fresh [N] (or
# [N, K]) f32 per block — on TPU the f32 score cache is the largest
# recurring training allocation. The CPU backend cannot honor donation
# and warns on every dispatch; that warning is noise for this
# by-design-portable code path, so it is silenced here and ONLY here.
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")


@functools.partial(jax.jit, static_argnames=("num_class",),
                   donate_argnames=("score0",))
def stacked_score_traj(stacked, score0, bins, num_bins, missing_is_nan,
                       *, num_class: int = 1):
    """Per-iteration score trajectory of a stacked tree block over a
    binned matrix: scan the K stacked trees from `score0`, returning
    (final score, [K, ...] score after each iteration). This replays
    the per-iteration valid-score updates (gbdt._update_score — the
    reference's AddScore(valid) cadence, score_updater.hpp:21-110) for
    a block trained by the fused scan: leaf values in `stacked` already
    carry shrinkage, so the trajectory is exactly what K train_one_iter
    calls would have left on the valid set, one point per iteration."""
    from ..learner.predict import predict_binned_tree

    def body(s, tr):
        if num_class == 1:
            s = s + predict_binned_tree(tr, bins, num_bins,
                                        missing_is_nan)
        else:
            for cls in range(num_class):
                tcls = jax.tree_util.tree_map(lambda a: a[cls], tr)
                s = s.at[:, cls].add(
                    predict_binned_tree(tcls, bins, num_bins,
                                        missing_is_nan))
        return s, s

    return jax.lax.scan(body, score0, stacked)


@jax.jit
def split_block(stacked, counters=None):
    """Every tree of a stacked block as TreeArrays of its own, in the
    order the tree list wants them (iteration-major, class-minor: the
    leading axes of a stacked field are [k] or [k, num_class]), and
    the leaf count the stop poll reads: the block's last tree's, the
    largest over its classes (a model has stalled only if EVERY class
    has), [nodes, cat_nodes] of the block and its trees' growth
    `counters` as the scan stacked them ([k(, num_class), C]: handed
    through, so that they are this program's outputs and ready with
    the views; the `entry.unpack_block` span carries both). ONE
    program per block length and class count, where slicing
    the fields tree by tree from the host was one program and one
    index transfer a field a tree (170 a block of ten)."""
    trees = [jax.tree_util.tree_map(lambda a: a[ix], stacked)
             for ix in np.ndindex(stacked.num_leaves.shape)]
    # what the block's trees decide on: their internal nodes, and those
    # of them that test a categorical column's bitset
    internal = (stacked.split_feature >= 0) & ~stacked.is_leaf
    decides = jnp.stack([jnp.sum(internal),
                         jnp.sum(internal & stacked.is_cat)])
    return trees, jnp.max(stacked.num_leaves[-1]), decides, counters


def build_fused_train(*, objective, bins, feature_mask_fn,
                      num_bins, missing_is_nan, is_cat, grower_kwargs,
                      shrinkage: float, extra_seed: int, needs_rng: bool,
                      sample_fn=None, num_class: int = 1,
                      mesh=None, row_pad: int = 0):
    """Return run(score, it0, k, sample_keys=None) ->
    (score', stacked TreeArrays, counters).

    `counters` is what each tree of the block ran, counted inside the
    growth program by the passes themselves and stacked by the scan
    beside the trees: int32 [k(, num_class), C], C as
    grower_mxu.GROWTH_COUNTERS names them (passes by formulation, the
    bridge and the fixup iterations, the rows live in those passes,
    the leaves before the prune). Under `mesh` the rows are the
    mesh's and every device holds the same counts.

    `mesh` (None: one device) makes it the data-parallel learner's
    block: the SAME scan runs inside `shard_map` over the mesh's row
    axis, on each device's rows, and the grower sums its histograms
    over that axis in every pass (`psum_axis`). `bins` is then the
    row-sharded matrix, `row_pad` rows longer than the objective's
    state, which is padded and placed on the mesh here, once; the
    score goes in and comes out row-sharded and the trees come out
    replicated. A padded row has no gradient and no count.

    The bin matrix, the objective's per-row state (label, weight, ...)
    and the tables it names in `table_state` (a ranking objective's
    query buckets) enter the compiled program as ARGUMENTS, not as values the
    trace closes over: a closed-over array is lowered as a literal, so
    the program would carry a second copy of the dataset, and its
    persistent-cache key would change with every dataset of the same
    shape. So do the per-feature tables (num_bins, missing_is_nan,
    is_cat), last. `run.program` is the jitted function, `run.operands`
    the arrays it is called with after (score, it0, sample_keys) and
    `run.feature_tables` those tables — what an ahead-of-time compile
    needs (testing/tpu_aot.py);
    `run.arguments(score, it0, k=, sample_keys=)` is the whole argument
    tuple of a call, for `run.program.trace`.

    `objective.get_gradients` must be pure jnp (all built-in objectives
    are); `grower_kwargs` are the static grow_tree_mxu settings
    (GBDT._mxu_grow_kwargs — shared with the per-iteration path);
    `feature_mask_fn(it)` produces the per-iteration feature_fraction
    mask (traced iteration index).

    sample_fn(grad, hess, it, key) -> (grad', hess', cnt) implements
    bagging/GOSS inside the scan (None = no sampling; cnt_weight used).
    For key-consuming samplers (GOSS) the caller passes sample_keys
    [k, 2] — the same keys the per-iteration path would draw.

    num_class > 1 grows one tree per class per step; stacked tree
    leaves gain a leading [k, num_class] shape and score is [N, K].
    """
    from ..distributed.fused import objective_row_state
    from ..learner.grower_mxu import grow_tree_mxu
    from ..learner.histogram_mxu import node_values_mxu

    axis = mesh.axis_names[0] if mesh is not None else None
    num_data = bins.shape[0] - row_pad
    row_names, row_arrays = objective_row_state(objective, num_data)
    # what the objective laid out at init that is not one value a row
    # (a ranking objective's query buckets): arguments too, by name
    table_names = tuple(getattr(objective, "table_state", ()))
    table_arrays = tuple(getattr(objective, n) for n in table_names)
    shrink = jnp.float32(shrinkage)
    interpret = bool(grower_kwargs.get("interpret", False))

    def one_tree(bins, feats, grad, hess, cnt, fmask, it):
        rng = jax.random.fold_in(jax.random.PRNGKey(extra_seed), it) \
            if needs_rng else None
        tree, row_node, counters = grow_tree_mxu(
            bins, grad, hess, cnt, fmask, *feats, rng_key=rng,
            growth_counters=True, psum_axis=axis, **grower_kwargs)
        # device-side stand-in for the "no further splits" break: a tree
        # that made no split becomes all-zero and the scan carries on
        # (train_one_iter's ok-zeroing, gbdt.py)
        ok = (tree.num_leaves > 1).astype(jnp.float32)
        tree = tree._replace(leaf_value=tree.leaf_value * (shrink * ok))
        vals = node_values_mxu(row_node, tree.leaf_value,
                               interpret=interpret)
        return tree, vals, counters

    def body(bins, feats, obj, score, xs):
        it, key = xs
        with jax.named_scope("objective." + getattr(obj, "name", "custom")):
            grad, hess = obj.get_gradients(score)
        # this device's rows; the rows past num_data pad the last shard
        rows = score.shape[0]
        real = None
        if row_pad:
            first = jax.lax.axis_index(axis) * rows
            real = (first + jnp.arange(rows) < num_data) \
                .astype(jnp.float32)
            grad, hess = grad * real, hess * real
        if sample_fn is not None:
            grad, hess, cnt = sample_fn(grad, hess, it, key)
        else:
            cnt = jnp.ones(rows, jnp.float32) if real is None else real
        fmask = feature_mask_fn(it)
        if num_class == 1:
            tree, vals, counters = one_tree(bins, feats, grad, hess, cnt,
                                            fmask, it)
            return score + vals, (tree, counters)
        grown = []
        for cls in range(num_class):
            t, vals, counters = one_tree(bins, feats, grad[:, cls],
                                         hess[:, cls], cnt, fmask, it)
            score = score.at[:, cls].add(vals)
            grown.append((t, counters))
        return score, jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *grown)

    # `score` is donated: the caller hands over its train-score buffer
    # and must treat the passed-in array as consumed (use the returned
    # score'). GBDT.train_many reassigns self.train_score from the
    # result and its fault paths check .is_deleted() before reusing the
    # old buffer — tpulint JIT004 guards the bare-name discipline.
    def program(score, it0, sample_keys, bins, row_state, tables, feats):
        # the block length is sample_keys' leading axis: a static shape,
        # so each distinct length is its own compiled program
        k = sample_keys.shape[0]
        its = jnp.asarray(it0, jnp.int32) + jnp.arange(k, dtype=jnp.int32)
        obj = copy.copy(objective)
        for name, arr in zip(row_names + list(table_names),
                             row_state + tables):
            setattr(obj, name, arr)
        return jax.lax.scan(functools.partial(body, bins, feats, obj),
                            score, (its, sample_keys))

    if mesh is not None:
        # score, bins and row state by rows; trees and their counters
        # out whole
        by_rows = NamedSharding(mesh, P(axis))
        program = shard_map(
            program, mesh=mesh, out_specs=(P(axis), P()), check_vma=False,
            in_specs=(P(axis), P(), P(), P(axis), P(axis), P(), P()))

        def on_mesh(a):
            return jax.device_put(
                jnp.pad(a, (0, row_pad)) if row_pad else a, by_rows)

        row_arrays = [on_mesh(a) for a in row_arrays]
    program = jax.jit(program, donate_argnums=0)
    operands = (bins, tuple(row_arrays), table_arrays)
    # the per-feature tables are arguments too: a value the trace closes
    # over is a literal of the program, and these move with the data (a
    # column whose bin sample holds fewer distinct values has fewer
    # bins), so as literals every dataset of one shape was a program of
    # its own, compiled again
    feature_tables = (num_bins, missing_is_nan, is_cat)

    def arguments(score, it0, *, k: int, sample_keys=None):
        if sample_keys is None:
            sample_keys = jnp.zeros((k, 2), jnp.uint32)
        if mesh is not None and (row_pad or not score.sharding
                                 .is_equivalent_to(by_rows, score.ndim)):
            # a run's first block, or one whose rows do not divide the
            # mesh: every other block takes the last one's score as it
            # came out, on the mesh already
            score = on_mesh(score)
        return (score, it0, sample_keys) + operands + (feature_tables,)

    def run(score, it0, *, k: int, sample_keys=None):
        score, (stacked, counters) = program(*arguments(
            score, it0, k=k, sample_keys=sample_keys))
        return (score[:num_data] if row_pad else score), stacked, counters

    run.program = program
    run.operands = operands
    run.feature_tables = feature_tables
    run.arguments = arguments
    return run
