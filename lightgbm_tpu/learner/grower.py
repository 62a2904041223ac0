"""Tree growth: fully-jitted best-first growth with batched frontier passes.

TPU-native redesign of the reference tree learners:

- SerialTreeLearner (serial_tree_learner.cpp:159-210) grows leaf-wise, one
  split per step, repartitioning row indices per leaf (data_partition.hpp:21).
  CUDASingleGPUTreeLearner (cuda_single_gpu_tree_learner.cpp:108-232) keeps
  that loop on host, with device kernels per phase.
- Here the WHOLE growth loop is one `lax.while_loop` on device with static
  shapes: a `row_node [N]` vector (the device-resident descendant of
  CUDADataPartition's data_index_to_leaf_index, cuda_data_partition.cu:288),
  tree arrays indexed by node id (CUDATree, cuda_tree.hpp:28), and per-pass
  histograms for every frontier node at once.

Growth policy: each pass histograms all not-yet-scanned leaves, scans their
best splits, then applies the top-`budget` splits ranked by gain where
`budget = num_leaves - current`. With `leafwise=True` only the single best
leaf splits per pass — exactly the reference's leaf-wise order
(serial_tree_learner.cpp:188-206); the default batched mode reaches the same
num_leaves in ~depth passes instead of num_leaves-1, trading exact split
order for an O(num_leaves/depth)× reduction in full-data passes — the right
trade on TPU where every pass is one fused scatter over the whole binned
matrix.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..parallel.comm import CommSpec
from .histogram import build_histograms
from .monotone import recompute_bounds
from .split import (BestSplits, SplitHyperParams, _split_gain,
                    find_best_splits, leaf_gain, leaf_output)

__all__ = ["CegbParams", "TreeArrays", "grow_tree"]


@dataclasses.dataclass(frozen=True)
class CegbParams:
    """Static CEGB settings (reference Config cegb_* params,
    cost_effective_gradient_boosting.hpp:23)."""
    tradeoff: float = 1.0
    penalty_split: float = 0.0
    has_coupled: bool = False
    has_lazy: bool = False


class TreeArrays(NamedTuple):
    """Struct-of-arrays tree, sized [max_nodes + 1] (last row = scratch).

    Device-resident counterpart of the reference Tree (include/LightGBM/
    tree.h:25) / CUDATree (cuda_tree.hpp:28). Node 0 is the root; internal
    nodes carry split info, leaves carry output values.
    """
    split_feature: jax.Array   # i32, used-feature idx; -1 for leaf
    threshold_bin: jax.Array   # i32; numerical: left iff bin <= t
    default_left: jax.Array    # bool (NaN direction)
    is_cat: jax.Array          # bool; decision: bin in cat_bitset -> left
    cat_bitset: jax.Array      # [M+1, W] uint32 bin-bitset per node
    left: jax.Array            # i32 child id
    right: jax.Array           # i32 child id
    parent: jax.Array          # i32, -1 for root
    leaf_value: jax.Array      # f32 node output
    sum_grad: jax.Array        # f32
    sum_hess: jax.Array        # f32
    count: jax.Array           # f32
    gain: jax.Array            # f32 split gain of internal nodes
    depth: jax.Array           # i32
    is_leaf: jax.Array         # bool
    num_nodes: jax.Array       # i32 scalar
    num_leaves: jax.Array      # i32 scalar


class _GrowState(NamedTuple):
    tree: TreeArrays
    row_node: jax.Array        # [N] i32
    slot_of_node: jax.Array    # [M+1] i32, -1 = not in frontier this pass
    slot_nodes: jax.Array      # [S] i32 node id per slot; M = inactive
    best: BestSplits           # per-NODE arrays [M+1]
    node_force: jax.Array      # [M+1] forced-split spec idx per node (-1=none)
    forced_ok: jax.Array       # [M+1] forced split of node is applicable
    feat_used: jax.Array       # [F] feature used by any model split (CEGB)
    row_feat_used: jax.Array   # [N, F] row charged for feature (CEGB lazy)
    cons_min: jax.Array        # [M+1] monotone lower bound per node
    cons_max: jax.Array        # [M+1] monotone upper bound per node
    path_mask: jax.Array       # [M+1, F] features used on root path (or [1,1])
    hist_cache: jax.Array      # [M+1, F, B, 3] per-node hists (intermediate/
                               # advanced monotone rescan) or [1] dummy
    pass_idx: jax.Array
    done: jax.Array


def _init_tree(max_nodes: int, root_grad, root_hess, root_count,
               root_value, bitset_words: int = 1) -> TreeArrays:
    m1 = max_nodes + 1
    zf = jnp.zeros(m1, jnp.float32)
    zi = jnp.zeros(m1, jnp.int32)
    zb = jnp.zeros(m1, bool)
    return TreeArrays(
        split_feature=jnp.full(m1, -1, jnp.int32),
        threshold_bin=zi, default_left=zb, is_cat=zb,
        cat_bitset=jnp.zeros((m1, bitset_words), jnp.uint32),
        left=jnp.full(m1, -1, jnp.int32), right=jnp.full(m1, -1, jnp.int32),
        parent=jnp.full(m1, -1, jnp.int32),
        leaf_value=zf.at[0].set(root_value),
        sum_grad=zf.at[0].set(root_grad),
        sum_hess=zf.at[0].set(root_hess),
        count=zf.at[0].set(root_count),
        gain=zf, depth=zi, is_leaf=zb.at[0].set(True),
        num_nodes=jnp.asarray(1, jnp.int32),
        num_leaves=jnp.asarray(1, jnp.int32))


def _merge_gathered_best(gathered: BestSplits) -> BestSplits:
    """Pick the max-gain split across devices per slot (the reference's
    SyncUpGlobalBestSplit max-gain reducer, parallel_tree_learner.h:191-214).
    gathered fields: [D, S]."""
    win = jnp.argmax(gathered.gain, axis=0)                   # [S]

    def pick(name, field):
        if name == "per_feature_gain":  # disjoint shards: elementwise max
            return jnp.max(field, axis=0)
        if field.ndim == 3:             # [D, S, W] bitsets
            return jnp.take_along_axis(field, win[None, :, None], axis=0)[0]
        return jnp.take_along_axis(field, win[None], axis=0)[0]

    return BestSplits(*[pick(f, getattr(gathered, f))
                        for f in BestSplits._fields])


@functools.partial(
    jax.jit,
    static_argnames=("num_leaves", "max_depth", "hp", "leafwise", "bmax",
                     "feature_block", "max_passes", "comm",
                     "interaction_groups", "feature_fraction_bynode",
                     "hist_impl", "partition_impl", "cegb_cfg",
                     "monotone_method"))
def grow_tree(bins: jax.Array, grad: jax.Array, hess: jax.Array,
              cnt_weight: jax.Array, feature_mask: jax.Array,
              num_bins: jax.Array, missing_is_nan: jax.Array,
              is_cat_feat: jax.Array, *, num_leaves: int, max_depth: int,
              hp: SplitHyperParams, leafwise: bool = False, bmax: int,
              feature_block: int = 8, max_passes: int = 0,
              comm: Optional[CommSpec] = None,
              monotone: Optional[jax.Array] = None,
              interaction_groups: Optional[tuple] = None,
              feature_fraction_bynode: float = 1.0,
              rng_key: Optional[jax.Array] = None,
              hist_impl: str = "scatter",
              partition_impl: str = "auto",
              forced: Optional[Tuple[jax.Array, jax.Array, jax.Array,
                                     jax.Array]] = None,
              cegb_cfg: Optional[CegbParams] = None,
              cegb_state: Optional[Tuple[jax.Array, jax.Array, jax.Array]]
              = None, monotone_method: str = "basic", efb=None,
              bins_ft: Optional[jax.Array] = None):
    """Grow one tree. grad/hess must already include bagging/objective
    weights (zeros for out-of-bag rows); `cnt_weight` is 1.0 for in-bag rows
    and 0.0 otherwise so min_data_in_leaf counts sampled rows only.

    With `efb` (an efb.EfbDev), `bins` is the BUNDLED [N, Fb] matrix:
    histograms build in bundle space and are expanded back to original
    features before the scan, and routing translates through the bundle
    tables — every other argument stays in original-feature space
    (reference feature_group.h:25; see efb.py).

    With `comm.hist_agg == "reduce_scatter"` the data/voting histogram
    merge switches from the full psum to the reference's Reduce-Scatter
    (data_parallel_tree_learner.cpp:184-233): each device scans only its
    feature block and a small [D, S] allgather merges the winners. When
    `bins_ft` (the one-time all_to_all transpose from
    distributed/hist_agg.py::build_feature_shards, [N_global, F/world]
    per device) is supplied, the block histograms are built directly from
    all rows — byte-identical to the serial learner; without it, local
    full-width histograms fold through psum_scatter (numerically but not
    bitwise equal).

    Returns (tree, row_node) — row_node maps every row (in- and out-of-bag)
    to its leaf for learner-side score updates (reference
    score_updater.hpp:21-110 AddScore(tree_learner) path).
    """
    n = bins.shape[0]
    f = feature_mask.shape[0] if efb is not None else bins.shape[1]
    hist_bmax = efb.bundle_bmax if efb is not None else bmax
    m = 2 * num_leaves - 1             # max nodes
    s = num_leaves + 1                 # frontier slots (2k children <= S)
    if max_passes <= 0:
        max_passes = num_leaves - 1
    # intermediate/advanced monotone methods: whole-tree bound recompute
    # + all-leaves rescan from a histogram cache each iteration (the
    # vectorized equivalent of the reference's leaves_to_update refresh,
    # monotone_constraints.hpp:558-587). Bounds recomputed at pass start
    # are only sound for one split per pass — leaf-wise is required.
    mono_rescan = monotone_method != "basic" and monotone is not None
    if mono_rescan:
        if not leafwise:
            raise ValueError(
                "monotone_constraints_method=%r requires leaf-wise growth"
                % monotone_method)
        if comm is not None and comm.mode == "voting":
            raise ValueError(
                "monotone_constraints_method=%r is not supported with the "
                "voting tree learner (partial histograms cannot be "
                "cached)" % monotone_method)
        # the all-nodes histogram cache is [M+1, F, bmax, 3] f32 — on wide
        # feature sets this can dwarf HBM (F=1000, 255 leaves, 256 bins
        # ~ 1.5 GB). Warn before allocating so an OOM is attributable.
        cache_bytes = (m + 1) * f * bmax * 3 * 4
        if cache_bytes > (1 << 30):
            from ..utils.log import Log
            Log.warning(
                "monotone_constraints_method=%s allocates a %.1f GiB "
                "histogram cache ([%d nodes, %d features, %d bins]); "
                "reduce num_leaves/max_bin or use "
                "monotone_constraints_method='basic' if this OOMs."
                % (monotone_method, cache_bytes / 2**30, m + 1, f, bmax))
    k_top = num_leaves - 1             # static top-k size
    rows_sharded = comm is not None and comm.mode in ("data", "voting")
    # Reduce-scatter histogram aggregation (distributed/hist_agg.py):
    # device d owns the contiguous feature block [d*Fp, (d+1)*Fp). The
    # exact flavor needs the bins_ft transpose; voting reduces to the
    # exact data-parallel scan only when the top-2k vote selection covers
    # every feature. EFB (bundle-space histograms) and the rescanning
    # monotone methods (whole-tree full-width cache) keep the psum merge.
    rs_mode = (rows_sharded and comm.hist_agg == "reduce_scatter"
               and not mono_rescan and efb is None)
    use_rs_exact = rs_mode and bins_ft is not None and (
        comm.mode == "data" or 2 * comm.top_k >= f)
    use_rs_scatter = rs_mode and not use_rs_exact and comm.mode == "data"
    if use_rs_exact or use_rs_scatter:
        ndev = comm.num_devices
        fp = bins_ft.shape[1] if use_rs_exact else -(-f // ndev)
        fpad = fp * ndev
        myd = jax.lax.axis_index(comm.axis)
    if comm is not None and comm.mode == "feature":
        # deterministic round-robin feature shard (the reference balances by
        # total bin count, feature_parallel_tree_learner.cpp:38-57; round
        # robin gives the same expected balance for quantized features)
        my = jax.lax.axis_index(comm.axis)
        feature_mask = feature_mask * (
            (jnp.arange(f, dtype=jnp.int32) % comm.num_devices) == my
        ).astype(feature_mask.dtype)

    if use_rs_exact:
        # full-row gathers: with the feature-shard transpose this device
        # histograms ALL rows of its features, so grad/hess/cnt (loop
        # constants) gather once up front; summing the gathered arrays IS
        # the serial root reduction — no psum, no blocked-sum skew
        grad_full = jax.lax.all_gather(grad, comm.axis, tiled=True)
        hess_full = jax.lax.all_gather(hess, comm.axis, tiled=True)
        cnt_full = jax.lax.all_gather(cnt_weight, comm.axis, tiled=True)
        root_g = jnp.sum(grad_full)
        root_h = jnp.sum(hess_full)
        root_c = jnp.sum(cnt_full)
    else:
        root_g = jnp.sum(grad)
        root_h = jnp.sum(hess)
        root_c = jnp.sum(cnt_weight)
        if rows_sharded:
            # root grad/hess sums allreduced
            # (data_parallel_tree_learner.cpp:126)
            root_g = jax.lax.psum(root_g, comm.axis)
            root_h = jax.lax.psum(root_h, comm.axis)
            root_c = jax.lax.psum(root_c, comm.axis)
    root_val = leaf_output(root_g, root_h, hp.lambda_l1, hp.lambda_l2,
                           hp.max_delta_step)
    w_cat = (bmax + 31) // 32          # bitset words per node
    tree = _init_tree(m, root_g, root_h, root_c, root_val, bitset_words=w_cat)

    best0 = BestSplits(
        gain=jnp.full(m + 1, -jnp.inf, jnp.float32),
        feature=jnp.full(m + 1, -1, jnp.int32),
        threshold_bin=jnp.zeros(m + 1, jnp.int32),
        default_left=jnp.zeros(m + 1, bool),
        left_grad=jnp.zeros(m + 1, jnp.float32),
        left_hess=jnp.zeros(m + 1, jnp.float32),
        left_count=jnp.zeros(m + 1, jnp.float32),
        left_output=jnp.zeros(m + 1, jnp.float32),
        right_output=jnp.zeros(m + 1, jnp.float32),
        per_feature_gain=jnp.zeros((1, 1), jnp.float32),
        cat_bitset=jnp.zeros((m + 1, w_cat), jnp.uint32))

    use_interaction = interaction_groups is not None and \
        len(interaction_groups) > 0
    if use_interaction:
        # group masks [G, F]; allowed(node) = union of groups that contain
        # the node's full path-feature set (reference ColSampler
        # interaction-constraint filtering, col_sampler.hpp:20)
        import numpy as _np
        gm = _np.zeros((len(interaction_groups), f), _np.bool_)
        for gi, grp in enumerate(interaction_groups):
            for fi in grp:
                if 0 <= fi < f:
                    gm[gi, fi] = True
        group_masks = jnp.asarray(gm)
        path_mask0 = jnp.zeros((m + 1, f), bool)
    else:
        group_masks = None
        path_mask0 = jnp.zeros((1, 1), bool)
    use_bynode = feature_fraction_bynode < 1.0 and rng_key is not None
    k_bynode = max(1, int(round(feature_fraction_bynode * f)))

    # Forced splits (reference SerialTreeLearner::ForceSplits,
    # serial_tree_learner.cpp:459): `forced` carries a flattened spec tree
    # (feature [K], threshold bin [K], left/right child spec idx [K]); the
    # root node is bound to spec 0 and children inherit the spec's subtree
    # indices, reproducing the reference's BFS application order (forced
    # nodes outrank every gain-chosen split in the selection step).
    use_forced = forced is not None
    if use_forced:
        forced_feat, forced_bin, forced_left, forced_right = forced
        n_spec = forced_feat.shape[0]

    # CEGB (cost_effective_gradient_boosting.hpp): per-(node, feature) gain
    # penalty = tradeoff * (penalty_split * n_leaf
    #   + coupled[f] * [f unused in model]
    #   + lazy[f] * #in-bag rows in leaf not yet charged for f)
    use_cegb = cegb_cfg is not None
    if use_cegb:
        # (coupled [F], lazy [F], feat_used [F] bool, row_feat_used [N,F])
        cegb_coupled, cegb_lazy, feat_used0, row_feat_used0 = cegb_state
    else:
        feat_used0 = jnp.zeros(1, bool)
        row_feat_used0 = jnp.zeros((1, 1), bool)

    state = _GrowState(
        tree=tree,
        row_node=jnp.zeros(n, jnp.int32),
        slot_of_node=jnp.full(m + 1, -1, jnp.int32).at[0].set(0),
        slot_nodes=jnp.full(s, m, jnp.int32).at[0].set(0),
        best=best0,
        node_force=(jnp.full(m + 1, -1, jnp.int32).at[0].set(0) if use_forced
                    else jnp.full(1, -1, jnp.int32)),
        forced_ok=jnp.zeros(m + 1 if use_forced else 1, bool),
        feat_used=feat_used0,
        row_feat_used=row_feat_used0,
        cons_min=jnp.full(m + 1, -jnp.inf, jnp.float32),
        cons_max=jnp.full(m + 1, jnp.inf, jnp.float32),
        path_mask=path_mask0,
        hist_cache=(jnp.zeros((m + 1, f, bmax, 3), jnp.float32)
                    if mono_rescan else jnp.zeros(1, jnp.float32)),
        pass_idx=jnp.asarray(0, jnp.int32),
        done=jnp.asarray(False))

    def cond(st: _GrowState):
        return (~st.done) & (st.pass_idx < max_passes)

    def body(st: _GrowState) -> _GrowState:
        tree = st.tree
        # ---- 1. histograms for frontier slots ----
        row_slot = st.slot_of_node[st.row_node]            # [N]
        if use_rs_exact:
            # exact reduce-scatter: histogram ALL rows of THIS device's
            # feature block from the bins_ft transpose — the identical
            # scatter-adds the serial learner performs, restricted to a
            # column block, so the block histogram is byte-equal to the
            # serial one (per-feature accumulation is independent of how
            # columns group into blocks)
            row_slot_full = jax.lax.all_gather(row_slot, comm.axis,
                                               tiled=True)
            hist_sh = build_histograms(
                bins_ft, grad_full, hess_full, row_slot_full, cnt_full,
                num_slots=s, bmax=hist_bmax, feature_block=feature_block)
            hist = None
        elif hist_impl == "pallas":
            from .histogram_pallas import build_histograms_pallas
            hist = build_histograms_pallas(
                bins, grad, hess, cnt_weight, row_slot, num_slots=s,
                bmax=hist_bmax, partition_impl=partition_impl)
        else:
            hist = build_histograms(bins, grad, hess, row_slot, cnt_weight,
                                    num_slots=s, bmax=hist_bmax,
                                    feature_block=feature_block)
        if efb is not None:
            # bundle-space histograms -> per-original-feature histograms;
            # everything downstream (scan, forced splits, monotone cache)
            # is in original-feature space from here on. Linear, so the
            # data-parallel psum below commutes with it.
            from ..efb import expand_histograms
            hist = expand_histograms(hist, efb)
        # ---- 2. best-split scan per slot (with collectives if parallel) ----
        sn = st.slot_nodes                                  # [S] (M=dummy)
        hist_cache = st.hist_cache
        if mono_rescan:
            # cache the (globally merged) frontier histograms per node,
            # then rescan EVERY node with freshly recomputed bounds — the
            # vectorized form of the reference's refresh-and-refind of
            # affected leaves (monotone_constraints.hpp:558 Update ->
            # leaves_to_update -> serial_tree_learner re-find)
            gh = jax.lax.psum(hist, comm.axis) if (
                comm is not None and comm.mode == "data") else hist
            hist_cache = hist_cache.at[sn].set(gh)
            sn = jnp.arange(m + 1, dtype=jnp.int32)
            hist = hist_cache
            s_scan = m + 1
        else:
            s_scan = s

        # per-slot feature mask: bytree fraction x bynode sample x
        # interaction-allowed set (reference ColSampler, col_sampler.hpp:20)
        slot_fmask = jnp.broadcast_to(feature_mask[None, :], (s_scan, f))
        if use_bynode:
            # rescan slots ARE nodes: a fixed key keeps each node's
            # by-node feature sample stable across re-scans (the
            # reference samples once per leaf)
            ku = jax.random.fold_in(rng_key,
                                    1 if mono_rescan else st.pass_idx)
            u = jax.random.uniform(ku, (s_scan, f))
            u = jnp.where(feature_mask[None, :] > 0, u, jnp.inf)
            kth = jnp.sort(u, axis=1)[:, k_bynode - 1][:, None]
            slot_fmask = slot_fmask * (u <= kth)
        if use_interaction:
            pm = st.path_mask[sn]                           # [S, F]
            subset = jnp.all((~pm[:, None, :]) | group_masks[None, :, :],
                             axis=2)                        # [S, G]
            allowed = jnp.einsum("sg,gf->sf", subset.astype(jnp.float32),
                                 group_masks.astype(jnp.float32)) > 0
            allowed = allowed | pm  # path features stay available
            slot_fmask = slot_fmask * allowed
        rand_bins = None
        if hp.extra_trees and rng_key is not None:
            kr = jax.random.fold_in(jax.random.fold_in(rng_key, 7919),
                                    1 if mono_rescan else st.pass_idx)
            rand_bins = jax.random.randint(kr, (s_scan, f), 0, bmax)
        if use_cegb:
            gp = cegb_cfg.tradeoff * cegb_cfg.penalty_split * \
                tree.count[sn][:, None] * jnp.ones((s_scan, f), jnp.float32)
            if cegb_cfg.has_coupled:
                gp += cegb_cfg.tradeoff * cegb_coupled[None, :] * \
                    (~st.feat_used)[None, :].astype(jnp.float32)
            if cegb_cfg.has_lazy:
                rs = st.row_node if mono_rescan else \
                    jnp.where(row_slot < 0, s, row_slot)
                uncharged = jnp.zeros((s_scan + 1, f), jnp.float32) \
                    .at[rs].add((~st.row_feat_used).astype(jnp.float32) *
                                cnt_weight[:, None])[:s_scan]
                if rows_sharded:
                    # the on-demand cost is a sum over ALL of a node's
                    # rows; shards hold disjoint row sets, so merge like
                    # the histogram reduce (every shard must apply the
                    # identical penalty or trees diverge)
                    uncharged = jax.lax.psum(uncharged, comm.axis)
                gp += cegb_cfg.tradeoff * cegb_lazy[None, :] * uncharged
        else:
            gp = None
        if mono_rescan:
            cons_min_s, cons_max_s = recompute_bounds(
                tree, monotone, num_bins, method=monotone_method,
                missing_is_nan=missing_is_nan)
        else:
            cons_min_s, cons_max_s = st.cons_min[sn], st.cons_max[sn]
        mono_kw = dict(monotone=monotone, cons_min=cons_min_s,
                       cons_max=cons_max_s, depth=tree.depth[sn],
                       rand_bins=rand_bins, gain_penalty=gp)

        def scan_hist(h, fm):
            return find_best_splits(
                h, tree.sum_grad[sn], tree.sum_hess[sn], tree.count[sn],
                tree.leaf_value[sn], num_bins, missing_is_nan, is_cat_feat,
                fm, hp, cat_columns=hp.cat_columns, **mono_kw)

        if comm is None or (mono_rescan and comm.mode == "data"):
            bs = scan_hist(hist, slot_fmask)  # cache already merged
        elif use_rs_exact or use_rs_scatter:
            # Reduce-Scatter scan (data_parallel_tree_learner.cpp:184-233):
            # scan ONLY this device's feature block, then merge the [D, S]
            # winners through a small allgather — the wire moves each
            # histogram byte once instead of world times. The exact
            # flavor's hist_sh is already the global block histogram; the
            # scatter flavor folds full-width partials here.
            if use_rs_exact:
                # Scan at the SERIAL operand shape: the best-split prefix
                # sum lowers to a GEMM whose rounding depends on the
                # operand width ([S,Fp,B,C] vs [S,F,B,C] pick different
                # kernel tilings), so a narrow block scan drifts from the
                # serial scan by ulps. GEMM output rows are independent of
                # each other, so embedding the block at its global column
                # offset in a zero tensor of the serial shape makes the
                # owned columns' results bit-equal to serial; the
                # ownership mask hides the zero columns, and the argmax
                # merge below ties to the lowest device = lowest feature
                # id, matching the serial first-max tie-break.
                full = jnp.zeros((hist_sh.shape[0], fpad) + hist_sh.shape[2:],
                                 hist_sh.dtype)
                full = jax.lax.dynamic_update_slice(
                    full, hist_sh, (0, myd * fp, 0, 0))
                own = ((jnp.arange(f) >= myd * fp) &
                       (jnp.arange(f) < (myd + 1) * fp))
                local = scan_hist(
                    full[:, :f],
                    slot_fmask * own[None, :].astype(slot_fmask.dtype))
            else:
                from ..distributed.hist_agg import reduce_scatter_hist
                hist_sh = reduce_scatter_hist(
                    jnp.pad(hist, ((0, 0), (0, fpad - f), (0, 0), (0, 0))),
                    comm.axis)

                def shard1(a, fill):
                    pad = jnp.full(fpad - f, fill, a.dtype)
                    return jax.lax.dynamic_slice_in_dim(
                        jnp.concatenate([a, pad]), myd * fp, fp)

                def shard2(a, fill):
                    pad = jnp.full((a.shape[0], fpad - f), fill, a.dtype)
                    return jax.lax.dynamic_slice_in_dim(
                        jnp.concatenate([a, pad], axis=1), myd * fp, fp,
                        axis=1)

                # padded tail columns scan as masked-out single-bin
                # features; block-local winner features translate back to
                # global ids before the merge
                mono_kw_sh = dict(
                    monotone=(shard1(monotone, 0) if monotone is not None
                              else None),
                    cons_min=cons_min_s, cons_max=cons_max_s,
                    depth=tree.depth[sn],
                    rand_bins=(shard2(rand_bins, 0) if rand_bins is not None
                               else None),
                    gain_penalty=(shard2(gp, 0.0) if gp is not None
                                  else None))
                local = find_best_splits(
                    hist_sh, tree.sum_grad[sn], tree.sum_hess[sn],
                    tree.count[sn], tree.leaf_value[sn],
                    shard1(num_bins, 1), shard1(missing_is_nan, False),
                    shard1(is_cat_feat, False), shard2(slot_fmask, 0), hp,
                    **mono_kw_sh)
                local = local._replace(feature=jnp.where(
                    local.feature >= 0, local.feature + myd * fp,
                    local.feature))
            gathered = BestSplits(*[
                jax.lax.all_gather(getattr(local, fld), comm.axis)
                for fld in BestSplits._fields])
            bs = _merge_gathered_best(gathered)
        elif comm.mode == "data":
            # histogram merge == the ReduceScatter of
            # data_parallel_tree_learner.cpp:184-186; psum lets every device
            # scan all features (no best-split sync round needed after)
            bs = scan_hist(jax.lax.psum(hist, comm.axis), slot_fmask)
        elif comm.mode == "feature":
            # local scan over this device's feature shard, then global
            # max-gain sync (feature_parallel_tree_learner.cpp:58-84)
            local = scan_hist(hist, slot_fmask)
            gathered = BestSplits(*[
                jax.lax.all_gather(getattr(local, fld), comm.axis)
                for fld in BestSplits._fields])
            bs = _merge_gathered_best(gathered)
        else:  # voting (PV-Tree, voting_parallel_tree_learner.cpp)
            # local scan with constraints scaled down by num_machines
            # (voting_parallel_tree_learner.cpp:62-63)
            hp_local = dataclasses.replace(
                hp,
                min_data_in_leaf=max(1, hp.min_data_in_leaf //
                                     comm.num_devices),
                min_sum_hessian_in_leaf=hp.min_sum_hessian_in_leaf /
                comm.num_devices)
            local = find_best_splits(
                hist, tree.sum_grad[sn] / comm.num_devices,
                tree.sum_hess[sn] / comm.num_devices,
                tree.count[sn] / comm.num_devices,
                tree.leaf_value[sn], num_bins, missing_is_nan, is_cat_feat,
                slot_fmask, hp_local, **mono_kw)
            k_vote = min(comm.top_k, f)
            _, vote_idx = jax.lax.top_k(local.per_feature_gain, k_vote)
            votes = jnp.zeros((s, f), jnp.float32)
            votes = jax.vmap(lambda v, i: v.at[i].add(1.0))(votes, vote_idx)
            gvotes = jax.lax.psum(votes, comm.axis)
            # global top-2k selection per slot; aggregate only those columns
            k_sel = min(2 * comm.top_k, f)
            _, sel_idx = jax.lax.top_k(gvotes, k_sel)
            sel_mask = jnp.zeros((s, f), jnp.float32)
            sel_mask = jax.vmap(
                lambda v, i: v.at[i].set(1.0))(sel_mask, sel_idx)
            hist_sel = hist * sel_mask[:, :, None, None]
            ghist = jax.lax.psum(hist_sel, comm.axis)
            bs = scan_hist(ghist, sel_mask * slot_fmask)
        if use_forced:
            # override gain-chosen splits on forced nodes with the spec's
            # (feature, threshold); stats gathered from the histogram like
            # FeatureHistogram::GatherInfoForThreshold
            # (feature_histogram.hpp:862+)
            nf_slot = st.node_force[sn]                     # [S]
            has_f = (nf_slot >= 0) & (sn < m)
            sp = jnp.clip(nf_slot, 0, n_spec - 1)
            ff = jnp.clip(forced_feat[sp], 0, f - 1)        # [S]
            fb = forced_bin[sp]
            if use_rs_exact or use_rs_scatter:
                # only the feature's owner holds its block histogram; the
                # psum of the single nonzero contribution is an exact copy
                owned = (ff >= myd * fp) & (ff < (myd + 1) * fp)
                lff = jnp.clip(ff - myd * fp, 0, fp - 1)
                hsel = jnp.take_along_axis(
                    hist_sh, lff[:, None, None, None], axis=1)[:, 0]
                hsel = hsel * owned[:, None, None].astype(hsel.dtype)
                hsel = jax.lax.psum(hsel, comm.axis)
            else:
                hsel = jnp.take_along_axis(
                    hist, ff[:, None, None, None], axis=1)[:, 0]  # [S,B,3]
                if rows_sharded and not mono_rescan:  # cache merged
                    hsel = jax.lax.psum(hsel, comm.axis)
            lmask = (jnp.arange(hsel.shape[1])[None, :] <=
                     fb[:, None]).astype(hsel.dtype)
            lg = jnp.sum(hsel[..., 0] * lmask, axis=1)
            lh = jnp.sum(hsel[..., 1] * lmask, axis=1)
            lc = jnp.sum(hsel[..., 2] * lmask, axis=1)
            pg, ph, pc = tree.sum_grad[sn], tree.sum_hess[sn], tree.count[sn]
            pout = tree.leaf_value[sn]
            rg_, rh_, rc_ = pg - lg, ph - lh, pc - lc
            l1, l2 = hp.lambda_l1, hp.lambda_l2
            shift = leaf_gain(pg, ph, l1, l2, hp.max_delta_step,
                              hp.path_smooth, pc, pout)
            fgain = _split_gain(lg, lh, lc, rg_, rh_, rc_, l1, l2, hp,
                                pout) - shift
            lout = leaf_output(lg, lh, l1, l2, hp.max_delta_step,
                               hp.path_smooth, lc, pout)
            rout = leaf_output(rg_, rh_, l1, l2, hp.max_delta_step,
                               hp.path_smooth, rc_, pout)
            valid = has_f & (lc > 0) & (rc_ > 0) & (forced_feat[sp] >= 0)
            bs = bs._replace(
                gain=jnp.where(valid, fgain, bs.gain),
                feature=jnp.where(valid, ff, bs.feature),
                threshold_bin=jnp.where(valid, fb, bs.threshold_bin),
                default_left=jnp.where(valid, False, bs.default_left),
                left_grad=jnp.where(valid, lg, bs.left_grad),
                left_hess=jnp.where(valid, lh, bs.left_hess),
                left_count=jnp.where(valid, lc, bs.left_count),
                left_output=jnp.where(valid, lout, bs.left_output),
                right_output=jnp.where(valid, rout, bs.right_output),
                cat_bitset=jnp.where(valid[:, None], jnp.uint32(0),
                                     bs.cat_bitset))
            forced_ok = st.forced_ok.at[sn].set(valid).at[m].set(False)
        else:
            forced_ok = st.forced_ok
        # scatter slot results into per-node best arrays (dummy -> row m)
        best = BestSplits(*[
            getattr(st.best, fld).at[sn].set(getattr(bs, fld))
            if fld != "per_feature_gain" else st.best.per_feature_gain
            for fld in BestSplits._fields])
        # ---- 3. choose splits: top-budget by gain ----
        eligible = tree.is_leaf & jnp.isfinite(best.gain) & (best.gain > 0)
        if use_forced:
            # forced nodes split regardless of gain sign/threshold and
            # outrank all gain-chosen candidates in the top-k selection
            eligible = tree.is_leaf & jnp.isfinite(best.gain) & \
                ((best.gain > 0) | forced_ok)
        if max_depth > 0:
            eligible &= tree.depth < max_depth
        gains = jnp.where(eligible[:m], best.gain[:m], -jnp.inf)
        if use_forced:
            gains = jnp.where(eligible[:m] & forced_ok[:m],
                              1e30 + best.gain[:m], gains)
        budget = num_leaves - tree.num_leaves
        k_allowed = jnp.minimum(jnp.asarray(1 if leafwise else k_top),
                                budget)
        top_vals, top_idx = jax.lax.top_k(gains, k_top)
        take = (jnp.arange(k_top) < k_allowed) & jnp.isfinite(top_vals)
        split_mask = jnp.zeros(m + 1, bool).at[top_idx].set(take)
        split_mask = split_mask.at[m].set(False)
        k = jnp.sum(split_mask.astype(jnp.int32))

        # ---- 4. apply splits ----
        order = jnp.cumsum(split_mask.astype(jnp.int32)) - 1   # [M+1]
        child_l = jnp.where(split_mask, tree.num_nodes + 2 * order, m)
        child_r = jnp.where(split_mask, tree.num_nodes + 2 * order + 1, m)
        nodes = jnp.arange(m + 1, dtype=jnp.int32)

        rg = tree.sum_grad - best.left_grad
        rh = tree.sum_hess - best.left_hess
        rc = tree.count - best.left_count
        feat = best.feature
        new_tree = tree._replace(
            split_feature=jnp.where(split_mask, feat, tree.split_feature),
            threshold_bin=jnp.where(split_mask, best.threshold_bin,
                                    tree.threshold_bin),
            default_left=jnp.where(split_mask, best.default_left,
                                   tree.default_left),
            is_cat=jnp.where(split_mask,
                             is_cat_feat[jnp.clip(feat, 0, f - 1)],
                             tree.is_cat),
            cat_bitset=jnp.where(split_mask[:, None], best.cat_bitset,
                                 tree.cat_bitset),
            left=jnp.where(split_mask, child_l, tree.left),
            right=jnp.where(split_mask, child_r, tree.right),
            gain=jnp.where(split_mask, best.gain, tree.gain),
            is_leaf=tree.is_leaf & ~split_mask,
            num_nodes=tree.num_nodes + 2 * k,
            num_leaves=tree.num_leaves + k)
        # children: scatter at child ids (row m is scratch)
        def scat(arr, lv, rv):
            return arr.at[child_l].set(lv).at[child_r].set(rv)
        new_tree = new_tree._replace(
            parent=scat(new_tree.parent, nodes, nodes),
            leaf_value=scat(new_tree.leaf_value, best.left_output,
                            best.right_output),
            sum_grad=scat(new_tree.sum_grad, best.left_grad, rg),
            sum_hess=scat(new_tree.sum_hess, best.left_hess, rh),
            count=scat(new_tree.count, best.left_count, rc),
            depth=scat(new_tree.depth, tree.depth + 1, tree.depth + 1),
            is_leaf=scat(new_tree.is_leaf, split_mask, split_mask),
            split_feature=scat(new_tree.split_feature,
                               jnp.full(m + 1, -1, jnp.int32),
                               jnp.full(m + 1, -1, jnp.int32)),
            left=scat(new_tree.left, jnp.full(m + 1, -1, jnp.int32),
                      jnp.full(m + 1, -1, jnp.int32)),
            right=scat(new_tree.right, jnp.full(m + 1, -1, jnp.int32),
                       jnp.full(m + 1, -1, jnp.int32)))
        # reset best-split state of new children
        new_best = best._replace(
            gain=scat(best.gain, jnp.full(m + 1, -jnp.inf, jnp.float32),
                      jnp.full(m + 1, -jnp.inf, jnp.float32)))
        if use_forced:
            # children of a forced node inherit the spec's subtree
            nf = st.node_force
            spx = jnp.clip(nf, 0, n_spec - 1)
            # inherit only when the forced split itself was applied; a node
            # that fell back to a gain-chosen split stops forcing (the
            # reference stops its BFS when a forced split is inapplicable)
            inherit = split_mask & (nf >= 0) & forced_ok
            node_force = scat(nf,
                              jnp.where(inherit, forced_left[spx], -1),
                              jnp.where(inherit, forced_right[spx], -1))
            zb_ = jnp.zeros(m + 1, bool)
            forced_ok = scat(forced_ok, zb_, zb_)
        else:
            node_force = st.node_force
        if use_cegb and cegb_cfg.has_coupled:
            feat_used = st.feat_used.at[jnp.clip(feat, 0, f - 1)].max(
                split_mask)
        else:
            feat_used = st.feat_used

        # monotone bound propagation (basic method: after a split on a
        # monotone feature, mid = (l_out + r_out)/2 caps the increasing
        # side and floors the other — monotone_constraints.hpp
        # BasicLeafConstraints::UpdateConstraints)
        if hp.has_monotone and not mono_rescan:
            mcf = monotone[jnp.clip(feat, 0, f - 1)]
            mid = (best.left_output + best.right_output) * 0.5
            pmin, pmax = st.cons_min, st.cons_max
            lmin = jnp.where(mcf < 0, jnp.maximum(pmin, mid), pmin)
            lmax = jnp.where(mcf > 0, jnp.minimum(pmax, mid), pmax)
            rmin = jnp.where(mcf > 0, jnp.maximum(pmin, mid), pmin)
            rmax = jnp.where(mcf < 0, jnp.minimum(pmax, mid), pmax)
            cons_min = scat(st.cons_min, lmin, rmin)
            cons_max = scat(st.cons_max, lmax, rmax)
        else:
            # intermediate/advanced recompute bounds from the whole tree
            # at every pass start; the incremental arrays stay unused
            cons_min, cons_max = st.cons_min, st.cons_max
        if use_interaction:
            fsel = (jnp.arange(f)[None, :] ==
                    jnp.clip(feat, 0, f - 1)[:, None]) & \
                split_mask[:, None]                        # [M+1, F]
            child_pm = st.path_mask | fsel
            path_mask = st.path_mask.at[child_l].set(child_pm) \
                .at[child_r].set(child_pm)
        else:
            path_mask = st.path_mask

        # ---- 5. frontier slots for the children ----
        slot_l = jnp.where(split_mask, 2 * order, s)
        slot_r = jnp.where(split_mask, 2 * order + 1, s)
        slot_nodes = jnp.full(s + 1, m, jnp.int32) \
            .at[slot_l].set(jnp.where(split_mask, child_l, m)) \
            .at[slot_r].set(jnp.where(split_mask, child_r, m))[:s]
        slot_of_node = jnp.full(m + 1, -1, jnp.int32) \
            .at[child_l].set(jnp.where(split_mask, slot_l, -1)) \
            .at[child_r].set(jnp.where(split_mask, slot_r, -1)) \
            .at[m].set(-1)

        # ---- 6. route rows through the new splits ----
        pnode = st.row_node
        pm = split_mask[pnode]                               # [N]
        pf = jnp.clip(feat[pnode], 0, f - 1)
        if efb is not None:
            from ..efb import route_bins
            binv = route_bins(bins, pf, efb)
        else:
            binv = jnp.take_along_axis(bins, pf[:, None], axis=1)[:, 0] \
                .astype(jnp.int32)
        thr = best.threshold_bin[pnode]
        isc = is_cat_feat[pf]
        is_nan_bin = missing_is_nan[pf] & (binv == num_bins[pf] - 1)
        bitw = best.cat_bitset[pnode, binv // 32]                  # [N]
        in_set = ((bitw >> (binv % 32).astype(jnp.uint32)) &
                  jnp.uint32(1)) == 1
        go_left = jnp.where(
            isc, in_set,
            jnp.where(is_nan_bin, best.default_left[pnode], binv <= thr))
        row_node = jnp.where(
            pm, jnp.where(go_left, child_l[pnode], child_r[pnode]), pnode)
        if use_cegb and cegb_cfg.has_lazy:
            # rows in a just-split node are now charged for its feature
            # (CalculateOndemandCosts marking, the reference's
            # is_feature_used_ per-datapoint flags)
            row_feat_used = st.row_feat_used.at[jnp.arange(n), pf].max(pm)
        else:
            row_feat_used = st.row_feat_used

        done = (k == 0) | (new_tree.num_leaves >= num_leaves)
        return _GrowState(new_tree, row_node, slot_of_node, slot_nodes,
                          new_best, node_force, forced_ok, feat_used,
                          row_feat_used, cons_min, cons_max, path_mask,
                          hist_cache, st.pass_idx + 1, done)

    final = jax.lax.while_loop(cond, body, state)
    if use_cegb:
        return final.tree, final.row_node, (final.feat_used,
                                            final.row_feat_used)
    return final.tree, final.row_node
