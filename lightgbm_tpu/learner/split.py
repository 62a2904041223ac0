"""Vectorized best-split search over histograms.

Replaces the reference's per-feature sequential gain scans
(FeatureHistogram::FindBestThresholdSequentially, feature_histogram.hpp:85-270
— a compile-time-specialized template over {L1, max_delta_step, smoothing,
missing-type, NA-direction}) with ONE batched computation over
[slots, features, bins]: cumulative sums along the bin axis, the closed-form
gain at every threshold, NA-left/NA-right evaluated as two masked variants,
and a flat argmax. Categorical splits (feature_histogram.hpp:278-485) use
the one-hot scan for low-cardinality features and the sorted-by-ratio
two-direction scan otherwise, emitting the left set as a bin bitset.

All math follows feature_histogram.hpp:737-860:
  ThresholdL1(s, l1) = sign(s) * max(|s| - l1, 0)
  output  = -ThresholdL1(g, l1) / (h + l2)            (clipped by max_delta_step,
                                                       smoothed toward parent)
  gain(output) = -(2 * ThresholdL1(g, l1) * output + (h + l2) * output^2)
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["SplitHyperParams", "BestSplits", "find_best_splits",
           "leaf_output", "leaf_gain"]


@dataclasses.dataclass(frozen=True)
class SplitHyperParams:
    """Static split-search hyperparameters (subset of Config)."""
    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    min_gain_to_split: float = 0.0
    min_data_in_leaf: int = 20
    min_sum_hessian_in_leaf: float = 1e-3
    max_delta_step: float = 0.0
    path_smooth: float = 0.0
    cat_l2: float = 10.0
    cat_smooth: float = 10.0
    max_cat_threshold: int = 32
    max_cat_to_onehot: int = 4
    min_data_per_group: int = 100
    has_monotone: bool = False     # enables the constrained-output gain path
    monotone_penalty: float = 0.0
    extra_trees: bool = False      # one random threshold per (slot, feature)
    has_categorical: bool = False  # enables the categorical scan paths
    # used-feature indices of the categorical columns, for callers whose
    # histogram holds every used feature in order (the growers pass it on
    # as find_best_splits' `cat_columns`); None: not known
    cat_columns: Optional[Tuple[int, ...]] = None


class BestSplits(NamedTuple):
    """Per-slot best split (reference SplitInfo, split_info.hpp:22)."""
    gain: jax.Array          # [S] split gain (already minus gain_shift)
    feature: jax.Array       # [S] used-feature index, -1 if none
    threshold_bin: jax.Array  # [S] bin t: numerical left iff bin <= t
    default_left: jax.Array  # [S] bool, NaN direction
    left_grad: jax.Array     # [S]
    left_hess: jax.Array
    left_count: jax.Array
    left_output: jax.Array   # [S]
    right_output: jax.Array  # [S]
    per_feature_gain: jax.Array  # [S, F] best gain per feature (for voting)
    cat_bitset: jax.Array    # [S, W] uint32; categorical: bin in set -> left


def _threshold_l1(s, l1):
    return jnp.sign(s) * jnp.maximum(jnp.abs(s) - l1, 0.0)


def leaf_output(g, h, l1, l2, max_delta_step=0.0, path_smooth=0.0,
                count=None, parent_output=None):
    """CalculateSplittedLeafOutput (feature_histogram.hpp:743-764)."""
    ret = -_threshold_l1(g, l1) / (h + l2)
    if max_delta_step > 0:
        ret = jnp.clip(ret, -max_delta_step, max_delta_step)
    if path_smooth > 0 and count is not None and parent_output is not None:
        n_over = count / path_smooth
        ret = ret * n_over / (n_over + 1.0) + parent_output / (n_over + 1.0)
    return ret


def _gain_given_output(g, h, l1, l2, output):
    """GetLeafGainGivenOutput (feature_histogram.hpp:851-860)."""
    sg = _threshold_l1(g, l1)
    return -(2.0 * sg * output + (h + l2) * output * output)


def leaf_gain(g, h, l1, l2, max_delta_step=0.0, path_smooth=0.0,
              count=None, parent_output=None):
    """GetLeafGain (feature_histogram.hpp:826-842)."""
    if max_delta_step <= 0 and path_smooth <= 0:
        sg = _threshold_l1(g, l1)
        return (sg * sg) / (h + l2)
    out = leaf_output(g, h, l1, l2, max_delta_step, path_smooth, count,
                      parent_output)
    return _gain_given_output(g, h, l1, l2, out)


def _split_gain(lg, lh, lc, rg, rh, rc, l1, l2, hp: SplitHyperParams,
                parent_output):
    """GetSplitGains without monotone (feature_histogram.hpp:785-806)."""
    return (leaf_gain(lg, lh, l1, l2, hp.max_delta_step, hp.path_smooth,
                      lc, parent_output) +
            leaf_gain(rg, rh, l1, l2, hp.max_delta_step, hp.path_smooth,
                      rc, parent_output))


def _monotone_penalty_factor(depth: jax.Array, p: float) -> jax.Array:
    """ComputeMonotoneSplitGainPenalty (monotone_constraints.hpp:355-364)."""
    eps = 1e-10
    d = depth.astype(jnp.float32)
    small = 1.0 - p / jnp.exp2(d) + eps
    large = 1.0 - jnp.exp2(p - 1.0 - d) + eps
    out = jnp.where(p <= 1.0, small, large)
    return jnp.where(p >= d + 1.0, eps, out)


@functools.partial(jax.jit, static_argnames=("hp", "cat_columns"))
def find_best_splits(hist: jax.Array, parent_grad: jax.Array,
                     parent_hess: jax.Array, parent_count: jax.Array,
                     parent_output: jax.Array, num_bins: jax.Array,
                     missing_is_nan: jax.Array, is_cat: jax.Array,
                     feature_mask: jax.Array,
                     hp: SplitHyperParams,
                     monotone: jax.Array = None,
                     cons_min: jax.Array = None,
                     cons_max: jax.Array = None,
                     depth: jax.Array = None,
                     rand_bins: jax.Array = None,
                     gain_penalty: jax.Array = None,
                     cat_columns: Optional[Tuple[int, ...]] = None
                     ) -> BestSplits:
    """Find the best split per slot.

    Args:
      hist: [S, F, B, 3] (grad, hess, count) histograms.
      parent_*: [S] node aggregates; parent_output: [S] node output value.
      num_bins: [F] per-feature bin counts (incl. NaN bin when present).
      missing_is_nan: [F] bool, feature has a trailing NaN bin.
      is_cat: [F] bool.
      feature_mask: [F] or [S, F] float/bool — 0 disables a feature
        (feature_fraction / feature-parallel shard / voting selection).
      gain_penalty: optional [S, F] gain subtracted per (slot, feature)
        after threshold selection — the CEGB DeltaGain hook (reference
        SerialTreeLearner::FindBestSplitsFromHistograms subtracting
        CostEfficientGradientBoosting::DetlaGain,
        cost_effective_gradient_boosting.hpp:46-70).
      cat_columns: static; the indices f with is_cat[f], where the caller
        knows them (SplitHyperParams.cat_columns and a histogram over
        every used feature): the categorical search then sorts, gathers
        and scans those columns only. None: every column is searched and
        is_cat masks the result.
    """
    s, f, b, _ = hist.shape
    l1, l2 = hp.lambda_l1, hp.lambda_l2
    bins_r = jnp.arange(b, dtype=jnp.int32)

    # prefix sums along bins as a triangular-matrix contraction: XLA's
    # cumsum lowering is a serial/log-shift chain that measured ~2 orders
    # of magnitude slower than the MXU on this backend (it dominated tree
    # time); Precision.HIGHEST (bf16x6) keeps f32-equivalent accuracy
    tri = (bins_r[:, None] <= bins_r[None, :]).astype(jnp.float32)

    def cumsum_bins(x):                                        # [S,F,B,C]
        return jnp.einsum("sfbc,bt->sftc", x, tri,
                          precision=jax.lax.Precision.HIGHEST)
    # normalize feature_mask to [S, F]
    fmask = jnp.broadcast_to(
        feature_mask.astype(jnp.float32).reshape(
            (1, f) if feature_mask.ndim == 1 else (s, f)), (s, f))

    tot = jnp.stack([parent_grad, parent_hess, parent_count], -1)  # [S, 3]
    tot = tot[:, None, None, :]                                    # [S,1,1,3]

    # gain_shift: unsmoothed closed-form gain of the unsplit node
    # (feature_histogram.hpp:295-301 passes USE_SMOOTHING=false here)
    gain_shift = leaf_gain(parent_grad, parent_hess, l1, l2,
                           hp.max_delta_step)                      # [S]
    min_gain_shift = gain_shift + hp.min_gain_to_split

    # ---------- numerical features ----------
    with jax.named_scope("split.numerical"):
        prefix = cumsum_bins(hist)  # [S,F,B,3]
        nan_idx = jnp.maximum(num_bins - 1, 0)
        nan_sums = jnp.take_along_axis(
            hist, nan_idx[None, :, None, None].astype(jnp.int32),
            axis=2)  # [S,F,1,3]
        nan_sums = jnp.where(missing_is_nan[None, :, None, None], nan_sums,
                             0.0)

        # threshold t valid iff t <= num_bins-2 (-1 more when NaN bin present)
        t_limit = num_bins - 2 - missing_is_nan.astype(jnp.int32)      # [F]
        valid_t = bins_r[None, None, :] <= t_limit[None, :, None]  # [1,F,B]
        valid_t = valid_t & (~is_cat[None, :, None]) & \
            (fmask[:, :, None] > 0)  # [S,F,B]
        if hp.extra_trees and rand_bins is not None:
            # extra-trees: evaluate ONE random threshold per (slot, feature)
            # (reference USE_RAND specialization, feature_histogram.hpp:85)
            valid_t = valid_t & (bins_r[None, None, :] ==
                                 (rand_bins % jnp.maximum(t_limit + 1, 1)
                                  [None, :])[:, :, None])

        def eval_option(left):  # [S,F,B,3]
            right = tot - left
            lg, lh, lc = left[..., 0], left[..., 1], left[..., 2]
            rg, rh, rc = right[..., 0], right[..., 1], right[..., 2]
            ok = ((lc >= hp.min_data_in_leaf) & (rc >= hp.min_data_in_leaf) &
                  (lh >= hp.min_sum_hessian_in_leaf) &
                  (rh >= hp.min_sum_hessian_in_leaf))
            if hp.has_monotone:
                # constrained-output gain path (GetSplitGains USE_MC branch,
                # feature_histogram.hpp:806-824): clamp child outputs to the
                # node's [min, max] constraint, kill order-violating splits
                po = parent_output[:, None, None]
                lout = leaf_output(lg, lh, l1, l2, hp.max_delta_step,
                                   hp.path_smooth, lc, po)
                rout = leaf_output(rg, rh, l1, l2, hp.max_delta_step,
                                   hp.path_smooth, rc, po)
                cmin = cons_min[:, None, None]
                cmax = cons_max[:, None, None]
                lout = jnp.clip(lout, cmin, cmax)
                rout = jnp.clip(rout, cmin, cmax)
                mc = monotone[None, :, None]
                violate = ((mc > 0) & (lout > rout)) | \
                          ((mc < 0) & (lout < rout))
                g = _gain_given_output(lg, lh, l1, l2, lout) + \
                    _gain_given_output(rg, rh, l1, l2, rout)
                if hp.monotone_penalty > 0:
                    pen = _monotone_penalty_factor(depth, hp.monotone_penalty)
                    g = jnp.where(mc != 0, g * pen[:, None, None], g)
                g = jnp.where(violate, -jnp.inf, g)
            else:
                g = _split_gain(lg, lh, lc, rg, rh, rc, l1, l2, hp,
                                parent_output[:, None, None])
            return jnp.where(ok & valid_t, g, -jnp.inf)

        gain_na_right = eval_option(prefix)  # NaN stays right
        gain_na_left = jnp.where(
            missing_is_nan[None, :, None],
            eval_option(prefix + nan_sums), -jnp.inf)  # NaN joins left

    # ---------- categorical ----------
    # FindBestThresholdCategoricalInner (feature_histogram.hpp:278-485).
    # A column of at most max_cat_to_onehot bins is searched one bin
    # against the rest, with the ORIGINAL l2. Any other column: the bins
    # with count >= cat_smooth, sorted by g / (h + cat_smooth), are
    # scanned from both ends for at most min(max_cat_threshold,
    # (used + 1) / 2) steps with l2 + cat_l2 in the gain (gain_shift keeps
    # the original l2 in both). A step's bin joins the left set; a gain is
    # EVALUATED at a step only once the bins added since the last
    # evaluation hold min_data_per_group rows (the reference's
    # cnt_cur_group: a step whose left side is still under
    # min_data_in_leaf or min_sum_hessian_in_leaf adds to the group and
    # evaluates nothing), and the right side keeps at least
    # max(min_data_in_leaf, min_data_per_group) rows. Bin 0 (unseen,
    # negative, NaN) always stays right. For a threshold at step p the left
    # set is the first p + 1 bins in scan direction, emitted as a bin
    # bitset. Counts are the histogram's own (the reference's newer
    # versions estimate them from the hessian sums).
    # Only the first max_cat_threshold bins of either order can enter a
    # left set, so only those are gathered and summed; with `cat_columns`
    # all of this runs over the categorical columns alone.
    cl2 = l2 + hp.cat_l2
    use_onehot_f = num_bins <= hp.max_cat_to_onehot                # [F]
    cat_basic_valid = (bins_r[None, None, :] >= 1) & \
        (bins_r[None, None, :] < num_bins[None, :, None])
    if hp.has_categorical:
        with jax.named_scope("split.categorical"):
            if cat_columns is None:
                cols = np.arange(f)
                hist_c, onehot_c, valid_c = hist, use_onehot_f, \
                    cat_basic_valid
            else:
                cols = np.asarray(cat_columns, np.int32)
                hist_c = hist[:, cols]                          # [S,Fc,B,3]
                onehot_c = use_onehot_f[cols]
                valid_c = cat_basic_valid[:, cols]
            fc = len(cols)
            # column of the categorical block that holds feature f
            col_of = np.zeros(f, np.int32)
            col_of[cols] = np.arange(fc, dtype=np.int32)
            k = min(int(hp.max_cat_threshold), b)      # steps of a scan
            steps_r = jnp.arange(k, dtype=jnp.int32)
            po3 = parent_output[:, None, None]
            # -- one-hot (original l2, feature_histogram.hpp:318-372) --
            lg, lh, lc = hist_c[..., 0], hist_c[..., 1], hist_c[..., 2]
            rg = tot[..., 0] - lg
            rh = tot[..., 1] - lh
            rc = tot[..., 2] - lc
            oh_ok = ((lc >= hp.min_data_in_leaf) &
                     (rc >= hp.min_data_in_leaf) &
                     (lh >= hp.min_sum_hessian_in_leaf) &
                     (rh >= hp.min_sum_hessian_in_leaf))
            onehot_gain = (leaf_gain(lg, lh, l1, l2, hp.max_delta_step,
                                     hp.path_smooth, lc, po3) +
                           leaf_gain(rg, rh, l1, l2, hp.max_delta_step,
                                     hp.path_smooth, rc, po3))
            onehot_gain = jnp.where(oh_ok & valid_c, onehot_gain, -jnp.inf)
            # -- sorted two-direction scan (l2 + cat_l2) --
            sort_ok = valid_c & (lc >= hp.cat_smooth)
            ratio = jnp.where(sort_ok, lg / (lh + hp.cat_smooth), jnp.inf)
            used_bin = jnp.sum(sort_ok, axis=2)                    # [S,Fc]
            max_num_cat = jnp.minimum(hp.max_cat_threshold,
                                      (used_bin + 1) // 2)         # [S,Fc]
            pos_limit = jnp.minimum(used_bin, max_num_cat)[:, :, None]
            min_rc = max(hp.min_data_in_leaf, hp.min_data_per_group)
            tri_k = (steps_r[:, None] <= steps_r[None, :]).astype(
                jnp.float32)

            def scan_dir(top):
                sh = jnp.take_along_axis(
                    hist_c, jnp.minimum(top, b - 1)[..., None],
                    axis=2)                                      # [S,Fc,K,3]
                sp = jnp.einsum("sfbc,bt->sftc", sh, tri_k,
                                precision=jax.lax.Precision.HIGHEST)
                slg, slh, slc = sp[..., 0], sp[..., 1], sp[..., 2]
                srg = tot[..., 0] - slg
                srh = tot[..., 1] - slh
                src = tot[..., 2] - slc
                left_ok = ((slc >= hp.min_data_in_leaf) &
                           (slh >= hp.min_sum_hessian_in_leaf))

                def step(group, x):
                    cnt, may = x
                    group = group + cnt
                    evaluated = may & (group >= hp.min_data_per_group)
                    return jnp.where(evaluated, 0.0, group), evaluated

                _, evaluated = jax.lax.scan(
                    step, jnp.zeros((s, fc), sh.dtype),
                    (jnp.moveaxis(sh[..., 2], 2, 0),
                     jnp.moveaxis(left_ok, 2, 0)))
                ok = (jnp.moveaxis(evaluated, 0, 2) &
                      (steps_r[None, None, :] < pos_limit) &
                      (src >= min_rc) &
                      (srh >= hp.min_sum_hessian_in_leaf))
                g = (leaf_gain(slg, slh, l1, cl2, hp.max_delta_step,
                               hp.path_smooth, slc, po3) +
                     leaf_gain(srg, srh, l1, cl2, hp.max_delta_step,
                               hp.path_smooth, src, po3))
                return jnp.where(ok, g, -jnp.inf), sp

            # ONE stable sort: the used bins come first, ascending; the
            # scan from the high end walks the same order backwards, as
            # the reference does (bins of equal ratio then enter in the
            # opposite order, which a second sort would not give)
            order = jnp.argsort(ratio, axis=2)                   # [S,Fc,B]
            top_a = order[:, :, :k]
            back = used_bin[:, :, None] - 1 - steps_r[None, None, :]
            # a step past the used bins names no bin (b: out of range)
            top_d = jnp.where(back >= 0, jnp.take_along_axis(
                order, jnp.maximum(back, 0), axis=2), b)         # [S,Fc,K]
            gain_a, sp_a = scan_dir(top_a)                       # [S,Fc,K]
            gain_d, sp_d = scan_dir(top_d)
            # the low end keeps a step both ends evaluate alike, as in the
            # reference; where the two ends reach one gain at DIFFERENT
            # steps the earlier step wins here and the low end there
            cat_dir_bwd = gain_d > gain_a                        # [S,Fc,K]
            sorted_gain = jnp.pad(
                jnp.maximum(gain_a, gain_d), ((0, 0), (0, 0), (0, b - k)),
                constant_values=-jnp.inf)                        # [S,Fc,B]
            cat_gain = jnp.where(onehot_c[None, :, None], onehot_gain,
                                 sorted_gain)
            if cat_columns is not None:
                cat_gain = jnp.full((s, f, b), -jnp.inf).at[:, cols].set(
                    cat_gain)
            cat_gain = jnp.where(
                is_cat[None, :, None] & (fmask[:, :, None] > 0) &
                (cat_gain > min_gain_shift[:, None, None]), cat_gain,
                -jnp.inf)
    else:
        cat_gain = jnp.full((s, f, b), -jnp.inf)

    # ---------- combine & argmax ----------
    num_gain = jnp.maximum(gain_na_right, gain_na_left)
    num_gain = jnp.where(num_gain > min_gain_shift[:, None, None],
                         num_gain, -jnp.inf)
    all_gain = jnp.where(is_cat[None, :, None], cat_gain, num_gain)  # [S,F,B]
    if gain_penalty is not None:
        # constant across thresholds of one feature, so the per-feature
        # argmax is unchanged; only cross-feature competition and the
        # stored/selection gain see the penalty (as in the reference)
        all_gain = all_gain - gain_penalty[:, :, None]

    flat = all_gain.reshape(s, f * b)
    best_idx = jnp.argmax(flat, axis=1)                            # [S]
    best_gain = jnp.take_along_axis(flat, best_idx[:, None], 1)[:, 0]
    best_f = (best_idx // b).astype(jnp.int32)
    best_t = (best_idx % b).astype(jnp.int32)
    has_split = jnp.isfinite(best_gain)

    sel = (jnp.arange(s), best_f, best_t)
    chose_na_left = gain_na_left[sel] >= gain_na_right[sel]
    best_is_cat = is_cat[best_f]
    num_left = jnp.where(chose_na_left[:, None], (prefix + nan_sums)[sel],
                         prefix[sel])                              # [S, 3]
    w = (b + 31) // 32
    if hp.has_categorical:
        use_oh = use_onehot_f[best_f]                              # [S]
        # the winner's column and step in the categorical block
        sel_c = (jnp.arange(s), jnp.asarray(col_of)[best_f],
                 jnp.minimum(best_t, k - 1))
        dir_bwd = cat_dir_bwd[sel_c]                               # [S]
        sorted_left = jnp.where(dir_bwd[:, None], sp_d[sel_c], sp_a[sel_c])
        cat_left = jnp.where(use_oh[:, None], hist[sel], sorted_left)
        left = jnp.where(best_is_cat[:, None], cat_left, num_left)
        # best one-hot split uses original l2; sorted uses l2 + cat_l2
        # (feature_histogram.hpp:384,476-489)
        eff_l2 = jnp.where(best_is_cat & ~use_oh, cl2, l2)
        # bin bitset of the left set: one-hot -> {best_t}; sorted -> the
        # first best_t+1 bins in the winning scan direction. Only the best
        # feature's row per slot is needed, so gather its [S, K] steps
        # first and invert those (a bin no step names keeps rank b).
        order_sel = jnp.where(dir_bwd[:, None], top_d[sel_c[:2]],
                              top_a[sel_c[:2]])
        rank_sel = jnp.full((s, b), b, jnp.int32).at[
            jnp.arange(s)[:, None], order_sel].set(
            jnp.broadcast_to(steps_r[None, :], (s, k)),
            mode="drop")                                # bin -> sorted pos
        member_sorted = rank_sel <= best_t[:, None]                # [S, B]
        member_oh = bins_r[None, :] == best_t[:, None]
        member = best_is_cat[:, None] & jnp.where(
            use_oh[:, None], member_oh, member_sorted)
        pad = w * 32 - b
        member_p = jnp.pad(member, ((0, 0), (0, pad))) if pad else member
        weights = jnp.left_shift(
            jnp.uint32(1), jnp.arange(32, dtype=jnp.uint32))
        cat_bitset = jnp.sum(
            member_p.reshape(s, w, 32).astype(jnp.uint32) *
            weights[None, None, :], axis=2, dtype=jnp.uint32)      # [S, W]
    else:
        left = num_left
        eff_l2 = l2
        cat_bitset = jnp.zeros((s, w), jnp.uint32)
    lgs, lhs, lcs = left[..., 0], left[..., 1], left[..., 2]
    rgs = parent_grad - lgs
    rhs = parent_hess - lhs
    rcs = parent_count - lcs
    lout = leaf_output(lgs, lhs, l1, eff_l2, hp.max_delta_step,
                       hp.path_smooth, lcs, parent_output)
    rout = leaf_output(rgs, rhs, l1, eff_l2, hp.max_delta_step,
                       hp.path_smooth, rcs, parent_output)
    if hp.has_monotone:
        lout = jnp.clip(lout, cons_min, cons_max)
        rout = jnp.clip(rout, cons_min, cons_max)

    # per-feature best gain (minus the gain shift) for voting
    per_feature_gain = jnp.max(all_gain, axis=2) - gain_shift[:, None]

    return BestSplits(
        gain=jnp.where(has_split, best_gain - gain_shift, -jnp.inf),
        feature=jnp.where(has_split, best_f, -1),
        threshold_bin=best_t,
        default_left=jnp.where(best_is_cat, False, chose_na_left),
        left_grad=lgs, left_hess=lhs, left_count=lcs,
        left_output=lout, right_output=rout,
        per_feature_gain=per_feature_gain,
        cat_bitset=cat_bitset)
