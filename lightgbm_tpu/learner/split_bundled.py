"""Segmented bundle-space best-split search (EFB fast path).

The expansion design (efb.expand_histograms + split.find_best_splits)
materializes an [S, F, Bmax, 3] tensor per growth pass — at wide F that
tensor dominates the pass. The
reference never expands: FeatureHistogram scans each sub-feature's
offset range of the bundled histogram directly (feature_histogram.hpp
offset scans over feature_group.h:25 ranges; bundling at
dataset.cpp:239-355 FastFeatureBundling).

This is that scan, TPU-first: every bundle position (g, p) hosts at most
one numeric threshold candidate (the EfbScan bijection, efb.py), so one
[S, Fb, Bb] batched computation — a csum along bundle bins, two static
gathers for the segment prefix, and the reconstructed default mass —
evaluates every threshold of every feature with NO expanded tensor.
Categorical features (never multi-bundled; identity columns) run through
the standard scan on a gathered [S, Fc, Bmax] slice.

Gain forms, NaN direction handling, monotone constraints, and min-data
gating mirror split.find_best_splits exactly. Two intended differences
from the expansion baseline:
- summation order (segment csum + default mass vs expanded csum),
  f32-equivalent via Precision.HIGHEST;
- EXACT-tie argmax order: candidates rank by bundle position here vs
  feature-major (f, t) order there — and a multi-bundled feature's
  default-bin threshold is hosted at its segment's LAST position, so
  a gain tie between the default threshold and a later empty-bin
  threshold resolves to the later bin. Ties need exactly equal f32
  gains (same partition), so the chosen SPLIT PARTITION is identical
  either way; only the recorded threshold/feature label can differ.
  The parity tests (test_efb_mxu.py) pass bit-exact on real data.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .split import (BestSplits, SplitHyperParams, leaf_output, leaf_gain,
                    _gain_given_output, _split_gain,
                    _monotone_penalty_factor, find_best_splits)

__all__ = ["find_best_splits_bundled"]


@functools.partial(jax.jit, static_argnames=("hp",))
def find_best_splits_bundled(hist_b: jax.Array, parent_grad: jax.Array,
                             parent_hess: jax.Array,
                             parent_count: jax.Array,
                             parent_output: jax.Array,
                             num_bins: jax.Array,
                             missing_is_nan: jax.Array, is_cat: jax.Array,
                             feature_mask: jax.Array,
                             hp: SplitHyperParams, efb,
                             monotone: jax.Array = None,
                             cons_min: jax.Array = None,
                             cons_max: jax.Array = None,
                             depth: jax.Array = None,
                             rand_bins: jax.Array = None,
                             gain_penalty: jax.Array = None) -> BestSplits:
    """find_best_splits over BUNDLED histograms [S, Fb, Bb, 3].

    Same contract as split.find_best_splits (per-ORIGINAL-feature
    num_bins/missing/is_cat/feature_mask, BestSplits in original feature
    ids) with `efb` an EfbDev whose .scan tables are present.
    """
    t = efb.scan
    s, fb, bb, _ = hist_b.shape
    f = int(num_bins.shape[0])
    bmax = efb.flat_pos.shape[1]
    l1, l2 = hp.lambda_l1, hp.lambda_l2
    P = fb * bb

    bins_r = jnp.arange(bb, dtype=jnp.int32)
    tri = (bins_r[:, None] <= bins_r[None, :]).astype(jnp.float32)
    csum = jnp.einsum("sfbc,bt->sftc", hist_b, tri,
                      precision=jax.lax.Precision.HIGHEST)
    flat_c = csum.reshape(s, P, 3)
    flat_h = hist_b.reshape(s, P, 3)
    # any single column's bin total is the node total (every row lands in
    # exactly one bin of every column) — expand_histograms' convention
    total = jnp.sum(hist_b[:, 0], axis=1)                       # [S, 3]

    fid = t.fid.reshape(P)
    fid_c = jnp.clip(fid, 0, f - 1)
    cand_t = t.cand_t.reshape(P)

    def c_at(idx):                                              # [P] csum
        safe = jnp.clip(idx, 0, P - 1)
        return jnp.where((idx >= 0)[None, :, None], flat_c[:, safe], 0.0)

    seg_sum = c_at(t.seg_hi_flat.reshape(P)) - \
        c_at(t.seg_lo_m1_flat.reshape(P))                       # [S, P, 3]
    dmass = jnp.where(t.is_multi_pos.reshape(P)[None, :, None],
                      total[:, None] - seg_sum, 0.0)
    pre_raw = c_at(t.prefix_flat.reshape(P))
    pre = jnp.where((t.prefix_flat.reshape(P) >= 0)[None, :, None],
                    pre_raw - c_at(t.seg_lo_m1_flat.reshape(P)), 0.0)
    left_nr = pre + jnp.where(t.incl_def.reshape(P)[None, :, None],
                              dmass, 0.0)                       # NaN right
    nan_pos = t.nan_flat.reshape(P)
    nan_stat = jnp.where(
        t.has_nan_pos.reshape(P)[None, :, None],
        jnp.where((nan_pos >= 0)[None, :, None],
                  flat_h[:, jnp.clip(nan_pos, 0, P - 1)], dmass), 0.0)
    left_nl = left_nr + nan_stat                                # NaN left

    # normalize feature_mask to [S, F] then gather per position
    fmask = jnp.broadcast_to(
        feature_mask.astype(jnp.float32).reshape(
            (1, f) if feature_mask.ndim == 1 else (s, f)), (s, f))
    fm_pos = fmask[:, fid_c] * (fid >= 0)                       # [S, P]

    valid = (cand_t >= 0)[None, :] & (fm_pos > 0)               # [S, P]
    if hp.extra_trees and rand_bins is not None:
        t_lim = (num_bins - 2 - missing_is_nan.astype(jnp.int32))[fid_c]
        rsel = rand_bins[:, fid_c] % jnp.maximum(t_lim + 1, 1)[None, :]
        valid = valid & (cand_t[None, :] == rsel)

    tot = jnp.stack([parent_grad, parent_hess, parent_count], -1)
    gain_shift = leaf_gain(parent_grad, parent_hess, l1, l2,
                           hp.max_delta_step)                   # [S]
    min_gain_shift = gain_shift + hp.min_gain_to_split

    mono_pos = monotone[fid_c] if monotone is not None else None

    def eval_option(left):                                      # [S, P, 3]
        right = tot[:, None] - left
        lg, lh, lc = left[..., 0], left[..., 1], left[..., 2]
        rg, rh, rc = right[..., 0], right[..., 1], right[..., 2]
        ok = ((lc >= hp.min_data_in_leaf) & (rc >= hp.min_data_in_leaf) &
              (lh >= hp.min_sum_hessian_in_leaf) &
              (rh >= hp.min_sum_hessian_in_leaf))
        if hp.has_monotone:
            po = parent_output[:, None]
            lout = leaf_output(lg, lh, l1, l2, hp.max_delta_step,
                               hp.path_smooth, lc, po)
            rout = leaf_output(rg, rh, l1, l2, hp.max_delta_step,
                               hp.path_smooth, rc, po)
            lout = jnp.clip(lout, cons_min[:, None], cons_max[:, None])
            rout = jnp.clip(rout, cons_min[:, None], cons_max[:, None])
            mc = mono_pos[None, :]
            violate = ((mc > 0) & (lout > rout)) | \
                      ((mc < 0) & (lout < rout))
            g = _gain_given_output(lg, lh, l1, l2, lout) + \
                _gain_given_output(rg, rh, l1, l2, rout)
            if hp.monotone_penalty > 0:
                pen = _monotone_penalty_factor(depth, hp.monotone_penalty)
                g = jnp.where(mc != 0, g * pen[:, None], g)
            g = jnp.where(violate, -jnp.inf, g)
        else:
            g = _split_gain(lg, lh, lc, rg, rh, rc, l1, l2, hp,
                            parent_output[:, None])
        return jnp.where(ok & valid, g, -jnp.inf)

    gain_nr = eval_option(left_nr)                              # [S, P]
    has_nan_p = t.has_nan_pos.reshape(P)
    gain_nl = jnp.where(has_nan_p[None, :], eval_option(left_nl),
                        -jnp.inf)
    num_gain = jnp.maximum(gain_nr, gain_nl)
    num_gain = jnp.where(num_gain > min_gain_shift[:, None], num_gain,
                         -jnp.inf)
    if gain_penalty is not None:
        num_gain = num_gain - gain_penalty[:, fid_c] * (fid >= 0)

    best_p = jnp.argmax(num_gain, axis=1)                       # [S]
    sel = (jnp.arange(s), best_p)
    num_best_gain = num_gain[sel]
    num_f = fid[best_p]
    num_t = cand_t[best_p]
    chose_na_left = gain_nl[sel] >= gain_nr[sel]
    num_left = jnp.where(chose_na_left[:, None], left_nl[sel],
                         left_nr[sel])                          # [S, 3]

    # per-feature best gain (voting-parallel): scatter-max positions->F
    pf_base = jnp.full((s, f), -jnp.inf)
    per_feature_gain = pf_base.at[:, fid_c].max(
        jnp.where(fid[None, :] >= 0, num_gain, -jnp.inf))
    per_feature_gain = per_feature_gain - gain_shift[:, None]

    # ---------- categorical sub-scan (identity columns; exact) ----------
    fc = int(t.cat_feats.shape[0])
    if hp.has_categorical and fc > 0:
        cf = t.cat_feats
        fp = efb.flat_pos[cf]                                   # [Fc, bmax]
        hist_cat = jnp.where(
            efb.is_valid_pos[cf][None, :, :, None],
            flat_h[:, fp.reshape(-1)].reshape(s, fc, bmax, 3), 0.0)
        bs_cat = find_best_splits(
            hist_cat, parent_grad, parent_hess, parent_count,
            parent_output, num_bins[cf], missing_is_nan[cf],
            jnp.ones(fc, bool), fmask[:, cf], hp,
            monotone=monotone[cf] if monotone is not None else None,
            cons_min=cons_min, cons_max=cons_max, depth=depth,
            rand_bins=rand_bins[:, cf] if rand_bins is not None else None,
            gain_penalty=gain_penalty[:, cf]
            if gain_penalty is not None else None)
        cat_gain = bs_cat.gain + gain_shift                     # undo shift
        cat_better = cat_gain > jnp.where(jnp.isfinite(num_best_gain),
                                          num_best_gain, -jnp.inf)
        cat_better = cat_better & (bs_cat.feature >= 0)
        per_feature_gain = per_feature_gain.at[:, cf].max(
            bs_cat.per_feature_gain)
        best_gain = jnp.where(cat_better, cat_gain, num_best_gain)
        best_f = jnp.where(cat_better, cf[jnp.clip(bs_cat.feature, 0)],
                           num_f)
        best_t = jnp.where(cat_better, bs_cat.threshold_bin, num_t)
        left = jnp.where(
            cat_better[:, None],
            jnp.stack([bs_cat.left_grad, bs_cat.left_hess,
                       bs_cat.left_count], -1), num_left)
        chose_na_left = jnp.where(cat_better, False, chose_na_left)
        cat_bitset = jnp.where(cat_better[:, None], bs_cat.cat_bitset, 0)
        best_is_cat = cat_better
        cat_lout, cat_rout = bs_cat.left_output, bs_cat.right_output
    else:
        best_gain, best_f, best_t = num_best_gain, num_f, num_t
        left = num_left
        w = (bmax + 31) // 32
        cat_bitset = jnp.zeros((s, w), jnp.uint32)
        best_is_cat = jnp.zeros(s, bool)
        cat_lout = cat_rout = jnp.zeros(s, jnp.float32)

    has_split = jnp.isfinite(best_gain)
    lgs, lhs, lcs = left[..., 0], left[..., 1], left[..., 2]
    rgs = parent_grad - lgs
    rhs = parent_hess - lhs
    rcs = parent_count - lcs
    lout = leaf_output(lgs, lhs, l1, l2, hp.max_delta_step,
                       hp.path_smooth, lcs, parent_output)
    rout = leaf_output(rgs, rhs, l1, l2, hp.max_delta_step,
                       hp.path_smooth, rcs, parent_output)
    if hp.has_monotone:
        lout = jnp.clip(lout, cons_min, cons_max)
        rout = jnp.clip(rout, cons_min, cons_max)
    # categorical outputs come from the sub-scan (cat_l2 semantics)
    lout = jnp.where(best_is_cat, cat_lout, lout)
    rout = jnp.where(best_is_cat, cat_rout, rout)

    return BestSplits(
        gain=jnp.where(has_split, best_gain - gain_shift, -jnp.inf),
        feature=jnp.where(has_split, best_f, -1),
        threshold_bin=jnp.maximum(best_t, 0),
        default_left=jnp.where(best_is_cat, False, chose_na_left),
        left_grad=lgs, left_hess=lhs, left_count=lcs,
        left_output=lout, right_output=rout,
        per_feature_gain=per_feature_gain,
        cat_bitset=cat_bitset)
