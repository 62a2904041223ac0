"""Fused best-split scan as a single Pallas kernel.

The XLA formulation in split.py (find_best_splits) is ~50 small
elementwise/reduce ops over [S, F, B] tensors; on this backend each op is
a separate kernel launch and the launch overhead dominates tree time
(measured ~275 ms/tree of the 498 ms total at the Higgs bench config —
vs ~15 ms of actual compute+bandwidth). This kernel is the TPU analog of
the reference's CUDABestSplitFinder (cuda_best_split_finder.cu:603
FindBestSplitsForLeafKernel): one launch scans a block of slots end to
end in VMEM — prefix sums along bins via a triangular MXU contraction,
the exact gain forms of split.py (shared helpers), NaN-direction
two-option scan, basic monotone clipping, and the per-slot argmax.

Scope (the grower falls back to find_best_splits outside it):
numerical features only (no categorical sorted scan), no extra_trees
random thresholds, no CEGB gain penalty, no per-feature voting gains.
Bit-parity with find_best_splits is regression-tested: same gain math,
same flat (feature*B + bin) argmax tie-breaking, same
NaN-direction choice (na_left wins ties).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .split import (BestSplits, SplitHyperParams, _gain_given_output,
                    _monotone_penalty_factor, _split_gain, leaf_gain,
                    leaf_output)

__all__ = ["find_best_splits_kernel", "kernel_supports"]

_COMPILER_PARAMS = pltpu.CompilerParams(
    vmem_limit_bytes=100 * 1024 * 1024)

# per-slot output columns (selection only; gains/outputs recomputed in
# XLA from the picked sums — see kernel tail comment)
_O_HAS = 0      # has_split (0/1)
_O_FEAT = 1     # best feature idx (f32; -1 if none)
_O_BIN = 2      # best threshold bin (f32)
_O_NAL = 3      # chose NaN-left direction (0/1)
_O_LGR = 4      # left grad sum, NaN-right option
_O_LHR = 5
_O_LCR = 6
_O_LGL = 7      # left sums, NaN-left option
_O_LHL = 8
_O_LCL = 9
_N_OUT = 16     # padded


def kernel_supports(hp: SplitHyperParams) -> bool:
    """Whether the fused scan kernel covers this hyperparameter set."""
    return not hp.has_categorical and not hp.extra_trees


def _scan_kernel(sb: int, f: int, b: int, hp: SplitHyperParams,
                 has_monotone: bool):
    l1, l2 = hp.lambda_l1, hp.lambda_l2

    def kernel(hist_ref, parent_ref, fmask_ref, feat_tbl_ref, mono_ref,
               out_ref):
        # hist block [sb, 3, F, B] (channel-major for clean lane layout)
        hist = hist_ref[0].reshape(sb, 3, f, b)
        parent = parent_ref[:]                   # [sb, 8]: g h c out mn mx
        def pcol(c):
            # slice + expand_dims (the fused `[:, c:c+1, None]` indexing
            # lowers to an unsupported Mosaic gather)
            return jnp.expand_dims(parent[:, c:c + 1], 2)    # [sb, 1, 1]

        pg = pcol(0)
        ph = pcol(1)
        pc = pcol(2)
        po = pcol(3)

        # prefix sums along bins: [sb*3*F, B] @ tri[B, B] on the MXU with
        # the f32 bf16x6 decomposition (exact enough for f64-free parity
        # with jnp.cumsum; same contraction split.py uses)
        iota_b = jax.lax.broadcasted_iota(jnp.int32, (b, b), 0)
        iota_bt = jax.lax.broadcasted_iota(jnp.int32, (b, b), 1)
        # where() instead of bool-cast, f32 iotas instead of i32->f32
        # casts: Mosaic rejects sitofp on these layouts
        tri = jnp.where(iota_b <= iota_bt, jnp.float32(1.0),
                        jnp.float32(0.0))
        flat = hist.reshape(sb * 3 * f, b)
        prefix = jax.lax.dot_general(
            flat, tri, dimension_numbers=(((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32).reshape(sb, 3, f, b)

        feat_tbl = feat_tbl_ref[:]               # [F, 8]
        num_bins = jnp.expand_dims(feat_tbl[:, 0:1], 0)      # [1, F, 1]
        m_nan = jnp.expand_dims(feat_tbl[:, 1:2], 0) > 0.5
        fmask = fmask_ref[:].reshape(sb, f)[:, :, None] > 0

        # 2-D iota + cast (route-kernel-proven pattern), then expand:
        # Mosaic supports neither 3-D f32 iota nor some 3-D sitofp layouts
        bins_r = jnp.expand_dims(
            jax.lax.broadcasted_iota(jnp.int32, (f, b), 1)
            .astype(jnp.float32), 0)                          # [1, F, B]
        # NaN bin sums (last numeric bin when missing_is_nan)
        nan_pos = jnp.maximum(num_bins - 1.0, 0.0)
        is_nan_bin = (bins_r == nan_pos) & m_nan
        h_g, h_h, h_c = hist[:, 0], hist[:, 1], hist[:, 2]    # [sb, F, B]
        nan_g = jnp.sum(jnp.where(is_nan_bin, h_g, 0.0), axis=2,
                        keepdims=True)
        nan_h = jnp.sum(jnp.where(is_nan_bin, h_h, 0.0), axis=2,
                        keepdims=True)
        nan_c = jnp.sum(jnp.where(is_nan_bin, h_c, 0.0), axis=2,
                        keepdims=True)

        t_limit = num_bins - 2.0 - jnp.where(m_nan, 1.0, 0.0)
        valid_t = (bins_r <= t_limit) & fmask    # [sb, F, B]

        gain_shift3 = leaf_gain(pg, ph, l1, l2,
                                hp.max_delta_step)            # [sb, 1, 1]
        min_shift = gain_shift3 + hp.min_gain_to_split

        if has_monotone:
            mono = jnp.expand_dims(mono_ref[:][:, 0:1], 0)  # [1, F, 1]
            cmin = pcol(4)
            cmax = pcol(5)

        def eval_opt(lg, lh, lc):
            rg = pg - lg
            rh = ph - lh
            rc = pc - lc
            ok = ((lc >= hp.min_data_in_leaf) &
                  (rc >= hp.min_data_in_leaf) &
                  (lh >= hp.min_sum_hessian_in_leaf) &
                  (rh >= hp.min_sum_hessian_in_leaf))
            if has_monotone:
                lout = leaf_output(lg, lh, l1, l2, hp.max_delta_step,
                                   hp.path_smooth, lc, po)
                rout = leaf_output(rg, rh, l1, l2, hp.max_delta_step,
                                   hp.path_smooth, rc, po)
                lout = jnp.clip(lout, cmin, cmax)
                rout = jnp.clip(rout, cmin, cmax)
                violate = ((mono > 0) & (lout > rout)) | \
                          ((mono < 0) & (lout < rout))
                g = _gain_given_output(lg, lh, l1, l2, lout) + \
                    _gain_given_output(rg, rh, l1, l2, rout)
                if hp.monotone_penalty > 0:
                    depth = pcol(6)
                    pen = _monotone_penalty_factor(depth,
                                                   hp.monotone_penalty)
                    g = jnp.where(mono != 0, g * pen, g)
                g = jnp.where(violate, -jnp.inf, g)
            else:
                g = _split_gain(lg, lh, lc, rg, rh, rc, l1, l2, hp, po)
            return jnp.where(ok & valid_t, g, -jnp.inf)

        g_right = eval_opt(prefix[:, 0], prefix[:, 1], prefix[:, 2])
        g_left = jnp.where(
            m_nan, eval_opt(prefix[:, 0] + nan_g, prefix[:, 1] + nan_h,
                            prefix[:, 2] + nan_c), -jnp.inf)
        combined = jnp.maximum(g_right, g_left)
        combined = jnp.where(combined > min_shift, combined, -jnp.inf)

        # hierarchical argmax (Mosaic cannot reshape the lane dim into
        # [F, B]): feature winner by per-feature max, then bin winner
        # within it, both as min-index-achieving-max selects (Mosaic's
        # argmax/isfinite lowerings emit unsupported casts). First-max-
        # wins at each stage reproduces split.py's flat (f*B + b) argmax
        # tie order exactly.
        neg_inf = jnp.float32(-jnp.inf)
        big_idx = jnp.float32(1e9)
        iota_f2 = jax.lax.broadcasted_iota(jnp.int32, (sb, f), 1)
        iota_ff = iota_f2.astype(jnp.float32)                 # [sb, F]
        per_f = jnp.max(combined, axis=2)                     # [sb, F]
        fmax = jnp.max(per_f, axis=1, keepdims=True)          # [sb, 1]
        bf = jnp.min(jnp.where(per_f == fmax, iota_ff, big_idx),
                     axis=1, keepdims=True)                   # [sb, 1] f32
        sel_f2 = jnp.where(iota_ff == bf, jnp.float32(1.0),
                           jnp.float32(0.0))                  # [sb, F]
        sel_f = jnp.expand_dims(sel_f2, 2) > 0.5              # [sb, F, 1]

        # everything per-slot from here stays 2-D [sb, 1]: Mosaic 1-D
        # vector casts/selects are unsupported (same as the route kernel)
        def frow_max(x):                                      # -> [sb, B]
            return jnp.max(jnp.where(sel_f, x, neg_inf), axis=1)

        def frow_sum(x):                                      # -> [sb, B]
            return jnp.sum(jnp.where(sel_f, x, 0.0), axis=1)

        rowg = frow_max(combined)
        iota_b2 = jax.lax.broadcasted_iota(jnp.int32, (sb, b), 1)
        iota_bf = iota_b2.astype(jnp.float32)
        bmax_v = jnp.max(rowg, axis=1, keepdims=True)
        bt = jnp.min(jnp.where(rowg == bmax_v, iota_bf, big_idx),
                     axis=1, keepdims=True)                   # [sb, 1] f32
        sel_b = iota_bf == bt                                 # [sb, B]

        def pick(x):                                          # -> [sb, 1]
            return jnp.sum(jnp.where(sel_b, frow_sum(x), 0.0), axis=1,
                           keepdims=True)

        def pick_gain(x):                                     # -> [sb, 1]
            return jnp.max(jnp.where(sel_b, frow_max(x), neg_inf),
                           axis=1, keepdims=True)

        best_gain = pick_gain(combined)
        # isfinite lowers through unsupported casts; gains are either
        # finite or -inf by construction
        has_split = best_gain > jnp.float32(-3e38)

        na_left = pick_gain(g_left) >= pick_gain(g_right)     # [sb, 1]
        lg_r = pick(prefix[:, 0])
        lh_r = pick(prefix[:, 1])
        lc_r = pick(prefix[:, 2])
        nan_gb = jnp.broadcast_to(nan_g, (sb, f, b))
        nan_hb = jnp.broadcast_to(nan_h, (sb, f, b))
        nan_cb = jnp.broadcast_to(nan_c, (sb, f, b))
        lg_l = lg_r + pick(nan_gb)
        lh_l = lh_r + pick(nan_hb)
        lc_l = lc_r + pick(nan_cb)

        # emit ONLY the selection (indices, direction, picked sums) —
        # all exact integers / exact prefix values. Gains and outputs are
        # recomputed in XLA by the wrapper from these sums, so in-kernel
        # division/dot approximations never reach the returned numbers
        # (they can only perturb near-tie selections, ~1e-4 relative).
        one = jnp.float32(1.0)
        zero = jnp.float32(0.0)
        cols = [
            jnp.where(has_split, one, zero),
            jnp.where(has_split, bf, -1.0),
            bt,
            # ungated: split.py emits chose_na_left even for no-split
            # slots (downstream only reads committed splits)
            jnp.where(na_left, one, zero),
            lg_r, lh_r, lc_r, lg_l, lh_l, lc_l,
        ]
        out = jnp.concatenate(
            cols + [jnp.zeros((sb, _N_OUT - len(cols)), jnp.float32)],
            axis=1)                                           # [sb, 16]
        out_ref[:] = out

    return kernel


@functools.partial(
    jax.jit, static_argnames=("hp", "slot_block", "interpret"))
def find_best_splits_kernel(hist: jax.Array, parent_grad: jax.Array,
                            parent_hess: jax.Array, parent_count: jax.Array,
                            parent_output: jax.Array, num_bins: jax.Array,
                            missing_is_nan: jax.Array, is_cat: jax.Array,
                            feature_mask: jax.Array, hp: SplitHyperParams,
                            monotone=None, cons_min=None, cons_max=None,
                            depth=None, *, slot_block: int = 8,
                            interpret: bool = False) -> BestSplits:
    """find_best_splits (numerical subset) in one Pallas launch.

    Same contract as split.find_best_splits for the shapes it supports
    (kernel_supports(hp)); cat_bitset/per_feature_gain are zeros.
    """
    s, f, b, _ = hist.shape
    sb = slot_block
    spad = (-s) % sb
    bpad = ((b + 127) // 128) * 128 - b

    h = jnp.transpose(hist, (0, 3, 1, 2))                     # [S, 3, F, B]
    if spad or bpad:
        h = jnp.pad(h, ((0, spad), (0, 0), (0, 0), (0, bpad)))
    b_k = b + bpad

    has_mono = hp.has_monotone and monotone is not None
    parent_cols = [parent_grad, parent_hess, parent_count, parent_output]
    if has_mono:
        parent_cols += [cons_min, cons_max,
                        (depth if depth is not None
                         else jnp.zeros(s)).astype(jnp.float32)]
    parent = jnp.stack(
        parent_cols + [jnp.zeros(s, jnp.float32)] *
        (8 - len(parent_cols)), axis=1).astype(jnp.float32)   # [S, 8]
    if spad:
        parent = jnp.pad(parent, ((0, spad), (0, 0)))

    fmask = jnp.broadcast_to(
        feature_mask.astype(jnp.float32).reshape(
            (1, f) if feature_mask.ndim == 1 else (s, f)), (s, f))
    # numerical-only kernel: categorical features are masked off
    fmask = fmask * (~is_cat).astype(jnp.float32)[None, :]
    if spad:
        fmask = jnp.pad(fmask, ((0, spad), (0, 0)))

    feat_tbl = jnp.stack(
        [num_bins.astype(jnp.float32),
         missing_is_nan.astype(jnp.float32)] +
        [jnp.zeros(f, jnp.float32)] * 6, axis=1)              # [F, 8]
    mono_in = jnp.zeros((f, 8), jnp.float32)
    if has_mono:
        mono_in = mono_in.at[:, 0].set(monotone.astype(jnp.float32))

    nblk = (s + spad) // sb
    out = pl.pallas_call(
        _scan_kernel(sb, f, b_k, hp, has_mono),
        grid=(nblk,),
        in_specs=[
            pl.BlockSpec((1, sb * 3, f, b_k),
                         lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((sb, 8), lambda i: (i, 0)),
            pl.BlockSpec((sb, f), lambda i: (i, 0)),
            pl.BlockSpec((f, 8), lambda i: (0, 0)),
            pl.BlockSpec((f, 8), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((sb, _N_OUT), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((s + spad, _N_OUT), jnp.float32),
        interpret=interpret,
        **({} if interpret else {"compiler_params": _COMPILER_PARAMS}),
    )(h.reshape(nblk, sb * 3, f, b_k), parent, fmask, feat_tbl, mono_in)

    out = out[:s]
    w = (b + 31) // 32
    has_split = out[:, _O_HAS] > 0.5
    na_left = out[:, _O_NAL] > 0.5
    lg = jnp.where(na_left, out[:, _O_LGL], out[:, _O_LGR])
    lh = jnp.where(na_left, out[:, _O_LHL], out[:, _O_LHR])
    lc = jnp.where(na_left, out[:, _O_LCL], out[:, _O_LCR])
    rg = parent_grad - lg
    rh = parent_hess - lh
    rc = parent_count - lc
    # gains/outputs recomputed exactly here ([S]-sized XLA ops) from the
    # kernel's picked prefix sums — in-kernel approximations affect only
    # the selection of near-tie candidates, never the returned numbers
    l1, l2 = hp.lambda_l1, hp.lambda_l2
    gain_shift = leaf_gain(parent_grad, parent_hess, l1, l2,
                           hp.max_delta_step)
    if hp.has_monotone and monotone is not None:
        bfc = jnp.clip(out[:, _O_FEAT].astype(jnp.int32), 0, f - 1)
        lout = leaf_output(lg, lh, l1, l2, hp.max_delta_step,
                           hp.path_smooth, lc, parent_output)
        rout = leaf_output(rg, rh, l1, l2, hp.max_delta_step,
                           hp.path_smooth, rc, parent_output)
        lout = jnp.clip(lout, cons_min, cons_max)
        rout = jnp.clip(rout, cons_min, cons_max)
        g = _gain_given_output(lg, lh, l1, l2, lout) + \
            _gain_given_output(rg, rh, l1, l2, rout)
        if hp.monotone_penalty > 0:
            pen = _monotone_penalty_factor(
                depth if depth is not None else jnp.zeros(s),
                hp.monotone_penalty)
            g = jnp.where(monotone[bfc] != 0, g * pen, g)
    else:
        g = _split_gain(lg, lh, lc, rg, rh, rc, l1, l2, hp, parent_output)
        lout = leaf_output(lg, lh, l1, l2, hp.max_delta_step,
                           hp.path_smooth, lc, parent_output)
        rout = leaf_output(rg, rh, l1, l2, hp.max_delta_step,
                           hp.path_smooth, rc, parent_output)
    gain = jnp.where(has_split, g - gain_shift, -jnp.inf)
    return BestSplits(
        gain=gain,
        feature=jnp.where(has_split, out[:, _O_FEAT].astype(jnp.int32),
                          -1),
        threshold_bin=out[:, _O_BIN].astype(jnp.int32),
        default_left=na_left,  # ungated, matching split.py's junk slots
        left_grad=lg, left_hess=lh, left_count=lc,
        left_output=lout, right_output=rout,
        per_feature_gain=jnp.zeros((1, 1), jnp.float32),
        cat_bitset=jnp.zeros((s, w), jnp.uint32))
