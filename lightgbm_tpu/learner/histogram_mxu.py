"""Sort-free MXU histogram + routing kernels.

On a TPU per-row memory ops (gather, scatter, sort) run far below the
rate of dense matmuls: the argsort+regroup prologue of the grouped Pallas
histogram (histogram_pallas.py) and the per-row table gathers of the
routing step dominate a growth pass built on them. These kernels remove
every per-row memory op from the growth pass:

- `build_histograms_mxu`: hist[s, f, b, c] = slotOH^T @ (binOH * data_c) —
  both one-hot matrices are built in VMEM per row-block (never hitting HBM)
  and contracted on the MXU with bf16 inputs / f32 accumulation. Gradients
  and hessians are split hi/lo into two bf16 matmuls (double-bf16), giving
  ~2e-6 relative error vs exact f32 scatter — well inside the reference's
  own f32-histogram option (hist_t, USE_SINGLE_PRECISION).
  This is the TPU answer to the CUDA shared-memory scatter kernels
  (cuda_histogram_constructor.cu:18-307): on a systolic-array machine the
  histogram is reformulated as matrix multiplication instead of scatter.

- `route_rows_mxu`: one pass over the binned matrix that advances every
  row through the splits applied this pass (cuda_data_partition.cu:288's
  GenDataToLeftBitVector equivalent). All per-node lookups (split feature,
  threshold bin, children, categorical bitsets, next-pass slot) go through
  ONE [rows, nodes] one-hot f32 matmul against a packed node table —
  no gathers. Categorical bitset words are carried as two 16-bit halves so
  every table value stays exactly representable in f32.

Per-row scalars (node id, slot id, the channel operand, a looked-up
value) cross every kernel boundary ALONG LANES: [1, rows], [8, rows].
An [rows, k] array with k < 128 is tiled (8, 128) on the TPU, 512 bytes
a row in HBM for 4 bytes of content, and a [nb, 1] column in VMEM is
nb/8 vector registers with one lane of 128 in use; so the kernels take
and return these vectors lane-dense and compute on them in that
orientation ([1, nb] per-row values, [X, nb] per-row table rows, the
node lookup tbl^T [K, M] x node_oh^T [M, nb]).

HBM traffic per pass: one read of the binned matrix + small blocks;
flops: nchan * S * N * F * B MACs (bf16; nchan = 5 with double-precision
sums, 4 with single-bf16 hessians) for the histogram, negligible for
routing.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as _np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.log import Log

__all__ = ["build_histograms_mxu", "build_histograms_mxu_v2",
           "build_histograms_mxu_auto", "hist_num_channels",
           "route_rows_mxu", "HistOperands", "prepare_hist_operands",
           "pack_route_tables", "node_values_mxu", "node_sums_mxu",
           "quantize_gradients", "pack_bins_4bit", "unpack_bins_4bit"]

# v5e has 128 MB VMEM; the default 16 MB scoped limit starves the
# accumulate-in-VMEM histogram output on small row counts.
_COMPILER_PARAMS = pltpu.CompilerParams(
    vmem_limit_bytes=100 * 1024 * 1024)

# features per accumulating dot in the v2/fused kernels: batching widens
# the MXU output tile (a [nb, C*S] x [nb, G*B] dot instead of G narrow
# ones), measured ~15% faster at small S on v5e
_FGROUP = 4


def _round_up(x: int, k: int) -> int:
    return ((x + k - 1) // k) * k


# ---------------------------------------------------------------------------
# 4-bit packed bin storage (reference 4-bit DenseBin, src/io/dense_bin.hpp:42)
# ---------------------------------------------------------------------------

def pack_bins_4bit(bins):
    """Pack a [N, F] bin matrix whose values all fit 4 bits (max_bin <= 15
    incl. the NaN bin) into [N, ceil(F/2)] uint8: feature j < Fh rides
    column j's LOW nibble, feature Fh+j its HIGH nibble. The split layout
    (features [0..Fh) low, [Fh..F) high — NOT interleaved nibbles) keeps
    per-feature extraction a static column pick + shift/mask inside the
    kernels, with no lane interleave. Accepts numpy or jax input; exact:
    training on packed storage grows bit-identical trees.

    Any bin id above 15 (a caller configuring more bins than a nibble
    holds — the NaN bin counts) makes packing lossy, so it is refused:
    returns None with a logged warning and the caller keeps the uint8
    storage path instead of training on silently truncated bins."""
    xp = jnp if isinstance(bins, jax.Array) else _np
    vmax = int(bins.max()) if bins.size else 0
    if vmax > 15:
        Log.warning(
            "pack_bins_4bit: bin id %d exceeds the 4-bit limit of 15 "
            "(max_bin incl. the NaN bin must be <= 15); keeping uint8 "
            "bin storage", vmax)
        return None
    n, f = bins.shape
    fh = (f + 1) // 2
    lo = bins[:, :fh].astype(xp.uint8)
    hi = xp.zeros((n, fh), xp.uint8)
    if f > fh:
        if xp is jnp:
            hi = hi.at[:, :f - fh].set(bins[:, fh:].astype(xp.uint8))
        else:
            hi[:, :f - fh] = bins[:, fh:].astype(xp.uint8)
    return lo | (hi << 4)


def unpack_bins_4bit(packed, num_features: int):
    """Inverse of pack_bins_4bit -> [N, num_features] uint8."""
    xp = jnp if isinstance(packed, jax.Array) else _np
    fh = packed.shape[1]
    lo = packed & xp.uint8(15)
    hi = packed >> 4
    return xp.concatenate([lo, hi], axis=1)[:, :num_features]


def _packed_cols(bins_i, js, fh: int):
    """Per-feature [nb, 1] i32 bin values from a packed i32 block for the
    static feature ids `js` (kernel-side unpack: column pick + nibble)."""
    out = []
    for j in js:
        if j < fh:
            out.append(jnp.bitwise_and(bins_i[:, j:j + 1], 15))
        else:
            c = j - fh
            out.append(jnp.right_shift(bins_i[:, c:c + 1], 4) & 15)
    return out


# ---------------------------------------------------------------------------
# histogram
# ---------------------------------------------------------------------------

def _hist_kernel(nb: int, fc: int, b: int, s: int, flane: int,
                 mm_dtype=jnp.bfloat16, nchan: int = 5):
    fcb = fc * b

    def kernel(block_any_ref, slot_ref, bins_ref, data_ref, out_ref):
        ci = pl.program_id(0)
        ri = pl.program_id(1)

        @pl.when(ri == 0)
        def _():
            out_ref[0] = jnp.zeros_like(out_ref[0])

        # late growth passes have most rows parked in finished leaves
        # (slot -1); blocks with no active row skip all compute
        @pl.when(block_any_ref[ri] != 0)
        def _():
            iota_s = jax.lax.broadcasted_iota(jnp.int32, (s, nb), 0)
            slot_oh = (slot_ref[:] == iota_s)                # [S, nb] bool

            # chunk-extract without lane slicing: a [flane, fc*B] 0/1
            # selector copies feature ci*fc+j//B into one-hot column space
            # via the MXU (bin values <= 255 are exact in bf16)
            bins_f = bins_ref[:].astype(jnp.int32) \
                .astype(jnp.bfloat16)                        # [nb, flane]
            frow = jax.lax.broadcasted_iota(jnp.int32, (flane, fcb), 0)
            jcol = jax.lax.broadcasted_iota(jnp.int32, (flane, fcb), 1)
            sel = (frow == ci * fc + jcol // b).astype(jnp.bfloat16)
            ext = jax.lax.dot_general(
                bins_f, sel, dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)          # [nb, fc*B]
            binidx = jax.lax.broadcasted_iota(jnp.int32, (nb, fcb), 1) % b
            bin_oh = (ext == binidx.astype(jnp.float32)) \
                .astype(mm_dtype)                            # [nb, fc*B]

            data = data_ref[:]                               # [8, nb] f32
            for c in range(nchan):  # hi/lo pairs + cnt, or g/h/cnt
                lhs = jnp.where(slot_oh, data[c:c + 1, :],
                                jnp.float32(0.0)).astype(mm_dtype)
                part = jax.lax.dot_general(
                    lhs, bin_oh,
                    dimension_numbers=(((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)      # [S, fc*B]
                out_ref[0, c * s:(c + 1) * s, :] += part

    return kernel


def hist_num_channels(double_prec: bool = True, quantized: bool = False,
                      const_hess: float = 0.0) -> int:
    """Channels _hist_channels builds for this posture (static)."""
    if const_hess:
        # [g, cnt] quantized, [g_hi, g_lo, cnt] exact (regardless of
        # double_prec: the dropped channel is the hessian)
        return 2 if quantized else 3
    return 3 if quantized else (5 if double_prec else 4)


def _hist_channels(grad, hess, cnt, double_prec: bool,
                   quantized: bool = False, const_hess: float = 0.0):
    """Channel matrix [8, N] (rows along lanes) for the histogram kernels
    (hi/lo bf16 pairs + count, or grad-hi/lo + single-bf16 hessian +
    count).

    quantized=True: the caller passes stochastically-rounded INTEGER
    gradients/hessians in [-127, 127] (quantize_gradients) — bf16-exact,
    so each rides a single channel with no hi/lo split: 3 channels
    instead of 5, the flop lever of quantized GBDT training adapted to
    the MXU formulation. f32 accumulation is integer-exact to 2^24 and
    ~1e-7-relative beyond, far inside the stochastic-rounding noise.

    const_hess != 0 drops the hessian channel entirely (the reference's
    IsConstantHessian fast path, objective_function.h:42): per-row
    hessians are const_hess x the count weight, so the hessian histogram
    is reconstructed as const_hess * count in _combine_hist — EXACT (no
    quantization noise on hessians) and one fewer MXU channel
    (quantized 3 -> 2, exact 5 -> 3)."""
    g = grad.astype(jnp.float32)
    h = hess.astype(jnp.float32)
    if const_hess:
        if quantized:
            chans = [g, cnt.astype(jnp.float32)]
        else:
            g_hi = jax.lax.reduce_precision(g, exponent_bits=8,
                                            mantissa_bits=7)
            chans = [g_hi, g - g_hi, cnt.astype(jnp.float32)]
        nchan = len(chans)
        data = jnp.stack(chans + [jnp.zeros_like(g)] * (8 - nchan))
        return data, nchan
    if quantized:
        chans = [g, h, cnt.astype(jnp.float32)]
        data = jnp.stack(chans + [jnp.zeros_like(g)] * 5)
        return data, 3
    # reduce_precision (not a bf16 round-trip, which XLA elides under
    # --xla_allow_excess_precision) keeps the hi/lo split honest
    g_hi = jax.lax.reduce_precision(g, exponent_bits=8, mantissa_bits=7)
    if double_prec:
        h_hi = jax.lax.reduce_precision(h, exponent_bits=8, mantissa_bits=7)
        chans = [g_hi, g - g_hi, h_hi, h - h_hi, cnt.astype(jnp.float32)]
    else:
        # mixed precision: gradient sums (the squared gain numerator) stay
        # hi/lo-exact, hessian sums ride single bf16 — the denominator is
        # smoothed by lambda_l2/min_hessian and tolerates ~2^-9 error
        chans = [g_hi, g - g_hi, h, cnt.astype(jnp.float32)]
    nchan = len(chans)
    data = jnp.stack(chans + [jnp.zeros_like(g)] * (8 - nchan))  # [8, N]
    return data, nchan


class HistOperands(NamedTuple):
    """What the kernels read of the rows that does not change within a
    tree, in the form their BlockSpecs take it. Built once per tree by
    the grower (prepare_hist_operands, outside every pass) and passed
    to the wrappers as `operands=`; a wrapper called without it builds
    the same thing for itself, at its own row block. The true row count
    is the length of the per-pass vector (row_node / row_slot) that
    comes with it; rows past it are padding (bins 0, channels 0)."""
    bins: jax.Array                        # [R, fcols], rows padded
    lanes: Optional[jax.Array]             # [R, plane]: + 128-lane pad
    data: Optional[jax.Array]              # [8, R] f32 (_hist_channels)
    table: Optional[jax.Array] = None      # [W, n + 1] bf16 (_row_table)
    bins_t: Optional[jax.Array] = None     # [fsub, R]: bins transposed


#: rows of per-tree operands are padded to a multiple of this: every
#: row block the wrappers use (1024 ... 8192) divides it
OPERAND_ROW_MULTIPLE = 8192


def _pad_rows(x, rows: int, axis: int = 0, **kw):
    """x padded along `axis` to `rows` (no-op when already there)."""
    extra = rows - x.shape[axis]
    if not extra:
        return x
    return jnp.pad(x, [(0, extra if a == axis else 0)
                       for a in range(x.ndim)], **kw)


def _lane_row(x, rows: int, **kw):
    """A per-row vector [n] as the kernels take it: [1, rows] int32,
    rows along lanes (a bitcast of the padded vector, 4 bytes a row)."""
    return _pad_rows(x.astype(jnp.int32), rows, **kw)[None, :]


def _bins_t(bins_t: jax.Array, rows: int) -> jax.Array:
    """The routing kernels' view of the bins, from their transpose
    [F, n]: [fsub, rows], a feature per sublane row so that a row's
    split-feature bin is pulled across sublanes with the row ids along
    lanes; the feature axis is padded to the dtype's sublane tile (32
    rows of uint8). Taken from the bins as they come, not from a padded
    copy that the row-major operands share: XLA would give that copy
    the transposed layout and pay a relayout of the 128-lane operand in
    every pass."""
    fsub = _round_up(bins_t.shape[0], 32 // bins_t.dtype.itemsize)
    return jnp.pad(bins_t, ((0, fsub - bins_t.shape[0]),
                            (0, rows - bins_t.shape[1])))


def _row_table(bins_t: jax.Array, data: jax.Array, nchan: int) -> jax.Array:
    """Everything the slot-grouped kernel (histogram_pallas) reads of a
    row as ONE bf16 column, so a pass moves a row once: [W, n + 1] from
    the transposed bins [F, n], rows along lanes as in `data` and the
    routing kernels' bins. A row's W values are its bins (byte values,
    exact in bf16), then the channels as the very bf16 operand the
    one-hot kernels build from `data` ([8, n]; the MXU is fed bf16
    either way, so nothing is lost), then its slot within its group,
    which is the only part a pass writes (with the move; 255: none).
    One extra all-zero, slot-less row at the end stands for padding.
    Lane-major because that is what HBM holds compactly: W values of a
    row are W sublanes of one lane (96 bytes a row at W = 34), where a
    [n, 34] array a kernel can address is tiled to 128 lanes, 256 bytes
    a row; the stream partition transposes on the MXU as it moves."""
    n = bins_t.shape[1]
    tab = jnp.concatenate(
        [bins_t.astype(jnp.bfloat16), data[:nchan].astype(jnp.bfloat16),
         jnp.full((1, n), 255, jnp.bfloat16)], axis=0)
    pad = jnp.zeros((tab.shape[0], 1), jnp.bfloat16).at[-1, 0].set(255)
    return jnp.concatenate([tab, pad], axis=1)


def prepare_hist_operands(bins, grad, hess, cnt, *, double_prec=True,
                          quantized=False, const_hess=0.0,
                          row_multiple: int = OPERAND_ROW_MULTIPLE,
                          lanes: bool = False, channels: bool = True,
                          table: bool = False,
                          route: bool = False) -> HistOperands:
    """The per-tree operands of the histogram and routing kernels from
    the binned matrix and one tree's gradients: bins padded in rows to
    `row_multiple` (and, lanes=True, to 128 lanes: the one-hot kernels'
    block), the channel operand of _hist_channels padded likewise,
    (table=True) the slot-grouped build's row table and (route=True)
    the transposed bins of the routing kernels. The grower calls this
    once per tree and asks for what its static plan uses; a wrapper
    without `operands=` calls it with its own row block, so the arrays
    a kernel sees are the same either way."""
    rows = _round_up(bins.shape[0], row_multiple)
    bins_p = _pad_rows(bins, rows)
    plane = _round_up(bins.shape[1], 128)
    lanes_p = None
    if lanes:
        # padded lanes are never sliced by the kernels (j < f); the
        # value only needs to be in range for the int cast
        lanes_p = bins_p if plane == bins.shape[1] else \
            jnp.pad(bins, ((0, rows - bins.shape[0]),
                           (0, plane - bins.shape[1])))
    data = tab = None
    bins_tr = bins.T if table or route else None     # one transpose
    if channels or table:
        data, nchan = _hist_channels(grad, hess, cnt, double_prec,
                                     quantized, const_hess)  # [8, N]
        if table:
            tab = _row_table(bins_tr, data, nchan)
        data = _pad_rows(data, rows, axis=1) if channels else None
    return HistOperands(bins_p, lanes_p, data, tab,
                        _bins_t(bins_tr, rows) if route else None)


def _kernel_operands(operands: Optional[HistOperands], nb: int, bins,
                     grad, hess, cnt, route: bool = False,
                     **posture) -> HistOperands:
    """A one-hot kernel's operands at its row block `nb`: the tree's
    (whose rows `nb` divides already, for every block that divides
    OPERAND_ROW_MULTIPLE), else prepared here from the plain arrays."""
    if operands is None:
        return prepare_hist_operands(bins, grad, hess, cnt,
                                     row_multiple=nb, lanes=True,
                                     route=route, **posture)
    rows = _round_up(operands.bins.shape[0], nb)
    return operands._replace(
        bins=_pad_rows(operands.bins, rows),
        lanes=_pad_rows(operands.lanes, rows),
        data=_pad_rows(operands.data, rows, axis=1),
        bins_t=_pad_rows(operands.bins_t, rows, axis=1) if route else None)


def quantize_gradients(grad, hess, key, *, pmax_axis=None):
    """Stochastically-rounded integer gradients for the 3-channel
    histogram mode: g_q = floor(g/gs + u), gs = max|g|/127 (and likewise
    hessians). Unbiased (E[g_q]*gs = g); per-tree scales. Returns
    (g_q, h_q, gscale, hscale) with g_q/h_q integer-valued f32.

    hess=None (the constant-hessian fast path): skip hessian
    quantization entirely — returns (g_q, None, gscale, 1.0), saving
    the hessian PRNG draw and keeping hessian sums exact.

    pmax_axis: shard_map axis name for distributed training — scales must
    agree across shards so every rank bins identical integers."""
    g = grad.astype(jnp.float32)
    gmax = jnp.max(jnp.abs(g))
    if pmax_axis:
        gmax = jax.lax.pmax(gmax, pmax_axis)
    gscale = jnp.maximum(gmax, 1e-30) / 127.0
    ku, kv = jax.random.split(key)
    ug = jax.random.uniform(ku, g.shape)
    # clip: f32 rounding at the band edge (127 + u -> 128.0) can escape
    # the documented [-127, 127] contract a few times per billion rows
    g_q = jnp.clip(jnp.floor(g / gscale + ug), -127.0, 127.0)
    if hess is None:
        return g_q, None, gscale, jnp.float32(1.0)
    h = hess.astype(jnp.float32)
    # abs: custom objectives may hand back negative hessians; scaling by
    # max|h| keeps h_q inside the bf16-exact [-127, 127] band either way
    hmax = jnp.max(jnp.abs(h))
    if pmax_axis:
        hmax = jax.lax.pmax(hmax, pmax_axis)
    hscale = jnp.maximum(hmax, 1e-30) / 127.0
    uh = jax.random.uniform(kv, h.shape)
    h_q = jnp.clip(jnp.floor(h / hscale + uh), -127.0, 127.0)
    return g_q, h_q, gscale, hscale


def _combine_hist(out, *, nchan: int, s: int, f: int, b: int, bmax: int,
                  double_prec: bool, const_hess: float = 0.0) -> jax.Array:
    """Kernel output [*, nchan*s, f*b] -> [S, F, bmax, 3] with the hi/lo
    channel recombination (shared postlude of the v2/fused kernels).
    const_hess != 0: the hessian channel was dropped by _hist_channels;
    reconstruct it exactly as const_hess * count."""
    out = out.reshape(nchan, s, f, b)[..., :bmax]
    out = jnp.transpose(out, (1, 0, 2, 3))                   # [S, C, F, B]
    if const_hess:
        if nchan == 2:   # quantized: [g_int, cnt]
            g, c = out[:, 0], out[:, 1]
        else:            # exact: [g_hi, g_lo, cnt]
            g, c = out[:, 0] + out[:, 1], out[:, 2]
        return jnp.stack([g, c * jnp.float32(const_hess), c], axis=-1)
    if nchan == 3:  # quantized: integer g/h sums ride single channels
        return jnp.stack([out[:, 0], out[:, 1], out[:, 2]], axis=-1)
    if double_prec:
        return jnp.stack([out[:, 0] + out[:, 1], out[:, 2] + out[:, 3],
                          out[:, 4]], axis=-1)               # [S, F, B, 3]
    return jnp.stack([out[:, 0] + out[:, 1], out[:, 2], out[:, 3]],
                     axis=-1)


def _slot_lhs(slot, data, *, nb: int, s: int, nchan: int, mm_dtype):
    """The histogram dots' left operand, transposed: [C*S, nb], row
    c*S + k holding channel c of the rows in slot k and 0 elsewhere.
    slot: [1, nb] i32 (-1 = no slot); data: [8, nb] f32 channels."""
    iota_s = jax.lax.broadcasted_iota(jnp.int32, (s, nb), 0)
    slot_oh = (slot == iota_s)                               # [S, nb] bool
    return jnp.concatenate(
        [jnp.where(slot_oh, data[c:c + 1, :], jnp.float32(0.0))
         for c in range(nchan)], axis=0).astype(mm_dtype)    # [C*S, nb]


def _hist_accumulate(hist_ref, lhs, bins_i, *, nb: int, f: int, b: int,
                     mm_dtype, fh: int = 0, rows_axis: int = 1):
    """Shared accumulation body of the v2/fused/grouped kernels:
    per-feature-group bin one-hots, accumulating dots against the
    slot-masked channel operand `lhs`, whose rows run along `rows_axis`:
    [C*S, nb] from _slot_lhs (a plain matmul), or [nb, C*S] where slot
    and channels come out of a row-major table (histogram_pallas).
    bins_i: [nb, lanes] i32 (fh > 0: 4-bit packed columns, feature j at
    column j % fh, nibble j // fh)."""
    iota_b = jax.lax.broadcasted_iota(jnp.int32, (nb, b), 1)
    for gj in range(0, f, _FGROUP):
        js = range(gj, min(gj + _FGROUP, f))
        cols = _packed_cols(bins_i, js, fh) if fh else \
            [bins_i[:, j:j + 1] for j in js]
        oh = jnp.concatenate(
            [(c == iota_b) for c in cols],
            axis=1).astype(mm_dtype)                         # [nb, G*B]
        part = jax.lax.dot_general(
            lhs, oh, dimension_numbers=(((rows_axis,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # [C*S, G*B]
        hist_ref[0, :, gj * b:(gj + len(js)) * b] += part


def _node_lookup(node, tbl_t, m: int, nb: int):
    """The node-table row of every row, across lanes: (node_oh^T
    [M, nb] bf16, tbl^T [K, M] x node_oh^T -> [K, nb] f32). bf16
    operands are exact: the node table was designed around base-256
    digits (every entry <= 256), and one-hot columns make the f32
    accumulation a pure selection."""
    iota_m = jax.lax.broadcasted_iota(jnp.int32, (m, nb), 0)
    node_oh = (node == iota_m).astype(jnp.bfloat16)          # [M, nb]
    gath = jax.lax.dot_general(
        tbl_t.astype(jnp.bfloat16), node_oh,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                  # [K, nb]
    return node_oh, gath


def _own_slot(gath):
    """[1, nb] f32: the node's own next-pass slot (unsplit nodes)."""
    return (gath[_COL_SLOT_Q:_COL_SLOT_Q + 1, :] * 256.0 +
            gath[_COL_SLOT_R:_COL_SLOT_R + 1, :])


def _route_decide(node, node_oh, gath, bins_blk, ftbl, member_t, *,
                  nb: int, fh: int = 0, loc_t=None,
                  efb_range: bool = False):
    """Shared split-decision math of the route/fused kernels: numerical
    thresholds, NaN-bin default direction, categorical bitset membership.
    Every per-row value is a [1, nb] lane vector and every per-row table
    row an [X, nb] block, reduced across sublanes.
    node: [1, nb] i32; node_oh, gath: _node_lookup's ([M, nb] bf16,
    [K, nb] f32 node-table row per row); bins_blk: [fsub, nb] f32, the
    transposed bins (fh > 0: 4-bit packed byte rows, feature j at row
    j % fh, nibble j // fh — byte values <= 255 stay f32-exact, the
    nibble is recovered arithmetically after the row pick);
    ftbl: [Fp, 2] f32 per-feature (num_bins, missing_is_nan);
    loc_t is not None: bins_blk holds EFB bundle columns; the split
    feature's bundle column (_COL_BCOL) is selected, then the original
    local bin is decoded through the [Bb, Fp] transposed loc_table
    (efb.py: default bin folded in for out-of-segment positions) — the
    decision math below then runs on original bins unchanged;
    member_t: [Bpad, M] categorical left-set membership per bin, or None
    when the table holds no categorical splits. Returns (new node ids,
    next-pass kernel slot) as [1, nb] f32 pairs — rows of unsplit nodes
    keep their node and their own slot; routed rows take the chosen
    child's slot, carried in the PARENT row (_COL_SLOTL/_COL_SLOTR) so
    no second node-table lookup is needed."""

    def col(c):
        return gath[c:c + 1, :]                              # [1, nb] f32

    def pull(idx, table):
        # table[idx[r], r] per row r: a one-hot select down the sublanes
        iota = jax.lax.broadcasted_iota(
            jnp.int32, table.shape, 0).astype(jnp.float32)
        return jnp.sum(jnp.where(idx == iota, table, 0.0), axis=0,
                       keepdims=True)                        # [1, nb] f32

    split = col(_COL_SPLIT)
    pf = col(_COL_FEAT_Q) * 256.0 + col(_COL_FEAT_R)
    thr = col(_COL_THR)
    defl = col(_COL_DEFLEFT) > 0.5
    child_l = col(_COL_LEFT_Q) * 256.0 + col(_COL_LEFT_R)
    child_r = col(_COL_RIGHT_Q) * 256.0 + col(_COL_RIGHT_R)

    # predicates as 0/1 f32 (Mosaic lacks i1-valued selects)
    one = jnp.float32(1.0)
    zero = jnp.float32(0.0)
    defl_f = jnp.where(defl, one, zero)
    if efb_range:
        # EFB bundle-RANGE decision: the row's bundle bin compared to
        # per-node position constants (pack_route_tables efb columns).
        # In-segment rows go left iff pos <= P(t); the NaN position goes
        # by default_left; out-of-segment rows (the split feature sits
        # at its default bin) go by the precomputed default side. No
        # original-bin decode, no [rows, F]-wide work — identity columns
        # (dense numerics, categoricals) reduce to the plain bin compare
        # because their segment spans the whole column.
        pval = pull(col(_COL_BCOL_Q) * 256.0 + col(_COL_BCOL_R), bins_blk)
        seg_lo = col(_COL_SEG_LO)
        seg_hi = col(_COL_SEG_HI)
        pt = col(_COL_PT)
        dbl = col(_COL_DBLEFT)
        pnan = col(_COL_PNAN)
        in_f = jnp.where((pval >= seg_lo) & (pval <= seg_hi), one, zero)
        nanp_f = jnp.where(pval == pnan, one, zero)
        le_f = jnp.where(pval <= pt, one, zero)
        num_gl = in_f * (nanp_f * defl_f + (one - nanp_f) * le_f) + \
            (one - in_f) * dbl
        binv = pval  # categorical columns are identity: bin == position
    else:
        # per-feature flags (num_bins, missing_is_nan) index the
        # full-width feature table regardless of bin packing/bundling
        iota_f = jax.lax.broadcasted_iota(
            jnp.int32, (ftbl.shape[0], nb), 0).astype(jnp.float32)
        feat_oh = (pf == iota_f)                             # [Fp, nb] bool
        if loc_t is not None:
            # EFB expansion fallback: bundle-column select, then
            # original-local-bin decode through the loc table: the loc
            # row of the split feature is one MXU dot (entries <= 256,
            # bf16-exact; the 0/1 operand keeps the accumulation a
            # selection)
            pval = pull(col(_COL_BCOL_Q) * 256.0 + col(_COL_BCOL_R),
                        bins_blk)
            loc_row = jax.lax.dot_general(
                loc_t.astype(jnp.bfloat16), feat_oh.astype(jnp.bfloat16),
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)          # [Bb, nb]
            binv = pull(pval, loc_row)
        elif fh:
            # packed storage: pick byte row pf % fh, then the nibble
            fh_f = jnp.float32(fh)
            is_hi = jnp.where(pf >= fh_f, one, zero)
            pbyte = pull(pf - is_hi * fh_f, bins_blk)
            hi_val = jnp.floor(pbyte * jnp.float32(1.0 / 16.0))
            binv = is_hi * hi_val + (1.0 - is_hi) * \
                (pbyte - 16.0 * hi_val)
        else:
            binv = pull(pf, bins_blk)       # bins_t[pf[r], r]
        nbins = jnp.sum(jnp.where(feat_oh, ftbl[:, 0:1], 0.0), axis=0,
                        keepdims=True)
        mnan = jnp.sum(jnp.where(feat_oh, ftbl[:, 1:2], 0.0), axis=0,
                       keepdims=True) > 0.5
        is_nan_bin = mnan & (binv == nbins - 1.0)
        nan_f = jnp.where(is_nan_bin, one, zero)
        le_f = jnp.where(binv <= thr, one, zero)
        num_gl = nan_f * defl_f + (one - nan_f) * le_f
    if member_t is not None:
        iscat_f = jnp.where(col(_COL_ISCAT) > 0.5, one, zero)
        memb = jax.lax.dot_general(
            member_t.astype(jnp.bfloat16), node_oh,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # [Bpad, nb]
        in_set_f = pull(binv, memb)                          # 0/1 f32
        gl_f = iscat_f * in_set_f + (one - iscat_f) * num_gl
    else:
        gl_f = num_gl
    child_f = gl_f * child_l + (one - gl_f) * child_r
    slot_l = col(_COL_SLOTL_Q) * 256.0 + col(_COL_SLOTL_R)
    slot_r = col(_COL_SLOTR_Q) * 256.0 + col(_COL_SLOTR_R)
    slot_child = gl_f * slot_l + (one - gl_f) * slot_r
    new_node = split * child_f + (one - split) * node.astype(jnp.float32)
    new_slot = split * slot_child + (one - split) * _own_slot(gath)
    return new_node, new_slot


def _hist_kernel_v2(nb: int, f: int, b: int, s: int,
                    mm_dtype=jnp.bfloat16, nchan: int = 5, fh: int = 0):
    """Extraction-free histogram kernel: the [flane, fc*B] selector matmul
    of _hist_kernel (whose cost scales with the 128-lane padding, ~4.6x
    waste at F=28 and the S-independent floor of every pass) is replaced
    by per-feature static lane slices + a VPU broadcast-compare. One grid
    pass over rows, one [nb, nchan*S] x [nb, B] dot per feature."""

    def kernel(block_any_ref, slot_ref, bins_ref, data_ref, out_ref):
        ri = pl.program_id(0)

        @pl.when(ri == 0)
        def _():
            out_ref[0] = jnp.zeros_like(out_ref[0])

        @pl.when(block_any_ref[ri] != 0)
        def _():
            _hist_accumulate(
                out_ref, _slot_lhs(slot_ref[:], data_ref[:], nb=nb, s=s,
                                   nchan=nchan, mm_dtype=mm_dtype),
                bins_ref[:].astype(jnp.int32), nb=nb, f=f, b=b,
                mm_dtype=mm_dtype, fh=fh)

    return kernel


@functools.partial(
    jax.jit, static_argnames=("num_slots", "bmax", "row_block", "fchunk",
                              "interpret", "use_f32", "double_prec",
                              "quantized", "const_hess"))
def build_histograms_mxu(bins: jax.Array, grad: jax.Array, hess: jax.Array,
                         cnt: jax.Array, row_slot: jax.Array, *,
                         num_slots: int, bmax: int, row_block: int = 1024,
                         fchunk: int = 4, use_f32: bool = False,
                         double_prec: bool = True, quantized: bool = False,
                         const_hess: float = 0.0,
                         interpret: bool = False) -> jax.Array:
    """Per-slot histograms without sorting or gathering.

    Args mirror build_histograms; row_slot < 0 routes to no slot.
    Returns [num_slots, F, bmax, 3] f32 (grad, hess, count).

    double_prec=True splits gradients AND hessians into hi/lo bf16 pairs
    (~f32-accurate sums, 5 matmul channels). False keeps gradient sums
    hi/lo-exact but sums hessians as single bf16 (~2^-9 relative error;
    4 channels, ~1.3x faster) — the TPU analog of the reference GPU
    backend's gpu_use_dp switch.
    """
    n, f = bins.shape
    nb = row_block
    s = num_slots
    b = ((bmax + 127) // 128) * 128          # lane-aligned bin axis
    fc = fchunk
    nchunks = (f + fc - 1) // fc
    fpad = nchunks * fc
    flane = ((max(fpad, f) + 127) // 128) * 128

    npad = (-n) % nb
    if npad:
        bins = jnp.pad(bins, ((0, npad), (0, 0)))
    if flane != f:
        # padded feature columns always bin to 255 (a bin id real features
        # can also hit, but their chunks are sliced away below)
        bins = jnp.pad(bins, ((0, 0), (0, flane - f)),
                       constant_values=255)
    slot = jnp.where((row_slot < 0) | (row_slot >= s), -1, row_slot) \
        .astype(jnp.int32)
    if npad:
        slot = jnp.pad(slot, (0, npad), constant_values=-1)

    data, nchan = _hist_channels(grad, hess, cnt, double_prec, quantized,
                                 const_hess)
    data = _pad_rows(data, n + npad, axis=1)

    nblocks = (n + npad) // nb
    block_any = jnp.max(
        (slot >= 0).astype(jnp.int32).reshape(nblocks, nb), axis=1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nchunks, nblocks),
        in_specs=[
            pl.BlockSpec((1, nb), lambda ci, ri, ba: (0, ri)),
            pl.BlockSpec((nb, flane), lambda ci, ri, ba: (ri, 0)),
            pl.BlockSpec((8, nb), lambda ci, ri, ba: (0, ri)),
        ],
        out_specs=pl.BlockSpec((1, nchan * s, fc * b),
                               lambda ci, ri, ba: (ci, 0, 0)))
    out = pl.pallas_call(
        _hist_kernel(nb, fc, b, s, flane,
                     jnp.float32 if use_f32 else jnp.bfloat16,
                     nchan=nchan),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nchunks, nchan * s, fc * b),
                                       jnp.float32),
        interpret=interpret,
        **({} if interpret else {"compiler_params": _COMPILER_PARAMS}),
    )(block_any, slot[None, :], bins, data)

    # [nchunks, C*S, fc*B] -> [S, F, B, 3]
    out = out.reshape(nchunks, nchan, s, fc, b)
    out = jnp.transpose(out, (2, 1, 0, 3, 4)).reshape(s, nchan, fpad, b)
    out = out[:, :, :f, :bmax]
    if const_hess:
        if nchan == 2:
            g, c = out[:, 0], out[:, 1]
        else:
            g, c = out[:, 0] + out[:, 1], out[:, 2]
        hist = jnp.stack([g, c * jnp.float32(const_hess), c], axis=-1)
    elif nchan == 3:
        hist = jnp.stack([out[:, 0], out[:, 1], out[:, 2]], axis=-1)
    elif double_prec:
        hist = jnp.stack([out[:, 0] + out[:, 1], out[:, 2] + out[:, 3],
                          out[:, 4]], axis=-1)               # [S, F, B, 3]
    else:
        hist = jnp.stack([out[:, 0] + out[:, 1], out[:, 2], out[:, 3]],
                         axis=-1)
    return hist


# VMEM budget for the v2/fused kernels: resident histogram output block
# plus the per-row-block input working set (binned lanes in i32/f32 and
# the bin one-hot scratch). Beyond it the chunked v1 kernel takes over
# (wide-feature datasets) — without the input term, wide-F data at tiny
# frontiers passed the output check and then failed scoped-VMEM
# allocation inside the fused kernel (observed at F=1000, bmax=64).
_V2_BUDGET_BYTES = 80 * 1024 * 1024
_V2_ROW_BLOCK = 4096  # worst-case block the grower/dispatcher may pick


def fits_v2(num_slots: int, num_features: int, bmax: int,
            double_prec: bool = True, quantized: bool = False,
            route_width: int = 0,
            row_block: int = _V2_ROW_BLOCK,
            const_hess: float = 0.0) -> bool:
    """Whether the extraction-free v2/fused kernels' working set fits
    the VMEM budget for this shape (single owner of the predicate — the
    grower and the auto dispatcher must agree). route_width: the
    original-feature table width when it differs from the bins width
    (EFB: bins hold bundle columns but routing gathers original-feature
    one-hots + the loc_table decode); row_block: the block the caller
    will actually use."""
    b = ((bmax + 127) // 128) * 128
    nchan = hist_num_channels(double_prec, quantized, const_hess)
    out = nchan * num_slots * num_features * b * 4
    plane = ((num_features + 127) // 128) * 128
    flane_r = ((max(route_width, num_features) + 127) // 128) * 128
    # bins block in i32 + f32 (~3 lane buffers) + the route decide's
    # iota/one-hot/where mask chain over the route width (~6 f32
    # temporaries, more under the EFB loc decode), plus the [nb, G*B]
    # bin one-hot scratch
    route_cost = 36 if route_width and route_width != num_features else 24
    inputs = row_block * (12 * plane + route_cost * flane_r +
                          2 * _FGROUP * b)
    return out + inputs <= _V2_BUDGET_BYTES


@functools.partial(
    jax.jit, static_argnames=("num_slots", "bmax", "row_block",
                              "interpret", "use_f32", "double_prec",
                              "quantized", "num_features", "const_hess"))
def build_histograms_mxu_v2(bins: jax.Array, grad: jax.Array,
                            hess: jax.Array, cnt: jax.Array,
                            row_slot: jax.Array, *, num_slots: int,
                            bmax: int, row_block: int = 4096,
                            use_f32: bool = False,
                            double_prec: bool = True,
                            quantized: bool = False,
                            num_features: int = 0,
                            const_hess: float = 0.0,
                            operands: Optional[HistOperands] = None,
                            interpret: bool = False) -> jax.Array:
    """Extraction-free variant of build_histograms_mxu (same contract):
    one grid pass over rows, per-feature static lane slices instead of
    the selector matmul, all channels in a single dot per feature.

    num_features > 0 marks `bins` as 4-bit packed storage
    (pack_bins_4bit) with that many logical features; the kernel unpacks
    nibbles in VMEM, halving the bin matrix's HBM traffic.

    operands: the tree's prepared bins and channel operand
    (prepare_hist_operands(lanes=True)), read instead of bins, grad,
    hess and cnt."""
    nb = row_block
    ops = _kernel_operands(operands, nb, bins, grad, hess, cnt,
                           double_prec=double_prec, quantized=quantized,
                           const_hess=const_hess)
    rows, fcols = ops.bins.shape
    f = num_features if num_features else fcols
    fh = fcols if num_features else 0
    s = num_slots
    b = ((bmax + 127) // 128) * 128
    flane = ops.lanes.shape[1]
    nchan = hist_num_channels(double_prec, quantized, const_hess)

    slot = _lane_row(
        jnp.where((row_slot < 0) | (row_slot >= s), -1, row_slot), rows,
        constant_values=-1)

    nblocks = rows // nb
    block_any = jnp.max(
        (slot >= 0).astype(jnp.int32).reshape(nblocks, nb), axis=1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((1, nb), lambda ri, ba: (0, ri)),
            pl.BlockSpec((nb, flane), lambda ri, ba: (ri, 0)),
            pl.BlockSpec((8, nb), lambda ri, ba: (0, ri)),
        ],
        out_specs=pl.BlockSpec((1, nchan * s, f * b),
                               lambda ri, ba: (0, 0, 0)))
    out = pl.pallas_call(
        _hist_kernel_v2(nb, f, b, s,
                        jnp.float32 if use_f32 else jnp.bfloat16,
                        nchan=nchan, fh=fh),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, nchan * s, f * b), jnp.float32),
        interpret=interpret,
        **({} if interpret else {"compiler_params": _COMPILER_PARAMS}),
    )(block_any, slot, ops.lanes, ops.data)

    return _combine_hist(out, nchan=nchan, s=s, f=f, b=b, bmax=bmax,
                         double_prec=double_prec, const_hess=const_hess)


def build_histograms_mxu_auto(bins, grad, hess, cnt, row_slot, *,
                              num_slots, bmax, double_prec=True,
                              quantized=False, num_features=0,
                              const_hess=0.0, operands=None,
                              interpret=False, **v1_cfg):
    """v2 kernel when its per-feature output block fits VMEM, else the
    chunked v1 kernel (wide-feature datasets). num_features > 0 marks
    `bins` as 4-bit packed (the v1 fallback unpacks on device — packed
    storage targets small-bmax shapes, which always fit v2). operands:
    the tree's prepared operands, for the v2 kernel; the v1 fallback
    pads to its own selector layout from the plain arguments."""
    f = num_features if num_features else bins.shape[1]
    if fits_v2(num_slots, f, bmax, double_prec, quantized,
               const_hess=const_hess):
        return build_histograms_mxu_v2(
            bins, grad, hess, cnt, row_slot, num_slots=num_slots,
            bmax=bmax, double_prec=double_prec, quantized=quantized,
            num_features=num_features, const_hess=const_hess,
            operands=operands, interpret=interpret)
    if num_features:
        bins = unpack_bins_4bit(bins, num_features)
    return build_histograms_mxu(
        bins, grad, hess, cnt, row_slot, num_slots=num_slots, bmax=bmax,
        double_prec=double_prec, quantized=quantized,
        const_hess=const_hess, interpret=interpret,
        **v1_cfg)


def _route_tables(tbl, member, feat_tbl, loc_table, *, f_route: int,
                  has_efb: bool):
    """The small per-pass tables as the routing kernels take them, rows
    of a table along lanes: tbl^T [Kp, M] (Kp: _N_COLS padded to the
    bf16 sublane tile), member^T [Bpad, M], feat_tbl padded or cut to
    [fp, 2] (fp: the routed feature count, a contraction dim under
    decode-mode EFB and lane-aligned there; cut only in range mode,
    where the table is unused), loc_table^T [Bb8, fp] (a placeholder
    without decode-mode EFB)."""
    fp = _round_up(f_route, 128 if has_efb else 8)
    tbl_t = _pad_rows(tbl.T, _round_up(tbl.shape[1], 16))
    feat_tbl = _pad_rows(feat_tbl[:fp], fp)
    if has_efb:
        loc_t = _pad_rows(_pad_rows(loc_table.astype(jnp.float32).T,
                                    _round_up(loc_table.shape[1], 8)),
                          fp, axis=1)
    else:
        loc_t = jnp.zeros((8, 128), jnp.float32)  # unused placeholder
    return tbl_t, member.T, feat_tbl, loc_t


def _whole(x):
    """BlockSpec of a small table that every grid step reads whole."""
    return pl.BlockSpec(x.shape, lambda ri: (0, 0))


def _fused_kernel(nb: int, f: int, b: int, s: int, m: int,
                  mm_dtype=jnp.bfloat16, nchan: int = 5,
                  has_cat: bool = True, fh: int = 0,
                  has_efb: bool = False, efb_range: bool = False):
    """Route + histogram in ONE sweep over the binned matrix: advance each
    row through the splits committed by the previous pass (the
    _route_kernel math) and immediately scatter-accumulate it into its new
    slot's histogram (the _hist_kernel_v2 math). Saves a full second read
    of bins + a kernel launch per growth pass. Blocks whose rows all sit
    in unsplit nodes skip everything except the cheap node-table gather
    (their rows keep their node and contribute to no slot)."""

    def kernel(node_ref, bins_ref, bins_t_ref, data_ref, tbl_ref,
               member_ref, feat_tbl_ref, loc_ref, hist_ref, node_out_ref,
               slot_ref):
        ri = pl.program_id(0)

        @pl.when(ri == 0)
        def _():
            hist_ref[0] = jnp.zeros_like(hist_ref[0])

        node = node_ref[:]                                   # [1, nb] i32
        node_oh, gath = _node_lookup(node, tbl_ref[:], m, nb)
        block_has_split = jnp.sum(gath[_COL_SPLIT:_COL_SPLIT + 1, :]) > 0.5

        @pl.when(~block_has_split)
        def _():
            node_out_ref[:] = node
            slot_ref[:] = _own_slot(gath).astype(jnp.int32)

        @pl.when(block_has_split)
        def _():
            new_node_f, new_slot_f = _route_decide(
                node, node_oh, gath, bins_t_ref[:].astype(jnp.int32)
                .astype(jnp.float32), feat_tbl_ref[:],
                member_ref[:] if has_cat else None,
                nb=nb, fh=fh, efb_range=efb_range,
                loc_t=loc_ref[:] if has_efb else None)
            node_out_ref[:] = new_node_f.astype(jnp.int32)
            slot_ref[:] = new_slot_f.astype(jnp.int32)

        # ---- histogram accumulation for every block holding slotted
        # rows. The slot rode along with the route (child slots live in
        # the parent's table row; unsplit nodes carry their own slot,
        # -1 outside the initial root pass) — no second node lookup.
        slot = slot_ref[:]                                   # [1, nb] i32
        block_any_slot = jnp.max(slot) >= 0

        @pl.when(block_any_slot)
        def _():
            _hist_accumulate(
                hist_ref, _slot_lhs(slot, data_ref[:], nb=nb, s=s,
                                    nchan=nchan, mm_dtype=mm_dtype),
                bins_ref[:].astype(jnp.int32), nb=nb, f=f, b=b,
                mm_dtype=mm_dtype, fh=fh)

    return kernel


@functools.partial(
    jax.jit, static_argnames=("num_slots", "bmax", "row_block", "has_cat",
                              "double_prec", "quantized", "num_features",
                              "efb_range", "const_hess", "interpret"))
def fused_route_hist_mxu(bins: jax.Array, grad: jax.Array, hess: jax.Array,
                         cnt: jax.Array, row_node: jax.Array,
                         tbl: jax.Array, member: jax.Array,
                         feat_tbl: jax.Array, *, num_slots: int, bmax: int,
                         row_block: int = 4096, has_cat: bool = True,
                         double_prec: bool = True, quantized: bool = False,
                         num_features: int = 0, loc_table=None,
                         efb_range: bool = False,
                         const_hess: float = 0.0,
                         operands: Optional[HistOperands] = None,
                         interpret: bool = False):
    """One sweep: route rows through the previous pass's packed split
    tables (pack_route_tables) AND build the per-slot histograms of the
    resulting frontier. Returns (hist [S, F, bmax, 3], new row_node [N]).

    Rows whose node did not split keep their node and land in no slot
    (slot -1), matching route_rows_mxu + build_histograms_mxu. Routing is
    idempotent: a second sweep through the same tables is the identity
    (children are not split in the table), which the grower uses to flush
    the final pass's routing after its loops.

    num_features > 0 marks `bins` as 4-bit packed (pack_bins_4bit) with
    that many logical features; nibbles unpack in VMEM.

    loc_table ([F_orig, Bb] i32/f32) marks `bins` as EFB bundle columns:
    histograms build in bundle space (f = bundle columns, bmax = Bb) and
    routing decodes the original local bin through loc_table (efb.py);
    feat_tbl stays original-feature-indexed. efb_range=True routes by
    the bundle-RANGE table columns instead — no loc table, no
    original-feature-width work (pack_route_tables efb=).

    operands: the tree's prepared bins (row-major and transposed) and
    channel operand (prepare_hist_operands(lanes=True, route=True)),
    read instead of bins, grad, hess and cnt; row_node keeps the true
    row count, and padding rows ride along at node 0 with all-zero
    channels.

    The node ids go in and come out as [1, rows] (rows along lanes); the
    new slot of a row never leaves VMEM."""
    nb = row_block
    ops = _kernel_operands(operands, nb, bins, grad, hess, cnt,
                           route=True, double_prec=double_prec,
                           quantized=quantized, const_hess=const_hess)
    n = row_node.shape[0]
    rows, fcols = ops.bins.shape
    has_efb = loc_table is not None and not efb_range
    f = num_features if num_features else fcols
    fh = fcols if num_features else 0
    s = num_slots
    b = ((bmax + 127) // 128) * 128
    plane = ops.lanes.shape[1]               # bins block width (packed)
    fsub = ops.bins_t.shape[0]
    m = tbl.shape[0]
    nchan = hist_num_channels(double_prec, quantized, const_hess)
    # route tables are original-feature-indexed under decode-mode EFB
    tbl_t, member_t, feat_tbl, loc_t = _route_tables(
        tbl, member, feat_tbl, loc_table, has_efb=has_efb,
        f_route=loc_table.shape[0] if has_efb else f)

    nblocks = rows // nb
    hist, node_out = pl.pallas_call(
        _fused_kernel(nb, f, b, s, m, nchan=nchan, has_cat=has_cat, fh=fh,
                      has_efb=has_efb, efb_range=efb_range),
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((1, nb), lambda ri: (0, ri)),
            pl.BlockSpec((nb, plane), lambda ri: (ri, 0)),
            pl.BlockSpec((fsub, nb), lambda ri: (0, ri)),
            pl.BlockSpec((8, nb), lambda ri: (0, ri)),
            _whole(tbl_t), _whole(member_t), _whole(feat_tbl),
            _whole(loc_t),
        ],
        out_specs=[
            pl.BlockSpec((1, nchan * s, f * b), lambda ri: (0, 0, 0)),
            pl.BlockSpec((1, nb), lambda ri: (0, ri)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, nchan * s, f * b), jnp.float32),
            jax.ShapeDtypeStruct((1, rows), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((1, nb), jnp.int32)],
        interpret=interpret,
        **({} if interpret else {"compiler_params": _COMPILER_PARAMS}),
    )(_lane_row(row_node, rows), ops.lanes, ops.bins_t, ops.data, tbl_t,
      member_t, feat_tbl, loc_t)

    h3 = _combine_hist(hist, nchan=nchan, s=s, f=f, b=b, bmax=bmax,
                       double_prec=double_prec, const_hess=const_hess)
    return h3, node_out[0, :n]


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------

# packed node-table column layout. The MXU truncates f32 operands to
# bf16, whose integers are exact only up to 256 — node/child ids can reach
# 2*num_leaves, so they are stored as (quotient, remainder) base-256 pairs
# and reassembled after the contraction. Every other column is <= 256.
_COL_SPLIT = 0     # 1.0 if the node was split this pass
_COL_FEAT_R = 1    # split feature % 256 (used-feature idx)
_COL_THR = 2       # threshold bin (mxu path requires max_bin <= 256)
_COL_DEFLEFT = 3   # NaN-direction default_left
_COL_ISCAT = 4     # categorical decision
_COL_LEFT_Q = 5    # left child id // 256
_COL_LEFT_R = 6    # left child id % 256
_COL_RIGHT_Q = 7   # right child id // 256
_COL_RIGHT_R = 8   # right child id % 256
_COL_SLOT_Q = 9    # next-pass slot // 256 (-1 encodes as (-1, 255))
_COL_SLOT_R = 10   # next-pass slot % 256
_COL_FEAT_Q = 11   # split feature // 256 (wide datasets)
_COL_SLOTL_Q = 12  # left child's next-pass slot // 256 (-1 = (-1, 255))
_COL_SLOTL_R = 13  # left child's next-pass slot % 256
_COL_SLOTR_Q = 14  # right child's next-pass slot // 256
_COL_SLOTR_R = 15  # right child's next-pass slot % 256
_COL_BCOL_Q = 16   # split feature's EFB bundle column // 256
_COL_BCOL_R = 17   # split feature's EFB bundle column % 256
# EFB bundle-RANGE routing (efb.EfbScan route tables): the split decision
# becomes position compares on the row's bundle bin — no original-bin
# decode, no [rows, F]-wide work. All values <= 256 (bf16-exact).
_COL_SEG_LO = 18   # first bundle position of the split feature's segment
_COL_SEG_HI = 19   # last bundle position of the segment
_COL_PT = 20       # last LEFT position for this threshold (seg_lo-1: none)
_COL_DBLEFT = 21   # default-bin side goes left (out-of-segment rows)
_COL_PNAN = 22     # NaN-bin position (-1: none); routes by default_left
_N_COLS = 23


def pack_route_tables(split_mask, feat, thr, default_left, is_cat,
                      child_l, child_r, slot_of_node, cat_bitset,
                      m_pad: int, bmax: int, bcol=None, efb=None):
    """Node tables for route_rows_mxu: ([m_pad, _N_COLS] f32 scalars,
    [m_pad, Bpad] 0/1 categorical left-set membership per bin).
    bcol: per-node EFB bundle column of the split feature (defaults to
    the feature id itself — identity when bins are unbundled).
    efb (EfbDev with .scan tables): fills the bundle-RANGE routing
    columns (_COL_SEG_LO.._COL_PNAN) from its static tables so the
    kernels can run the efb_range decision; zeros otherwise."""
    m1 = split_mask.shape[0]
    w = cat_bitset.shape[1]
    bpad = ((bmax + 127) // 128) * 128
    bits = jnp.arange(bpad, dtype=jnp.uint32)
    words = cat_bitset if w * 32 >= bpad else jnp.pad(
        cat_bitset, ((0, 0), (0, (bpad + 31) // 32 - w)))
    member = ((words[:, bits // 32] >> (bits % 32)[None, :]) &
              jnp.uint32(1)).astype(jnp.float32)      # [m1, Bpad]
    def qr(v):
        v = v.astype(jnp.int32)
        return ((v // 256).astype(jnp.float32)[:, None],
                (v % 256).astype(jnp.float32)[:, None])

    cl_q, cl_r = qr(child_l)
    cr_q, cr_r = qr(child_r)
    sl_q, sl_r = qr(slot_of_node)
    f_q, f_r = qr(feat)
    # children's kernel slots carried in the PARENT row so routing picks
    # the destination slot without a second node-table lookup
    cl_i = jnp.clip(child_l.astype(jnp.int32), 0, m1 - 1)
    cr_i = jnp.clip(child_r.astype(jnp.int32), 0, m1 - 1)
    slot_l = jnp.where(split_mask, slot_of_node[cl_i], -1)
    slot_r = jnp.where(split_mask, slot_of_node[cr_i], -1)
    slq_q, slq_r = qr(slot_l)
    srq_q, srq_r = qr(slot_r)
    bc_q, bc_r = qr(feat if bcol is None else bcol)
    if efb is not None and getattr(efb, "scan", None) is not None:
        er = efb.scan
        fr = feat.astype(jnp.int32)
        th = jnp.clip(thr.astype(jnp.int32), 0,
                      er.pos_thresh.shape[1] - 1)
        seg_lo_n = efb.seg_lo[fr].astype(jnp.float32)[:, None]
        seg_hi_n = efb.seg_hi[fr].astype(jnp.float32)[:, None]
        pt_n = er.pos_thresh[fr, th].astype(jnp.float32)[:, None]
        dbl_n = jnp.where(er.nan_is_default[fr], default_left,
                          er.db_le_t[fr, th]) \
            .astype(jnp.float32)[:, None]
        pnan_n = er.p_nan_f[fr].astype(jnp.float32)[:, None]
    else:
        z = jnp.zeros((m1, 1), jnp.float32)
        seg_lo_n = seg_hi_n = pt_n = dbl_n = z
        pnan_n = z - 1.0
    tbl = jnp.concatenate([
        split_mask.astype(jnp.float32)[:, None],
        f_r,
        thr.astype(jnp.float32)[:, None],
        default_left.astype(jnp.float32)[:, None],
        is_cat.astype(jnp.float32)[:, None],
        cl_q, cl_r, cr_q, cr_r,
        sl_q, sl_r,
        f_q,
        slq_q, slq_r, srq_q, srq_r,
        bc_q, bc_r,
        seg_lo_n, seg_hi_n, pt_n, dbl_n, pnan_n], axis=1)
    if m_pad > m1:
        tbl = jnp.pad(tbl, ((0, m_pad - m1), (0, 0)))
        member = jnp.pad(member, ((0, m_pad - m1), (0, 0)))
    return tbl, member


def _route_kernel(nb: int, m: int, has_cat: bool = True, fh: int = 0,
                  has_efb: bool = False, efb_range: bool = False,
                  counts_spad: int = 0, valid_rows: int = 0):
    # every per-row quantity is kept [1, nb] (2-D, rows along lanes) —
    # Mosaic lowers 2-D masks/selects cleanly where 1-D bool vectors hit
    # unsupported i1 casts.
    # counts_spad > 0: the same sweep also accumulates per-slot row counts
    # ([counts_spad, 128] f32 broadcast columns, exact to 2^24) — routing
    # AND the partition metadata of the scatter histogram in one pass.
    def kernel(node_ref, bins_t_ref, tbl_ref, member_ref, feat_tbl_ref,
               loc_ref, node_out_ref, slot_out_ref, *counts_refs):
        node = node_ref[:]                                   # [1, nb] i32
        node_oh, gath = _node_lookup(node, tbl_ref[:], m, nb)

        # blocks whose rows all sit in unsplit nodes (the common case in
        # late, narrow growth passes) skip the decision math entirely
        block_has_split = jnp.sum(gath[_COL_SPLIT:_COL_SPLIT + 1, :]) > 0.5

        @pl.when(~block_has_split)
        def _():
            node_out_ref[:] = node
            slot_out_ref[:] = _own_slot(gath).astype(jnp.int32)

        @pl.when(block_has_split)
        def _():
            new_node_f, new_slot_f = _route_decide(
                node, node_oh, gath, bins_t_ref[:].astype(jnp.int32)
                .astype(jnp.float32), feat_tbl_ref[:],
                member_ref[:] if has_cat else None,
                nb=nb, fh=fh, efb_range=efb_range,
                loc_t=loc_ref[:] if has_efb else None)
            node_out_ref[:] = new_node_f.astype(jnp.int32)
            slot_out_ref[:] = new_slot_f.astype(jnp.int32)

        if counts_spad:
            counts_ref, = counts_refs
            ri = pl.program_id(0)

            @pl.when(ri == 0)
            def _():
                counts_ref[0] = jnp.zeros_like(counts_ref[0])

            # read the routed slot back (same trick as the fused kernel:
            # child slots rode along in the parent's table row)
            slot = slot_out_ref[:]                       # [1, nb] i32
            iota_s = jax.lax.broadcasted_iota(
                jnp.int32, (counts_spad, nb), 0)
            rid = ri * nb + jax.lax.broadcasted_iota(
                jnp.int32, (1, nb), 1)
            ohc = ((slot == iota_s) & (rid < valid_rows)) \
                .astype(jnp.float32)                     # [spad, nb]
            csum = jnp.sum(ohc, axis=1, keepdims=True)   # [spad, 1]
            counts_ref[0] += jnp.broadcast_to(csum, (counts_spad, 128))

    return kernel


@functools.partial(
    jax.jit, static_argnames=("row_block", "num_features", "efb_range",
                              "interpret", "emit_counts", "num_slots",
                              "has_cat"))
def route_rows_mxu(bins: jax.Array, row_node: jax.Array, tbl: jax.Array,
                   member: jax.Array, feat_tbl: jax.Array, *,
                   row_block: int = 0, num_features: int = 0,
                   has_cat: bool = True,
                   loc_table=None, efb_range: bool = False,
                   emit_counts: bool = False, num_slots: int = 0,
                   operands: Optional[HistOperands] = None,
                   interpret: bool = False):
    """Advance rows one level and emit (new row_node, new row_slot).

    operands: the tree's prepared operands (prepare_hist_operands(
    route=True)), whose transposed bins are read instead of `bins`
    (once per tree instead of a transpose per call): the true row count
    is row_node's, and rows past it route nowhere and are not counted.
    tbl/member: from pack_route_tables (M_pad lane-friendly).
    feat_tbl: [F, 2] f32: (num_bins, missing_is_nan).
    has_cat=False (as in fused_route_hist_mxu): no node of the table
    decides on a categorical, so the membership lookup, a [Bpad, M] x
    [M, nb] matmul that is most of a wide call's MXU work, is skipped.
    num_features > 0 marks `bins` as 4-bit packed (pack_bins_4bit).
    loc_table marks `bins` as EFB bundle columns decoded per row
    (expansion fallback); efb_range=True instead runs the bundle-RANGE
    decision off the packed table columns — no loc table, no
    original-feature-width work (pack_route_tables efb=).

    emit_counts=True (requires num_slots > 0): the on-device parallel
    partition mode — the same sweep additionally returns per-slot row
    counts [num_slots] i32 (rows whose new slot is s; parked rows
    excluded), the exact metadata the scatter histogram's
    partition_rows needs, so routing stops being a count-only second
    pass. Returns (row_node, row_slot, counts) instead of 2-tuple.
    Both partition implementations consume these counts for the
    groups' block starts; neither counts again.

    Node and slot ids cross the kernel as [1, rows] vectors (rows along
    lanes), 4 bytes a row each.
    """
    n = row_node.shape[0]
    if operands is not None:
        bins_p, bins_t = operands.bins, operands.bins_t
    else:
        bins_p, bins_t = bins, None
    fcols = bins_p.shape[1]
    has_efb = loc_table is not None and not efb_range
    f = num_features if num_features else fcols
    fh = fcols if num_features else 0
    m = tbl.shape[0]
    # row_block 0 = auto: 4096 (fewer grid steps) at the flagship
    # shape, but ONLY for narrow-input dense routing — wide tables
    # ([m, nb] one-hot), wide bins blocks, and both EFB modes (the
    # expansion decode OOM'd at a 2048 block on 250-column bundles,
    # grower_mxu.py sweep note) keep the conservative 1024. The table cutoff is m <= 1024: the one-hot
    # route tensor is [m, nb] f32, so nb=4096 at m=2048 is a 32 MiB
    # operand (4096*2048*4) before the matmul's output — past the
    # ~16 MiB/core VMEM budget the measured case (m=896, 14 MiB) stays
    # inside, and exactly the fits_v2-style bound the histogram side
    # enforces for its own scan tensors.
    if row_block:
        nb = row_block
    elif m <= 1024 and fcols <= 128 and loc_table is None \
            and not efb_range:
        nb = 4096
    else:
        nb = 1024
    rows = _round_up(bins_p.shape[0], nb)
    if bins_t is None:
        bins_t = _bins_t(bins_p.T, rows)
    bins_t = _pad_rows(bins_t, rows, axis=1)
    fsub = bins_t.shape[0]
    tbl_t, member_t, feat_tbl, loc_t = _route_tables(
        tbl, member, feat_tbl, loc_table, has_efb=has_efb,
        f_route=loc_table.shape[0] if has_efb else f)

    nblocks = rows // nb
    spad = ((max(num_slots, 1) + 127) // 128) * 128 if emit_counts else 0
    out_specs = [pl.BlockSpec((1, nb), lambda ri: (0, ri))] * 2
    out_shape = [jax.ShapeDtypeStruct((1, rows), jnp.int32)] * 2
    if emit_counts:
        out_specs.append(pl.BlockSpec((1, spad, 128), lambda ri: (0, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((1, spad, 128), jnp.float32))
    out = pl.pallas_call(
        _route_kernel(nb, m, has_cat=has_cat, fh=fh, has_efb=has_efb,
                      efb_range=efb_range, counts_spad=spad,
                      valid_rows=n),
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((1, nb), lambda ri: (0, ri)),
            pl.BlockSpec((fsub, nb), lambda ri: (0, ri)),
            _whole(tbl_t), _whole(member_t), _whole(feat_tbl),
            _whole(loc_t),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        **({} if interpret else {"compiler_params": _COMPILER_PARAMS}),
    )(_lane_row(row_node, rows), bins_t, tbl_t, member_t, feat_tbl, loc_t)
    if emit_counts:
        node_out, slot_out, counts = out
        return (node_out[0, :n], slot_out[0, :n],
                counts[0, :num_slots, 0].astype(jnp.int32))
    node_out, slot_out = out
    return node_out[0, :n], slot_out[0, :n]


# ---------------------------------------------------------------------------
# exact per-node sums (leaf-value recomputation)
# ---------------------------------------------------------------------------

def _node_sums_kernel(nb: int, m: int):
    def kernel(node_ref, data_ref, out_ref):
        ri = pl.program_id(0)

        @pl.when(ri == 0)
        def _():
            out_ref[0] = jnp.zeros_like(out_ref[0])

        iota_m = jax.lax.broadcasted_iota(jnp.int32, (m, nb), 0)
        # full-f32 contraction: only 8 output rows, so unlike the
        # histogram dots this one is cheap enough to keep exact — the
        # "exact leaf refit" contract of node_sums_mxu depends on it
        oh = (node_ref[:] == iota_m).astype(jnp.float32)     # [M, nb]
        out_ref[0] += jax.lax.dot_general(
            data_ref[:], oh, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)              # [8, M]

    return kernel


@functools.partial(jax.jit, static_argnames=("num_nodes", "row_block",
                                             "interpret"))
def node_sums_mxu(row_node: jax.Array, grad: jax.Array, hess: jax.Array,
                  cnt: jax.Array, *, num_nodes: int, row_block: int = 4096,
                  interpret: bool = False) -> jax.Array:
    """Exact per-node (grad, hess, count) sums from the row->node vector —
    a full-f32 one-hot contraction, gather-free. Used to recompute
    leaf values exactly after quantized growth (quantization then only
    ever perturbs the split SEARCH, never the fitted outputs; the
    reference's leaf output closed form gbdt.cpp:412 stays exact).
    Returns [num_nodes, 3] f32. Rows with node < 0 or >= num_nodes are
    ignored."""
    n = row_node.shape[0]
    m = _round_up(num_nodes, 128)
    nb = row_block
    rows = _round_up(n, nb)
    data, _ = _hist_channels(grad, hess, cnt, double_prec=True)  # [8, n]
    out = pl.pallas_call(
        _node_sums_kernel(nb, m),
        grid=(rows // nb,),
        in_specs=[
            pl.BlockSpec((1, nb), lambda ri: (0, ri)),
            pl.BlockSpec((8, nb), lambda ri: (0, ri)),
        ],
        out_specs=pl.BlockSpec((1, 8, m), lambda ri: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, 8, m), jnp.float32),
        interpret=interpret,
        **({} if interpret else {"compiler_params": _COMPILER_PARAMS}),
    )(_lane_row(row_node, rows, constant_values=-1),
      _pad_rows(data, rows, axis=1))[0, :, :num_nodes]
    return jnp.stack([out[0] + out[1], out[2] + out[3], out[4]],
                     axis=-1)                                # [M, 3]


# ---------------------------------------------------------------------------
# per-row node-value lookup (score updates)
# ---------------------------------------------------------------------------

def _values_kernel(nb: int, m: int):
    def kernel(node_ref, tbl_ref, out_ref):
        iota_m = jax.lax.broadcasted_iota(jnp.int32, (m, nb), 0)
        node_oh = (node_ref[:] == iota_m).astype(jnp.float32)  # [M, nb]
        # the MXU truncates f32 operands to bf16, so the table carries a
        # (hi, lo) split; summing the two product rows restores ~f32
        # accuracy (boosting scores drift and stall trees otherwise)
        got = jax.lax.dot_general(
            tbl_ref[:], node_oh, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # [8, nb]
        out_ref[:] = got[0:1, :] + got[1:2, :]

    return kernel


@functools.partial(jax.jit, static_argnames=("row_block", "interpret"))
def node_values_mxu(row_node: jax.Array, values: jax.Array, *,
                    row_block: int = 0,
                    interpret: bool = False) -> jax.Array:
    """values[row_node] without a gather: [N] <- [M] table via one-hot
    matmul (score updates, reference score_updater.hpp:21-110).
    row_block 0 = auto: 8192 (fewer grid steps) at the common table
    sizes; narrower for very wide tables (the [m, nb] f32 one-hot
    lives in VMEM). Node ids in and values out are [1, rows] vectors."""
    n = row_node.shape[0]
    m1 = values.shape[0]
    m = _round_up(m1, 128)
    if not row_block:
        row_block = 8192 if m <= 1024 else 2048
    # unlike a gather, the one-hot contraction touches EVERY table entry
    # (0 * NaN = NaN would poison all rows); never-referenced rows such as
    # the grower's scratch node can hold NaN, so sanitize first
    v = values.astype(jnp.float32)
    v = jnp.where(jnp.isfinite(v), v, 0.0)
    v_hi = jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)
    tbl_t = _pad_rows(_pad_rows(jnp.stack([v_hi, v - v_hi]), 8), m,
                      axis=1)                                # [8, m]
    nb = row_block
    rows = _round_up(n, nb)
    out = pl.pallas_call(
        _values_kernel(nb, m),
        grid=(rows // nb,),
        in_specs=[
            pl.BlockSpec((1, nb), lambda ri: (0, ri)),
            pl.BlockSpec((8, m), lambda ri: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, nb), lambda ri: (0, ri)),
        out_shape=jax.ShapeDtypeStruct((1, rows), jnp.float32),
        interpret=interpret,
        **({} if interpret else {"compiler_params": _COMPILER_PARAMS}),
    )(_lane_row(row_node, rows), tbl_t)
    return out[0, :n]
