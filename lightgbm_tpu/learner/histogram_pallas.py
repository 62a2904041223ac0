"""Slot-grouped histogram build: rows partitioned by frontier slot, so a
pass costs the same whatever the frontier's width.

The one-hot kernels of histogram_mxu.py contract a slot-masked channel
operand [rows, nchan*S] with the bin one-hot [rows, F*B]: nchan*S*N*F*B
MACs per pass, in which a row belongs to ONE slot. Up to nchan*S = 128
the MXU's rows are there anyway and the pass sits on a floor; beyond it
the cost grows with S. This module removes the S factor for the wide
passes:

1. live rows are partitioned ON DEVICE by slot GROUP: `group_width`
   consecutive slots (as many as fill the MXU's 128 rows: 25 at five
   channels, 42 at three) share a group, and the padded layout gives
   every `row_block` consecutive positions to ONE group
   (partition_table). Parked rows (slot -1: the larger sibling rebuilt by
   subtraction, finished leaves) are not in the layout at all: they are
   neither moved nor multiplied;
2. ONE kernel moves the rows (partition_stream): a sequential sweep of
   the tree's row table in row order ranks each 512-row tile against a
   triangular matrix on the MXU, moves the tile's live rows with a 0/1
   permutation operand on the MXU (exact: every output row is one input
   row times 1.0) into a tile sorted by group, the row's slot within
   its group written into the last column in the same step, appends
   each group's run to that group's ring in VMEM and sends every chunk
   a ring fills to the layout in HBM by DMA. The cost does not grow
   with the number of slots, nothing of the output is zero-filled, and
   no scatter, gather or sort runs outside the kernel. Two oracles give
   the identical table (partition_impl): "rank", the parent of this
   kernel (a rank sweep, _stable_positions, one scatter that inverts
   the rank, an XLA gather of the blocks in use), and "argsort", the
   stable sort;
3. only the blocks in use are written (each group's last block padded
   out by the kernel's final flush) and multiplied (the grouped kernel
   skips the layout's tail, which holds whatever was there);
4. each grid step runs the one-hot accumulation of histogram_mxu
   (_hist_accumulate) on its group's `group_width` slots:
   [rows, nchan*group_width] x [rows, G*B], the floor cost per row.
   Consecutive blocks of a group accumulate into the same VMEM-resident
   output block.

Which passes of a tree use this build is `use_grouped`'s to say, from
static shapes alone (grower_mxu.sweep asks it once per pass at trace
time); hist_backend=pallas forces it for every pass.

Accumulation precision: the channels of histogram_mxu._hist_channels,
bf16 operands, f32 accumulation, _combine_hist: only the summation
order differs from the one-hot kernels. In quantized mode the integer
sums are exact below 2^24, so histograms and models are bit-identical
across formulations; exact mode agrees to last-ulp summation-order
noise. A row reaches the kernel as one bf16 row (bins, channels, slot:
histogram_mxu._row_table, built once per tree, lane-major; the slot
column is written per pass, by the move); 4-bit packed bin pairs are
unpacked in VMEM. Mosaic addresses HBM in whole lane tiles, so the
stream partition declares its output at the table's PHYSICAL width (34
columns lie in 128 lanes of HBM either way): the same bytes the
grouped kernel read before, which reads the table's own columns of
them and nothing else.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .histogram_mxu import (_COMPILER_PARAMS, TILED_ROW_BLOCK, HistOperands,
                            _combine_hist, _hist_accumulate, feature_tile,
                            hist_num_channels, prepare_hist_operands)

__all__ = ["build_histograms_pallas", "build_histograms_scatter",
           "partition_table", "partition_stream", "partition_rows",
           "resolve_partition", "group_width", "use_grouped"]


#: rows per tile of the rank sweep (the triangular operand is
#: [_RANK_TILE, _RANK_TILE] bf16) and tiles per grid step
_RANK_TILE = 256
_RANK_TILES_PER_STEP = 8

#: rows per tile of the stream partition (its permutation operand is
#: [tile + slack, tile] bf16), which is also the rows per write of a
#: group's staged rows to the layout (a tile's rows then cross at most
#: one chunk boundary; a row block under this size is its own tile),
#: and tiles per grid step. DERIVATION: my chip runs, PR 38, 2,625,000
#: x 34, 18% of the rows live: a pass at 3 / 11 groups took 12.4 / 17.5
#: ms at a tile of 128, 8.8 / 11.9 at 256, 8.3 / 10.2 at 512 (the copies
#: to the rings go with tiles x groups, the permutation operand with
#: rows x tile); 32 tiles a step read as 8 do (8.7 / 11.8 at 256)
_STREAM_TILE = 512
_STREAM_TILES_PER_STEP = 8
#: VMEM the groups' rings may take: the tile halves until they fit (a
#: table of 974 columns in 21 groups takes 44 MiB at a tile of 256,
#: where 512 asked for more than the scoped VMEM holds; 34 or 143
#: columns take 11 or 22 MiB at 512)
_STREAM_RING_BYTES = 48 * 1024 * 1024
#: bf16 buffers the staged chunks leave VMEM from: a chunk's DMA is
#: waited for when its buffer comes round again
_STREAM_FLUSH_BUFFERS = 2

#: blocks gathered per trip of the rank path's gather loop (and the
#: multiple the layout's static block count is rounded to)
_GATHER_CHUNK_BLOCKS = 32

#: row block of the grouped kernel (see use_grouped for its derivation)
GROUPED_ROW_BLOCK = 2048

#: a pass is built slot-grouped from this operand width (nchan * slots)
#: on. DERIVATION: helpers/microbench_pass.py on the v5e at 2,625,000 x
#: 28 x 256, half the rows live (PERF.md section 5 has the table). The
#: one-hot pass sits on its floor up to a width of 128 and grows
#: linearly beyond (five channels: 59.2 ms at width 200, 97.8 at 360,
#: 296.1 at 1315; three: 68.7 at 216, 108.2 at 408, 188.9 at 789); the
#: grouped pass costs the route, the rank, the gather of the live rows
#: and the floor on them, 107-119 ms whatever the width. With HALF the
#: rows live the lines cross at a width of 431 (five channels) and 417
#: (three), but the grouped pass shrinks with the live share and the
#: one-hot pass does not, and a wide pass of a real tree holds the
#: smaller siblings less the parked leaves: in the Higgs tree the pass
#: at width 360 costs 66 ms grouped against 94 one-hot (the same run
#: with this constant at 400 is 27.7 ms a tree slower, PERF.md section
#: 6), while at width 200 the one-hot pass (59 ms) is still the cheaper.
GROUPED_MIN_WIDTH = 320
#: ...at the histogram columns (features x bins, the bins padded to a
#: lane tile) of the shape that derivation was made at: 28 x 256. The
#: one-hot pass grows with the columns, which are its MXU weight tiles;
#: of the grouped pass only the gather and the kernel do, its route,
#: rank and scatter do not see them. At MS LTR's 137 x 256 = 35,072
#: columns the pass at width 200 costs 186 ms one-hot and 61 grouped
#: (the same tree with this crossover at 200: 893.8 against 1,018.4 ms,
#: PERF.md section 6), so the crossover width falls as the columns
#: grow: by their square root, the gentlest law through the two shapes
#: measured, which puts it at 145 there (the pass at width 120, one MXU
#: tile, stays one-hot: not measured). Narrower data keeps 320.
GROUPED_MIN_WIDTH_COLUMNS = 28 * 256

#: ...and only where the rows outweigh the layout's padding (one block
#: per group at most) this many times: a padded row costs the gather
#: and the kernel what a live row does, so at the crossover width the
#: grouped pass wins only while the padding stays under about a fifth
#: of the rows (same table); tiny data sets stay one-hot
GROUPED_MIN_ROWS_PER_PAD = 8

#: positions are carried in f32 through the rank sweep: exact below
_MAX_POSITIONS = 1 << 24

#: a position of the layout carries its row id in the low bits of ONE
#: int32 and the row's slot within its group above them, so the single
#: scatter that inverts the rank delivers both (row ids and the padding
#: marker n stay under _MAX_POSITIONS wherever that scatter runs)
_SLOT_SHIFT = 24
#: "no slot" (padding), as the row table's slot column spells it
_NO_SLOT = 255


def group_width(nchan: int) -> int:
    """Slots that share one group: as many as fill the MXU's 128 rows
    with `nchan` channels each."""
    return max(1, 128 // nchan)


def hist_columns(num_features: int, bmax: int) -> int:
    """Columns of one slot's histogram as the kernels lay it out: every
    feature's bins padded to a lane tile."""
    return num_features * (-(-bmax // 128) * 128)


def grouped_min_width(columns: int) -> float:
    """The operand width from which a pass is built slot-grouped, at
    this many histogram columns (see GROUPED_MIN_WIDTH)."""
    return GROUPED_MIN_WIDTH * min(
        1.0, (GROUPED_MIN_WIDTH_COLUMNS / max(columns, 1)) ** 0.5)


def use_grouped(width: int, rows: int, row_block: int = GROUPED_ROW_BLOCK,
                columns: int = GROUPED_MIN_WIDTH_COLUMNS) -> bool:
    """Whether a pass whose one-hot operand would be `width` = nchan *
    slots wide, over `rows` rows and `columns` histogram columns, is
    built slot-grouped. A pure function of static shapes: the same
    answer on every platform and in every trace of a shape."""
    groups = -(-width // 128)
    pad_rows = groups * row_block
    return (width >= grouped_min_width(columns) and
            rows >= GROUPED_MIN_ROWS_PER_PAD * pad_rows and
            rows + pad_rows < _MAX_POSITIONS)


# ---------------------------------------------------------------------------
# partition
# ---------------------------------------------------------------------------

def _rank_kernel(gpad: int, t: int, tiles: int, dump: int):
    def kernel(base_ref, grp_ref, dst_ref, run_ref):
        @pl.when(pl.program_id(0) == 0)
        def _():
            run_ref[:] = base_ref[:]

        iota_g = jax.lax.broadcasted_iota(jnp.int32, (gpad, t), 0)
        # tri[j, i] = j < i: (one-hot @ tri)[g, i] counts the rows of
        # group g before row i in this tile
        tri = (jax.lax.broadcasted_iota(jnp.int32, (t, t), 0) <
               jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)) \
            .astype(jnp.bfloat16)
        for k in range(tiles):
            grp = grp_ref[k:k + 1, :]                        # [1, T] i32
            oh = grp == iota_g                               # [G, T] bool
            ohf = jnp.where(oh, jnp.float32(1.0), jnp.float32(0.0))
            before = jax.lax.dot_general(
                ohf.astype(jnp.bfloat16), tri,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)          # [G, T]
            run = run_ref[:]
            pos = jnp.sum(jnp.where(oh, before + run, jnp.float32(0.0)),
                          axis=0, keepdims=True)             # [1, T]
            dst_ref[k:k + 1, :] = jnp.where(
                grp >= 0, pos, jnp.float32(dump)).astype(jnp.int32)
            run_ref[:] = run + jnp.sum(ohf, axis=1, keepdims=True)

    return kernel


def _stable_positions(grp: jax.Array, base: jax.Array, *, num_groups: int,
                      dump: int, interpret: bool = False) -> jax.Array:
    """Layout position of every row under a stable partition by group:
    position[i] = base[grp[i]] + #{j < i : grp[j] == grp[i]}, and `dump`
    for rows with grp < 0. One sweep whose cost does not grow with the
    number of slots: each tile's rank comes from a triangular matmul,
    the per-group running count stays in VMEM across the (sequential)
    grid. Positions ride f32: exact below 2^24."""
    n = grp.shape[0]
    t, tiles = _RANK_TILE, _RANK_TILES_PER_STEP
    gpad = ((num_groups + 15) // 16) * 16    # bf16 sublane tile
    step = t * tiles
    npad = (-n) % step
    if npad:
        grp = jnp.pad(grp, (0, npad), constant_values=-1)
    grp2 = grp.reshape(-1, t)
    base_b = jnp.broadcast_to(
        jnp.pad(base.astype(jnp.float32), (0, gpad - num_groups))[:, None],
        (gpad, t))
    dst = pl.pallas_call(
        _rank_kernel(gpad, t, tiles, dump),
        grid=(grp2.shape[0] // tiles,),
        in_specs=[pl.BlockSpec((gpad, t), lambda i: (0, 0)),
                  pl.BlockSpec((tiles, t), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tiles, t), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(grp2.shape, jnp.int32),
        scratch_shapes=[pltpu.VMEM((gpad, t), jnp.float32)],
        name="partition_rank", interpret=interpret,
        **({} if interpret else {"compiler_params": _COMPILER_PARAMS}),
    )(base_b, grp2)
    return dst.reshape(-1)[:n]


def _layout(row_slot: jax.Array, *, num_slots: int, row_block: int,
            group: int, counts: jax.Array = None):
    """The padded layout of a pass, from its per-slot counts alone:
    (grp [n] i32: every row's group, -1 parked; gcounts [G] i32;
    blk_start [G + 1] i32: the first block of every group; block_group
    [TB] i32; blocks_used [] i32). TB is static: ceil(n / row_block) +
    groups, rounded up to whole gather chunks."""
    if group >= _NO_SLOT:
        raise ValueError("a group holds at most %d slots" % (_NO_SLOT - 1))
    n = row_slot.shape[0]
    s, nb = num_slots, row_block
    ng = -(-s // group)
    live = (row_slot >= 0) & (row_slot < s)
    grp = jnp.where(live, row_slot // group, -1).astype(jnp.int32)
    if counts is None:
        gcounts = jax.ops.segment_sum(
            live.astype(jnp.int32), jnp.where(live, grp, 0),
            num_segments=ng)
    else:
        c = counts[:s].astype(jnp.int32)
        gcounts = jnp.pad(c, (0, ng * group - s)).reshape(ng, group) \
            .sum(axis=1)

    tb = -(-n // nb) + ng
    chunk = min(_GATHER_CHUNK_BLOCKS, tb)
    tb = -(-tb // chunk) * chunk
    caps = jnp.maximum(1, -(-gcounts // nb))
    blk_start = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(caps).astype(jnp.int32)])
    # a block's group: the groups that start at or before it, less one
    # (a compare and a sum: no search, so no gather in the pass)
    j = jnp.arange(tb, dtype=jnp.int32)
    block_group = jnp.minimum(
        jnp.sum(blk_start[1:][None] <= j[:, None], axis=1,
                dtype=jnp.int32), ng - 1)
    return (grp, gcounts, blk_start, block_group,
            jnp.sum(caps, dtype=jnp.int32))


def partition_rows(row_slot: jax.Array, *, num_slots: int, row_block: int,
                   group: int = 1, counts: jax.Array = None,
                   impl: str = "rank", interpret: bool = False):
    """Device-side padded partition of the LIVE rows by slot group, as
    row ids: the body of the two oracles of partition_table.

    Group g holds slots [g*group, (g+1)*group). Every `row_block`
    consecutive positions of the layout hold rows of ONE group, in row
    order (a stable partition); each group owns at least one block, so
    its output block is always initialised. Rows with a slot outside
    [0, num_slots) are parked: they are not in the layout.

    counts: optional per-slot row counts ([num_slots] or longer, e.g.
    the route_rows_mxu(emit_counts=True) output): skips the counting
    pass here.

    impl: "rank" takes the positions from _stable_positions and inverts
    them with one collision-free scatter; "argsort" is the stable sort.
    Both give the identical layout.

    Returns (block_group [TB] i32, blocks_used [] i32, src [TB*row_block]
    i32, src_slot [TB*row_block] i32): src indexes the original rows, n
    marks padding; src_slot is the row's slot within its group
    (row_slot % group), 255 on padding: it rides the scatter that
    inverts the rank, packed above the row id, so delivering it costs
    no second pass over the rows. Blocks at and after blocks_used hold
    padding only and repeat the last group.
    """
    if impl not in ("argsort", "rank"):
        raise ValueError(f"unknown partition impl {impl!r}")
    n = row_slot.shape[0]
    nb = row_block
    ng = -(-num_slots // group)
    grp, gcounts, blk_start, block_group, blocks_used = _layout(
        row_slot, num_slots=num_slots, row_block=nb, group=group,
        counts=counts)
    tb = block_group.shape[0]
    slot_local = row_slot % group

    if impl == "argsort":
        # the retained O(N log N) oracle: the ONLY sanctioned sort on
        # the partition path (PERF001)
        order = jnp.argsort(  # tpulint: disable=PERF001
            jnp.where(grp >= 0, grp, ng))
        sort_start = jnp.concatenate(
            [jnp.zeros(1, jnp.int32),
             jnp.cumsum(gcounts)[:-1].astype(jnp.int32)])
        p = jnp.arange(tb * nb, dtype=jnp.int32)
        pg = block_group[p // nb]
        r = p - blk_start[pg] * nb
        take = r < gcounts[pg]     # a tail block's r is past its group
        src = jnp.where(
            take, order[jnp.clip(sort_start[pg] + r, 0, n - 1)], n)
        # the oracle pays a second gather for the slots
        src_slot = jnp.where(
            take, slot_local[jnp.clip(src, 0, n - 1)], _NO_SLOT)
    else:
        if tb * nb >= _MAX_POSITIONS:
            raise ValueError("partition impl 'rank' carries positions in "
                             "f32: %d rows are too many" % n)
        dst = _stable_positions(grp, blk_start[:ng] * nb, num_groups=ng,
                                dump=tb * nb, interpret=interpret)
        # parked rows are dropped by the scatter, so their slot bits
        # never land; bitcast, not convert: 255 << 24 is past int32
        packed = jax.lax.bitcast_convert_type(
            (slot_local.astype(jnp.uint32) << _SLOT_SHIFT) |
            jnp.arange(n, dtype=jnp.uint32), jnp.int32)
        fill = jax.lax.bitcast_convert_type(
            jnp.uint32((_NO_SLOT << _SLOT_SHIFT) | n), jnp.int32)
        packed = jax.lax.bitcast_convert_type(
            jnp.full(tb * nb, fill, jnp.int32).at[dst].set(
                packed, mode="drop", unique_indices=True), jnp.uint32)
        src = (packed & jnp.uint32((1 << _SLOT_SHIFT) - 1)) \
            .astype(jnp.int32)
        src_slot = (packed >> _SLOT_SHIFT).astype(jnp.int32)
    return block_group, blocks_used, src, src_slot


def _gather_used(table: jax.Array, src: jax.Array, src_slot: jax.Array,
                 rows_used: jax.Array, chunk_rows: int) -> jax.Array:
    """table[src] for the first `rows_used` positions of `src`, in fixed
    chunks under a dynamic trip count, with the pass's src_slot written
    into the table's last (slot) column on the way; the rest stays zero
    (the kernel never multiplies it)."""
    nchunks = (rows_used + chunk_rows - 1) // chunk_rows
    width = table.shape[1]
    is_slot_col = jax.lax.broadcasted_iota(
        jnp.int32, (chunk_rows, width), 1) == width - 1

    def body(c, buf):
        at = c * chunk_rows
        idx = jax.lax.dynamic_slice(src, (at,), (chunk_rows,))
        slot = jax.lax.dynamic_slice(src_slot, (at,), (chunk_rows,))
        blk = jnp.where(is_slot_col,
                        slot.astype(table.dtype)[:, None], table[idx])
        return jax.lax.dynamic_update_slice(buf, blk, (at, 0))

    return jax.lax.fori_loop(
        0, nchunks, body, jnp.zeros((src.shape[0], width), table.dtype))


def _stream_kernel(*, n: int, ng: int, gpad: int, t: int, tiles: int,
                   tp: int, w: int, nbuf: int):
    """The stream partition's body (partition_stream has the account).
    n rows, ng groups (gpad: padded to the bf16 sublane tile), tiles of
    t rows (a ring's chunk is a tile long), `tiles` a grid step; tp rows
    of the sorted tile; table rows of w columns; nbuf flush buffers."""
    c = t
    logc = c.bit_length() - 1
    ring = 2 * c
    wp = -(-w // 128) * 128
    f32 = jnp.float32

    def kernel(base_ref, end_ref, cnt_ref, code_ref, tab_ref, out_ref,
               run_ref, nf_ref, phase_ref, sorted_ref, stage_ref,
               fbuf_ref, sem):
        step = pl.program_id(0)

        def dma(slot, at):
            return pltpu.make_async_copy(
                fbuf_ref.at[pl.ds(pl.multiple_of(slot * c, c), c), :],
                out_ref.at[pl.ds(at, c), :], sem.at[slot])

        def flush(g, half, at, valid):
            """Chunk `half` of group g's ring to the layout's rows
            [at, at + c); rows of the chunk from `valid` on (None: all
            are rows) leave as padding."""
            nf = nf_ref[0]
            slot = nf % nbuf

            @pl.when(nf >= nbuf)
            def _():
                dma(slot, 0).wait()

            vals = stage_ref[g, pl.ds(pl.multiple_of(half * c, c), c), :]
            if valid is not None:
                pad = jnp.where(
                    jax.lax.broadcasted_iota(jnp.int32, (c, wp), 1) == w - 1,
                    f32(_NO_SLOT), f32(0.0))
                vals = jnp.where(
                    jax.lax.broadcasted_iota(jnp.int32, (c, wp), 0) < valid,
                    vals, pad)
            fbuf_ref[pl.ds(pl.multiple_of(slot * c, c), c), :] = \
                vals.astype(fbuf_ref.dtype)
            dma(slot, pl.multiple_of(at, c)).start()
            nf_ref[0] = nf + 1

        @pl.when(step == 0)
        def _():
            for g in range(ng):
                run_ref[g] = 0
            nf_ref[0] = 0
            phase_ref[:] = jnp.zeros_like(phase_ref)
            # the lanes past the table's columns are zero in every row
            # that leaves, and are written nowhere else
            sorted_ref[:] = jnp.zeros_like(sorted_ref)

        iota_g = jax.lax.broadcasted_iota(jnp.int32, (gpad, t), 0)
        # tri[j, i] = j < i: (one-hot @ tri)[g, i] counts the rows of
        # group g before row i in this tile; below[g, h] = h < g: the
        # same for the groups before group g
        tri = (jax.lax.broadcasted_iota(jnp.int32, (t, t), 0) <
               jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)) \
            .astype(jnp.bfloat16)
        below = (jax.lax.broadcasted_iota(jnp.int32, (gpad, gpad), 1) <
                 jax.lax.broadcasted_iota(jnp.int32, (gpad, gpad), 0)) \
            .astype(jnp.bfloat16)
        iota_p = jax.lax.broadcasted_iota(jnp.int32, (tp, t), 0)
        slot_row = jax.lax.broadcasted_iota(jnp.int32, (w, t), 0) == w - 1
        row_in_tile = jax.lax.broadcasted_iota(jnp.int32, (w, t), 1)
        iota8 = jax.lax.broadcasted_iota(jnp.int32, (8, wp), 0)

        def tile(k, _):
            code = code_ref[pl.ds(k, 1), :]                  # [1, T] i32
            grp = jnp.right_shift(code, 8)                   # -1: parked
            oh = grp == iota_g                               # [G, T] bool
            ohf = jnp.where(oh, f32(1.0), f32(0.0))
            before = jax.lax.dot_general(
                ohf.astype(jnp.bfloat16), tri,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=f32)                  # [G, T]
            cnt = jnp.sum(ohf, axis=1, keepdims=True)        # [G, 1]
            # a group's rows start at the phase its ring stands at, so
            # that whole sublane tiles move from the sorted tile to the
            # ring; each group's run starts on a tile of eight
            phase = phase_ref[:]
            tot = phase + cnt                                # [G, T]
            seg = jnp.where(cnt > 0, jnp.floor((tot + 7.0) * 0.125) * 8.0,
                            f32(0.0))
            start = jax.lax.dot_general(
                below, seg.astype(jnp.bfloat16),
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=f32)                  # [G, T]
            pos = jnp.sum(
                jnp.where(oh, start + phase + before, f32(0.0)),
                axis=0, keepdims=True)                       # [1, T]
            pos = jnp.where(grp >= 0, pos, f32(-1.0)).astype(jnp.int32)
            phase_ref[:] = tot - jnp.floor(tot * 0.125) * 8.0

            # the move: every row of the sorted tile is one row of the
            # table times 1.0 (exact), or no row (zeros). The table has
            # a row's values down the sublanes, so the product that
            # moves the rows also turns them; the pass's slot takes the
            # slot row's place on the way in
            perm = jnp.where(iota_p == pos, f32(1.0), f32(0.0)) \
                .astype(jnp.bfloat16)                        # [Tp, T]
            rows = tab_ref[:, pl.ds(pl.multiple_of(k * t, t), t)]
            local = jnp.bitwise_and(code, 255).astype(f32) \
                .astype(jnp.bfloat16)                        # [1, T]
            rows = jnp.where(slot_row, local, rows)          # [W, T]
            if n % (t * tiles):
                # past the table's end a block holds whatever was there
                first = (step * tiles + k) * t
                rows = jnp.where(row_in_tile < n - first, rows,
                                 jnp.zeros_like(rows))
            sorted_ref[:, :w] = jax.lax.dot_general(
                perm, rows, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=f32)                  # [Tp, W]

            def append(g, src):
                cnt_g = cnt_ref[k, g]
                run = run_ref[g]
                at = jnp.bitwise_and(run, ring - 1)
                ph = jnp.bitwise_and(at, 7)
                chunks = jnp.right_shift(ph + cnt_g + 7, 3)

                @pl.when(cnt_g > 0)
                def _():
                    dst = at - ph
                    # the first tile of eight keeps the rows the ring
                    # already holds there
                    d0 = pl.ds(pl.multiple_of(dst, 8), 8)
                    stage_ref[g, d0, :] = jnp.where(
                        iota8 >= ph,
                        sorted_ref[pl.ds(pl.multiple_of(src, 8), 8), :],
                        stage_ref[g, d0, :])

                    def copy(j, _):
                        d = jnp.bitwise_and(dst + 8 * j, ring - 1)
                        stage_ref[g, pl.ds(pl.multiple_of(d, 8), 8), :] = \
                            sorted_ref[pl.ds(pl.multiple_of(src + 8 * j, 8),
                                             8), :]
                        return 0

                    jax.lax.fori_loop(1, chunks, copy, 0)
                    run_ref[g] = run + cnt_g
                    full = jnp.right_shift(run, logc)

                    @pl.when(jnp.right_shift(run + cnt_g, logc) > full)
                    def _():
                        flush(g, jnp.bitwise_and(full, 1),
                              base_ref[g] + full * c, None)

                return src + jnp.where(cnt_g > 0, chunks * 8, 0)

            jax.lax.fori_loop(0, ng, append, jnp.int32(0))
            return 0

        jax.lax.fori_loop(0, tiles, tile, 0)

        @pl.when(step == pl.num_programs(0) - 1)
        def _():
            # what a ring still holds leaves with the group's last block
            # padded out behind it
            def finish(g, _):
                run = run_ref[g]
                full = jnp.right_shift(run, logc)
                at = base_ref[g] + full * c
                rest = jnp.right_shift(end_ref[g] - at, logc)

                @pl.when(rest > 0)
                def _():
                    flush(g, jnp.bitwise_and(full, 1), at, run - full * c)

                def pad(j, _):
                    flush(g, 0, at + j * c, 0)
                    return 0

                jax.lax.fori_loop(1, rest, pad, 0)
                return 0

            jax.lax.fori_loop(0, ng, finish, 0)
            for slot in range(nbuf):
                @pl.when(nf_ref[0] > slot)
                def _():
                    dma(slot, 0).wait()

    return kernel


def partition_stream(table: jax.Array, grp: jax.Array,
                     slot_local: jax.Array, blk_start: jax.Array, *,
                     row_block: int, blocks: int,
                     interpret: bool = False) -> jax.Array:
    """The gathered row table of a pass, written by ONE sweep of the
    tree's row table ([W, n + 1], histogram_mxu._row_table) in row
    order: [blocks * row_block, W lanes], group g's rows in row order
    from row blk_start[g] * row_block on, the row's slot within its
    group in column W - 1, every group's last block padded out with
    slot-less all-zero rows. Rows at and after the blocks in use are
    not written; the lanes past W are zero.

    Per tile of _STREAM_TILE rows (fewer where the groups' rings would
    not fit VMEM): the rows' ranks within their groups
    come from a triangular matmul (as in _stable_positions); a [Tp, T]
    0/1 permutation operand then moves the tile's live rows on the MXU
    into a tile sorted by group (f32 accumulation of one bf16 value
    times 1.0: exact), turned from the table's lane-major form on the
    way, the pass's slot in the slot row's place. From there whole
    sublane tiles are copied to the group's ring in VMEM (a group's run
    starts, in the sorted tile, at the phase its ring stands at), and
    every chunk of a tile's length that a ring fills leaves for the
    layout by DMA. The running counts are the scalar core's, int32: no
    row count is too large.
    """
    n = grp.shape[0]
    w = table.shape[0]
    ng, nb = blk_start.shape[0] - 1, row_block
    wp = -(-w // 128) * 128
    t = min(_STREAM_TILE, nb)
    # the groups' rings ([ng, 2t, wp] f32) have to fit VMEM: a wide
    # table of many groups moves its rows in shorter tiles
    while t > 128 and ng * 2 * t * wp * 4 > _STREAM_RING_BYTES:
        t //= 2
    tiles = _STREAM_TILES_PER_STEP
    if nb % t or t % 8:
        raise ValueError("row_block must be a multiple of 8 and, above "
                         "%d, of %d" % (t, t))
    gpad = ((ng + 15) // 16) * 16            # bf16 sublane tile
    # a group's run in the sorted tile: its phase (< 8), its rows, and
    # the rest of its last tile of eight
    tp = ((t + 14 * min(ng, t) + 15) // 16) * 16
    step = t * tiles
    code = jnp.where(grp >= 0, grp * 256 + slot_local, -1) \
        .astype(jnp.int32)
    code = jnp.pad(code, (0, (-n) % step), constant_values=-1) \
        .reshape(-1, t)
    # rows of every group in every tile: the scalar core's view
    tile_counts = jnp.sum(
        jnp.right_shift(code, 8)[:, None, :] ==
        jnp.arange(ng, dtype=jnp.int32)[None, :, None],
        axis=2, dtype=jnp.int32)                             # [tiles, G]
    base = (blk_start[:ng] * nb).astype(jnp.int32)
    end = (blk_start[1:ng + 1] * nb).astype(jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(code.shape[0] // tiles,),
        in_specs=[
            pl.BlockSpec((tiles, ng), lambda i, *_: (i, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((tiles, t), lambda i, *_: (i, 0)),
            pl.BlockSpec((w, step), lambda i, *_: (0, i))],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.SMEM((ng,), jnp.int32),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((gpad, t), jnp.float32),
            pltpu.VMEM((tp, wp), jnp.float32),
            pltpu.VMEM((ng, 2 * t, wp), jnp.float32),
            pltpu.VMEM((_STREAM_FLUSH_BUFFERS * t, wp), table.dtype),
            pltpu.SemaphoreType.DMA((_STREAM_FLUSH_BUFFERS,))])
    return pl.pallas_call(
        _stream_kernel(n=n, ng=ng, gpad=gpad, t=t, tiles=tiles, tp=tp,
                       w=w, nbuf=_STREAM_FLUSH_BUFFERS),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((blocks * nb, wp), table.dtype),
        name="partition_stream", interpret=interpret,
        **({} if interpret else {"compiler_params": _COMPILER_PARAMS}),
    )(base, end, tile_counts, code, table)


def partition_table(table: jax.Array, row_slot: jax.Array, *,
                    num_slots: int, row_block: int, group: int = 1,
                    counts: jax.Array = None, impl: str = "auto",
                    interpret: bool = False):
    """The live rows of `table` (the tree's row table, [W, n + 1]:
    histogram_mxu._row_table) in the slot-grouped kernel's layout, by
    the scheme `impl` names: (block_group [TB] i32, blocks_used [] i32,
    tab_g [TB*row_block, W, or W's lane tiles under "stream"]).

    "stream" (what "auto" means) is partition_stream: one kernel moves
    the rows. "rank" is partition_rows' rank and inverting scatter, then
    an XLA gather of the blocks in use; "argsort" the same behind the
    stable sort: the two oracles. All three give the identical table
    over the blocks in use; what lies behind them is not read.
    """
    impl = resolve_partition(impl)
    if impl != "stream":
        block_group, blocks_used, src, src_slot = partition_rows(
            row_slot, num_slots=num_slots, row_block=row_block,
            group=group, counts=counts, impl=impl, interpret=interpret)
        # the oracles gather rows: they turn the table first
        return block_group, blocks_used, _gather_used(
            table.T, src, src_slot, blocks_used * row_block,
            min(_GATHER_CHUNK_BLOCKS, block_group.shape[0]) * row_block)
    grp, _, blk_start, block_group, blocks_used = _layout(
        row_slot, num_slots=num_slots, row_block=row_block, group=group,
        counts=counts)
    return block_group, blocks_used, partition_stream(
        table, grp, row_slot % group, blk_start, row_block=row_block,
        blocks=block_group.shape[0], interpret=interpret)


def resolve_partition(impl: str) -> str:
    """What `partition_impl` resolves to (stream | rank | argsort):
    "auto" is the stream partition, which has no static condition to
    fail."""
    if impl not in ("auto", "rank", "argsort"):
        raise ValueError(f"unknown partition impl {impl!r}")
    return "stream" if impl == "auto" else impl


# ---------------------------------------------------------------------------
# kernel
# ---------------------------------------------------------------------------

def _grouped_kernel(nb: int, f: int, b: int, sg: int, nchan: int,
                    fcols: int, fh: int = 0, mm_dtype=jnp.bfloat16):
    def kernel(grp_ref, used_ref, tab_ref, out_ref):
        i = pl.program_id(0)
        g = grp_ref[i]
        prev = grp_ref[jnp.maximum(i - 1, 0)]

        @pl.when((i == 0) | (g != prev))
        def _():
            out_ref[0] = jnp.zeros_like(out_ref[0])

        @pl.when(i < used_ref[0])
        def _():
            tab = tab_ref[:].astype(jnp.float32)             # [Nb, W]
            # slot and channels come out of the row-major table, so the
            # slot-masked channel operand is built row-major too and
            # contracted over its rows
            slot = tab[:, fcols + nchan:fcols + nchan + 1].astype(jnp.int32)
            slot_oh = slot == jax.lax.broadcasted_iota(
                jnp.int32, (nb, sg), 1)                      # [Nb, Sg] bool
            lhs = jnp.concatenate(
                [jnp.where(slot_oh, tab[:, fcols + c:fcols + c + 1],
                           jnp.float32(0.0)) for c in range(nchan)],
                axis=1).astype(mm_dtype)                     # [Nb, C*Sg]
            _hist_accumulate(out_ref, lhs, tab[:, :fcols].astype(jnp.int32),
                             nb=nb, f=f, b=b, mm_dtype=mm_dtype, fh=fh,
                             rows_axis=0)

    return kernel


def _channel_window(fcols: int, nchan: int) -> int:
    """Lanes of the narrowest aligned window of the row table that holds
    a row's channels and slot (columns fcols .. fcols + nchan)."""
    cw = 128
    while fcols // cw != (fcols + nchan) // cw:
        cw *= 2
    return cw


def _grouped_tiled_kernel(nb: int, f: int, ft: int, b: int, sg: int,
                          nchan: int, coff: int, mm_dtype=jnp.bfloat16):
    """_grouped_kernel over a grid of (feature tile, block): the bins of
    the tile's `ft` features come in one block of the table, the
    channels and the slot in another (the lane tile that holds them,
    from column `coff` of it)."""

    def kernel(grp_ref, used_ref, tab_ref, ch_ref, out_ref):
        ti = pl.program_id(0)
        i = pl.program_id(1)
        g = grp_ref[i]
        prev = grp_ref[jnp.maximum(i - 1, 0)]

        @pl.when((i == 0) | (g != prev))
        def _():
            out_ref[0] = jnp.zeros_like(out_ref[0])

        @pl.when(i < used_ref[0])
        def _():
            ch = ch_ref[:].astype(jnp.float32)
            slot = ch[:, coff + nchan:coff + nchan + 1].astype(jnp.int32)
            slot_oh = slot == jax.lax.broadcasted_iota(
                jnp.int32, (nb, sg), 1)                      # [Nb, Sg] bool
            lhs = jnp.concatenate(
                [jnp.where(slot_oh, ch[:, coff + c:coff + c + 1],
                           jnp.float32(0.0)) for c in range(nchan)],
                axis=1).astype(mm_dtype)                     # [Nb, C*Sg]
            _hist_accumulate(
                out_ref, lhs,
                tab_ref[:].astype(jnp.float32).astype(jnp.int32), nb=nb,
                f=ft, b=b, mm_dtype=mm_dtype, rows_axis=0,
                live=f - ti * ft)

    return kernel


def _grouped_call(block_group: jax.Array, blocks_used: jax.Array,
                  tab_g: jax.Array, *, nb: int, f: int, b: int, sg: int,
                  ng: int, nchan: int, fcols: int, fh: int = 0,
                  feature_tile: int = 0,
                  interpret: bool = False) -> jax.Array:
    """The kernel over a gathered row table: [G, nchan*sg, F*B] f32
    (feature_tile > 0: [G, nchan*sg, tiles*feature_tile*B], the tiles
    built one after the other, each over every block). Blocks at and
    after blocks_used are neither fetched (their index repeats the last
    block in use) nor multiplied."""
    if feature_tile:
        ft = feature_tile
        tiles = -(-fcols // ft)
        cw = _channel_window(fcols, nchan)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(tiles, block_group.shape[0]),
            in_specs=[
                pl.BlockSpec((nb, ft), lambda ti, i, grp, used: (
                    jnp.minimum(i, used[0] - 1), ti)),
                pl.BlockSpec((nb, cw), lambda ti, i, grp, used: (
                    jnp.minimum(i, used[0] - 1), fcols // cw))],
            out_specs=pl.BlockSpec((1, nchan * sg, ft * b),
                                   lambda ti, i, grp, used: (grp[i], 0, ti)))
        return pl.pallas_call(
            _grouped_tiled_kernel(nb, f, ft, b, sg, nchan,
                                  fcols - fcols // cw * cw),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((ng, nchan * sg, tiles * ft * b),
                                           jnp.float32),
            name="grouped_hist_tiled", interpret=interpret,
            **({} if interpret else {"compiler_params": _COMPILER_PARAMS}),
        )(block_group, blocks_used[None], tab_g, tab_g)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(block_group.shape[0],),
        in_specs=[pl.BlockSpec(
            (nb, tab_g.shape[1]),
            lambda i, grp, used: (jnp.minimum(i, used[0] - 1), 0))],
        out_specs=pl.BlockSpec((1, nchan * sg, f * b),
                               lambda i, grp, used: (grp[i], 0, 0)))
    return pl.pallas_call(
        _grouped_kernel(nb, f, b, sg, nchan, fcols, fh=fh),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((ng, nchan * sg, f * b),
                                       jnp.float32),
        name="grouped_hist", interpret=interpret,
        **({} if interpret else {"compiler_params": _COMPILER_PARAMS}),
    )(block_group, blocks_used[None], tab_g)


@functools.partial(
    jax.jit,
    static_argnames=("num_slots", "bmax", "row_block", "num_features",
                     "double_prec", "quantized", "const_hess",
                     "partition_impl", "interpret"))
def build_histograms_scatter(bins: jax.Array, grad: jax.Array,
                             hess: jax.Array, cnt: jax.Array,
                             row_slot: jax.Array, *, num_slots: int,
                             bmax: int,
                             row_block: int = GROUPED_ROW_BLOCK,
                             num_features: int = 0,
                             double_prec: bool = True,
                             quantized: bool = False,
                             const_hess: float = 0.0,
                             slot_counts: jax.Array = None,
                             partition_impl: str = "auto",
                             operands: HistOperands = None,
                             interpret: bool = False) -> jax.Array:
    """Per-slot histograms via the slot-grouped build.

    Args mirror build_histograms_mxu_v2; row_slot < 0 routes to no
    slot. num_features > 0 marks `bins` as 4-bit packed
    (pack_bins_4bit) with that many logical features. slot_counts:
    optional per-slot row counts (route_rows_mxu emit_counts) so the
    partition skips its own counting pass. partition_impl selects how
    the live rows reach the layout (partition_table:
    auto|rank|argsort).
    operands: the tree's prepared row table
    (prepare_hist_operands(table=True)), read instead of bins, grad,
    hess and cnt.

    Returns [num_slots, F, bmax, 3] f32 (grad, hess, count).
    """
    if operands is None:
        operands = prepare_hist_operands(
            bins, grad, hess, cnt, double_prec=double_prec,
            quantized=quantized, const_hess=const_hess,
            row_multiple=1, channels=False, table=True)
    table = operands.table
    nchan = hist_num_channels(double_prec, quantized, const_hess)
    fcols = table.shape[0] - nchan - 1
    f = num_features if num_features else fcols
    fh = fcols if num_features else 0
    nb = row_block
    s = num_slots
    b = ((bmax + 127) // 128) * 128      # lane-aligned bin axis
    fb = f * b
    sg = min(group_width(nchan), s)
    ng = -(-s // sg)

    # a group's histograms too wide for VMEM, or too many features for
    # one kernel body, are built a tile of features at a time
    # (feature_tile, as for the one-hot sweep), TILED_ROW_BLOCK rows a
    # step
    ft = 0 if fh else max(0, feature_tile(
        sg, f, bmax, double_prec, quantized, row_block=nb,
        const_hess=const_hess))
    if ft:
        nb = min(nb, TILED_ROW_BLOCK)
    block_group, blocks_used, tab_g = partition_table(
        table, row_slot, num_slots=s, row_block=nb, group=sg,
        counts=slot_counts, impl=partition_impl, interpret=interpret)
    if ft:
        cw = _channel_window(fcols, nchan)
        lanes = max(-(-fcols // ft) * ft, (fcols // cw + 1) * cw)
        if tab_g.shape[1] < lanes:
            # (the oracles' unpadded table; the stream's has its lane
            # tiles already)
            tab_g = jnp.pad(tab_g, ((0, 0), (0, lanes - tab_g.shape[1])))
    out = _grouped_call(block_group, blocks_used, tab_g, nb=nb, f=f, b=b,
                        sg=sg, ng=ng, nchan=nchan, fcols=fcols, fh=fh,
                        feature_tile=ft, interpret=interpret)[..., :fb]

    # [G, C*sg, F*B] -> the shared postlude layout [1, C*S, F*B]
    out = jnp.transpose(out.reshape(ng, nchan, sg, fb), (1, 0, 2, 3)) \
        .reshape(nchan, ng * sg, fb)[:, :s].reshape(1, nchan * s, fb)
    return _combine_hist(out, nchan=nchan, s=s, f=f, b=b, bmax=bmax,
                         double_prec=double_prec, const_hess=const_hess)


def build_histograms_pallas(bins: jax.Array, grad: jax.Array,
                            hess: jax.Array, cnt: jax.Array,
                            row_slot: jax.Array, *, num_slots: int,
                            bmax: int,
                            row_block: int = GROUPED_ROW_BLOCK,
                            fchunk: int = 0,
                            partition_impl: str = "auto",
                            interpret: bool = False) -> jax.Array:
    """Compat contract of the original one-hot kernel for the portable
    grower (grower.py hist_impl="pallas"): exact full-precision
    channels on the slot-grouped build. fchunk is accepted and ignored
    (the kernel groups features by _FGROUP)."""
    del fchunk
    return build_histograms_scatter(
        bins, grad, hess, cnt, row_slot, num_slots=num_slots, bmax=bmax,
        row_block=row_block, partition_impl=partition_impl,
        interpret=interpret)
