"""Partition parity against the argsort oracle (`make kernels` /
`make perf`).

The partition contract (docs/Performance.md): the stream partition
(partition_stream: ONE kernel sweeps the row table, ranks each tile by a
triangular matmul, moves its live rows by a permutation matmul and
writes them to the layout by DMA; what partition_impl=auto runs) and
the "rank" path (the rank sweep, one collision-free scatter, an XLA
gather) both produce the IDENTICAL gathered table the retained stable
argsort oracle produces, over every row of every block in use, hence
byte-equal model.txt through every downstream consumer. The
adversarial shapes
here are the ones that break naive rank constructions: empty slots
(zero-count groups still own a block), all rows in one slot (single
giant run), a single row, N not a multiple of row_block or of the rank
sweep's tile (padded tail rows must not be counted), all rows parked,
and duplicate-heavy slot vectors (long equal runs where only a STABLE
rank preserves source order).

The perf-marked subset asserts the structural claims behind the win
(counts reuse, no sort primitive in the rank path's jaxpr, parked rows
out of the layout) with no wall-clock thresholds (tier-1 stays
timing-independent).
"""

import numpy as np
import pytest

pytestmark = pytest.mark.kernels

import jax
import jax.numpy as jnp

from lightgbm_tpu.analysis.tracecheck import (has_sort_primitive,
                                              primitive_names)
from lightgbm_tpu.learner.histogram_pallas import (_stable_positions,
                                                   partition_rows,
                                                   partition_table,
                                                   resolve_partition)

#: the two partitions held to the oracle: the production kernel (what
#: "auto" resolves to) and the parent's path, kept as the second oracle
IMPLS = ["auto", "rank"]


def _row_table(n, width, seed=0):
    """A row table as histogram_mxu._row_table lays it out: [width,
    n + 1] bf16 (a row's values down a column), bf16-exact values
    (negative ones too), the slot 255 everywhere, the last row the
    all-zero padding row."""
    rng = np.random.RandomState(seed)
    tab = rng.randint(-120, 256, (n + 1, width)).astype(np.float32)
    tab[:, -1] = 255
    tab[-1, :-1] = 0
    return jnp.asarray(tab.T, jnp.bfloat16)


def _table_parity(impl, row_slot, *, num_slots, row_block, group=1,
                  counts=None, width=7):
    """The WHOLE gathered table of `impl` against the argsort oracle's:
    block_group, blocks_used and every row of every block in use, bit
    for bit."""
    n = row_slot.shape[0]
    table = _row_table(n, width, seed=n)
    outs = {}
    for which in ("argsort", impl):
        bg, used, tab = partition_table(
            table, jnp.asarray(row_slot, jnp.int32), num_slots=num_slots,
            row_block=row_block, group=group, counts=counts, impl=which,
            interpret=True)
        tab = tab[:int(used) * row_block, :width]
        assert bg.shape[0] * row_block >= tab.shape[0]
        outs[which] = (np.asarray(bg), int(used), np.asarray(
            jax.lax.bitcast_convert_type(tab, jnp.uint16)))
    for a, b in zip(outs["argsort"], outs[impl]):
        assert np.array_equal(a, b)
    # the table's own content: a live position holds its row with the
    # slot within the group in the last column, padding is slot-less
    tab = np.asarray(tab.astype(jnp.float32))
    live = np.flatnonzero((row_slot >= 0) & (row_slot < num_slots))
    real = tab[:, -1] != 255
    assert real.sum() == live.size
    assert (tab[~real, :-1] == 0).all()


def _parity(row_slot, *, num_slots, row_block, group=1, counts=None,
            impl="rank", width=7):
    """Assert `impl` and argsort give byte-identical gathered tables
    (_table_parity), rank and argsort byte-identical row ids, and the
    layout's own invariants: every live row exactly once, in a block of
    its group, in row order; parked rows nowhere."""
    row_slot = np.asarray(row_slot)
    _table_parity(impl, row_slot, num_slots=num_slots,
                  row_block=row_block, group=group, counts=counts,
                  width=width)
    outs = {}
    for which in ("argsort", "rank"):
        out = partition_rows(jnp.asarray(row_slot, jnp.int32),
                             num_slots=num_slots, row_block=row_block,
                             group=group, counts=counts, impl=which,
                             interpret=True)
        outs[which] = tuple(np.asarray(o) for o in out)
    for a, b in zip(outs["argsort"], outs["rank"]):
        assert a.tobytes() == b.tobytes()
    bg, used, src, src_slot = outs["argsort"]
    n = row_slot.shape[0]
    live = (row_slot >= 0) & (row_slot < num_slots)
    real = src < n
    assert sorted(src[real].tolist()) == np.flatnonzero(live).tolist()
    # the slot within the group rides beside the row id: the grouped
    # build writes it into the gathered table, whose static part is
    # built once per tree
    np.testing.assert_array_equal(src_slot[real],
                                  row_slot[src[real]] % group)
    assert (src_slot[~real] == 255).all()
    assert (src[~real] == n).all()
    pos_grp = np.repeat(bg, row_block)
    np.testing.assert_array_equal(pos_grp[real],
                                  row_slot[src[real]] // group)
    assert not real[int(used) * row_block:].any()
    for g in range(-(-num_slots // group)):
        rows_g = src[real & (pos_grp == g)]
        assert (np.diff(rows_g) > 0).all()      # stable: row order kept
        assert (bg[:int(used)] == g).any()      # even an empty group
    return outs["argsort"]


@pytest.mark.parametrize("impl", IMPLS)
class TestAdversarialParity:
    def test_empty_slots(self, impl):
        # slots 1, 3, 5 get zero rows: their groups still own a block
        rng = np.random.RandomState(0)
        slot = rng.choice([0, 2, 4, 6], size=777)
        _parity(slot, num_slots=8, row_block=64, impl=impl)

    def test_all_rows_one_slot(self, impl):
        _parity(np.full(513, 3), num_slots=8, row_block=64, impl=impl)

    def test_single_row(self, impl):
        _parity(np.array([2]), num_slots=4, row_block=8, impl=impl)

    def test_n_not_multiple_of_row_block(self, impl):
        rng = np.random.RandomState(1)
        # also not a multiple of either sweep's 2048-row step
        _parity(rng.randint(0, 6, size=5001), num_slots=6, row_block=128,
                impl=impl)

    def test_duplicate_heavy(self, impl):
        # long equal runs: an unstable rank would permute within-slot
        # order and change which rows land in which block
        rng = np.random.RandomState(2)
        slot = np.repeat(rng.randint(0, 4, size=40), 100)
        _parity(slot, num_slots=4, row_block=32, impl=impl)

    def test_parked_rows_are_not_in_the_layout(self, impl):
        rng = np.random.RandomState(3)
        slot = rng.randint(-1, 5, size=900)   # -1 = parked
        bg, used, src, _ = _parity(slot, num_slots=5, row_block=64,
                                   impl=impl)
        assert (src < 900).sum() == (slot >= 0).sum()
        # ... so the blocks in use cover the live rows, not all rows
        assert int(used) <= -(-int((slot >= 0).sum()) // 64) + 5

    def test_all_rows_parked(self, impl):
        # a pass whose every row sits in a finished leaf: each group
        # still owns its (all-padding) block, and no slot is delivered
        bg, used, src, src_slot = _parity(np.full(700, -1), num_slots=6,
                                          row_block=64, group=3,
                                          impl=impl)
        assert int(used) == 2 and (src == 700).all()
        assert (src_slot == 255).all()

    def test_one_group_holds_all_rows(self, impl):
        # group >= num_slots: one group, and the slot within it is the
        # slot itself
        rng = np.random.RandomState(4)
        slot = rng.randint(-1, 7, size=1111)
        bg, used, src, src_slot = _parity(slot, num_slots=7,
                                          row_block=128, group=25,
                                          impl=impl)
        assert (bg == 0).all()
        real = src < 1111
        np.testing.assert_array_equal(src_slot[real], slot[src[real]])

    @pytest.mark.parametrize("n", [2049, 4097, 8193, 12345])
    def test_slot_delivery_off_every_row_block(self, n, impl):
        # N a multiple of none of the row blocks in use (1024 ... 8192)
        # nor of either sweep's step or tile
        rng = np.random.RandomState(n)
        slot = rng.randint(-1, 50, size=n)
        _parity(slot, num_slots=50, row_block=256, group=25, impl=impl)

    @pytest.mark.parametrize("groups", [1, 11, 21])
    def test_group_counts_of_the_growth_passes(self, groups, impl):
        # 25 slots a group, as at five channels: the narrowest grouped
        # pass, the 263-slot pass and the 511-slot fixup; full 256-row
        # tiles, the last one ragged
        rng = np.random.RandomState(groups)
        slots = 25 * groups - (groups > 1) * 12
        slot = rng.randint(0, slots, size=3000)
        slot[rng.rand(3000) > 0.2] = -1       # a fifth live, as a tree's
        _parity(slot, num_slots=slots, row_block=512, group=25,
                impl=impl)

    @pytest.mark.parametrize("width", [23, 34, 143])
    def test_table_widths_of_the_cells(self, width, impl):
        # one lane tile (Expo's 23 and Higgs's 34 columns) and two (MS
        # LTR's 143)
        rng = np.random.RandomState(width)
        slot = rng.randint(-1, 72, size=2500)
        _parity(slot, num_slots=72, row_block=256, group=25, impl=impl,
                width=width)

    def test_a_chunk_boundary_inside_a_tile(self, impl):
        # one group takes nearly every row: its ring fills a chunk in
        # every tile and the boundary falls at every phase
        rng = np.random.RandomState(8)
        slot = np.where(rng.rand(4000) < 0.93, 1, rng.randint(-1, 60, 4000))
        _parity(slot, num_slots=60, row_block=256, group=25, impl=impl)

    @pytest.mark.parametrize("case", ["all_parked", "grouped_slots",
                                      "one_group_holds_all"])
    def test_groups_and_parked(self, case, impl):
        # slot GROUPS (what the grouped kernel partitions by): several
        # slots share a block; all rows parked leaves one empty block
        # per group
        rng = np.random.RandomState(11)
        if case == "all_parked":
            bg, used, src, _ = _parity(np.full(700, -1), num_slots=60,
                                       row_block=64, group=25, impl=impl)
            assert int(used) == 3 and (src == 700).all()
        elif case == "grouped_slots":
            _parity(rng.randint(-1, 60, size=3000), num_slots=60,
                    row_block=64, group=25, impl=impl)
        else:
            _parity(rng.randint(25, 50, size=1000), num_slots=60,
                    row_block=64, group=25, impl=impl)


class TestPartitionArguments:
    def test_group_too_wide_for_the_slot_bits(self):
        with pytest.raises(ValueError, match="at most 254 slots"):
            partition_rows(jnp.zeros(8, jnp.int32), num_slots=300,
                           row_block=8, group=255)

    @pytest.mark.parametrize("fn", ["rows", "table", "resolve"])
    def test_unknown_impl_raises(self, fn):
        with pytest.raises(ValueError, match="unknown partition impl"):
            if fn == "rows":
                partition_rows(jnp.zeros(8, jnp.int32), num_slots=2,
                               row_block=8, impl="radix")
            elif fn == "table":
                partition_table(_row_table(8, 4), jnp.zeros(8, jnp.int32),
                                num_slots=2, row_block=8, impl="stream")
            else:
                resolve_partition("scan")

    def test_auto_resolves_to_the_stream_partition(self):
        # whatever the shape: the kernel has no static condition to
        # fail (the rank path's f32 positions stopped at 2^24 rows)
        assert resolve_partition("auto") == "stream"
        assert resolve_partition("rank") == "rank"
        assert resolve_partition("argsort") == "argsort"
        rng = np.random.RandomState(9)
        slot = jnp.asarray(rng.randint(0, 5, 300), jnp.int32)

        def part(impl):
            return jax.make_jaxpr(lambda t, s: partition_table(
                t, s, num_slots=5, row_block=32, impl=impl))(
                    _row_table(300, 6), slot)

        kernels = {eqn.params["name"]
                   for eqn in part("auto").jaxpr.eqns
                   if eqn.primitive.name == "pallas_call"}
        assert kernels == {"partition_stream"}

    def test_a_row_block_the_tile_does_not_divide_is_refused(self):
        with pytest.raises(ValueError, match="row_block"):
            partition_table(_row_table(700, 4), jnp.zeros(700, jnp.int32),
                            num_slots=2, row_block=300, interpret=True)


@pytest.mark.perf
class TestRankStructure:
    """Microbench-shaped assertions: the structural facts behind the
    chip numbers, with no wall-clock thresholds."""

    @pytest.mark.parametrize("impl", IMPLS)
    def test_counts_reuse_is_bit_identical(self, impl):
        # the route_rows_mxu(emit_counts=True) counts replace the
        # segment_sum: same bits either way, one less O(N) pass
        rng = np.random.RandomState(4)
        slot = rng.randint(-1, 7, size=3000)
        live = np.bincount(slot[slot >= 0], minlength=7).astype(np.int32)
        a = _parity(slot, num_slots=7, row_block=128, group=3, impl=impl)
        b = _parity(slot, num_slots=7, row_block=128, group=3,
                    counts=jnp.asarray(live), impl=impl)
        for x, y in zip(a, b):
            assert x.tobytes() == y.tobytes()

    def test_rank_path_has_no_sort_primitive(self):
        # shared predicate with TRACE001 (analysis.tracecheck): the
        # same walk the lint-time contract checker runs over the
        # manifest entry; the argsort oracle doubles as its positive
        # control
        slot = jnp.asarray(np.random.RandomState(5).randint(0, 6, 2048),
                           jnp.int32)

        def rank_part(s):
            return partition_rows(s, num_slots=6, row_block=128,
                                  impl="rank")

        def argsort_part(s):
            return partition_rows(s, num_slots=6, row_block=128,
                                  impl="argsort")

        assert not has_sort_primitive(jax.make_jaxpr(rank_part)(slot))
        assert has_sort_primitive(jax.make_jaxpr(argsort_part)(slot))

    def test_stream_pass_moves_no_row_outside_its_kernel(self):
        # the grouped pass under partition_impl=auto, as the grower
        # calls it (the tree's row table, the route's counts): no
        # scatter, gather or sort primitive anywhere in its jaxpr, the
        # kernels' bodies included; the rank path is the positive
        # control (its inversion is a scatter, its row move a gather)
        from lightgbm_tpu.learner.histogram_mxu import prepare_hist_operands
        from lightgbm_tpu.learner.histogram_pallas import \
            build_histograms_scatter
        rng = np.random.RandomState(12)
        n, f, slots = 1200, 5, 60
        bins = jnp.asarray(rng.randint(0, 31, (n, f)), jnp.uint8)
        vec = jnp.asarray(rng.randint(-9, 9, n), jnp.float32)
        ops = prepare_hist_operands(bins, vec, vec, jnp.ones(n), lanes=True,
                                    table=True, quantized=True)

        def grouped_pass(impl):
            return primitive_names(jax.make_jaxpr(
                lambda slot, cts: build_histograms_scatter(
                    None, None, None, None, slot, num_slots=slots,
                    bmax=31, quantized=True, row_block=64,
                    slot_counts=cts, partition_impl=impl, operands=ops))(
                jnp.zeros(n, jnp.int32), jnp.zeros(slots, jnp.int32)))

        moves = {"sort", "gather", "scatter", "scatter-add", "scatter_add"}
        assert not grouped_pass("auto") & moves
        assert {"gather", "scatter"} <= grouped_pass("rank")
        assert "sort" in grouped_pass("argsort")

    def test_stable_positions_match_argsort_rank(self):
        # _stable_positions directly vs the stable sort, with tail
        # padding crossing the sweep's tile and step boundaries
        rng = np.random.RandomState(6)
        for n in (1, 17, 2048, 2049, 9000):
            grp = rng.randint(-1, 5, n)
            base = np.array([0, 100000, 200000, 300000, 400000])
            got = np.asarray(_stable_positions(
                jnp.asarray(grp, jnp.int32), jnp.asarray(base, jnp.int32),
                num_groups=5, dump=1 << 20, interpret=True))
            want = np.full(n, 1 << 20)
            for g in range(5):
                idx = np.flatnonzero(grp == g)
                want[idx] = base[g] + np.arange(idx.size)
            np.testing.assert_array_equal(got, want, err_msg=str(n))


@pytest.mark.slow
class TestFusedModelParity:
    """Byte-equal model.txt through the fused multi-tree path with the
    pallas scatter backend (the consumer that actually partitions)."""

    def _train(self, partition_impl):
        import lightgbm_tpu as lgb
        rng = np.random.RandomState(7)
        X = rng.randn(500, 5).astype(np.float32)
        y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)
        params = {"objective": "binary", "num_leaves": 7,
                  "learning_rate": 0.2, "max_bin": 31, "verbosity": -1,
                  "min_data_in_leaf": 5, "use_quantized_grad": True,
                  "hist_backend": "pallas",
                  "partition_impl": partition_impl}
        ds = lgb.Dataset(X, label=y, params={"max_bin": 31})
        bst = lgb.Booster(params=params, train_set=ds)
        bst.update()
        g = bst.gbdt
        g._hist_impl = "mxu"
        g._mxu_interpret = True
        g._fused_run = None
        bst.update_batch(3)          # the fused scan dispatch
        return "\n".join(
            ln for ln in bst.model_to_string().splitlines()
            if not ln.startswith("[partition_impl:"))

    def test_byte_identical_three_ways(self):
        models = {impl: self._train(impl)
                  for impl in ("auto", "rank", "argsort")}
        assert models["auto"] == models["argsort"]
        assert models["rank"] == models["argsort"]
