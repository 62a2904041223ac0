"""Rank-vs-argsort partition parity (`make kernels` / `make perf`).

The partition contract (docs/Performance.md): partition_rows' "rank"
implementation (one sweep of triangular matmuls that carries per-group
running counts, then one collision-free scatter) produces the IDENTICAL
layout the retained stable argsort oracle produces, hence byte-equal
model.txt through every downstream consumer. The adversarial shapes
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

from lightgbm_tpu.analysis.tracecheck import has_sort_primitive
from lightgbm_tpu.learner.histogram_pallas import (_stable_positions,
                                                   partition_rows)


def _parity(row_slot, *, num_slots, row_block, group=1, counts=None):
    """Assert rank and argsort return byte-identical layouts (auto is
    asserted to BE rank once, in test_auto_resolves_to_rank), and the
    layout's own invariants: every live row exactly once, in a block of
    its group, in row order; parked rows nowhere."""
    row_slot = np.asarray(row_slot)
    outs = {}
    for impl in ("argsort", "rank"):
        out = partition_rows(jnp.asarray(row_slot, jnp.int32),
                             num_slots=num_slots, row_block=row_block,
                             group=group, counts=counts, impl=impl,
                             interpret=True)
        outs[impl] = tuple(np.asarray(o) for o in out)
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


class TestAdversarialParity:
    def test_empty_slots(self):
        # slots 1, 3, 5 get zero rows: their groups still own a block
        rng = np.random.RandomState(0)
        slot = rng.choice([0, 2, 4, 6], size=777)
        _parity(slot, num_slots=8, row_block=64)

    def test_all_rows_one_slot(self):
        _parity(np.full(513, 3), num_slots=8, row_block=64)

    def test_single_row(self):
        _parity(np.array([2]), num_slots=4, row_block=8)

    def test_n_not_multiple_of_row_block(self):
        rng = np.random.RandomState(1)
        # also not a multiple of the rank sweep's 2048-row step
        _parity(rng.randint(0, 6, size=5001), num_slots=6, row_block=128)

    def test_duplicate_heavy(self):
        # long equal runs: an unstable rank would permute within-slot
        # order and change which rows land in which block
        rng = np.random.RandomState(2)
        slot = np.repeat(rng.randint(0, 4, size=40), 100)
        _parity(slot, num_slots=4, row_block=32)

    def test_parked_rows_are_not_in_the_layout(self):
        rng = np.random.RandomState(3)
        slot = rng.randint(-1, 5, size=900)   # -1 = parked
        bg, used, src, _ = _parity(slot, num_slots=5, row_block=64)
        assert (src < 900).sum() == (slot >= 0).sum()
        # ... so the blocks in use cover the live rows, not all rows
        assert int(used) <= -(-int((slot >= 0).sum()) // 64) + 5

    def test_all_rows_parked(self):
        # a pass whose every row sits in a finished leaf: each group
        # still owns its (all-padding) block, and no slot is delivered
        bg, used, src, src_slot = _parity(np.full(700, -1), num_slots=6,
                                          row_block=64, group=3)
        assert int(used) == 2 and (src == 700).all()
        assert (src_slot == 255).all()

    def test_one_group_holds_all_rows(self):
        # group >= num_slots: one group, and the slot within it is the
        # slot itself
        rng = np.random.RandomState(4)
        slot = rng.randint(-1, 7, size=1111)
        bg, used, src, src_slot = _parity(slot, num_slots=7,
                                          row_block=128, group=25)
        assert (bg == 0).all()
        real = src < 1111
        np.testing.assert_array_equal(src_slot[real], slot[src[real]])

    @pytest.mark.parametrize("n", [2049, 4097, 8193, 12345])
    def test_slot_delivery_off_every_row_block(self, n):
        # N a multiple of none of the row blocks in use (1024 ... 8192)
        # nor of the rank sweep's step
        rng = np.random.RandomState(n)
        slot = rng.randint(-1, 50, size=n)
        _parity(slot, num_slots=50, row_block=256, group=25)

    def test_group_too_wide_for_the_slot_bits(self):
        with pytest.raises(ValueError, match="at most 254 slots"):
            partition_rows(jnp.zeros(8, jnp.int32), num_slots=300,
                           row_block=8, group=255)

    def test_unknown_impl_raises(self):
        with pytest.raises(ValueError, match="unknown partition impl"):
            partition_rows(jnp.zeros(8, jnp.int32), num_slots=2,
                           row_block=8, impl="radix")

    def test_auto_resolves_to_rank(self):
        rng = np.random.RandomState(9)
        slot = jnp.asarray(rng.randint(0, 5, 300), jnp.int32)
        a = partition_rows(slot, num_slots=5, row_block=32, impl="auto",
                           interpret=True)
        s = partition_rows(slot, num_slots=5, row_block=32, impl="rank",
                           interpret=True)
        for x, y in zip(a, s):
            assert np.asarray(x).tobytes() == np.asarray(y).tobytes()

    @pytest.mark.parametrize("case", ["all_parked", "grouped_slots",
                                      "one_group_holds_all"])
    def test_groups_and_parked(self, case):
        # slot GROUPS (what the grouped kernel partitions by): several
        # slots share a block; all rows parked leaves one empty block
        # per group
        rng = np.random.RandomState(11)
        if case == "all_parked":
            bg, used, src, _ = _parity(np.full(700, -1), num_slots=60,
                                       row_block=64, group=25)
            assert int(used) == 3 and (src == 700).all()
        elif case == "grouped_slots":
            _parity(rng.randint(-1, 60, size=3000), num_slots=60,
                    row_block=64, group=25)
        else:
            _parity(rng.randint(25, 50, size=1000), num_slots=60,
                    row_block=64, group=25)


@pytest.mark.perf
class TestRankStructure:
    """Microbench-shaped assertions: the structural facts behind the
    chip numbers, with no wall-clock thresholds."""

    def test_counts_reuse_is_bit_identical(self):
        # the route_rows_mxu(emit_counts=True) counts replace the
        # segment_sum: same bits either way, one less O(N) pass
        rng = np.random.RandomState(4)
        slot = rng.randint(-1, 7, size=3000)
        live = np.bincount(slot[slot >= 0], minlength=7).astype(np.int32)
        a = _parity(slot, num_slots=7, row_block=128, group=3)
        b = _parity(slot, num_slots=7, row_block=128, group=3,
                    counts=jnp.asarray(live))
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

    def test_byte_identical_rank_vs_argsort(self):
        assert self._train("rank") == self._train("argsort")
