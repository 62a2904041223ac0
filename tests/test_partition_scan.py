"""Scan-vs-argsort partition parity (`make kernels` / `make perf`).

The round-6 partition contract (docs/Performance.md): partition_rows'
"scan" implementation — stable rank via blocked prefix sums over the
per-slot counts the router already emits — produces the IDENTICAL
permutation the retained stable argsort oracle produces, hence
bit-identical (block_slot, src) layouts and byte-equal model.txt
through every downstream consumer. The adversarial shapes here are the
ones that break naive rank constructions: empty slots (zero-count
prefix entries), all rows in one slot (single giant run), a single
row, N not a multiple of row_block (padded tail rows must rank AFTER
every real row), and duplicate-heavy slot vectors (long equal runs
where only a STABLE rank preserves source order).

The perf-marked subset asserts the structural claims behind the win —
counts reuse (routing + counting + partitioning is one sweep) and the
absence of any sort primitive in the scan path's jaxpr — with no
wall-clock thresholds (tier-1 stays timing-independent).
"""

import numpy as np
import pytest

pytestmark = pytest.mark.kernels

import jax
import jax.numpy as jnp

from lightgbm_tpu.analysis.tracecheck import has_sort_primitive
from lightgbm_tpu.learner.histogram_pallas import (_stable_order_scan,
                                                   partition_rows)


def _parity(row_slot, *, num_slots, row_block, counts=None):
    """Assert scan and argsort return byte-identical layouts. (auto is
    asserted to BE scan once, in test_auto_resolves_to_scan — running
    it per-case would just re-dispatch the scan path a third time.)"""
    outs = {}
    for impl in ("argsort", "scan"):
        bs, src = partition_rows(jnp.asarray(row_slot, jnp.int32),
                                 num_slots=num_slots, row_block=row_block,
                                 counts=counts, impl=impl)
        outs[impl] = (np.asarray(bs), np.asarray(src))
    for a, b in zip(outs["argsort"], outs["scan"]):
        assert a.tobytes() == b.tobytes()
    return outs["argsort"]


class TestAdversarialParity:
    def test_empty_slots(self):
        # slots 1, 3, 5 get zero rows: their prefix-sum bases collapse
        # onto the next live slot's base
        rng = np.random.RandomState(0)
        slot = rng.choice([0, 2, 4, 6], size=777)
        _parity(slot, num_slots=8, row_block=64)

    def test_all_rows_one_slot(self):
        _parity(np.full(513, 3), num_slots=8, row_block=64)

    def test_single_row(self):
        _parity(np.array([2]), num_slots=4, row_block=8)

    def test_n_not_multiple_of_row_block(self):
        rng = np.random.RandomState(1)
        # also not a multiple of the scan's internal block size
        _parity(rng.randint(0, 6, size=5001), num_slots=6, row_block=128)

    def test_duplicate_heavy(self):
        # long equal runs: an unstable rank would permute within-slot
        # order and change which rows land in which block
        rng = np.random.RandomState(2)
        slot = np.repeat(rng.randint(0, 4, size=40), 100)
        _parity(slot, num_slots=4, row_block=32)

    def test_parked_rows_go_to_trash_slot(self):
        rng = np.random.RandomState(3)
        slot = rng.randint(-1, 5, size=900)   # -1 = parked
        bs, src = _parity(slot, num_slots=5, row_block=64)
        # parked rows appear only in trash-slot blocks
        trash_positions = np.repeat(bs == 5, 64)
        real = src[~trash_positions]
        real = real[real < 900]
        assert np.all(np.asarray(slot)[real] >= 0)

    def test_unknown_impl_raises(self):
        with pytest.raises(ValueError, match="unknown partition impl"):
            partition_rows(jnp.zeros(8, jnp.int32), num_slots=2,
                           row_block=8, impl="radix")

    def test_auto_resolves_to_scan(self):
        rng = np.random.RandomState(9)
        slot = jnp.asarray(rng.randint(0, 5, 300), jnp.int32)
        a = partition_rows(slot, num_slots=5, row_block=32, impl="auto")
        s = partition_rows(slot, num_slots=5, row_block=32, impl="scan")
        for x, y in zip(a, s):
            assert np.asarray(x).tobytes() == np.asarray(y).tobytes()


@pytest.mark.perf
class TestScanStructure:
    """Microbench-shaped assertions: the structural facts behind the
    round-6 numbers, with no wall-clock thresholds."""

    def test_counts_reuse_is_bit_identical(self):
        # the route_rows_mxu(emit_counts=True) counts replace the
        # segment_sum: same bits either way, one less O(N) pass
        rng = np.random.RandomState(4)
        slot = rng.randint(-1, 7, size=3000)
        live = np.bincount(slot[slot >= 0], minlength=7).astype(np.int32)
        a = _parity(slot, num_slots=7, row_block=128)
        b = _parity(slot, num_slots=7, row_block=128,
                    counts=jnp.asarray(live))
        for x, y in zip(a, b):
            assert x.tobytes() == y.tobytes()

    def test_scan_path_has_no_sort_primitive(self):
        # shared predicate with TRACE001 (analysis.tracecheck): the
        # same walk the lint-time contract checker runs over the
        # manifest entry; the argsort oracle doubles as its positive
        # control
        slot = jnp.asarray(np.random.RandomState(5).randint(0, 6, 2048),
                           jnp.int32)

        def scan_part(s):
            return partition_rows(s, num_slots=6, row_block=128,
                                  impl="scan")

        def argsort_part(s):
            return partition_rows(s, num_slots=6, row_block=128,
                                  impl="argsort")

        assert not has_sort_primitive(jax.make_jaxpr(scan_part)(slot))
        assert has_sort_primitive(jax.make_jaxpr(argsort_part)(slot))

    def test_stable_rank_matches_argsort_rank(self):
        # _stable_order_scan directly vs the stable sort, with tail
        # padding crossing the internal scan block boundary
        rng = np.random.RandomState(6)
        for n in (1, 17, 4096, 4097, 9000):
            slot = jnp.asarray(rng.randint(0, 5, n), jnp.int32)
            counts = jax.ops.segment_sum(jnp.ones(n, jnp.int32), slot,
                                         num_segments=6)
            start = jnp.concatenate(
                [jnp.zeros(1, jnp.int32),
                 jnp.cumsum(counts)[:-1].astype(jnp.int32)])
            got = np.asarray(_stable_order_scan(slot, start, 5))
            want = np.asarray(jnp.argsort(slot))
            assert got.tobytes() == want.tobytes(), n


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

    def test_byte_identical_scan_vs_argsort(self):
        assert self._train("scan") == self._train("argsort")
