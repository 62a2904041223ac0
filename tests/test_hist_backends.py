"""Histogram-backend parity suite (`make kernels`).

The hist_backend contract (config.py, docs/Performance.md): in the
quantized posture the mxu one-hot kernel, the slot-grouped build
(histogram_pallas.py), and the XLA segment-sum oracle produce
BIT-IDENTICAL histograms — integer gradient channels are bf16-exact and
f32 accumulation of integer sums is exact below 2^24 — so trees and
model.txt are byte-equal across backends and `hist_backend=auto`, which
chooses a formulation per pass from static shapes, is purely a speed
knob. Exact (non-quantized) mode rides hi/lo bf16 channel pairs and is
only ~f32-accurate; its error bound is pinned here too.

The fast subset (not slow) is tier-1; the slow subset adds tree- and
model-level byte parity through the boosters.
"""

import numpy as np
import pytest

pytestmark = pytest.mark.kernels

import jax
import jax.numpy as jnp

from lightgbm_tpu.data import BinnedDataset, Metadata
from lightgbm_tpu.learner.histogram import build_histograms
from lightgbm_tpu.learner.histogram_mxu import (build_histograms_mxu_auto,
                                                pack_bins_4bit,
                                                pack_route_tables,
                                                quantize_gradients,
                                                route_rows_mxu,
                                                unpack_bins_4bit)
from lightgbm_tpu.learner.histogram_pallas import (build_histograms_scatter,
                                                   partition_rows)

S = 8  # frontier slots for the kernel-level tests


def _inputs(n=2000, f=6, seed=0, max_bin=63, bin_dist="uniform"):
    """(bins, grad, hess, cnt, slot, bmax) with a chosen bin
    distribution; slots include parked rows (-1)."""
    rng = np.random.RandomState(seed)
    if bin_dist == "uniform":
        bins = rng.randint(0, max_bin, size=(n, f))
    elif bin_dist == "one_bin":            # every row in one bin
        bins = np.full((n, f), 3)
    elif bin_dist == "nan_heavy":          # 60% of rows in the NaN bin
        bins = rng.randint(0, max_bin - 1, size=(n, f))
        nan_rows = rng.rand(n) < 0.6
        bins[nan_rows] = max_bin - 1       # NaN bin = last bin
    elif bin_dist == "boundary15":         # 4-bit packing boundary
        assert max_bin == 16
        bins = rng.randint(0, 16, size=(n, f))
        bins[: n // 4] = 15                # pile on the top nibble value
    else:
        raise ValueError(bin_dist)
    bins = bins.astype(np.uint8)
    grad = jnp.asarray(rng.randn(n).astype(np.float32))
    hess = jnp.asarray(rng.rand(n).astype(np.float32) + 0.1)
    cnt = jnp.ones(n, jnp.float32)
    slot = jnp.asarray(rng.randint(-1, S, size=n).astype(np.int32))
    return jnp.asarray(bins), grad, hess, cnt, slot, max_bin


def _quant(grad, hess, seed=0):
    gq, hq, _, _ = quantize_gradients(grad, hess, jax.random.PRNGKey(seed))
    return gq, hq


class TestScatterKernelParity:
    """Pallas scatter vs MXU one-hot vs the XLA oracle."""

    def test_exact_mode_matches_oracle(self):
        bins, g, h, cnt, slot, bmax = _inputs()
        hs = build_histograms_scatter(bins, g, h, cnt, slot, num_slots=S,
                                      bmax=bmax, interpret=True)
        hr = build_histograms(bins, g, h, slot, cnt, num_slots=S,
                              bmax=bmax)
        np.testing.assert_allclose(np.asarray(hs), np.asarray(hr)[:S],
                                   rtol=1e-4, atol=1e-3)

    def test_exact_mode_f32_error_bound(self):
        # pin the accumulation-precision contract: hi/lo bf16 channel
        # pairs with f32 accumulation land within 1e-4 relative of a
        # float64 host reduce. A regression to single-bf16 sums (~2^-9
        # relative) fails this by two orders of magnitude.
        bins, g, h, cnt, slot, bmax = _inputs(n=4000, seed=5)
        hs = np.asarray(build_histograms_scatter(
            bins, g, h, cnt, slot, num_slots=S, bmax=bmax,
            interpret=True))
        bn, sl = np.asarray(bins), np.asarray(slot)
        g64 = np.asarray(g, np.float64)
        h64 = np.asarray(h, np.float64)
        want = np.zeros((S, bn.shape[1], bmax, 3))
        for r in range(bn.shape[0]):
            if sl[r] < 0:
                continue
            for f in range(bn.shape[1]):
                want[sl[r], f, bn[r, f]] += (g64[r], h64[r], 1.0)
        scale = np.abs(want).max()
        assert np.abs(hs - want).max() <= 1e-4 * scale + 1e-5

    @pytest.mark.parametrize("bin_dist", ["uniform", "one_bin",
                                          "nan_heavy"])
    def test_quantized_bit_identical(self, bin_dist):
        # the byte-parity foundation: all three backends, same bits
        bins, g, h, cnt, slot, bmax = _inputs(bin_dist=bin_dist, seed=2)
        gq, hq = _quant(g, h)
        hs = build_histograms_scatter(bins, gq, hq, cnt, slot,
                                      num_slots=S, bmax=bmax,
                                      quantized=True, interpret=True)
        hm = build_histograms_mxu_auto(bins, gq, hq, cnt, slot,
                                       num_slots=S, bmax=bmax,
                                       quantized=True, interpret=True)
        hr = build_histograms(bins, gq, hq, slot, cnt, num_slots=S,
                              bmax=bmax)
        np.testing.assert_array_equal(np.asarray(hs), np.asarray(hm))
        np.testing.assert_array_equal(np.asarray(hs),
                                      np.asarray(hr)[:S])

    def test_quantized_const_hess_channels(self):
        # const-hessian drops the hessian dot channel; the kernels
        # reconstruct it as const x count, exactly
        bins, g, h, cnt, slot, bmax = _inputs(seed=3)
        gq, _ = _quant(g, None)
        ch = 1.0
        hs = build_histograms_scatter(bins, gq, h, cnt, slot,
                                      num_slots=S, bmax=bmax,
                                      quantized=True, const_hess=ch,
                                      interpret=True)
        hm = build_histograms_mxu_auto(bins, gq, h, cnt, slot,
                                       num_slots=S, bmax=bmax,
                                       quantized=True, const_hess=ch,
                                       interpret=True)
        np.testing.assert_array_equal(np.asarray(hs), np.asarray(hm))
        np.testing.assert_array_equal(np.asarray(hs)[..., 1],
                                      np.asarray(hs)[..., 2] * ch)

    def test_packed4_boundary_bin15(self):
        # 4-bit packed storage at the nibble boundary: bin id 15 must
        # land in bin 15, not bleed into a neighbor feature's low nibble
        bins, g, h, cnt, slot, bmax = _inputs(max_bin=16,
                                              bin_dist="boundary15",
                                              seed=4)
        f = bins.shape[1]
        packed = jnp.asarray(pack_bins_4bit(np.asarray(bins)))
        gq, hq = _quant(g, h)
        hs = build_histograms_scatter(packed, gq, hq, cnt, slot,
                                      num_slots=S, bmax=bmax,
                                      num_features=f, quantized=True,
                                      interpret=True)
        hr = build_histograms(bins, gq, hq, slot, cnt, num_slots=S,
                              bmax=bmax)
        np.testing.assert_array_equal(np.asarray(hs),
                                      np.asarray(hr)[:S])

    def test_single_row_and_empty_slots(self):
        # one row per live slot, some slots empty: no cross-slot bleed,
        # empty slots all-zero
        n, f, bmax = 5, 4, 31
        rng = np.random.RandomState(9)
        bins = jnp.asarray(rng.randint(0, bmax, size=(n, f))
                           .astype(np.uint8))
        g = jnp.asarray(rng.randn(n).astype(np.float32))
        h = jnp.ones(n, jnp.float32)
        cnt = jnp.ones(n, jnp.float32)
        slot = jnp.asarray(np.array([0, 2, 4, 5, 7], np.int32))
        gq, hq = _quant(g, h)
        hs = np.asarray(build_histograms_scatter(
            bins, gq, hq, cnt, slot, num_slots=S, bmax=bmax,
            quantized=True, interpret=True))
        hr = np.asarray(build_histograms(bins, gq, hq, slot, cnt,
                                         num_slots=S, bmax=bmax))[:S]
        np.testing.assert_array_equal(hs, hr)
        for s in (1, 3, 6):
            assert not hs[s].any()

    def test_precomputed_slot_counts_match(self):
        # feeding route-emitted counts must be a pure shortcut
        bins, g, h, cnt, slot, bmax = _inputs(seed=6)
        gq, hq = _quant(g, h)
        sl = np.asarray(slot)
        counts = jnp.asarray(np.bincount(sl[sl >= 0], minlength=S)
                             .astype(np.int32))
        a = build_histograms_scatter(bins, gq, hq, cnt, slot,
                                     num_slots=S, bmax=bmax,
                                     quantized=True, interpret=True)
        b = build_histograms_scatter(bins, gq, hq, cnt, slot,
                                     num_slots=S, bmax=bmax,
                                     quantized=True, slot_counts=counts,
                                     interpret=True)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


#: (rows, features, max_bin, slots, posture) of the grouped build under
#: each partition: the layout is ONE, so the histograms are byte-equal
#: in every posture, exact mode included (the same rows in the same
#: blocks sum in the same order)
_PARTITION_CASES = {
    "five_channels_11_groups": (3100, 4, 31, 263, {}),
    "five_channels_21_groups": (3100, 3, 15, 511, {}),
    "three_channels_one_group": (1500, 6, 63, 40, {"quantized": True}),
    "three_channels_7_groups": (2900, 5, 31, 263, {"quantized": True}),
    "const_hessian_two_channels": (1700, 5, 31, 100,
                                   {"quantized": True, "const_hess": 1.0}),
    "packed4": (2300, 7, 16, 72, {"quantized": True, "packed4": True}),
    "two_lane_tiles": (1300, 130, 15, 72, {"quantized": True}),
    "rows_off_every_tile": (2048 + 257, 4, 31, 72, {"quantized": True}),
}


class TestPartitionImplParity:
    """build_histograms_scatter under partition_impl auto (the stream
    kernel), rank and argsort: byte-equal histograms."""

    @pytest.mark.parametrize("case", sorted(_PARTITION_CASES))
    @pytest.mark.parametrize("row_block", [256, 2048])
    def test_histograms_are_byte_equal(self, case, row_block):
        n, f, max_bin, slots, posture = _PARTITION_CASES[case]
        posture = dict(posture)
        rng = np.random.RandomState(len(case))
        bins = rng.randint(0, max_bin, size=(n, f)).astype(np.uint8)
        g = jnp.asarray(rng.randn(n).astype(np.float32))
        h = jnp.asarray(rng.rand(n).astype(np.float32) + 0.1)
        if posture.get("quantized"):
            g, h = _quant(g, h)
        kw = dict(num_slots=slots, bmax=max_bin, row_block=row_block,
                  interpret=True)
        if posture.pop("packed4", False):
            bins = pack_bins_4bit(bins)
            kw["num_features"] = f
        # a fifth of the rows live, the rest parked, as in a tree
        slot = np.where(rng.rand(n) < 0.2, rng.randint(0, slots, n), -1)
        got = {impl: np.asarray(build_histograms_scatter(
            jnp.asarray(bins), g, h, jnp.ones(n, jnp.float32),
            jnp.asarray(slot, jnp.int32), partition_impl=impl, **kw,
            **posture)) for impl in ("auto", "rank", "argsort")}
        assert got["auto"].tobytes() == got["argsort"].tobytes()
        assert got["rank"].tobytes() == got["argsort"].tobytes()
        assert got["auto"].any()


class TestPartitionRows:
    def test_padded_layout_invariants(self):
        rng = np.random.RandomState(1)
        n, nb = 997, 128
        slot = jnp.asarray(rng.randint(-1, S, size=n).astype(np.int32))
        block_group, used, src, _ = partition_rows(
            slot, num_slots=S, row_block=nb, interpret=True)
        bs, sr = np.asarray(block_group), np.asarray(src)
        sl = np.asarray(slot)
        assert sr.shape[0] == bs.shape[0] * nb
        # every LIVE row appears exactly once; parked rows (slot -1) are
        # not in the layout at all
        real = sr[sr < n]
        assert sorted(real.tolist()) == np.flatnonzero(sl >= 0).tolist()
        # every real row sits in a block of its own slot; padding
        # positions carry the dummy row n and contribute zeros
        pos_slot = bs[np.arange(sr.shape[0]) // nb]
        live = sr < n
        np.testing.assert_array_equal(pos_slot[live], sl[sr[live]])
        # nothing real past the blocks in use, which the kernel skips
        assert not live[int(used) * nb:].any()


class TestRouteEmitCounts:
    """route_rows_mxu(emit_counts=True): the fused routing+partition
    sweep returns the same routing plus exact per-slot counts."""

    def _route_args(self, n=1500, f=4, bmax=31, seed=0):
        rng = np.random.RandomState(seed)
        bins = jnp.asarray(rng.randint(0, bmax, size=(n, f))
                           .astype(np.uint8))
        m = 8
        z = np.zeros(m, np.int32)
        split_mask = jnp.asarray(np.array([1] + [0] * (m - 1), bool))
        feat = jnp.asarray(z)                       # split on feature 0
        thr = jnp.asarray(z + bmax // 2)
        default_left = jnp.asarray(np.zeros(m, bool))
        is_cat = jnp.asarray(np.zeros(m, bool))
        child_l = jnp.asarray(z + 1)
        child_r = jnp.asarray(z + 2)
        slot_of_node = jnp.asarray(
            np.array([-1, 0, 1] + [-1] * (m - 3), np.int32))
        cat_bitset = jnp.zeros((m, 1), jnp.uint32)
        tbl, member = pack_route_tables(
            split_mask, feat, thr, default_left, is_cat, child_l,
            child_r, slot_of_node, cat_bitset, m, bmax)
        feat_tbl = jnp.stack(
            [jnp.full(f, bmax, jnp.float32), jnp.zeros(f, jnp.float32)],
            axis=1)
        row_node = jnp.zeros(n, jnp.int32)
        return bins, row_node, tbl, member, feat_tbl, bmax

    def test_counts_match_bincount(self):
        bins, row_node, tbl, member, feat_tbl, bmax = self._route_args()
        rn, rs, counts = route_rows_mxu(bins, row_node, tbl, member,
                                        feat_tbl, emit_counts=True,
                                        num_slots=4, interpret=True)
        sl = np.asarray(rs)
        want = np.bincount(sl[sl >= 0], minlength=4)
        np.testing.assert_array_equal(np.asarray(counts), want)
        assert set(np.unique(sl)) <= {0, 1}

    def test_route_outputs_unchanged(self):
        bins, row_node, tbl, member, feat_tbl, bmax = self._route_args(
            seed=2)
        rn0, rs0 = route_rows_mxu(bins, row_node, tbl, member, feat_tbl,
                                  interpret=True)
        rn1, rs1, _ = route_rows_mxu(bins, row_node, tbl, member,
                                     feat_tbl, emit_counts=True,
                                     num_slots=4, interpret=True)
        np.testing.assert_array_equal(np.asarray(rn0), np.asarray(rn1))
        np.testing.assert_array_equal(np.asarray(rs0), np.asarray(rs1))


class TestPack4BitValidation:
    def test_refuses_wide_bins(self):
        bins = np.zeros((32, 4), np.uint8)
        bins[7, 2] = 16                      # exceeds the 4-bit limit
        assert pack_bins_4bit(bins) is None  # refuse, don't truncate

    def test_valid_packing_roundtrips(self):
        rng = np.random.RandomState(0)
        bins = rng.randint(0, 16, size=(64, 5)).astype(np.uint8)
        packed = pack_bins_4bit(bins)
        assert packed is not None
        np.testing.assert_array_equal(
            np.asarray(unpack_bins_4bit(jnp.asarray(packed), 5)), bins)


class TestBackendResolution:
    """config.hist_backend -> GBDT._resolved_hist_backend wiring."""

    def _booster(self, **over):
        import lightgbm_tpu as lgb
        rng = np.random.RandomState(3)
        X = rng.randn(300, 4).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.float32)
        params = {"objective": "binary", "num_leaves": 7,
                  "max_bin": 31, "verbosity": -1, "min_data_in_leaf": 5,
                  "use_quantized_grad": True, **over}
        ds = lgb.Dataset(X, label=y, params={"max_bin": 31})
        return lgb.Booster(params=params, train_set=ds)

    def test_config_rejects_unknown_backend(self):
        with pytest.raises(Exception):
            self._booster(hist_backend="vliw")

    def test_auto_stays_auto_and_records_the_plan(self):
        # `auto` is the static per-pass rule on every platform: nothing
        # is measured, nothing pinned to one kernel; the registry and
        # the booster hold the growth program's plan
        from lightgbm_tpu.observability import registry
        registry.reset()
        bst = self._booster(hist_backend="auto")
        g = bst.gbdt
        g._hist_impl = "mxu"
        assert g._resolved_hist_backend() == "auto"
        assert g._mxu_grow_kwargs()["hist_backend"] == "auto"
        snap = registry.hist_backend_snapshot()
        assert snap["choice"] == "auto" and snap["is_auto"] == 1
        # 7 leaves x overshoot 2: kernel widths 2, 4, 8, 15, the bridge
        # at 15 and the fixup body; 300 rows keep every pass one-hot
        assert [(p["stage"], p["sk"]) for p in snap["plan"]] == [
            ("pass", 2), ("pass", 4), ("pass", 8), ("pass", 15),
            ("bridge", 15), ("fixup", 15)]
        assert {p["formulation"] for p in snap["plan"]} == {"onehot"}
        assert snap["grouped_passes_per_tree"] == 0
        assert snap["onehot_passes_per_tree"] == 5
        text = registry.prometheus_text()
        assert "lightgbm_tpu_hist_backend_is_auto 1" in text
        assert "lightgbm_tpu_hist_backend_grouped_passes_per_tree 0" \
            in text

    def test_forced_backend_reaches_grow_kwargs(self):
        bst = self._booster(hist_backend="pallas")
        g = bst.gbdt
        g._hist_impl = "mxu"
        assert g._mxu_grow_kwargs()["hist_backend"] == "pallas"
        # pinned: a second resolution returns the cache
        assert g._resolved_hist_backend() == "pallas"
        from lightgbm_tpu.observability import registry
        snap = registry.hist_backend_snapshot()
        assert {p["formulation"] for p in snap["plan"]} == {"grouped"}
        assert snap["grouped_passes_per_tree"] == 5

    def test_plan_rides_the_build_program_span(self):
        # readable without a device sync: attributes of the span that
        # brackets the program's build
        bst = self._booster(hist_backend="auto")
        g = bst.gbdt
        assert g._hist_plan_attrs() == {}      # off the MXU growth path
        g._hist_impl = "mxu"
        assert g._hist_plan_attrs() == {
            "hist_plan": "2:onehot,4:onehot,8:onehot,15:onehot,"
                         "15:onehot,15:onehot",
            "partition": "stream",
            "grouped_passes_per_tree": 0}

    @pytest.mark.parametrize("impl,resolved", [
        ("auto", "stream"), ("rank", "rank"), ("argsort", "argsort")])
    def test_the_span_and_the_snapshot_name_the_partition(self, impl,
                                                          resolved):
        # which partition a program's grouped passes were built with:
        # static, beside the plan, whose text does not change
        from lightgbm_tpu.observability import registry
        registry.reset()
        g = self._booster(hist_backend="pallas",
                          partition_impl=impl).gbdt
        g._hist_impl = "mxu"
        attrs = g._hist_plan_attrs()
        assert attrs["partition"] == resolved
        assert attrs["hist_plan"].endswith("15:grouped")
        assert registry.hist_backend_snapshot()["partition"] == resolved
        assert "partition" not in registry.prometheus_text()

    def test_plan_without_subtraction_counts_every_child(self):
        # no sibling subtraction: a pass builds BOTH children from rows,
        # so its kernel width is the scan capacity itself (not the
        # smaller-sibling cap) and the rule sees the wider operand
        from lightgbm_tpu.learner.grower_mxu import (growth_plan,
                                                     hist_pass_plan)
        kw = dict(num_leaves=255, overshoot=2.0, hist_subtraction=False)
        gp = growth_plan(**kw)
        plan = hist_pass_plan(rows=2_625_000, **kw)
        assert [sk for st, sk, _ in plan if st == "pass"] == \
            list(gp.schedule)
        assert plan[-2][:2] == ("bridge", gp.s_max)
        assert plan[-1][:2] == ("fixup", gp.s_fix)
        from lightgbm_tpu.learner.histogram_pallas import GROUPED_MIN_WIDTH
        for _, sk, form in plan:
            assert form == ("grouped" if 5 * sk >= GROUPED_MIN_WIDTH
                            else "onehot"), (sk, form)


class TestGrowthPlan:
    """grower_mxu.growth_plan: the static schedule the one growth
    program runs, a pure function of the configuration."""

    @pytest.mark.parametrize("gate", [0.0, 0.93])
    @pytest.mark.parametrize("num_leaves,overshoot",
                             [(31, 0.0), (31, 2.0), (255, 2.0)])
    def test_schedule_doubles_up_to_the_frontier(self, num_leaves,
                                                 overshoot, gate):
        from lightgbm_tpu.learner.grower_mxu import growth_plan
        plan = growth_plan(num_leaves=num_leaves, overshoot=overshoot,
                           bridge_gate=gate)
        grown = int(np.ceil(num_leaves * overshoot)) if overshoot \
            else num_leaves
        assert (plan.L_g, plan.s_max) == (grown, grown + 1)
        # 2, 4, 8, ... and the last pass scans the whole frontier
        assert plan.schedule[-1] == plan.s_max
        assert plan.schedule[:-1] == [2 ** (i + 1) for i in
                                      range(len(plan.schedule) - 1)]
        assert plan.schedule[-2] < plan.s_max <= 2 * plan.schedule[-2]
        # the route tables hold every node id a pass can meet
        assert all(2 * s <= plan.m_cap_of(s) <= plan.m_pad
                   for s in plan.schedule)
        # overgrowing: the fixup body covers the frontier up to 512
        # slots; without it the tail runs narrow
        assert plan.s_fix == (min(512, plan.s_max) if overshoot
                              else min(64, plan.s_max))
        assert plan.k_fix == plan.s_fix // 2
        # the bridge gate never stops growth short of the leaf budget
        if overshoot and gate:
            assert num_leaves <= plan.gate_leaves <= plan.L_g
            assert plan.gate_leaves >= int(gate * plan.L_g)
        else:
            assert plan.gate_leaves is None


class TestPassRule:
    """histogram_pallas.use_grouped / grower_mxu.pass_formulation: which
    formulation a pass uses, a pure function of static shapes."""

    ROWS = 2_625_000

    @pytest.mark.parametrize("nchan", [2, 3, 4, 5])
    def test_boundary_widths(self, nchan):
        from lightgbm_tpu.learner import histogram_pallas as hp
        w = hp.GROUPED_MIN_WIDTH
        assert not hp.use_grouped(w - 1, self.ROWS)
        assert hp.use_grouped(w, self.ROWS)
        # the same boundary through the grower's own question, in slots
        from lightgbm_tpu.learner.grower_mxu import pass_formulation
        kw = dict(hist_backend="auto", nchan=nchan, rows=self.ROWS)
        sk = -(-w // nchan)
        assert pass_formulation(sk, **kw) == "grouped"
        assert pass_formulation(sk - 1, **kw) == "onehot"

    @pytest.mark.parametrize("features,bmax,first_grouped", [
        (28, 255, 72),      # Higgs: the shape the crossover was derived at
        (28, 63, 72),       # narrower than that: the same 320
        (137, 255, 40),     # MS LTR: measured, the 200-wide pass grouped
    ])
    def test_the_crossover_falls_with_the_columns(self, features, bmax,
                                                  first_grouped):
        from lightgbm_tpu.learner import histogram_pallas as hp
        from lightgbm_tpu.learner.grower_mxu import hist_pass_plan
        columns = hp.hist_columns(features, bmax)
        # only these two shapes were measured (PERF.md section 6): what
        # the law gives between and beyond them is pinned by no test
        assert hp.grouped_min_width(columns) <= hp.GROUPED_MIN_WIDTH
        plan = hist_pass_plan(rows=self.ROWS, num_leaves=255,
                              overshoot=2.0, columns=columns)
        grouped = [sk for stage, sk, form in plan
                   if form == "grouped" and stage == "pass"]
        assert min(grouped) == first_grouped
        # one threshold: every pass wider than a grouped one is grouped
        assert all(form == "grouped" for _, sk, form in plan
                   if sk >= first_grouped)
        # the default is the derivation's own shape
        assert hist_pass_plan(rows=self.ROWS, num_leaves=255,
                              overshoot=2.0) == hist_pass_plan(
            rows=self.ROWS, num_leaves=255, overshoot=2.0,
            columns=hp.hist_columns(28, 255))

    def test_tiny_data_stays_onehot(self):
        # the layout pads one row block per group at least: where that
        # outweighs the rows, the one-hot kernel keeps the pass
        from lightgbm_tpu.learner import histogram_pallas as hp
        width = 5 * 263
        groups = -(-width // 128)
        edge = hp.GROUPED_MIN_ROWS_PER_PAD * groups * hp.GROUPED_ROW_BLOCK
        assert hp.use_grouped(width, edge)
        assert not hp.use_grouped(width, edge - 1)
        assert not hp.use_grouped(width, 1000)
        # and where positions would not fit the rank sweep's f32
        assert not hp.use_grouped(width, 1 << 24)

    @pytest.mark.parametrize("hb,efb,want", [
        ("mxu", False, "onehot"), ("pallas", False, "grouped"),
        ("scatter", False, "scatter"), ("pallas", True, "onehot"),
        ("auto", True, "onehot")])
    def test_named_backends_keep_their_whole_run_meaning(self, hb, efb,
                                                         want):
        from lightgbm_tpu.learner.grower_mxu import pass_formulation
        for sk in (2, 24, 263):
            assert pass_formulation(sk, hist_backend=hb, nchan=5,
                                    rows=self.ROWS, has_efb=efb) == want

    @pytest.mark.parametrize("quant", [False, True])
    def test_the_benchmark_cells_plan(self, quant):
        # higgs_train / higgs_dp4_train: 255 leaves, overshoot 2.0,
        # subtraction on, 2,625,000 rows a chip. The six narrow passes
        # keep the one-hot kernel whatever the crossover; the quantized
        # posture's crossover comes out of the same formula, higher
        from lightgbm_tpu.learner import histogram_pallas as hp
        from lightgbm_tpu.learner.grower_mxu import hist_pass_plan
        plan = hist_pass_plan(rows=self.ROWS, num_leaves=255,
                              overshoot=2.0, quantized_grad=quant)
        assert [(st, sk) for st, sk, _ in plan] == [
            ("pass", 2), ("pass", 4), ("pass", 8), ("pass", 16),
            ("pass", 24), ("pass", 40), ("pass", 72), ("pass", 136),
            ("pass", 263), ("bridge", 263), ("fixup", 511)]
        nchan = 3 if quant else 5
        for _, sk, form in plan:
            want = "grouped" if nchan * sk >= hp.GROUPED_MIN_WIDTH \
                else "onehot"
            assert form == want, (sk, form)
        assert [form for _, _, form in plan[:5]] == ["onehot"] * 5
        assert [form for _, _, form in plan[-3:]] == ["grouped"] * 3


def _abstract_grow_args(n, f, packed4=False):
    sds = jax.ShapeDtypeStruct
    cols = (f + 1) // 2 if packed4 else f
    return (sds((n, cols), jnp.uint8), sds((n,), jnp.float32),
            sds((n,), jnp.float32), sds((n,), jnp.float32),
            sds((f,), jnp.float32), sds((f,), jnp.int32),
            sds((f,), jnp.bool_), sds((f,), jnp.bool_))


class TestOperandsPreparedOncePerTree:
    """What is fixed for a tree (the padded bins, the channel operand,
    the grouped build's row table) is built outside every pass: counted
    in the jaxpr of grow_tree_mxu on abstract arguments (no kernel
    runs), by the walk that feeds the registry's counter."""

    def _check(self, n, f, hb, posture, **over):
        from lightgbm_tpu.learner import grower_mxu as gm
        from lightgbm_tpu.learner.split import SplitHyperParams
        quant = posture == "quantized"
        ch = 0.25 if posture == "const_hessian" else 0.0
        packed4 = posture == "packed4"
        kw = dict(num_leaves=15, max_depth=0, hp=SplitHyperParams(),
                  bmax=16 if packed4 else 32, overshoot=2.0,
                  hist_backend=hb, quantized_grad=quant,
                  const_hessian=ch, packed4=packed4,
                  rng_key=jax.random.PRNGKey(0) if quant else None)
        kw.update(over)
        key = kw.pop("rng_key")
        jaxpr = jax.make_jaxpr(
            lambda *a: gm.grow_tree_mxu(*a, rng_key=key, **kw))(
                *_abstract_grow_args(n, f, packed4))
        built = gm.operand_builds(jaxpr)
        plan = gm.hist_pass_plan(
            rows=n, num_leaves=kw["num_leaves"], overshoot=kw["overshoot"],
            hist_backend=hb, quantized_grad=quant, const_hessian=ch)
        forms = [form for _, _, form in plan]
        # rows one to seven of ISSUE 29's table: nothing inside a pass
        assert built["per_pass"] == 0
        for body in built["passes"]:
            assert set(body) <= {"rank_scatter"}, body
        # ... and at most one build of each outside (hi/lo splits of
        # gradient and hessian; the table and its padding row; the
        # quantized posture's exact leaf refit stacks its own channels)
        tree = built["tree"]
        assert tree.get("bins_row_pad", 0) == 1
        assert tree.get("bins_lane_pad", 0) == int("onehot" in forms)
        assert tree.get("bins_transpose", 0) == 1
        assert tree.get("channels_stack", 0) == 1 + quant
        assert tree.get("channels_pad", 0) == \
            int("onehot" in forms) + quant
        assert tree.get("channels_split", 0) <= 2 + 2 * quant
        assert tree.get("table_bins", 0) == int("grouped" in forms)
        assert tree.get("table_concat", 0) == 2 * int("grouped" in forms)
        assert built["per_tree"] == {
            "bins_pad": 1, "bins_t": 1, "channels": 1 + quant,
            "row_table": int("grouped" in forms)}
        # no per-row scalar is made or read as a lane-padded column:
        # node and slot ids cross every kernel boundary along lanes
        assert built["id_columns_per_tree"] == 0
        assert built["id_columns_per_pass"] == 0
        assert "id_column" not in tree
        # the stream partition leaves a pass NO row-sized XLA equation;
        # under partition_impl=rank the scatter that inverts the rank
        # stays: one a grouped pass
        scatters = [b["rank_scatter"] for b in built["passes"]]
        rank = kw.get("partition_impl") == "rank"
        assert scatters == [1] * (forms.count("grouped") if rank else 0)
        return built

    @pytest.mark.parametrize("posture", ["exact", "const_hessian",
                                         "quantized", "packed4"])
    @pytest.mark.parametrize("hb", ["mxu", "auto", "pallas"])
    def test_nothing_row_sized_is_built_inside_a_pass(
            self, low_crossover, hb, posture):
        built = self._check(3001, 6, hb, posture)
        if hb == "auto":   # the lowered rule mixes both formulations
            assert built["per_tree"]["row_table"] == 1
            assert built["tree"]["bins_lane_pad"] == 1

    @pytest.mark.parametrize("partition_impl,bodies", [("auto", 0),
                                                       ("rank", 5)])
    def test_the_benchmark_cells_program(self, partition_impl, bodies):
        # higgs_train's own shape and plan: six one-hot passes, then
        # three grouped, the bridge and the fixup body: five pass
        # bodies that hold a row-sized equation under the rank path
        # (its scatter), none under the stream partition
        built = self._check(2_625_000, 28, "auto", "exact",
                            num_leaves=255, bmax=256,
                            partition_impl=partition_impl)
        assert len(built["passes"]) == bodies

    @pytest.mark.parametrize("posture", ["exact", "quantized"])
    def test_the_rank_path_keeps_its_one_scatter_a_pass(
            self, low_crossover, posture):
        self._check(3001, 6, "pallas", posture, partition_impl="rank")

    def test_the_counter_is_this_walk(self, monkeypatch):
        # the registry and the boosting.build_program span carry what
        # the walk finds in the program the booster actually traces,
        # and reading it costs no second trace of the grow core
        import lightgbm_tpu as lgb
        from lightgbm_tpu.learner import grower_mxu as gm
        from lightgbm_tpu.observability import registry
        # grow_tree_mxu's body prepares the operands once per trace
        traced, prepare = [], gm.prepare_hist_operands
        monkeypatch.setattr(
            gm, "prepare_hist_operands",
            lambda *a, **k: traced.append(1) or prepare(*a, **k))
        jax.clear_caches()
        rng = np.random.RandomState(3)
        X = rng.randn(300, 4).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.float32)
        ds = lgb.Dataset(X, label=y, params={"max_bin": 31})
        bst = lgb.Booster(params={
            "objective": "binary", "num_leaves": 7, "max_bin": 31,
            "verbosity": -1, "min_data_in_leaf": 5}, train_set=ds)
        g = bst.gbdt
        g._hist_impl, g._mxu_interpret = "mxu", True
        g._hist_backend = None
        registry.reset()
        assert "operand_builds_per_pass" not in \
            registry.hist_backend_snapshot()       # nothing traced yet
        g.train_one_iter()
        g.train_one_iter()
        assert len(traced) == 1
        snap = registry.hist_backend_snapshot()
        assert snap["operand_builds_per_pass"] == 0
        assert snap["operand_builds_per_tree"] == {
            "bins_pad": 1, "bins_t": 1, "channels": 1, "row_table": 0}
        assert snap["operand_builds_per_tree"] == \
            g._operand_builds["per_tree"]
        attrs = [sp["attrs"] for sp in registry.trace.spans()
                 if sp["name"] == "boosting.build_program"][-1]
        assert attrs["operand_builds_per_pass"] == 0
        assert attrs["operand_builds_per_tree"] == \
            "bins_pad:1,bins_t:1,channels:1,row_table:0"
        assert attrs["id_columns_per_tree"] == 0
        assert attrs["id_columns_per_pass"] == 0
        assert "lightgbm_tpu_hist_backend_operand_builds_per_pass 0" \
            in registry.prometheus_text()


def test_id_columns_counts_the_parents_idiom():
    # the negative case of id_columns_per_tree / _per_pass == 0 above:
    # what the kernels' wrappers did before the ids rode the lanes (an
    # [R] vector made an [R, 1] column on the way in, columns cut out of
    # an [R, 2] result on the way out) is what the counter finds, per
    # tree and per pass; the lane form ([1, R]) reads zero
    from lightgbm_tpu.learner import grower_mxu as gm
    rows = 4096

    def kernel_with_columns(node_col):               # [R, 1] -> [R, 2]
        return jnp.concatenate([node_col, node_col + 1], axis=1)

    def column_form(bins, row_node):
        def one_pass(rn):
            out = kernel_with_columns(rn.astype(jnp.int32)[:, None])
            return out[:rows - 8, 0], out[:rows - 8, 1]
        rn, rs = jax.lax.cond(bins[0, 0] > 0, one_pass,
                              lambda rn: (rn[:rows - 8], rn[:rows - 8]),
                              row_node)
        flushed = kernel_with_columns(row_node[:, None])[:, 0]
        return rn, rs, flushed

    def lane_form(bins, row_node):
        def one_pass(rn):
            out = jnp.concatenate([rn[None, :], rn[None, :] + 1])
            return out[0, :rows - 8], out[1, :rows - 8]
        rn, rs = jax.lax.cond(bins[0, 0] > 0, one_pass,
                              lambda rn: (rn[:rows - 8], rn[:rows - 8]),
                              row_node)
        return rn, rs, (row_node[None, :] * 2)[0]

    args = (jax.ShapeDtypeStruct((rows - 8, 4), jnp.uint8),
            jax.ShapeDtypeStruct((rows,), jnp.int32))
    built = gm.operand_builds(jax.make_jaxpr(column_form)(*args))
    assert built["id_columns_per_pass"] == 3      # 1 in, 2 out
    assert built["id_columns_per_tree"] == 2      # the flush: 1 in, 1 out
    built = gm.operand_builds(jax.make_jaxpr(lane_form)(*args))
    assert built["id_columns_per_pass"] == 0
    assert built["id_columns_per_tree"] == 0


def _grow_args(n=1500, f=4, seed=0):
    from lightgbm_tpu.learner.split import SplitHyperParams
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)
    ds = BinnedDataset.from_raw(X, Metadata(n, label=y), max_bin=31)
    p = np.full(n, 0.5, np.float32)
    args = (jnp.asarray(ds.bins), jnp.asarray(p - y),
            jnp.asarray(p * (1 - p)), jnp.ones(n, jnp.float32),
            jnp.ones(f, jnp.float32), jnp.asarray(ds.num_bins),
            jnp.asarray(ds.missing_types == 2),
            jnp.asarray(ds.is_categorical))
    kw = dict(num_leaves=15, max_depth=0,
              hp=SplitHyperParams(min_data_in_leaf=5),
              bmax=int(ds.num_bins.max()), interpret=True)
    return args, kw


def _per_pass_form(monkeypatch):
    """grow_tree_mxu as it was before a tree prepared its operands: the
    passes call the wrappers' self-preparing form (no `operands=`), so
    every pass pads the bins, stacks the channels and builds the row
    table for itself from the plain arrays."""
    from lightgbm_tpu.learner import grower_mxu as gm
    plain = {}
    real_prepare = gm.prepare_hist_operands

    def prepare(bins, grad, hess, cnt, **kw):
        plain["args"] = (bins, grad, hess, cnt)
        return real_prepare(bins, grad, hess, cnt, **kw)

    def unprepared(fn):
        def call(_b, _g, _h, _c, *a, operands=None, **kw):
            assert operands is not None
            return fn(*plain["args"], *a, **kw)
        return call

    real_route = gm.route_rows_mxu
    monkeypatch.setattr(gm, "prepare_hist_operands", prepare)
    monkeypatch.setattr(gm, "route_rows_mxu",
                        lambda _b, *a, **kw: real_route(
                            plain["args"][0], *a, **kw))
    for name in ("fused_route_hist_mxu", "build_histograms_scatter",
                 "build_histograms_mxu_auto"):
        monkeypatch.setattr(gm, name, unprepared(getattr(gm, name)))


def test_one_growth_program_per_shape():
    # the dataset is an argument of grow_tree_mxu, not a constant of
    # it: two datasets of one shape run ONE program, a third of another
    # shape builds one more (JAX's own events, the compile ledger)
    from lightgbm_tpu.learner.grower_mxu import grow_tree_mxu
    from lightgbm_tpu.observability import registry
    kw = dict(_grow_args(n=384, f=5)[1], num_leaves=7)
    jax.clear_caches()
    registry.compiles.reset()

    def grown(n, seed):
        args, kw_n = _grow_args(n=n, f=5, seed=seed)
        assert kw_n["bmax"] == kw["bmax"]
        tree, row_node = grow_tree_mxu(*args, **kw)
        assert int(tree.num_leaves) > 1 and row_node.shape == (n,)
        return registry.compiles.snapshot()["grow_tree_mxu"]

    first = grown(384, seed=1)
    assert first["lowered"] == first["built"] == 1
    again = grown(384, seed=2)
    assert again["lowered"] == again["built"] == 1
    other = grown(512, seed=3)
    assert other["lowered"] == other["built"] == 2


class TestPerPassRuleGrowsTheSameTree:
    """A tree grown with the per-pass rule against the all-one-hot
    oracle (hist_backend=mxu), at the kernel level (interpret mode)."""

    def test_rule_mixes_formulations_in_one_tree(self, low_crossover):
        from lightgbm_tpu.learner.grower_mxu import hist_pass_plan
        plan = hist_pass_plan(rows=1500, num_leaves=15)
        forms = [form for _, _, form in plan]
        assert "onehot" in forms and "grouped" in forms

    @pytest.mark.parametrize("quant", [False, True])
    def test_matches_all_onehot_oracle(self, low_crossover, quant):
        from lightgbm_tpu.learner.grower_mxu import grow_tree_mxu
        args, kw = _grow_args()
        key = jax.random.PRNGKey(3)
        t_ref, r_ref = grow_tree_mxu(*args, hist_backend="mxu",
                                     quantized_grad=quant, rng_key=key,
                                     **kw)
        t_got, r_got = grow_tree_mxu(*args, hist_backend="auto",
                                     quantized_grad=quant, rng_key=key,
                                     **kw)
        nn = int(t_ref.num_nodes)
        assert int(t_got.num_nodes) == nn
        for fld in ("split_feature", "threshold_bin", "left", "right",
                    "default_left"):
            np.testing.assert_array_equal(
                np.asarray(getattr(t_ref, fld))[:nn],
                np.asarray(getattr(t_got, fld))[:nn], err_msg=fld)
        np.testing.assert_array_equal(np.asarray(r_ref),
                                      np.asarray(r_got))
        lv_ref = np.asarray(t_ref.leaf_value)[:nn]
        lv_got = np.asarray(t_got.leaf_value)[:nn]
        if quant:
            # integer sums: bit-identical across formulations
            assert lv_ref.tobytes() == lv_got.tobytes()
        else:
            # the f32 bound of test_exact_mode_f32_error_bound
            np.testing.assert_allclose(lv_got, lv_ref, rtol=1e-4,
                                       atol=1e-5)

    @pytest.mark.parametrize("posture", ["exact", "quantized",
                                         "const_hessian"])
    @pytest.mark.parametrize("hb", ["auto", "pallas"])
    def test_prepared_operands_grow_the_per_pass_tree(
            self, low_crossover, monkeypatch, hb, posture):
        # operands prepared once per tree against the wrappers'
        # self-preparing form in every pass (what the grower did before
        # it prepared anything): the kernels see the same arrays, so
        # the trees agree to the byte, in exact mode too. 1500 rows are
        # a multiple of no row block.
        from lightgbm_tpu.learner import grower_mxu as gm
        args, kw = _grow_args()
        kw.update(hist_backend=hb, rng_key=jax.random.PRNGKey(3),
                  quantized_grad=posture == "quantized", overshoot=2.0,
                  const_hessian=0.25 if posture == "const_hessian"
                  else 0.0)
        t_new, r_new = gm.grow_tree_mxu(*args, **kw)
        _per_pass_form(monkeypatch)
        jax.clear_caches()
        t_old, r_old = gm.grow_tree_mxu(*args, **kw)
        assert int(t_new.num_leaves) > 8
        for fld in t_new._fields:
            a, b = np.asarray(getattr(t_new, fld)), \
                np.asarray(getattr(t_old, fld))
            assert a.tobytes() == b.tobytes(), fld
        assert np.asarray(r_new).tobytes() == np.asarray(r_old).tobytes()

    def test_the_per_pass_form_is_what_it_says(self, low_crossover,
                                               monkeypatch):
        # the oracle of the test above really builds per pass: the
        # walk that feeds the counter finds the operands inside the
        # pass bodies again (the tree's own, unused there, are dead
        # code to XLA)
        from lightgbm_tpu.learner import grower_mxu as gm
        _per_pass_form(monkeypatch)
        jax.clear_caches()
        args, kw = _grow_args()
        kw.update(hist_backend="auto", interpret=False)
        built = gm.operand_builds(jax.make_jaxpr(
            lambda *a: gm.grow_tree_mxu(*a, **kw))(*args))
        assert built["per_pass"] >= 4
        grouped = [b for b in built["passes"] if "table_bins" in b]
        assert grouped and all(b["table_bins"] == 1 for b in grouped)


# ----------------------------------------------------------------------
# tree/model byte parity through the boosters (interpret mode: minutes)
def _strip_backend_echo(model_str):
    """model.txt records every param, including hist_backend itself —
    the one line that legitimately differs across backends."""
    return "\n".join(l for l in model_str.splitlines()
                     if not l.startswith("[hist_backend:"))


@pytest.mark.slow
class TestModelByteParity:
    def _train(self, objective, hist_backend, num_class=1, seed=7):
        import lightgbm_tpu as lgb
        rng = np.random.RandomState(seed)
        X = rng.randn(500, 5).astype(np.float32)
        if num_class > 1:
            y = rng.randint(0, num_class, size=500).astype(np.float32)
        elif objective == "regression":
            y = (X[:, 0] + 0.3 * rng.randn(500)).astype(np.float32)
        else:
            y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)
        params = {"objective": objective, "num_leaves": 7,
                  "learning_rate": 0.2, "max_bin": 31, "verbosity": -1,
                  "min_data_in_leaf": 5, "use_quantized_grad": True,
                  "hist_backend": hist_backend}
        if num_class > 1:
            params["num_class"] = num_class
        ds = lgb.Dataset(X, label=y, params={"max_bin": 31})
        bst = lgb.Booster(params=params, train_set=ds)
        bst.update()
        g = bst.gbdt
        g._hist_impl = "mxu"
        g._mxu_interpret = True
        g._fused_run = None
        g._hist_backend = None   # re-resolve on the forced MXU path
        for _ in range(3):
            bst.update()
        return _strip_backend_echo(bst.model_to_string())

    @pytest.mark.parametrize("objective,num_class", [
        ("regression", 1), ("binary", 1), ("multiclass", 3)])
    def test_byte_identical_across_backends(self, objective, num_class,
                                            low_crossover):
        # `auto` runs the per-pass rule (its constants lowered so that
        # it mixes formulations at this size)
        ref = self._train(objective, "mxu", num_class)
        for hb in ("pallas", "scatter", "auto"):
            got = self._train(objective, hb, num_class)
            assert got == ref, f"{objective}: {hb} differs from mxu"
