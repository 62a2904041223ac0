"""Histogram-backend parity suite (`make kernels`).

The hist_backend contract (config.py, docs/Performance.md): in the
quantized posture the mxu one-hot kernel, the Pallas scatter kernel
(histogram_pallas.py), and the XLA segment-sum oracle produce
BIT-IDENTICAL histograms — integer gradient channels are bf16-exact and
f32 accumulation of integer sums is exact below 2^24 — so trees and
model.txt are byte-equal across backends and `hist_backend=auto` is
purely a speed knob. Exact (non-quantized) mode rides hi/lo bf16
channel pairs and is only ~f32-accurate; its error bound is pinned
here too.

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


class TestPartitionRows:
    def test_padded_layout_invariants(self):
        rng = np.random.RandomState(1)
        n, nb = 997, 128
        slot = jnp.asarray(rng.randint(-1, S, size=n).astype(np.int32))
        block_slot, src = partition_rows(slot, num_slots=S, row_block=nb)
        bs, sr = np.asarray(block_slot), np.asarray(src)
        sl = np.asarray(slot)
        assert sr.shape[0] == bs.shape[0] * nb
        # every real row appears exactly once
        real = sr[sr < n]
        assert sorted(real.tolist()) == list(range(n))
        # every REAL row sits in a block of its own slot (parked rows in
        # the trash slot S); padding positions carry the dummy row n and
        # may sit anywhere — they contribute zeros
        pos_slot = bs[np.arange(sr.shape[0]) // nb]
        live = sr < n
        expect = np.where(sl[sr[live]] < 0, S, sl[sr[live]])
        np.testing.assert_array_equal(pos_slot[live], expect)


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

    def test_auto_pins_mxu_on_cpu(self):
        from lightgbm_tpu.observability import registry
        registry.reset()
        bst = self._booster(hist_backend="auto")
        g = bst.gbdt
        g._hist_impl = "mxu"
        assert g._resolved_hist_backend() == "mxu"
        assert g._hist_autotune == {"choice": "mxu", "autotuned": False,
                                    "timings_ms": {}}
        snap = registry.hist_backend_snapshot()
        assert snap["choice"] == "mxu" and snap["is_mxu"] == 1
        assert "lightgbm_tpu_hist_backend_is_mxu 1" in \
            registry.prometheus_text()

    def test_forced_backend_reaches_grow_kwargs(self):
        bst = self._booster(hist_backend="pallas")
        g = bst.gbdt
        g._hist_impl = "mxu"
        assert g._mxu_grow_kwargs()["hist_backend"] == "pallas"
        # pinned: a second resolution returns the cache
        assert g._resolved_hist_backend() == "pallas"

    def test_autotune_failure_raises_with_each_backends_text(self):
        # on CPU the non-interpret kernels cannot build. A kernel that
        # does not build is not a slow kernel: nothing is chosen, and
        # the error carries each backend's exception text
        from lightgbm_tpu.learner.grower_mxu import (HistAutotuneError,
                                                     autotune_hist_backend)
        bins = jnp.asarray(np.random.RandomState(0).randint(
            0, 15, size=(256, 4)).astype(np.uint8))
        with pytest.raises(HistAutotuneError) as info:
            autotune_hist_backend(bins, num_slots=4, bmax=15)
        assert set(info.value.errors) == {"mxu", "pallas"}
        assert all("interpret" in t for t in info.value.errors.values())
        assert info.value.timings_ms == {}

    def test_autotune_failure_surfaces_and_is_recorded(self, monkeypatch):
        # through the booster: the failure is not turned into the other
        # backend, and hist_backend_snapshot() keeps the text
        import jax
        from lightgbm_tpu.observability import registry
        registry.reset()
        bst = self._booster(hist_backend="auto")
        g = bst.gbdt
        g._hist_impl = "mxu"
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with pytest.raises(Exception, match="autotune"):
            g._resolved_hist_backend()
        snap = registry.hist_backend_snapshot()
        assert snap["choice"] == "" and snap["autotuned"] is True
        assert "interpret" in snap["mxu_error"]
        assert "interpret" in snap["pallas_error"]
        # strings stay out of the Prometheus families
        assert "_error" not in registry.prometheus_text()

    def test_fused_rejects_unresolved_auto(self):
        from lightgbm_tpu.boosting.fused import build_fused_train
        with pytest.raises(ValueError, match="resolved hist_backend"):
            build_fused_train(
                objective=None, bins=None,
                feature_mask_fn=None, num_bins=None,
                missing_is_nan=None, is_cat=None,
                grower_kwargs={"hist_backend": "auto"}, shrinkage=0.1,
                extra_seed=0, needs_rng=False)


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
    def test_byte_identical_across_backends(self, objective, num_class):
        ref = self._train(objective, "mxu", num_class)
        for hb in ("pallas", "scatter"):
            got = self._train(objective, hb, num_class)
            assert got == ref, f"{objective}: {hb} differs from mxu"
