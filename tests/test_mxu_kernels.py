"""MXU kernel-path tests (interpret mode, runs on CPU).

grower_mxu/histogram_mxu are the TPU fast path; Pallas interpret mode
executes the same kernel logic on CPU so the suite can check it without
hardware. Equality target: grower.grow_tree with identical inputs.
"""

import numpy as np
import pytest

pytestmark = pytest.mark.slow  # Pallas interpret mode: minutes per test

import jax
import jax.numpy as jnp

from lightgbm_tpu.data import BinnedDataset, Metadata
from lightgbm_tpu.learner.grower import grow_tree
from lightgbm_tpu.learner.grower_mxu import grow_tree_mxu
from lightgbm_tpu.learner.histogram import build_histograms
from lightgbm_tpu.learner.histogram_mxu import (build_histograms_mxu,
                                                node_values_mxu)
from lightgbm_tpu.learner.split import SplitHyperParams


def _data(n=4000, f=6, seed=0, with_nan=False, with_cat=False):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f).astype(np.float32)
    if with_cat:
        X[:, 2] = rng.randint(0, 12, size=n)
    if with_nan:
        X[rng.rand(n) < 0.05, 1] = np.nan
    y = (np.nan_to_num(X[:, 0]) + 0.5 * np.nan_to_num(X[:, 1]) > 0) \
        .astype(np.float32)
    ds = BinnedDataset.from_raw(
        X, Metadata(n, label=y), max_bin=63,
        categorical_features=[2] if with_cat else None)
    p = np.full(n, 0.5, np.float32)
    return ds, jnp.asarray(p - y), jnp.asarray(p * (1 - p))


def _mxu_args(ds, g, h):
    """Positional args of grow_tree_mxu for a dataset + grad/hess."""
    return (jnp.asarray(ds.bins), g, h, jnp.ones(ds.num_data, jnp.float32),
            jnp.ones(ds.num_features, jnp.float32),
            jnp.asarray(ds.num_bins), jnp.asarray(ds.missing_types == 2),
            jnp.asarray(ds.is_categorical))


def _grow_both(ds, grad, hess, num_leaves=15, **extra):
    bins = jnp.asarray(ds.bins)
    cnt = jnp.ones(ds.num_data, jnp.float32)
    args = (bins, grad, hess, cnt,
            jnp.ones(ds.num_features, jnp.float32),
            jnp.asarray(ds.num_bins), jnp.asarray(ds.missing_types == 2),
            jnp.asarray(ds.is_categorical))
    kw = dict(num_leaves=num_leaves, max_depth=0,
              hp=SplitHyperParams(min_data_in_leaf=20),
              bmax=int(ds.num_bins.max()), **extra)
    t_ref, r_ref = grow_tree(*args, leafwise=False, **kw)
    t_mxu, r_mxu = grow_tree_mxu(*args, interpret=True, **kw)
    return t_ref, r_ref, t_mxu, r_mxu


def _assert_same_tree(t_ref, r_ref, t_mxu, r_mxu):
    assert int(t_ref.num_leaves) == int(t_mxu.num_leaves)
    nn = int(t_ref.num_nodes)
    for fld in ("split_feature", "threshold_bin", "left", "right",
                "is_cat", "default_left"):
        np.testing.assert_array_equal(
            np.asarray(getattr(t_ref, fld))[:nn],
            np.asarray(getattr(t_mxu, fld))[:nn], err_msg=fld)
    np.testing.assert_allclose(np.asarray(t_ref.leaf_value)[:nn],
                               np.asarray(t_mxu.leaf_value)[:nn],
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(r_ref), np.asarray(r_mxu))


class TestMXUGrower:
    def test_matches_reference_grower(self):
        ds, g, h = _data()
        _assert_same_tree(*_grow_both(ds, g, h))

    def test_matches_with_nan(self):
        ds, g, h = _data(with_nan=True, seed=1)
        _assert_same_tree(*_grow_both(ds, g, h))

    def test_matches_with_categorical(self):
        ds, g, h = _data(with_cat=True, seed=2)
        _assert_same_tree(*_grow_both(ds, g, h))

    def test_histogram_matches_scatter(self):
        ds, g, h = _data(n=3000)
        bins = jnp.asarray(ds.bins)
        cnt = jnp.ones(ds.num_data, jnp.float32)
        slot = jnp.asarray(
            np.random.RandomState(0).randint(-1, 8, size=ds.num_data)
            .astype(np.int32))
        bmax = int(ds.num_bins.max())
        hm = build_histograms_mxu(bins, g, h, cnt, slot, num_slots=8,
                                  bmax=bmax, interpret=True)
        hr = build_histograms(bins, g, h, slot, cnt, num_slots=8, bmax=bmax)
        np.testing.assert_allclose(np.asarray(hm), np.asarray(hr)[:8],
                                   rtol=1e-4, atol=1e-4)

    def test_histogram_single_precision_close(self):
        # gpu_use_dp=false mode: grad sums stay hi/lo-exact, hessian sums
        # ride single bf16 (~2^-9 relative)
        ds, g, h = _data(n=3000)
        bins = jnp.asarray(ds.bins)
        cnt = jnp.ones(ds.num_data, jnp.float32)
        slot = jnp.asarray(
            np.random.RandomState(1).randint(0, 8, size=ds.num_data)
            .astype(np.int32))
        bmax = int(ds.num_bins.max())
        hm = build_histograms_mxu(bins, g, h, cnt, slot, num_slots=8,
                                  bmax=bmax, double_prec=False,
                                  interpret=True)
        hr = build_histograms(bins, g, h, slot, cnt, num_slots=8, bmax=bmax)
        np.testing.assert_allclose(np.asarray(hm[..., 0]),
                                   np.asarray(hr)[:8, ..., 0],
                                   rtol=1e-4, atol=1e-4)  # grads hi/lo
        np.testing.assert_allclose(np.asarray(hm[..., 1]),
                                   np.asarray(hr)[:8, ..., 1],
                                   rtol=2e-2, atol=1e-2)  # hess bf16
        np.testing.assert_array_equal(np.asarray(hm[..., 2]),
                                      np.asarray(hr)[:8, ..., 2])

    def test_node_values_lookup(self):
        rng = np.random.RandomState(0)
        node = jnp.asarray(rng.randint(0, 61, size=5000).astype(np.int32))
        vals = np.full(62, np.nan, np.float32)
        vals[:61] = rng.randn(61)
        vals_d = jnp.asarray(vals)
        got = node_values_mxu(node, vals_d, interpret=True)
        np.testing.assert_allclose(np.asarray(got),
                                   vals[np.asarray(node)], rtol=1e-5)

    def test_large_node_ids_route_exactly(self):
        # child/node ids beyond 256 exercise the base-256 table packing
        ds, g, h = _data(n=20000, f=8, seed=3)
        t_ref, r_ref, t_mxu, r_mxu = _grow_both(ds, g, h, num_leaves=255)
        _assert_same_tree(t_ref, r_ref, t_mxu, r_mxu)

    @pytest.mark.parametrize("tail_cap", [0, 2, 4])
    def test_subtraction_matches_full_build(self, tail_cap):
        # the sibling-subtraction path (smaller child built, larger =
        # parent - smaller, stale parents 2 slots) must grow the same
        # tree as building every child's histogram from rows
        ds, g, h = _data(n=6000, f=8, seed=4, with_nan=True)
        args = _mxu_args(ds, g, h)
        kw = dict(num_leaves=31, max_depth=0,
                  hp=SplitHyperParams(min_data_in_leaf=20),
                  bmax=int(ds.num_bins.max()), interpret=True,
                  tail_split_cap=tail_cap)
        t0, r0 = grow_tree_mxu(*args, hist_subtraction=False, **kw)
        t1, r1 = grow_tree_mxu(*args, hist_subtraction=True, **kw)
        _assert_same_tree(t0, r0, t1, r1)

    @pytest.mark.parametrize("overshoot", [2.0, 3.0])
    def test_overshoot_prune_matches_leafwise(self, overshoot):
        # overgrow-and-prune replays the exact best-first order over the
        # recorded gains; with ample overshoot the per-row leaf outputs
        # must match the strict leaf-wise scatter grower up to kernel
        # precision, and the pruned tree must be self-consistent
        from lightgbm_tpu.learner.predict import predict_binned_tree
        ds, g, h = _data(n=6000, f=8, seed=6, with_nan=True)
        args = _mxu_args(ds, g, h)
        kw = dict(num_leaves=31, max_depth=0,
                  hp=SplitHyperParams(min_data_in_leaf=20),
                  bmax=int(ds.num_bins.max()))
        t_lw, r_lw = grow_tree(*args, leafwise=True, **kw)
        t_ov, r_ov = grow_tree_mxu(*args, interpret=True,
                                   overshoot=overshoot, **kw)
        assert int(t_ov.num_leaves) == 31
        # row_node agrees with routing fresh rows through the pruned tree
        vals_route = predict_binned_tree(
            t_ov, args[0], jnp.asarray(ds.num_bins),
            jnp.asarray(ds.missing_types == 2))
        vals_rows = np.asarray(t_ov.leaf_value)[np.asarray(r_ov)]
        np.testing.assert_allclose(np.asarray(vals_route), vals_rows,
                                   rtol=1e-5, atol=1e-6)
        # per-row outputs match strict leaf-wise growth (kernel-precision
        # tie-breaks allowed at overshoot=2 where coverage can clip)
        v_lw = np.asarray(t_lw.leaf_value)[np.asarray(r_lw)]
        if overshoot >= 3.0:
            mismatch = np.mean(np.abs(v_lw - vals_rows) > 1e-2)
            assert mismatch < 0.02, f"row mismatch rate {mismatch}"

    def test_overshoot_bridge_gate_valid_tree(self):
        # growth_bridge_gate skips the bridge/fixups for near-complete
        # trees; the pruned tree must still reach the leaf budget and
        # stay self-consistent (the gate only trims overshoot COVERAGE,
        # never the final structure invariants)
        from lightgbm_tpu.learner.predict import predict_binned_tree
        ds, g, h = _data(n=6000, f=8, seed=9, with_nan=True)
        args = _mxu_args(ds, g, h)
        t, r = grow_tree_mxu(
            *args, num_leaves=31, max_depth=0,
            hp=SplitHyperParams(min_data_in_leaf=20),
            bmax=int(ds.num_bins.max()), interpret=True, overshoot=2.0,
            bridge_gate=0.93)
        assert int(t.num_leaves) == 31
        vals_route = predict_binned_tree(
            t, args[0], jnp.asarray(ds.num_bins),
            jnp.asarray(ds.missing_types == 2))
        vals_rows = np.asarray(t.leaf_value)[np.asarray(r)]
        np.testing.assert_allclose(np.asarray(vals_route), vals_rows,
                                   rtol=1e-5, atol=1e-6)

    def test_overshoot_respects_max_depth(self):
        # overgrow-and-prune must not let the overshoot expansion smuggle
        # in nodes deeper than max_depth
        ds, g, h = _data(n=6000, f=8, seed=7)
        args = _mxu_args(ds, g, h)
        t, _ = grow_tree_mxu(
            *args, num_leaves=31, max_depth=3,
            hp=SplitHyperParams(min_data_in_leaf=20),
            bmax=int(ds.num_bins.max()), interpret=True, overshoot=2.0)
        nn = int(t.num_nodes)
        # this dataset fills the full depth-3 tree; == 8 also catches an
        # under-grown stub, not just an over-deep one
        assert int(t.num_leaves) == 8
        assert int(np.asarray(t.depth)[:nn].max()) <= 3

    def test_hybrid_tail_reaches_num_leaves(self):
        # the throttled tail must still fill the leaf budget
        ds, g, h = _data(n=6000, f=8, seed=5)
        args = _mxu_args(ds, g, h)
        t, _ = grow_tree_mxu(
            *args, num_leaves=31, max_depth=0,
            hp=SplitHyperParams(min_data_in_leaf=20),
            bmax=int(ds.num_bins.max()), interpret=True, tail_split_cap=2)
        assert int(t.num_leaves) == 31


class TestQuantizedGrad:
    """use_quantized_grad: 3-channel integer histograms + exact leaf refit
    (split search may differ from exact histograms on near-tie gains; the
    fitted leaf values must not)."""

    def test_quantized_histogram_integer_sums(self):
        from lightgbm_tpu.learner.histogram_mxu import (
            build_histograms_mxu_v2, quantize_gradients)
        ds, g, h = _data(n=3000)
        bins = jnp.asarray(ds.bins)
        cnt = jnp.ones(ds.num_data, jnp.float32)
        slot = jnp.asarray(
            np.random.RandomState(3).randint(-1, 8, size=ds.num_data)
            .astype(np.int32))
        bmax = int(ds.num_bins.max())
        gq, hq, gs, hs = quantize_gradients(g, h, jax.random.PRNGKey(0))
        hm = build_histograms_mxu_v2(bins, gq, hq, cnt, slot, num_slots=8,
                                     bmax=bmax, quantized=True,
                                     interpret=True)
        # per-slot integer sums must match an exact host scatter of gq/hq
        gq_h = np.asarray(gq)
        hq_h = np.asarray(hq)
        sl = np.asarray(slot)
        bn = np.asarray(ds.bins)
        want = np.zeros((8, ds.num_features, bmax, 3))
        for r in range(ds.num_data):
            if sl[r] < 0:
                continue
            for f in range(ds.num_features):
                want[sl[r], f, bn[r, f], 0] += gq_h[r]
                want[sl[r], f, bn[r, f], 1] += hq_h[r]
                want[sl[r], f, bn[r, f], 2] += 1
        np.testing.assert_allclose(np.asarray(hm), want, atol=1e-3)

    def test_quantization_unbiased_and_in_range(self):
        from lightgbm_tpu.learner.histogram_mxu import quantize_gradients
        rng = np.random.RandomState(0)
        g = jnp.asarray(rng.randn(20000).astype(np.float32))
        h = jnp.asarray(rng.rand(20000).astype(np.float32))
        gq, hq, gs, hs = quantize_gradients(g, h, jax.random.PRNGKey(1))
        gq_h, hq_h = np.asarray(gq), np.asarray(hq)
        assert np.all(gq_h == np.round(gq_h))
        assert gq_h.min() >= -127 and gq_h.max() <= 127
        assert hq_h.min() >= 0 and hq_h.max() <= 127
        # unbiased: mean reconstruction error ~0 vs per-element scale
        err = gq_h * float(gs) - np.asarray(g)
        assert abs(err.mean()) < float(gs) * 0.02

    def test_node_sums_exact(self):
        from lightgbm_tpu.learner.histogram_mxu import node_sums_mxu
        ds, g, h = _data(n=5000)
        cnt = jnp.ones(ds.num_data, jnp.float32)
        node = jnp.asarray(
            np.random.RandomState(4).randint(0, 29, size=ds.num_data)
            .astype(np.int32))
        got = np.asarray(node_sums_mxu(node, g, h, cnt, num_nodes=29,
                                       interpret=True))
        nh = np.asarray(node)
        gh, hh = np.asarray(g, np.float64), np.asarray(h, np.float64)
        for j in range(29):
            m = nh == j
            np.testing.assert_allclose(got[j, 0], gh[m].sum(), rtol=2e-5,
                                       atol=1e-5)
            np.testing.assert_allclose(got[j, 1], hh[m].sum(), rtol=2e-5,
                                       atol=1e-5)
            np.testing.assert_allclose(got[j, 2], m.sum(), rtol=0,
                                       atol=0.01)

    def test_quantized_grower_leaf_values_exact(self):
        # the tree may pick slightly different near-tie splits; whatever
        # tree it grows, leaf values must equal the exact refit over the
        # final row partition (incl. after overgrow-and-prune remapping)
        ds, g, h = _data(n=4000, seed=6)
        args = _mxu_args(ds, g, h)
        hp = SplitHyperParams(min_data_in_leaf=20)
        t, rn = grow_tree_mxu(
            *args, num_leaves=15, max_depth=0, hp=hp,
            bmax=int(ds.num_bins.max()), interpret=True, overshoot=2.0,
            quantized_grad=True, rng_key=jax.random.PRNGKey(2))
        assert int(t.num_leaves) == 15
        rn_h = np.asarray(rn)
        gh = np.asarray(g, np.float64)
        hh = np.asarray(h, np.float64)
        lv = np.asarray(t.leaf_value)
        for j in np.where(np.asarray(t.is_leaf))[0]:
            m = rn_h == j
            if not m.any():
                continue
            want = -gh[m].sum() / (hh[m].sum() + hp.lambda_l2)
            np.testing.assert_allclose(lv[j], want, rtol=1e-3, atol=1e-4)

    def test_quantized_grower_close_to_exact_tree(self):
        # on a well-separated dataset the quantized search picks the same
        # splits as the exact one
        ds, g, h = _data(n=4000, seed=7)
        args = _mxu_args(ds, g, h)
        kw = dict(num_leaves=15, max_depth=0,
                  hp=SplitHyperParams(min_data_in_leaf=20),
                  bmax=int(ds.num_bins.max()), interpret=True)
        t0, _ = grow_tree_mxu(*args, **kw)
        t1, _ = grow_tree_mxu(*args, **kw, quantized_grad=True,
                              rng_key=jax.random.PRNGKey(3))
        nn = int(t0.num_nodes)
        assert int(t1.num_nodes) == nn
        same = (np.asarray(t0.split_feature)[:nn] ==
                np.asarray(t1.split_feature)[:nn]).mean()
        assert same >= 0.9


class TestPerPassRule:
    """hist_backend=auto (a formulation per pass, from static shapes)
    through the drivers of grow_tree_mxu. The rule's
    constants are lowered (conftest.low_crossover) so that one small
    tree mixes one-hot and slot-grouped passes."""

    @staticmethod
    def _bytes_equal(out_a, out_b):
        for fld, x, y in zip(out_a[0]._fields, out_a[0], out_b[0]):
            assert np.asarray(x).tobytes() == np.asarray(y).tobytes(), fld
        assert np.asarray(out_a[1]).tobytes() == \
            np.asarray(out_b[1]).tobytes()

    @pytest.mark.parametrize("quant", [False, True])
    def test_sharded_rule_matches_sharded_onehot(self, low_crossover,
                                                 quant):
        # the data-parallel grower (CPU mesh, histogram psum): under the
        # rule each shard partitions its own rows and the psum is
        # unchanged. Quantized sums are integers: byte-equal to the
        # all-one-hot sharded grower; exact mode: the same structure,
        # leaf values within the f32 bound
        from lightgbm_tpu.parallel.comm import CommSpec
        from lightgbm_tpu.parallel.learner import make_sharded_grower
        from lightgbm_tpu.parallel.mesh import make_mesh
        ds, g, h = _data(n=2000, f=5, seed=6)
        args = _mxu_args(ds, g, h)
        mesh = make_mesh(4)
        comm = CommSpec(axis="data", mode="data", num_devices=4)
        outs = {}
        for hb in ("mxu", "auto"):
            grower = make_sharded_grower(
                mesh, comm, num_leaves=15, max_depth=-1,
                hp=SplitHyperParams(min_data_in_leaf=20), leafwise=False,
                bmax=int(ds.num_bins.max()), use_mxu=True, interpret=True,
                with_rng=quant,
                mxu_kwargs=dict(overshoot=2.0, hist_backend=hb,
                                quantized_grad=quant))
            with mesh:
                outs[hb] = grower(*args, *(
                    (jax.random.PRNGKey(2),) if quant else ()))
        if quant:
            self._bytes_equal(outs["auto"], outs["mxu"])
            return
        t_ref, r_ref = outs["mxu"]
        t_got, r_got = outs["auto"]
        nn = int(t_ref.num_nodes)
        assert int(t_got.num_nodes) == nn
        for fld in ("split_feature", "threshold_bin", "left", "right"):
            np.testing.assert_array_equal(
                np.asarray(getattr(t_ref, fld))[:nn],
                np.asarray(getattr(t_got, fld))[:nn], err_msg=fld)
        np.testing.assert_allclose(np.asarray(t_got.leaf_value)[:nn],
                                   np.asarray(t_ref.leaf_value)[:nn],
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(r_ref), np.asarray(r_got))

    @pytest.mark.parametrize("posture", ["const_hessian", "packed4",
                                         "no_subtraction"])
    def test_rule_in_other_postures(self, low_crossover, posture):
        # three channels exact (const-hessian regression), 4-bit packed
        # bins, and no sibling subtraction (every child built from rows,
        # so the live rows are ALL rows): structure equal to all-one-hot
        ds, g, h = _data(n=1500, f=6, seed=7)
        args = list(_mxu_args(ds, g, h))
        extra = {}
        if posture == "const_hessian":
            args[2] = jnp.ones_like(h)
            extra["const_hessian"] = 1.0
        elif posture == "packed4":
            from lightgbm_tpu.learner.histogram_mxu import pack_bins_4bit
            rng = np.random.RandomState(8)
            X = rng.randn(1500, 6).astype(np.float32)
            y = (X[:, 0] > 0).astype(np.float32)
            ds = BinnedDataset.from_raw(X, Metadata(1500, label=y),
                                        max_bin=14)
            args = list(_mxu_args(ds, g, h))
            args[0] = jnp.asarray(pack_bins_4bit(np.asarray(ds.bins)))
            extra["packed4"] = True
        else:
            extra["hist_subtraction"] = False
        kw = dict(num_leaves=15, max_depth=0,
                  hp=SplitHyperParams(min_data_in_leaf=20),
                  bmax=int(ds.num_bins.max()), interpret=True, **extra)
        t_ref, r_ref = grow_tree_mxu(*args, hist_backend="mxu", **kw)
        t_got, r_got = grow_tree_mxu(*args, hist_backend="auto", **kw)
        _assert_same_tree(t_ref, r_ref, t_got, r_got)
