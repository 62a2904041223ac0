"""4-bit packed bin storage (reference 4-bit DenseBin, dense_bin.hpp:42).

Packing is a pure storage transform: the MXU kernels unpack nibbles in
VMEM, so packed and unpacked training must produce bit-identical trees.
Fast layout tests run in the default tier; kernel-parity tests ride the
slow tier (Pallas interpret mode).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from lightgbm_tpu.data import BinnedDataset, Metadata
from lightgbm_tpu.learner.grower_mxu import grow_tree_mxu
from lightgbm_tpu.learner.histogram_mxu import (
    build_histograms_mxu_v2, fused_route_hist_mxu, pack_bins_4bit,
    pack_route_tables, route_rows_mxu, unpack_bins_4bit)
from lightgbm_tpu.learner.split import SplitHyperParams


def _small_bin_data(n=3000, f=7, seed=0, with_nan=False):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f).astype(np.float32)
    if with_nan:
        X[rng.rand(n) < 0.05, 1] = np.nan
    y = (np.nan_to_num(X[:, 0]) + 0.5 * np.nan_to_num(X[:, 1]) > 0) \
        .astype(np.float32)
    ds = BinnedDataset.from_raw(X, Metadata(n, label=y), max_bin=15)
    assert int(ds.num_bins.max()) <= 16
    p = np.full(n, 0.5, np.float32)
    return ds, jnp.asarray(p - y), jnp.asarray(p * (1 - p))


class TestPackLayout:
    def test_roundtrip_even_odd(self):
        rng = np.random.RandomState(3)
        for f in (1, 2, 7, 8, 15):
            bins = rng.randint(0, 16, size=(64, f)).astype(np.uint8)
            packed = pack_bins_4bit(bins)
            assert packed.shape == (64, (f + 1) // 2)
            np.testing.assert_array_equal(
                unpack_bins_4bit(packed, f), bins)

    def test_roundtrip_device(self):
        rng = np.random.RandomState(4)
        bins = rng.randint(0, 16, size=(32, 5)).astype(np.uint8)
        packed = pack_bins_4bit(jnp.asarray(bins))
        np.testing.assert_array_equal(
            np.asarray(unpack_bins_4bit(packed, 5)), bins)

    def test_split_nibble_layout(self):
        # feature j < Fh in column j's low nibble, Fh+j in the high one
        bins = np.arange(8, dtype=np.uint8).reshape(1, 8) % 16
        packed = pack_bins_4bit(bins)
        fh = 4
        for j in range(8):
            col = j if j < fh else j - fh
            nib = (packed[0, col] >> 4) if j >= fh else (packed[0, col] & 15)
            assert nib == bins[0, j]


@pytest.mark.slow
class TestPackedKernels:
    def test_hist_v2_parity(self):
        ds, g, h = _small_bin_data()
        bins = jnp.asarray(ds.bins)
        cnt = jnp.ones(ds.num_data, jnp.float32)
        slot = jnp.asarray(
            np.random.RandomState(0).randint(-1, 8, size=ds.num_data)
            .astype(np.int32))
        bmax = int(ds.num_bins.max())
        h_ref = build_histograms_mxu_v2(bins, g, h, cnt, slot, num_slots=8,
                                        bmax=bmax, interpret=True)
        h_pk = build_histograms_mxu_v2(
            pack_bins_4bit(bins), g, h, cnt, slot, num_slots=8, bmax=bmax,
            num_features=ds.num_features, interpret=True)
        np.testing.assert_array_equal(np.asarray(h_ref), np.asarray(h_pk))

    def _route_tables(self, ds, m_pad=128):
        f = ds.num_features
        m1 = 8
        w_cat = (int(ds.num_bins.max()) + 31) // 32
        split_mask = jnp.zeros(m1, bool).at[0].set(True)
        feat = jnp.zeros(m1, jnp.int32).at[0].set(f - 1)  # high nibble
        thr = jnp.zeros(m1, jnp.int32).at[0].set(7)
        child_l = jnp.full(m1, m1 - 1, jnp.int32).at[0].set(1)
        child_r = jnp.full(m1, m1 - 1, jnp.int32).at[0].set(2)
        slot_of = jnp.full(m1, -1, jnp.int32).at[1].set(0).at[2].set(1)
        return pack_route_tables(
            split_mask, feat, thr, jnp.zeros(m1, bool),
            jnp.zeros(m1, bool), child_l, child_r, slot_of,
            jnp.zeros((m1, w_cat), jnp.uint32), m_pad,
            int(ds.num_bins.max()))

    def test_route_parity(self):
        ds, _, _ = _small_bin_data(with_nan=True, seed=5)
        bins = jnp.asarray(ds.bins)
        tbl, member = self._route_tables(ds)
        feat_tbl = jnp.stack(
            [jnp.asarray(ds.num_bins, jnp.float32),
             jnp.asarray(ds.missing_types == 2, jnp.float32)], axis=1)
        node0 = jnp.zeros(ds.num_data, jnp.int32)
        rn_ref, rs_ref = route_rows_mxu(bins, node0, tbl, member,
                                        feat_tbl, interpret=True)
        rn_pk, rs_pk = route_rows_mxu(
            pack_bins_4bit(bins), node0, tbl, member, feat_tbl,
            num_features=ds.num_features, interpret=True)
        np.testing.assert_array_equal(np.asarray(rn_ref), np.asarray(rn_pk))
        np.testing.assert_array_equal(np.asarray(rs_ref), np.asarray(rs_pk))

    def test_fused_parity(self):
        ds, g, h = _small_bin_data(with_nan=True, seed=6)
        bins = jnp.asarray(ds.bins)
        cnt = jnp.ones(ds.num_data, jnp.float32)
        tbl, member = self._route_tables(ds)
        feat_tbl = jnp.stack(
            [jnp.asarray(ds.num_bins, jnp.float32),
             jnp.asarray(ds.missing_types == 2, jnp.float32)], axis=1)
        node0 = jnp.zeros(ds.num_data, jnp.int32)
        bmax = int(ds.num_bins.max())
        h_ref, rn_ref = fused_route_hist_mxu(
            bins, g, h, cnt, node0, tbl, member, feat_tbl,
            num_slots=4, bmax=bmax, interpret=True)
        h_pk, rn_pk = fused_route_hist_mxu(
            pack_bins_4bit(bins), g, h, cnt, node0, tbl, member, feat_tbl,
            num_slots=4, bmax=bmax, num_features=ds.num_features,
            interpret=True)
        np.testing.assert_array_equal(np.asarray(h_ref), np.asarray(h_pk))
        np.testing.assert_array_equal(np.asarray(rn_ref), np.asarray(rn_pk))

    def test_grower_identical_trees(self):
        ds, g, h = _small_bin_data(with_nan=True, seed=7)
        cnt = jnp.ones(ds.num_data, jnp.float32)
        args_tail = (cnt, jnp.ones(ds.num_features, jnp.float32),
                     jnp.asarray(ds.num_bins),
                     jnp.asarray(ds.missing_types == 2),
                     jnp.asarray(ds.is_categorical))
        kw = dict(num_leaves=15, max_depth=0,
                  hp=SplitHyperParams(min_data_in_leaf=20),
                  bmax=int(ds.num_bins.max()), interpret=True,
                  overshoot=2.0)
        t_ref, r_ref = grow_tree_mxu(jnp.asarray(ds.bins), g, h,
                                     *args_tail, **kw)
        t_pk, r_pk = grow_tree_mxu(pack_bins_4bit(jnp.asarray(ds.bins)),
                                   g, h, *args_tail, packed4=True, **kw)
        nn = int(t_ref.num_nodes)
        assert int(t_ref.num_leaves) == int(t_pk.num_leaves)
        for fld in ("split_feature", "threshold_bin", "left", "right",
                    "default_left"):
            np.testing.assert_array_equal(
                np.asarray(getattr(t_ref, fld))[:nn],
                np.asarray(getattr(t_pk, fld))[:nn], err_msg=fld)
        np.testing.assert_array_equal(
            np.asarray(t_ref.leaf_value)[:nn],
            np.asarray(t_pk.leaf_value)[:nn])
        np.testing.assert_array_equal(np.asarray(r_ref), np.asarray(r_pk))


# ----------------------------------------------------------------------
# routing against NumPy, every variant of _route_decide
def _forest_level(ds, m1, nslots, seed, with_cat=False):
    """One random level of a forest over `ds`: which of the m1 nodes
    split, on what, into which children, and the next-pass slot of every
    node. Children are arbitrary ids below m1 (routing reads tables, not
    a tree's shape); ids above 256 exercise the base-256 pairs."""
    rng = np.random.RandomState(seed)
    f = ds.num_features
    nbins = np.asarray(ds.num_bins)
    is_cat_feat = np.asarray(ds.is_categorical)
    split = rng.rand(m1) < 0.6
    feat = rng.randint(0, f, size=m1)
    if with_cat:   # half of the split nodes decide on a categorical
        cats = np.flatnonzero(is_cat_feat)
        pick = rng.rand(m1) < 0.5
        feat = np.where(pick, cats[rng.randint(0, len(cats), m1)], feat)
    is_cat = is_cat_feat[feat] & split
    thr = (rng.rand(m1) * np.maximum(nbins[feat] - 1, 1)).astype(np.int64)
    w_cat = (int(nbins.max()) + 31) // 32
    bitset = rng.randint(0, 2 ** 31, size=(m1, w_cat)).astype(np.uint32)
    return dict(
        split=split, feat=feat, thr=thr, defl=rng.rand(m1) < 0.5,
        is_cat=is_cat, bitset=bitset,
        child_l=rng.randint(0, m1, size=m1),
        child_r=rng.randint(0, m1, size=m1),
        slot_of=rng.randint(-1, nslots, size=m1),
        row_node=rng.randint(0, m1, size=ds.num_data))


def _numpy_route(bins, lvl, num_bins, missing_is_nan):
    """(new node, new slot) of every row, one row at a time."""
    node = lvl["row_node"]
    out_node, out_slot = node.copy(), lvl["slot_of"][node]
    for r in np.flatnonzero(lvl["split"][node]):
        nd = node[r]
        ft = lvl["feat"][nd]
        b = int(bins[r, ft])
        if lvl["is_cat"][nd]:
            left = bool((lvl["bitset"][nd, b // 32] >> (b % 32)) & 1)
        elif missing_is_nan[ft] and b == num_bins[ft] - 1:
            left = bool(lvl["defl"][nd])
        else:
            left = b <= lvl["thr"][nd]
        out_node[r] = lvl["child_l"][nd] if left else lvl["child_r"][nd]
        out_slot[r] = lvl["slot_of"][out_node[r]]
    return out_node, out_slot


def _routing_case(variant, m_cap, nslots, seed):
    """(kernel bins, kernel kwargs, tables, feat_tbl, level, oracle)"""
    from lightgbm_tpu.efb import (build_plan, bundle_matrix,
                                  make_device_tables)
    rng = np.random.RandomState(seed)
    n = 1531                       # no row block divides it
    efb_dev, kw = None, {}
    if variant.startswith("efb"):
        f = 16
        X = np.zeros((n, f))
        for g in range(0, f, 8):   # exclusive features: no conflicts
            X[np.arange(n), rng.randint(g, g + 8, size=n)] = \
                rng.rand(n) + 0.5
        X[rng.rand(n) < 0.05, 1] = np.nan
    else:
        f = 7
        X = rng.randn(n, f)
        X[rng.rand(n) < 0.05, 1] = np.nan
        if variant == "categorical":
            X[:, 3] = rng.randint(0, 9, size=n)
            X[:, 5] = rng.randint(0, 5, size=n)
    ds = BinnedDataset.from_raw(
        X.astype(np.float32), Metadata(n, label=np.zeros(n, np.float32)),
        max_bin=15,
        categorical_features=[3, 5] if variant == "categorical" else None)
    bins = np.asarray(ds.bins)
    num_bins = np.asarray(ds.num_bins)
    mnan = np.asarray(ds.missing_types == 2)
    m1 = m_cap - 28                # ids up to ~1000 at m_cap 1024
    lvl = _forest_level(ds, m1, nslots, seed + 1,
                        with_cat=variant == "categorical")
    kbins = jnp.asarray(bins)
    bcol = None
    if variant == "packed4":
        kbins, kw = pack_bins_4bit(kbins), dict(num_features=f)
    elif variant.startswith("efb"):
        plan = build_plan(bins, ds.num_bins, ds.default_bins,
                          np.asarray(ds.is_categorical),
                          max_bundle_bins=256)
        assert plan is not None and plan.effective
        seg = variant == "efb_range"
        efb_dev = make_device_tables(
            plan, ds.default_bins,
            num_bins=ds.num_bins if seg else None,
            missing_is_nan=mnan if seg else None,
            is_cat=np.asarray(ds.is_categorical) if seg else None)
        kbins = jnp.asarray(bundle_matrix(bins, plan))
        bcol = efb_dev.col_of_feat[jnp.asarray(lvl["feat"])]
        kw = dict(efb_range=True) if seg else \
            dict(loc_table=efb_dev.loc_table)
    tbl, member = pack_route_tables(
        jnp.asarray(lvl["split"]), jnp.asarray(lvl["feat"], jnp.int32),
        jnp.asarray(lvl["thr"], jnp.int32), jnp.asarray(lvl["defl"]),
        jnp.asarray(lvl["is_cat"]), jnp.asarray(lvl["child_l"], jnp.int32),
        jnp.asarray(lvl["child_r"], jnp.int32),
        jnp.asarray(lvl["slot_of"], jnp.int32), jnp.asarray(lvl["bitset"]),
        m_cap, int(num_bins.max()), bcol=bcol, efb=efb_dev)
    assert tbl.shape[0] == m_cap
    feat_tbl = jnp.stack([jnp.asarray(num_bins, jnp.float32),
                          jnp.asarray(mnan, jnp.float32)], axis=1)
    want = _numpy_route(bins, lvl, num_bins, mnan)
    return ds, kbins, kw, tbl, member, feat_tbl, lvl, want, efb_dev


_VARIANTS = ["plain", "categorical", "packed4", "efb_decode", "efb_range"]


class TestRoutingAgainstNumpy:
    """Node and slot ids after one level, EXACTLY, against a routing
    written here in NumPy on the original bins: every variant of
    _route_decide, both table widths, a row count no row block divides.
    The ids go in and come out of the kernels along lanes; the public
    contract stays [N] int32 in, [N] int32 out."""

    @pytest.mark.parametrize("emit_counts", [False, True],
                             ids=["plain_out", "emit_counts"])
    @pytest.mark.parametrize("m_cap", [128, 1024])
    @pytest.mark.parametrize("variant", _VARIANTS)
    def test_route_rows_mxu(self, variant, m_cap, emit_counts):
        nslots = 40
        _, kbins, kw, tbl, member, feat_tbl, lvl, want, _ = _routing_case(
            variant, m_cap, nslots, seed=11)
        # without categorical splits the membership lookup may be
        # skipped or kept: both settings run, one per table width
        out = route_rows_mxu(
            kbins, jnp.asarray(lvl["row_node"], jnp.int32), tbl, member,
            feat_tbl, emit_counts=emit_counts, num_slots=nslots,
            has_cat=variant == "categorical" or m_cap == 128,
            interpret=True, **kw)
        assert out[0].shape == out[1].shape == (len(want[0]),)
        assert out[0].dtype == out[1].dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(out[0]), want[0])
        np.testing.assert_array_equal(np.asarray(out[1]), want[1])
        if emit_counts:
            np.testing.assert_array_equal(
                np.asarray(out[2]),
                np.bincount(want[1][want[1] >= 0], minlength=nslots))

    @pytest.mark.parametrize("m_cap", [128, 1024])
    @pytest.mark.parametrize("variant", _VARIANTS)
    def test_fused_route_hist_mxu(self, variant, m_cap):
        nslots = 6
        ds, kbins, kw, tbl, member, feat_tbl, lvl, want, efb_dev = \
            _routing_case(variant, m_cap, nslots, seed=23)
        n = ds.num_data
        rng = np.random.RandomState(5)
        g = jnp.asarray(rng.randn(n).astype(np.float32))
        h = jnp.asarray(rng.rand(n).astype(np.float32))
        bmax = efb_dev.bundle_bmax if efb_dev is not None \
            else int(np.asarray(ds.num_bins).max())
        hist, rn = fused_route_hist_mxu(
            kbins, g, h, jnp.ones(n, jnp.float32),
            jnp.asarray(lvl["row_node"], jnp.int32), tbl, member, feat_tbl,
            num_slots=nslots, bmax=bmax, row_block=1024,
            has_cat=variant == "categorical", interpret=True, **kw)
        assert rn.shape == (n,) and rn.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(rn), want[0])
        # the slot never leaves the kernel: read it from the histogram
        # it selected, rows per slot and the sums of a feature's bins
        hist = np.asarray(hist)
        live = want[1] >= 0
        np.testing.assert_array_equal(
            hist[:, 0, :, 2].sum(axis=1),
            np.bincount(want[1][live], minlength=nslots))
        gsum = np.zeros(nslots)
        np.add.at(gsum, want[1][live], np.asarray(g, np.float64)[live])
        np.testing.assert_allclose(hist[:, 0, :, 0].sum(axis=1), gsum,
                                   rtol=1e-4, atol=1e-4)
