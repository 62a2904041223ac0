"""Per-component timing of the EFB MXU path at the wide-sparse shape —
locates the deficit vs the portable grower without in-jit guesswork."""

import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax
import jax.numpy as jnp

from bench_efb import make_sparse  # noqa: E402


def timeit(fn, *args, reps=5, **kw):
    out = fn(*args, **kw)
    jax.tree_util.tree_map(
        lambda a: np.asarray(a).ravel()[:1], out)
    t0 = time.time()
    for _ in range(reps):
        out = fn(*args, **kw)
    jax.tree_util.tree_map(lambda a: np.asarray(a).ravel()[:1], out)
    return (time.time() - t0) / reps


def main():
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import lightgbm_tpu as lgb
    from lightgbm_tpu.efb import build_plan, bundle_matrix, \
        make_device_tables
    from lightgbm_tpu.learner.histogram_mxu import (
        fits_v2, fused_route_hist_mxu, pack_route_tables, route_rows_mxu)
    from lightgbm_tpu.learner.split import SplitHyperParams
    from lightgbm_tpu.learner.split_bundled import find_best_splits_bundled

    X, y = make_sparse()
    ds = lgb.Dataset(X, label=y, params={"max_bin": 63})
    b = ds.binned
    plan = build_plan(np.asarray(b.bins), b.num_bins, b.default_bins,
                      np.asarray(b.is_categorical), max_bundle_bins=256)
    efb = make_device_tables(plan, b.default_bins, num_bins=b.num_bins,
                             missing_is_nan=(b.missing_types == 2),
                             is_cat=np.asarray(b.is_categorical))
    bund = jnp.asarray(bundle_matrix(np.asarray(b.bins), plan))
    n, fb = bund.shape
    bb = efb.bundle_bmax
    f = b.num_features
    print(f"n={n} F={f} Fb={fb} Bb={bb}")
    g = jnp.asarray(np.random.RandomState(0).randn(n), jnp.float32)
    h = jnp.ones(n, jnp.float32)
    cnt = jnp.ones(n, jnp.float32)
    feat_tbl = jnp.stack([jnp.asarray(b.num_bins, jnp.float32),
                          jnp.asarray((b.missing_types == 2),
                                      jnp.float32)], axis=1)
    m_pad = 256
    node0 = jnp.zeros(n, jnp.int32)
    tbl, member = pack_route_tables(
        jnp.zeros(m_pad, bool), jnp.zeros(m_pad, jnp.int32),
        jnp.zeros(m_pad, jnp.int32), jnp.zeros(m_pad, bool),
        jnp.zeros(m_pad, bool), jnp.full(m_pad, 255, jnp.int32),
        jnp.full(m_pad, 255, jnp.int32),
        jnp.full(m_pad, -1, jnp.int32).at[0].set(0),
        jnp.zeros((m_pad, (63 + 31) // 32), jnp.uint32), m_pad, 63,
        efb=efb)

    for sk in (2, 16, 64, 127):
        ok = fits_v2(sk, fb, bb, True, False, route_width=0,
                     row_block=1024)
        if ok:
            dt = timeit(fused_route_hist_mxu, bund, g, h, cnt, node0,
                        tbl, member, feat_tbl, num_slots=sk, bmax=bb,
                        has_cat=False, double_prec=True, quantized=False,
                        efb_range=True, row_block=1024)
        else:
            dt = float("nan")
        print(f"fused sweep sk={sk:4d}: fits_v2={ok} {dt * 1000:8.1f} ms")

    dt = timeit(route_rows_mxu, bund, node0, tbl, member, feat_tbl,
                efb_range=True)
    print(f"route only:            {dt * 1000:8.1f} ms")

    s = 127
    rng = np.random.RandomState(1)
    hist_b = jnp.asarray(rng.rand(s, fb, bb, 3).astype(np.float32))
    pg = jnp.asarray(rng.randn(s).astype(np.float32))
    ph = jnp.ones(s, jnp.float32) * 100
    pc = jnp.ones(s, jnp.float32) * 1000
    po = jnp.zeros(s, jnp.float32)
    hp = SplitHyperParams(min_data_in_leaf=20)
    dt = timeit(find_best_splits_bundled, hist_b, pg, ph, pc, po,
                jnp.asarray(b.num_bins),
                jnp.asarray(b.missing_types == 2),
                jnp.asarray(b.is_categorical),
                jnp.ones(f, jnp.float32), hp, efb)
    print(f"bundled scan S={s}:    {dt * 1000:8.1f} ms")


if __name__ == "__main__":
    main()
