"""Per-pass floor microbench at the MAIN bench shape (1M x 28 x 255).

A tree's time splits into the histogram dots, a fixed floor per growth
pass, the sibling reconstruction and glue.  This times the fused
route+hist sweep (the whole per-pass kernel cost) across kernel-slot
counts and row blocks to separate:
  - MXU row-padding waste (C*sk < 128 on early passes),
  - per-grid-step overhead (489 steps at row_block=2048),
  - the dot's true slot-proportional cost,
and times the sibling-reconstruction dot at f32-HIGHEST vs an exact
split-bf16 2-pass formulation.

All timings are CHAINED IN-JIT (k dependency-chained iterations per
dispatch, long-minus-short differencing), so the host's dispatch and
sync cost cancels out of a kernel's number.

Usage: python helpers/microbench_pass.py [sweep|recon|tree|all]
"""

import sys
import time

import numpy as np

import os
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

N = 1_000_000
F = 28
BMAX = 256
M_PAD = 896          # round_up(2*447-1+1, 128) at overshoot 1.75


def timeit_chained(body, carry0, reps=16):
    """Per-iteration seconds of `body` (carry -> carry), timed as one
    jitted fori_loop dispatch of 2+reps iterations minus one of 2."""

    @jax.jit
    def chain(c0, k):
        return jax.lax.fori_loop(0, k, lambda i, c: body(c), c0)

    def run(k):
        out = chain(carry0, jnp.asarray(k, jnp.int32))
        jax.tree_util.tree_map(lambda a: np.asarray(a).ravel()[:1], out)

    run(2)  # compile + warm
    best = np.inf
    for _ in range(2):
        t0 = time.time()
        run(2 + reps)
        dt_long = time.time() - t0
        t0 = time.time()
        run(2)
        dt_short = time.time() - t0
        best = min(best, (dt_long - dt_short) / reps)
    return best


def make_pass_state(sk, rng):
    """Ping-pong tables: sk parents split into children that split
    straight back, so EVERY chained iteration routes through a split
    node (full decision math + slot pickup) and builds sk slots —
    the steady-pass cost, not the settled-rows shortcut."""
    from lightgbm_tpu.learner.histogram_mxu import pack_route_tables
    m1 = M_PAD
    ids = np.arange(m1)
    is_parent = ids < sk
    is_child = (ids >= sk) & (ids < 3 * sk)
    split = is_parent | is_child
    feat = ids % F
    thr = np.full(m1, 128)
    child_l = np.where(is_parent, sk + 2 * ids,
                       np.where(is_child, (ids - sk) // 2, -1))
    child_r = np.where(is_parent, sk + 2 * ids + 1,
                       np.where(is_child, (ids - sk) // 2, -1))
    slot = np.where(split, ids % sk, -1)
    tbl, member = pack_route_tables(
        jnp.asarray(split), jnp.asarray(feat, jnp.int32),
        jnp.asarray(thr, jnp.int32), jnp.zeros(m1, bool),
        jnp.zeros(m1, bool), jnp.asarray(child_l, jnp.int32),
        jnp.asarray(child_r, jnp.int32), jnp.asarray(slot, jnp.int32),
        jnp.zeros((m1, (BMAX + 31) // 32), jnp.uint32), M_PAD, BMAX)
    row_node = jnp.asarray(rng.randint(0, max(sk, 1), N), jnp.int32)
    return tbl, member, row_node


def bench_sweep():
    from lightgbm_tpu.learner.histogram_mxu import fused_route_hist_mxu
    rng = np.random.RandomState(0)
    bins = jnp.asarray(rng.randint(0, BMAX, (N, F)), jnp.uint8)
    g = jnp.asarray(rng.randint(-127, 128, N), jnp.float32)
    h = jnp.asarray(rng.randint(0, 128, N), jnp.float32)
    cnt = jnp.ones(N, jnp.float32)
    feat_tbl = jnp.stack([jnp.full(F, 255.0), jnp.zeros(F)], axis=1)

    def _r128(x):
        return min(M_PAD, ((x + 127) // 128) * 128)

    print("# fused_route_hist_mxu per pass, quantized (3ch), chained")
    print("sk\trb\tm_cap\tms")
    # m_cap mirrors the grower's per-pass slice (round_up to lanes of
    # the live node-id range); the sk=72 full-width row quantifies the
    # table-width cost at mid frontier
    for sk in (16, 72, 136, 232):
        tbl, member, row_node = make_pass_state(sk, rng)
        for rb in (2048, 4096, 8192):
            for m_cap in ({_r128(3 * sk), M_PAD} if sk == 72 and
                          rb == 2048 else {_r128(3 * sk)}):
                t = tbl[:m_cap]
                mem = member[:m_cap]

                def body(rn):
                    _h, rn2 = fused_route_hist_mxu(
                        bins, g, h, cnt, rn, t, mem, feat_tbl,
                        num_slots=sk, bmax=BMAX, has_cat=False,
                        double_prec=True, quantized=True, row_block=rb)
                    return rn2

                try:
                    dt = timeit_chained(body, row_node)
                except Exception as e:
                    print(f"{sk}\t{rb}\t{m_cap}\tFAIL {type(e).__name__}")
                    continue
                print(f"{sk}\t{rb}\t{m_cap}\t{dt * 1e3:.2f}", flush=True)


def bench_recon():
    s, sk, p_all = 448, 232, 226
    fb3 = F * BMAX * 3
    rng = np.random.RandomState(1)
    kern2 = jnp.asarray(rng.rand(sk, fb3), jnp.float32)
    parent = jnp.asarray(rng.rand(p_all, fb3), jnp.float32)
    mk = jnp.asarray(rng.randint(-1, 2, (s, sk)), jnp.float32)
    mp = jnp.asarray((rng.rand(s, p_all) < 0.01), jnp.float32)

    def recon_highest(kern2):
        return jax.lax.dot_general(
            jnp.concatenate([mk, mp], axis=1),
            jnp.concatenate([kern2, parent], axis=0),
            dimension_numbers=(((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)

    def recon_split(kern2):
        lhs = jnp.concatenate([mk, mp], axis=1).astype(jnp.bfloat16)
        rhs = jnp.concatenate([kern2, parent], axis=0)
        hi = jax.lax.reduce_precision(rhs, exponent_bits=8,
                                      mantissa_bits=7)
        lo = rhs - hi
        d = lambda r: jax.lax.dot_general(
            lhs, r.astype(jnp.bfloat16),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return d(hi) + d(lo)

    a = timeit_chained(lambda k2: recon_highest(k2)[:sk], kern2,
                       reps=300)
    b = timeit_chained(lambda k2: recon_split(k2)[:sk], kern2,
                       reps=300)
    ra = np.asarray(recon_highest(kern2))
    rb = np.asarray(recon_split(kern2))
    rel = np.abs(ra - rb).max() / max(np.abs(ra).max(), 1e-30)
    print(f"# recon dot [s={s}, {sk}+{p_all}] x [{fb3}], chained")
    print(f"highest\t{a * 1e3:.2f} ms")
    print(f"split2\t{b * 1e3:.2f} ms\tmax rel diff {rel:.2e}")

    # the parent-carry dot (sel_p): [P, s] x [s, F*B*3]
    selp = jnp.asarray((rng.rand(p_all, s) < 0.004), jnp.float32)
    hist = jnp.asarray(rng.rand(s, fb3), jnp.float32)

    def carry_highest(hist):
        return jax.lax.dot_general(
            selp, hist, dimension_numbers=(((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)

    def carry_split(hist):
        hi = jax.lax.reduce_precision(hist, exponent_bits=8,
                                      mantissa_bits=7)
        sl = selp.astype(jnp.bfloat16)
        d = lambda r: jax.lax.dot_general(
            sl, r.astype(jnp.bfloat16),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return d(hi) + d(hist - hi)

    pad = jnp.zeros((s - p_all, fb3), jnp.float32)
    a = timeit_chained(
        lambda h_: jnp.concatenate([carry_highest(h_), pad]), hist,
        reps=300)
    b = timeit_chained(
        lambda h_: jnp.concatenate([carry_split(h_), pad]), hist,
        reps=300)
    print(f"carry_highest\t{a * 1e3:.2f} ms")
    print(f"carry_split\t{b * 1e3:.2f} ms")


if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    if which in ("sweep", "all"):
        bench_sweep()
    if which in ("recon", "all"):
        bench_recon()


def bench_tree():
    """Chained whole-tree growth on the REAL bench data/config —
    separates the grower's cost from the boosting ring's (grad/quantize/
    score/stacking glue): ring = fused-block per-tree minus this."""
    sys.path.insert(0, REPO)
    from bench import make_higgs_like, PARAMS, MAX_BIN, N_FEATURES
    import lightgbm_tpu as lgb
    from lightgbm_tpu.learner.grower_mxu import grow_tree_mxu

    X, y = make_higgs_like(N, N_FEATURES)
    ds = lgb.Dataset(X, label=y, params={"max_bin": MAX_BIN})
    bst = lgb.Booster(params=dict(PARAMS), train_set=ds)
    g = bst.gbdt
    kw = g._mxu_grow_kwargs()
    print("# grower kwargs:", {k: v for k, v in kw.items()
                               if not hasattr(v, "shape")})
    yd = jnp.asarray(y)
    p = jnp.float32(0.5)
    grad0 = p - yd
    hess0 = jnp.full(N, 0.25, jnp.float32)
    cnt = jnp.ones(N, jnp.float32)
    fmask = jnp.ones(N_FEATURES, jnp.float32)
    key = jax.random.PRNGKey(3)

    def body(rn):
        # dependency chain without changing the data: 0*rn is not
        # foldable for floats per IEEE (rn is int -> cast first)
        g_in = grad0 + 0.0 * rn.astype(jnp.float32)
        tree, rn2 = grow_tree_mxu(
            g.bins, g_in, hess0, cnt, fmask, g.num_bins_d,
            g.missing_is_nan_d, g.is_cat_d, rng_key=key, **kw)
        return rn2

    dt = timeit_chained(body, jnp.zeros(N, jnp.int32), reps=10)
    print(f"whole-tree growth (chained): {dt * 1e3:.1f} ms/tree")


if __name__ == "__main__" and "tree" in sys.argv[1:]:
    bench_tree()
