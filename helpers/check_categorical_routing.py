"""The routing kernels' categorical variant, THROUGH MOSAIC, against a
routing replayed in NumPy: what interpret mode on a CPU cannot see
(PR 25's lesson: a kernel that passed every interpret-mode test returned
sums 28-45% off on the chip).

Two parts, both with `interpret=False`:

  cases  the cases of tests/test_packed_bins.py::TestRoutingAgainstNumpy
         (every variant of `_route_decide`, both table widths, 1,531
         rows), with the test module's kernels rebound to run compiled;
  full   one random forest level at the size of the benchmark's
         categorical cell (--rows x --features x 256 bins, six
         categorical columns of 13, 32, 8, 30, 255 and 255 bins, half of
         the splitting nodes deciding on one): `route_rows_mxu` with
         counts and `fused_route_hist_mxu` (40 slots, the widest pass
         the tree builds one-hot), both with `has_cat=True`, at node
         tables of 128 and 1,024 rows. Node and slot of EVERY row
         exactly; rows per slot exactly; gradient sums per slot to 1e-4.

Exit code 1 if anything disagrees. On a CPU backend it takes --interpret
and proves nothing about the chip.

Usage: python helpers/check_categorical_routing.py [--rows N]
       [--features F] [--parts cases,full] [--interpret]
Writes chiprun_out/check_categorical_routing.json beside what it prints.
"""

import argparse
import json
import os
import sys
import time
import types

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests"))

CAT_BINS = (13, 32, 8, 30, 255, 255)


def numpy_route(bins, lvl, num_bins, missing_is_nan):
    """(new node, new slot) of every row: test_packed_bins._numpy_route,
    all rows at once."""
    node = lvl["row_node"]
    ft = lvl["feat"][node]
    b = bins[np.arange(len(node)), ft].astype(np.int64)
    cat_left = (lvl["bitset"][node, b // 32] >> (b % 32).astype(
        np.uint32)) & 1 == 1
    num_left = np.where(missing_is_nan[ft] & (b == num_bins[ft] - 1),
                        lvl["defl"][node], b <= lvl["thr"][node])
    left = np.where(lvl["is_cat"][node], cat_left, num_left)
    out = np.where(lvl["split"][node],
                   np.where(left, lvl["child_l"][node],
                            lvl["child_r"][node]), node)
    return out, lvl["slot_of"][out]


def run_cases(interpret: bool) -> dict:
    import functools
    import itertools
    import test_packed_bins as T
    for name in ("route_rows_mxu", "fused_route_hist_mxu"):
        fn = getattr(T, name)

        def compiled(*a, _fn=fn, **kw):
            kw["interpret"] = interpret
            return _fn(*a, **kw)

        setattr(T, name, functools.wraps(fn)(compiled))
    suite = T.TestRoutingAgainstNumpy()
    ran, wrong = 0, []
    for variant, m_cap in itertools.product(T._VARIANTS, (128, 1024)):
        calls = [("fused", lambda: suite.test_fused_route_hist_mxu(
            variant, m_cap))]
        calls += [("route,counts=%s" % e, lambda e=e:
                   suite.test_route_rows_mxu(variant, m_cap, e))
                  for e in (False, True)]
        for what, call in calls:
            ran += 1
            try:
                call()
            except AssertionError as exc:
                wrong.append("%s %s m_cap=%d: %s" % (
                    what, variant, m_cap, str(exc)[:200]))
    print("cases: %d of %d exact" % (ran - len(wrong), ran), flush=True)
    for w in wrong:
        print("  WRONG " + w, flush=True)
    return {"cases": ran, "cases_wrong": wrong}


def run_full(rows: int, features: int, interpret: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import test_packed_bins as T
    from lightgbm_tpu.learner.histogram_mxu import (
        fused_route_hist_mxu, pack_route_tables, route_rows_mxu)
    rng = np.random.RandomState(35)
    num_bins = np.full(features, 255, np.int64)
    num_bins[:len(CAT_BINS)] = CAT_BINS
    is_cat = np.arange(features) < len(CAT_BINS)
    bins = np.empty((rows, features), np.uint8)
    for j in range(features):
        bins[:, j] = rng.randint(0, num_bins[j], rows)
    mnan = np.zeros(features, bool)
    mnan[len(CAT_BINS)] = True                 # one column with a NaN bin
    ds = types.SimpleNamespace(num_features=features, num_bins=num_bins,
                               is_categorical=is_cat, num_data=rows)
    feat_tbl = jnp.stack([jnp.asarray(num_bins, jnp.float32),
                          jnp.asarray(mnan, jnp.float32)], axis=1)
    kbins = jnp.asarray(bins)
    g = rng.randn(rows).astype(np.float32)
    h = rng.rand(rows).astype(np.float32)
    out = {"rows": rows, "features": features, "full": []}
    for m_cap, nslots in ((128, 40), (1024, 40)):
        lvl = T._forest_level(ds, m_cap - 28, nslots, seed=m_cap,
                              with_cat=True)
        want_node, want_slot = numpy_route(bins, lvl, num_bins, mnan)
        tbl, member = pack_route_tables(
            jnp.asarray(lvl["split"]), jnp.asarray(lvl["feat"], jnp.int32),
            jnp.asarray(lvl["thr"], jnp.int32), jnp.asarray(lvl["defl"]),
            jnp.asarray(lvl["is_cat"]),
            jnp.asarray(lvl["child_l"], jnp.int32),
            jnp.asarray(lvl["child_r"], jnp.int32),
            jnp.asarray(lvl["slot_of"], jnp.int32),
            jnp.asarray(lvl["bitset"]), m_cap, 256)
        row_node = jnp.asarray(lvl["row_node"], jnp.int32)
        t0 = time.perf_counter()
        rn, rs, cts = jax.block_until_ready(route_rows_mxu(
            kbins, row_node, tbl, member, feat_tbl, emit_counts=True,
            num_slots=nslots, has_cat=True, interpret=interpret))
        t_route = time.perf_counter() - t0
        live = want_slot >= 0
        rec = {"m_cap": m_cap, "slots": nslots,
               "categorical_nodes": int(lvl["is_cat"].sum()),
               "route_node_exact": bool(np.array_equal(np.asarray(rn),
                                                       want_node)),
               "route_slot_exact": bool(np.array_equal(np.asarray(rs),
                                                       want_slot)),
               "route_counts_exact": bool(np.array_equal(
                   np.asarray(cts)[:nslots],
                   np.bincount(want_slot[live], minlength=nslots))),
               "route_first_call_s": round(t_route, 2)}
        t0 = time.perf_counter()
        hist, rn2 = jax.block_until_ready(fused_route_hist_mxu(
            kbins, jnp.asarray(g), jnp.asarray(h),
            jnp.ones(rows, jnp.float32), row_node, tbl, member, feat_tbl,
            num_slots=nslots, bmax=256, has_cat=True,
            interpret=interpret))
        hist = np.asarray(hist)
        gsum = np.bincount(want_slot[live], weights=g[live].astype(
            np.float64), minlength=nslots)
        rec.update(
            fused_node_exact=bool(np.array_equal(np.asarray(rn2),
                                                 want_node)),
            fused_counts_exact=bool(np.array_equal(
                hist[:, 0, :, 2].sum(axis=1),
                np.bincount(want_slot[live], minlength=nslots))),
            fused_grad_sum_rel=float(np.abs(
                hist[:, 0, :, 0].sum(axis=1) - gsum).max()
                / np.abs(gsum).max()),
            fused_first_call_s=round(time.perf_counter() - t0, 2))
        print("full, node table of %d rows: %s" % (m_cap, rec), flush=True)
        out["full"].append(rec)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=11_000_000)
    ap.add_argument("--features", type=int, default=17)
    ap.add_argument("--parts", default="cases,full")
    ap.add_argument("--interpret", action="store_true")
    args = ap.parse_args(argv)
    import jax
    if jax.default_backend() == "cpu" and not args.interpret:
        print("a CPU backend takes --interpret", file=sys.stderr)
        return 2
    out = {"platform": jax.default_backend(), "interpret": args.interpret}
    parts = args.parts.split(",")
    if "cases" in parts:
        out.update(run_cases(args.interpret))
    if "full" in parts:
        out.update(run_full(args.rows, args.features, args.interpret))
    ok = not out.get("cases_wrong") and all(
        v for rec in out.get("full", []) for k, v in rec.items()
        if k.endswith("_exact")) and all(
        rec["fused_grad_sum_rel"] < 1e-4 for rec in out.get("full", []))
    out["ok"] = bool(ok)
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out",
                           "check_categorical_routing.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
