"""Compile for a TPU that is not there: the pre-flight before chip time.

libtpu can describe a topology without owning a chip, and JAX lowers and
compiles for the devices of that description like for real ones. So
whether Mosaic accepts a kernel (VMEM, tiling, unaligned slices), what a
program's compile costs and how much device memory it asks for are all
known in the sandbox; only *running* needs the chip. Nothing here
executes on a device, so nothing here is a speed.

    python -m lightgbm_tpu.testing.tpu_aot               # smoke's shapes
    python -m lightgbm_tpu.testing.tpu_aot --rows 65536 --leaves 31

compiles every ``pallas_call`` site, the per-iteration grower, the fused
multi-tree scan, the serving predictor and the four-device data-parallel
grower and fused block the way chip_smoke.py will meet them, and prints
one line per program. tests/test_tpu_aot.py runs
the kernel sites at a small shape (slow tier).
"""

from __future__ import annotations

import time
from typing import Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["TOPOLOGY", "topology_devices", "compile_for", "kernel_sites"]

#: one v5e host: four chips in a 2x2 mesh
TOPOLOGY = "v5e:2x2"


def topology_devices(name: str = TOPOLOGY) -> Sequence:
    """Devices of the described topology (no chip is touched). Raises
    whatever libtpu raises when it cannot describe one — the caller
    decides whether that is a skip or an error."""
    from jax.experimental import topologies
    return topologies.get_topology_desc(
        topology_name=name, platform="tpu").devices


def _on(device, x):
    """ShapeDtypeStruct of `x` placed on `device`; a spec that already
    says where it lives (a sharded argument) is kept."""
    from jax.sharding import SingleDeviceSharding
    if isinstance(x, jax.ShapeDtypeStruct) and x.sharding is not None:
        return x
    return jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                sharding=SingleDeviceSharding(device))


def compile_for(device, fn: Callable, *args):
    """Trace, lower and compile ``fn(*args)`` for `device` (a topology
    device). `fn` is a plain or an already-jitted function (whose
    donation and static arguments are then kept); `args` are arrays or
    ShapeDtypeStructs, of which only shapes and dtypes are read.
    Returns (compiled, seconds)."""
    t0 = time.perf_counter()
    specs = jax.tree_util.tree_map(lambda a: _on(device, a), args)
    jitted = fn if hasattr(fn, "trace") else jax.jit(fn)
    compiled = jitted.trace(*specs).lower().compile()
    return compiled, time.perf_counter() - t0


def _sds(shape, dtype, sharding=None):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype),
                                sharding=sharding)


def kernel_sites(*, rows: int, features: int, bmax: int, slots: int,
                 quantized: bool = True) -> Dict[str, Tuple[Callable, List]]:
    """One (fn, args) per ``pallas_call`` site in learner/, shaped like
    a growth pass over `rows` x `features` bins with `slots` frontier
    slots. The route tables are sized for 2*slots nodes."""
    from ..learner import histogram_mxu as hm
    from ..learner.histogram_pallas import (GROUPED_ROW_BLOCK,
                                            build_histograms_scatter,
                                            partition_table)

    n, f, s = rows, features, slots
    m_pad = hm._round_up(2 * s, 128)
    bpad = hm._round_up(bmax, 128)
    bins = _sds((n, f), jnp.uint8)
    vec = _sds((n,), jnp.float32)
    ivec = _sds((n,), jnp.int32)
    tbl = _sds((m_pad, hm._N_COLS), jnp.float32)
    member = _sds((m_pad, bpad), jnp.float32)
    feat_tbl = _sds((f, 2), jnp.float32)
    hist_kw = dict(num_slots=s, bmax=bmax, quantized=quantized)

    def hist_v1(b, g, h, c, sl):
        return hm.build_histograms_mxu(b, g, h, c, sl, **hist_kw)

    def hist_v2(b, g, h, c, sl):
        return hm.build_histograms_mxu_v2(b, g, h, c, sl, **hist_kw)

    def hist_scatter(b, g, h, c, sl):
        return build_histograms_scatter(b, g, h, c, sl, **hist_kw)

    def node_sums(node, g, h, c):
        return hm.node_sums_mxu(node, g, h, c, num_nodes=2 * s)

    def node_values(node, vals):
        return hm.node_values_mxu(node, vals)

    sites = {
        "build_histograms_mxu": (hist_v1, [bins, vec, vec, vec, ivec]),
        "build_histograms_mxu_v2": (hist_v2, [bins, vec, vec, vec, ivec]),
        "build_histograms_scatter": (hist_scatter,
                                     [bins, vec, vec, vec, ivec]),
        "node_sums_mxu": (node_sums, [ivec, vec, vec, vec]),
        "node_values_mxu": (node_values, [ivec, _sds((2 * s,),
                                                     jnp.float32)]),
    }

    # the stream partition at the row tables of the benchmark's cells
    # (rows x table columns: Higgs, MS LTR with its two lane tiles,
    # Expo) and at the fewest and the most groups a growth pass has
    # (72 slots and the 511-slot fixup, 25 slots a group): whatever
    # `rows` is, since the staging the kernel keeps in VMEM is what
    # Mosaic has to accept
    for trows, width in ((2_625_000, 34), (2_270_296, 143),
                         (11_000_000, 23)):
        for groups in (3, 21):
            def stream(table, sl, counts, groups=groups):
                return partition_table(
                    table, sl, num_slots=25 * groups, group=25,
                    row_block=GROUPED_ROW_BLOCK, counts=counts)

            sites["partition_stream_%dx%d_g%d" % (trows, width, groups)] = (
                stream, [_sds((width, trows + 1), jnp.bfloat16),
                         _sds((trows,), jnp.int32),
                         _sds((25 * groups,), jnp.int32)])

    # the routing kernels, one site per variant of _route_decide: what
    # differs between variants is static (the bins' storage, the tables)
    fh = (f + 1) // 2
    f_orig, bb = 4 * f, min(bmax, 64)      # EFB: 4 features a bundle
    variants = {
        "": (bins, feat_tbl, {}),
        "_categorical": (bins, feat_tbl, dict(has_cat=True)),
        "_packed4": (_sds((n, fh), jnp.uint8), feat_tbl,
                     dict(num_features=f)),
        "_efb_decode": (bins, _sds((f_orig, 2), jnp.float32),
                        dict(loc_table=_sds((f_orig, bb), jnp.int32))),
        "_efb_range": (bins, _sds((f_orig, 2), jnp.float32),
                       dict(efb_range=True)),
    }
    for tag, (vbins, vft, kw) in variants.items():
        loc = kw.pop("loc_table", None)
        extra = [] if loc is None else [loc]
        route_kw = {"has_cat": False, **kw}
        fused_kw = {**hist_kw, **route_kw}
        if "num_features" in kw:
            fused_kw["bmax"] = 15
        # EFB keeps the 1024 block in both modes (grower_mxu.sweep)
        block = dict(row_block=1024) if "efb" in tag else {}

        def fused(b, g, h, c, node, t, mem, ft, *loc_, kw=fused_kw,
                  block=block):
            return hm.fused_route_hist_mxu(
                b, g, h, c, node, t, mem, ft, **block, **kw,
                **(dict(loc_table=loc_[0]) if loc_ else {}))

        def route(b, node, t, mem, ft, *loc_, kw=route_kw):
            return hm.route_rows_mxu(
                b, node, t, mem, ft, emit_counts=True, num_slots=s, **kw,
                **(dict(loc_table=loc_[0]) if loc_ else {}))

        sites["fused_route_hist_mxu" + tag] = (
            fused, [vbins, vec, vec, vec, ivec, tbl, member, vft] + extra)
        sites["route_rows_mxu" + tag] = (
            route, [vbins, ivec, tbl, member, vft] + extra)
    return sites


def _synthetic(rows: int, features: int, seed: int = 17,
               categorical: int = 0):
    rng = np.random.RandomState(seed)
    X = rng.randn(rows, features).astype(np.float32)
    y = (X[:, 0] - X[:, 1] + X[:, 2] * X[:, 3] > 0).astype(np.float32)
    # the first `categorical` columns integer-coded, 300 levels each
    X[:, :categorical] = rng.randint(0, 300, (rows, categorical))
    return X, y


def _gbdt(params: dict, rows: int, features: int):
    """The GBDT ``lgb.Booster(params)`` builds here over synthetic rows
    (on this CPU host: portable grower, one device). `categorical` in
    `params` (this module's own key) passes that many leading columns as
    `categorical_feature`."""
    import lightgbm_tpu as lgb
    params = dict(params)
    ncat = int(params.pop("categorical", 0))
    X, y = _synthetic(rows, features, categorical=ncat)
    ds = lgb.Dataset(X, label=y, params={"max_bin": params["max_bin"]},
                     categorical_feature=list(range(ncat)) or "auto")
    return lgb.Booster(params=dict(params, verbosity=-1),
                       train_set=ds).gbdt


def training_programs(params: dict, *, rows: int, features: int,
                      block: int) -> Dict[str, Tuple[Callable, List]]:
    """The two growth programs of the serial MXU path at this shape —
    the per-iteration grower (what a run that is not fused-eligible
    dispatches) and one fused block of `block` trees (what
    ``lgb.train(params)`` dispatches) — taken from a Booster built here,
    with the kernel path a TPU backend selects forced on (this host is a
    CPU, where GBDT._setup_train picks the portable grower)."""
    from ..learner.grower_mxu import grow_tree_mxu
    g = _gbdt(params, rows, features)
    g._hist_impl = "mxu"
    kw = g._mxu_grow_kwargs()

    def grow(bins, grad, hess, cnt, fmask, key):
        return grow_tree_mxu(bins, grad, hess, cnt, fmask, g.num_bins_d,
                             g.missing_is_nan_d, g.is_cat_d, rng_key=key,
                             **kw)

    vec = _sds((rows,), jnp.float32)
    run = g._build_fused()
    return {
        "grow_tree_mxu": (grow, [g.bins, vec, vec, vec,
                                 _sds((features,), jnp.float32),
                                 _sds((2,), jnp.uint32)]),
        "fused_block_k%d" % block: (
            run.program, [vec, _sds((), jnp.int32),
                          _sds((block, 2), jnp.uint32), *run.operands]),
    }


def _data_parallel_gbdt(params: dict, rows: int, features: int,
                        devices: Sequence):
    """This host's serial booster over synthetic rows, given the chip's
    ``tree_learner=data`` learner and the described mesh of `devices`
    (rows split evenly), and the row-wise and replicated shardings on
    that mesh."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from ..distributed.crossbar import CROSSBAR
    from ..parallel import CommSpec
    g = _gbdt(params, rows, features)
    g._learner = CROSSBAR["mxu", "data"]
    g.mesh = Mesh(np.array(devices), ("data",))
    g.comm = CommSpec(axis="data", mode="data", num_devices=len(devices),
                      top_k=g.config.top_k, hist_agg="psum")
    g._sharded_rng = bool(g.config.use_quantized_grad)
    return (g, NamedSharding(g.mesh, P("data")),
            NamedSharding(g.mesh, P()))


def sharded_grower_program(params: dict, *, rows: int, features: int,
                           devices: Sequence):
    """(jitted grower, placed arg specs) of ``tree_learner=data`` with
    the MXU grower inside shard_map over `devices`: what a run that
    leaves the fused block dispatches per tree."""
    g, rowwise, whole = _data_parallel_gbdt(params, rows, features,
                                            devices)
    specs = [_sds((rows, features), jnp.uint8, rowwise)] + \
        [_sds((rows,), jnp.float32, rowwise)] * 3 + \
        [_sds((features,), jnp.float32, whole),
         _sds((features,), jnp.int32, whole),
         _sds((features,), jnp.bool_, whole),
         _sds((features,), jnp.bool_, whole)]
    if g._sharded_rng:
        specs.append(_sds((2,), jnp.uint32, whole))
    return g._create_grower(), specs


def sharded_fused_program(params: dict, *, rows: int, features: int,
                          block: int, devices: Sequence):
    """(program, placed arg specs) of one fused block of `block` trees
    of the same learner: what ``lgb.train`` dispatches per block on
    four chips. A described device holds no array, so what the builder
    places on the mesh becomes its spec."""
    from unittest import mock
    g, rowwise, whole = _data_parallel_gbdt(params, rows, features,
                                            devices)
    g.bins = _sds(g.bins.shape, g.bins.dtype, rowwise)
    with mock.patch.object(jax, "device_put", lambda a, where: _sds(
            a.shape, a.dtype, where)):
        run = g._build_fused()
    return run.program, [_sds((rows,), jnp.float32, rowwise),
                         _sds((), jnp.int32, whole),
                         _sds((block, 2), jnp.uint32, whole),
                         *run.operands]


def serving_programs(*, features: int, leaves: int, max_bin: int,
                     buckets: Sequence[int] = (16, 1024)
                     ) -> Dict[str, Tuple[Callable, List]]:
    """The serving predictor at the bucket sizes given, over a forest of
    the trainer's width (two trees, trained here on the portable path)."""
    import lightgbm_tpu as lgb
    from ..learner.predict import predict_binned_forest
    X, y = _synthetic(40 * leaves, features)
    bst = lgb.train({"objective": "binary", "num_leaves": leaves,
                     "max_bin": max_bin, "min_data_in_leaf": 2,
                     "verbosity": -1}, lgb.Dataset(X, label=y), 2)
    forest = bst.device_forest()
    bin_dtype = forest.bin_rows(X[:1]).dtype

    def predict(stacked, tree_class, bins, num_bins, minan, valid):
        return predict_binned_forest(
            stacked, tree_class, bins, num_bins, minan,
            num_outputs=forest.num_outputs, row_valid=valid)

    return {"serving_predict_b%d" % b: (predict, [
        forest.stacked, forest.tree_class, _sds((b, features), bin_dtype),
        forest.num_bins, forest.missing_is_nan, _sds((b,), jnp.bool_)])
        for b in buckets}


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=1_000_000)
    ap.add_argument("--features", type=int, default=28)
    ap.add_argument("--leaves", type=int, default=255)
    ap.add_argument("--max-bin", type=int, default=255)
    ap.add_argument("--block", type=int, default=10,
                    help="fused block length (default: fused_block_size)")
    ap.add_argument("--categorical", type=int, default=0,
                    help="leading columns passed as categorical_feature "
                         "in the training programs (the has_cat variants "
                         "of the routing kernels, the sorted split scan)")
    ap.add_argument("--only", default="",
                    help="comma-separated substrings of group names "
                         "(kernel, defaults, bench/mxu, bench/pallas, "
                         "serving, data_parallel)")
    args = ap.parse_args(argv)
    device = topology_devices()[0]
    base = {"objective": "binary", "num_leaves": args.leaves,
            "max_bin": args.max_bin, "min_data_in_leaf": 20,
            "categorical": args.categorical}
    bench = dict(base, use_quantized_grad=True, growth_overshoot=1.75,
                 growth_bridge_gate=0.93)
    shape = dict(rows=args.rows, features=args.features)
    slots = int(np.ceil(args.leaves * 1.75)) + 1
    groups = [
        ("kernel", lambda: kernel_sites(bmax=args.max_bin, slots=slots,
                                        **shape)),
        # the library's defaults: hist_backend=auto, a formulation per
        # pass (one-hot and slot-grouped kernels in one program)
        ("defaults", lambda: training_programs(
            base, block=args.block, **shape)),
        ("bench/mxu", lambda: training_programs(
            dict(bench, hist_backend="mxu"), block=args.block, **shape)),
        ("bench/pallas", lambda: training_programs(
            dict(bench, hist_backend="pallas"), block=args.block, **shape)),
        ("serving", lambda: serving_programs(
            features=args.features, leaves=args.leaves,
            max_bin=args.max_bin)),
    ]
    def sharded():
        devices = topology_devices()
        grower, specs = sharded_grower_program(base, devices=devices,
                                               **shape)
        return {"grow_tree_mxu_x%d" % len(devices): (grower, specs),
                "fused_block_k%d_x%d" % (args.block, len(devices)):
                sharded_fused_program(base, block=args.block,
                                      devices=devices, **shape)}

    groups.append(("data_parallel", sharded))
    only = [t for t in args.only.split(",") if t]
    failed = 0
    for group, build in groups:
        if only and not any(t in group for t in only):
            continue
        for name, (fn, fargs) in build().items():
            label = "%s:%s" % (group, name)
            try:
                compiled, dt = compile_for(device, fn, *fargs)
            except Exception as exc:   # report every program, then fail
                failed += 1
                print("FAIL %-40s %s: %s" % (
                    label, type(exc).__name__, str(exc)[:800]), flush=True)
                continue
            mem = compiled.memory_analysis()
            print("ok   %-40s compile %6.1fs  temp %5.0f MiB  args %5.0f "
                  "MiB" % (label, dt, mem.temp_size_in_bytes / 2 ** 20,
                           mem.argument_size_in_bytes / 2 ** 20),
                  flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
