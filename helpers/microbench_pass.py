"""One growth pass per histogram formulation, at a benchmark cell's
shape (2,625,000 x 28 x 256 unless --rows / --features say otherwise):
the derivation of histogram_pallas.GROUPED_MIN_WIDTH.

For each kernel width sk in {24, 40, 72, 136, 263} and for five (exact)
and three (quantized) channels it times, on the chip:

  onehot   fused_route_hist_mxu, at the row block grower_mxu.sweep picks
  route    route_rows_mxu(emit_counts=True), the grouped pass's first step
  partition_stream
           partition_table from the routed slots and the tree's row
           table: the one kernel that moves the live rows into the
           grouped kernel's layout (what partition_impl=auto runs)
  part     partition_rows from the routed slots: the rank sweep plus the
           scatter that inverts it (part_argsort: the retained oracle):
           the first half of partition_impl=rank
  scatter  that scatter alone, from positions ranked outside the clock
  table    the row table's build (bins and channels as one bf16 row):
           what a tree now does once, and a pass used to do
  gather   the XLA gather of the blocks in use from a table built
           outside the clock, with the pass's slot column written on the
           way: the second half of partition_impl=rank
  kernel   the grouped kernel over the gathered table
  grouped  route + build_histograms_scatter as sweep() runs them
           (grouped_rank: the same under partition_impl=rank, prepared)

onehot and grouped call the wrappers' self-preparing form on arrays
that are not loop-invariant (every repetition pads the bins, stacks the
channels, builds the table: a pass of a tree before ISSUE 29, whose
passes sit in cond branches that share nothing); onehot_prepared and
grouped_prepared take
the operands of prepare_hist_operands, built ONCE outside the chained
repetitions, as a pass of a tree does now.

--live is the share of the rows that are live (the smaller sibling; the
other child is parked): 0.18 by default, what a grouped pass of the
benchmark's trees holds (growth.grouped_live_row_share 18-22%); the
tables of PRs 25 to 31 were taken at 0.5. A comma-separated list runs
every share.

All timings are CHAINED IN-JIT: k dependency-chained repetitions in one
dispatch, long minus short, so the host's dispatch and sync cost cancels
out of a kernel's number. Every repetition starts from the same state.

With --check the route stage's node and slot of every row are first
compared, exactly, with the same splits replayed in NumPy (`route_exact`;
false makes the exit code 1), then each pass is built once by both
formulations and the row carries their largest difference over the
largest entry, per output channel (`agree_rel`: 0.0 three times in the
quantized posture, f32 summation noise in exact mode): a time for a
kernel that computes something else is worth nothing, and interpret mode
on a CPU cannot see what the chip's compiler does to a kernel. The same
flag holds the stream partition to the argsort oracle (`layout_exact`:
block_group, blocks_used and every row of every block in use of the
gathered table, bit for bit; false makes the exit code 1). --stages
check makes that comparison alone, on a table and slots drawn on the
device, at every --live share under one build, and times nothing.

Usage: python helpers/microbench_pass.py [--rows N] [--features F]
       [--sk 24,40,...] [--nchan 5,3] [--live 0.18,0.5]
       [--stages onehot,grouped,...] [--reps K] [--check] [--interpret]
       [--out NAME.json]
Writes chiprun_out/microbench_pass.json (or --out; after every pass, so
a call cut short keeps what it measured) beside the table it prints.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from lightgbm_tpu.learner import histogram_pallas as hp  # noqa: E402
from lightgbm_tpu.learner.histogram_mxu import (  # noqa: E402
    _hist_channels, _round_up, _row_table, fits_v2, fused_route_hist_mxu,
    hist_num_channels, pack_route_tables, prepare_hist_operands,
    route_rows_mxu)

F = 28
BMAX = 256


def timeit_chained(step, reps):
    """Seconds per call of `step` (() -> a scalar that depends on all of
    its work), timed as one jitted fori_loop of 1+reps repetitions minus
    one of 1. The carry threads a value XLA cannot fold, so repetitions
    neither fuse nor vanish."""

    @jax.jit
    def chain(k):
        def body(_, c):
            # c is always 0, which XLA cannot know
            return jnp.minimum(c + step(c), 0)
        return jax.lax.fori_loop(0, k, body, jnp.int32(0))

    def run(k):
        t0 = time.perf_counter()
        int(chain(jnp.int32(k)))
        return time.perf_counter() - t0

    run(1)  # compile + warm
    return min((run(1 + reps) - run(1)) / reps for _ in range(2))


def opaque(x):
    """An int32 zero that depends on every element of x's first row."""
    return (jnp.sum(jnp.ravel(x)[:8].astype(jnp.float32)) < -1e30) \
        .astype(jnp.int32)


def opaque_all(x):
    """An int32 zero that depends on EVERY element of x: a stage whose
    result nothing else reads would otherwise be computed for the eight
    elements `opaque` looks at (XLA narrows an elementwise producer to
    the slice that is used)."""
    return (jnp.sum(x, dtype=jnp.float32) < -1e30).astype(jnp.int32)


def make_state(sk, n, rng, live):
    """Route tables in which each of sk parents splits, at the bin that
    sends the share `live` of uniform bins left, into a left child that
    owns kernel slot = parent and a parked right child, and a row_node
    vector spread over the parents: a wide pass under sibling
    subtraction. Returns the tables, row_node and the threshold bin."""
    m_pad = _round_up(4 * sk, 128)
    thr = max(int(round(live * BMAX)) - 1, 0)
    ids = np.arange(m_pad)
    split = ids < sk
    slot = np.full(m_pad, -1)
    slot[sk + 2 * np.arange(sk)] = np.arange(sk)         # left children
    tbl, member = pack_route_tables(
        jnp.asarray(split), jnp.asarray(ids % F, jnp.int32),
        jnp.full(m_pad, thr, jnp.int32), jnp.zeros(m_pad, bool),
        jnp.zeros(m_pad, bool),
        jnp.asarray(np.where(split, sk + 2 * ids, 0), jnp.int32),
        jnp.asarray(np.where(split, sk + 2 * ids + 1, 0), jnp.int32),
        jnp.asarray(slot, jnp.int32),
        jnp.zeros((m_pad, (BMAX + 31) // 32), jnp.uint32), m_pad, BMAX)
    row_node = jnp.asarray(rng.randint(0, sk, n), jnp.int32)
    return tbl, member, row_node, thr


def onehot_row_block(sk, quant):
    """The row block grower_mxu.sweep picks for a one-hot pass."""
    if sk <= 64:
        return 2048
    for rb in (8192, 4096, 2048):
        if fits_v2(sk, F, BMAX, True, quant, row_block=rb):
            return rb
    return 2048


def layout_checker(sk, sg, nb, width, interpret):
    """(table, slots, counts) -> whether the stream partition's
    block_group, blocks_used and gathered table (every row of every
    block in use, bit for bit) are the argsort oracle's. One table at a
    time is alive beside the comparison: at 11M rows a layout is 2.8
    GB. Compiled once, so a sweep over live shares pays one build."""
    def part(impl):
        return jax.jit(lambda table, rs, cts: hp.partition_table(
            table, rs, num_slots=sk, row_block=nb, group=sg, counts=cts,
            impl=impl, interpret=interpret))

    stream, oracle = part("auto"), part("argsort")

    @jax.jit
    def differ(a, b, used):
        bits = [jax.lax.bitcast_convert_type(x[:, :width], jnp.uint16)
                for x in (a, b)]
        in_use = jnp.arange(a.shape[0])[:, None] < used * nb
        return jnp.sum((bits[0] != bits[1]) & in_use)

    def exact(table, rs, cts):
        bg, used, tab = stream(table, rs, cts)
        bg0, used0, tab0 = oracle(table, rs, cts)
        return bool(int(differ(tab, tab0, used0)) == 0 and
                    int(used) == int(used0) and
                    np.array_equal(np.asarray(bg), np.asarray(bg0)))

    return exact


def check_layouts(sk, nchan, n, lives, interpret):
    """The layout comparison alone, at every live share in `lives`:
    table and slots drawn on the device (no route, no histogram), one
    result row a share."""
    nb = hp.GROUPED_ROW_BLOCK
    sg = min(hp.group_width(nchan), sk)
    exact = layout_checker(sk, sg, nb, F + nchan + 1, interpret)

    @jax.jit
    def draw(key, live):
        kb, kd, ks, kl = jax.random.split(key, 4)
        table = _row_table(
            jax.random.randint(kb, (F, n), 0, BMAX).astype(jnp.uint8),
            jax.random.normal(kd, (8, n)), nchan)
        rs = jnp.where(jax.random.uniform(kl, (n,)) < live,
                       jax.random.randint(ks, (n,), 0, sk), -1)
        cts = jnp.sum(rs[None, :] == jnp.arange(sk)[:, None], axis=1,
                      dtype=jnp.int32)
        return table, rs, cts

    rows = []
    for i, live in enumerate(lives):
        table, rs, cts = draw(jax.random.PRNGKey(8 * sk + nchan + 4096 * i),
                              jnp.float32(live))
        rows.append({"sk": sk, "nchan": nchan, "rows": n, "features": F,
                     "live_share": round(float(jnp.sum(cts)) / n, 4),
                     "layout_exact": exact(table, rs, cts)})
        print("  live %s layout_exact %s" % (
            rows[-1]["live_share"], rows[-1]["layout_exact"]), flush=True)
    return rows


def bench_pass(sk, nchan, n, reps, interpret, rng, extras, check=False,
               only=None, live=0.18):
    quant = nchan == 3
    bins = jnp.asarray(rng.randint(0, BMAX, (n, F)), jnp.uint8)
    if quant:
        g = jnp.asarray(rng.randint(-127, 128, n), jnp.float32)
        h = jnp.asarray(rng.randint(0, 128, n), jnp.float32)
    else:
        g = jnp.asarray(rng.randn(n), jnp.float32)
        h = jnp.asarray(rng.rand(n), jnp.float32)
    cnt = jnp.ones(n, jnp.float32)
    feat_tbl = jnp.stack([jnp.full(F, float(BMAX)), jnp.zeros(F)], axis=1)
    tbl, member, row_node, thr = make_state(sk, n, rng, live)
    assert hist_num_channels(True, quant) == nchan
    kw = dict(quantized=quant, double_prec=True)
    nb = hp.GROUPED_ROW_BLOCK
    sg = min(hp.group_width(nchan), sk)
    ng = -(-sk // sg)
    rb = onehot_row_block(sk, quant)

    ops = jax.jit(lambda: prepare_hist_operands(
        bins, g, h, cnt, lanes=True, table=True, route=True, **kw))()

    def route(c):
        return route_rows_mxu(None, row_node + c, tbl, member, feat_tbl,
                              emit_counts=True, num_slots=sk,
                              operands=ops, interpret=interpret)

    # the stages' inputs, computed once outside the clock
    rn0, rs, cts = jax.jit(route)(jnp.int32(0))
    if check:
        exact = layout_checker(sk, sg, nb, ops.table.shape[0], interpret)(
            ops.table, rs, cts)
        print("  layout_exact %s" % exact, flush=True)
    block_group, used, src, src_slot = jax.jit(lambda: hp.partition_rows(
        rs, num_slots=sk, row_block=nb, group=sg, counts=cts,
        interpret=interpret))()
    data, _ = _hist_channels(g, h, cnt, True, quant)
    live_share = float(jnp.mean((rs >= 0).astype(jnp.float32)))
    chunk = min(hp._GATHER_CHUNK_BLOCKS, block_group.shape[0]) * nb

    def table_of(c):
        return _row_table(bins.T, data + c.astype(data.dtype), nchan)

    table_rows = jax.jit(jnp.transpose)(ops.table)   # as the oracles do

    def gather(c):
        return hp._gather_used(table_rows, src, src_slot + c, used * nb,
                               chunk)

    tab_g = jax.jit(gather)(jnp.int32(0))

    # the scatter alone: the positions of the live rows, ranked once
    live = rs >= 0
    grp = jnp.where(live, rs // sg, -1).astype(jnp.int32)
    gcounts = jnp.pad(cts[:sk], (0, ng * sg - sk)).reshape(ng, sg).sum(1)
    blk_start = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(
        jnp.maximum(1, -(-gcounts // nb))).astype(jnp.int32)])
    dst = jax.jit(lambda: hp._stable_positions(
        grp, blk_start[:ng] * nb, num_groups=ng, dump=src.shape[0],
        interpret=interpret))()

    def scatter(c):
        return opaque(jnp.full(src.shape[0], n, jnp.int32).at[dst].set(
            jnp.arange(n, dtype=jnp.int32) + c, mode="drop",
            unique_indices=True))

    def part(impl):
        return lambda c: opaque(hp.partition_rows(
            rs + c, num_slots=sk, row_block=nb, group=sg, counts=cts,
            impl=impl, interpret=interpret)[2])

    def stream(c):
        return opaque(hp.partition_table(
            ops.table, rs + c, num_slots=sk, row_block=nb, group=sg,
            counts=cts, interpret=interpret)[2])

    def plain(c):
        """The plain arrays as a pass of a tree meets them when nothing
        is prepared: not loop-invariant (c is a zero XLA cannot see), or
        the chained repetitions would share one preparation, which is
        what the passes of a tree, each in its own cond branch, cannot
        do."""
        return (bins + c.astype(bins.dtype), g + c.astype(g.dtype), h, cnt)

    def grouped(c, operands=None, partition_impl="auto"):
        raw = plain(c) if operands is None else (None,) * 4
        rn, rs_, cts_ = route_rows_mxu(
            raw[0], row_node + c, tbl, member, feat_tbl, emit_counts=True,
            num_slots=sk, operands=operands, interpret=interpret)
        return opaque(hp.build_histograms_scatter(
            *raw, rs_, num_slots=sk, bmax=BMAX, slot_counts=cts_,
            operands=operands, partition_impl=partition_impl,
            interpret=interpret, **kw)) + opaque(rn)

    def onehot(c, operands=None):
        raw = plain(c) if operands is None else (None,) * 4
        hist, rn = fused_route_hist_mxu(
            *raw, row_node + c, tbl, member, feat_tbl,
            num_slots=sk, bmax=BMAX, has_cat=False, row_block=rb,
            operands=operands, interpret=interpret, **kw)
        return opaque(hist) + opaque(rn)

    stages = {
        "onehot": onehot,
        "onehot_prepared": lambda c: onehot(c, ops),
        "route": lambda c: opaque(route(c)[1]),
        "partition_stream": stream,
        "part": part("rank"),
        "scatter": scatter,
        "table": lambda c: opaque_all(table_of(c)),
        "gather": lambda c: opaque(gather(c)),
        "kernel": lambda c: opaque(hp._grouped_call(
            block_group, used, tab_g + c.astype(tab_g.dtype), nb=nb, f=F,
            b=BMAX, sg=sg, ng=ng, nchan=nchan, fcols=F,
            interpret=interpret)),
        "grouped": grouped,
        "grouped_prepared": lambda c: grouped(c, ops),
        "grouped_rank": lambda c: grouped(c, ops, "rank"),
    }
    if "argsort" in extras:
        stages["part_argsort"] = part("argsort")
    if only:
        stages = {k: v for k, v in stages.items() if k in only}
    row = {"sk": sk, "nchan": nchan, "rows": n, "features": F,
           "onehot_row_block": rb,
           "groups": ng, "live_share": round(live_share, 4),
           "blocks_used": int(used), "blocks_static": int(
               block_group.shape[0])}
    if check:
        # the route stage against make_state's splits replayed in NumPy:
        # node and slot of every row, exactly, from the prepared and the
        # self-preparing form and from the fused kernel's own routing
        node = np.asarray(row_node)
        left = np.asarray(bins)[np.arange(n), node % F] <= thr
        want_node = sk + 2 * node + np.where(left, 0, 1)
        want_slot = np.where(left, node, -1)
        rn1, rs1 = jax.jit(lambda: route_rows_mxu(
            bins, row_node, tbl, member, feat_tbl, interpret=interpret))()
        h_one, rn2 = jax.jit(lambda: fused_route_hist_mxu(
            bins, g, h, cnt, row_node, tbl, member, feat_tbl, num_slots=sk,
            bmax=BMAX, has_cat=False, row_block=rb, interpret=interpret,
            **kw))()
        h_one = np.asarray(h_one)
        row["route_exact"] = bool(
            all(np.array_equal(np.asarray(x), want_node)
                for x in (rn0, rn1, rn2)) and
            all(np.array_equal(np.asarray(x), want_slot)
                for x in (rs, rs1)) and
            np.array_equal(np.asarray(cts),
                           np.bincount(node[left], minlength=sk)))
        print("  route_exact %s" % row["route_exact"], flush=True)
        row["layout_exact"] = exact
        h_grp = np.asarray(jax.jit(lambda: hp.build_histograms_scatter(
            bins, g, h, cnt, rs, num_slots=sk, bmax=BMAX, slot_counts=cts,
            interpret=interpret, **kw))())
        scale = np.abs(h_one).max(axis=(0, 1, 2)) + 1e-30
        row["agree_rel"] = [float(x) for x in
                            np.abs(h_grp - h_one).max(axis=(0, 1, 2)) / scale]
        # ... and each by the prepared operands: the same bits
        p_one, _ = jax.jit(lambda: fused_route_hist_mxu(
            None, None, None, None, row_node, tbl, member, feat_tbl,
            num_slots=sk, bmax=BMAX, has_cat=False, row_block=rb,
            operands=ops, interpret=interpret, **kw))()
        p_grp = jax.jit(lambda: hp.build_histograms_scatter(
            None, None, None, None, rs, num_slots=sk, bmax=BMAX,
            slot_counts=cts, operands=ops, interpret=interpret, **kw))()
        row["prepared_identical"] = bool(
            np.asarray(p_one).tobytes() == h_one.tobytes() and
            np.asarray(p_grp).tobytes() == h_grp.tobytes())
        print("  agree_rel %s prepared_identical %s" % (
            row["agree_rel"], row["prepared_identical"]), flush=True)
    for name, fn in stages.items():
        try:
            row[name + "_ms"] = round(timeit_chained(fn, reps) * 1e3, 3)
        except Exception as e:      # a kernel that does not build: say so
            row[name + "_ms"] = None
            row[name + "_error"] = "%s: %s" % (type(e).__name__,
                                               str(e)[:300])
        print("  %s %s" % (name, row[name + "_ms"]), flush=True)
    return row


def main():
    global F
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=2_625_000)
    ap.add_argument("--features", type=int, default=F)
    ap.add_argument("--live", default="0.18",
                    help="share(s) of the rows that are live")
    ap.add_argument("--sk", default="24,40,72,136,263")
    ap.add_argument("--nchan", default="5,3")
    ap.add_argument("--stages", default="",
                    help="time these stages only (default: all; "
                         "'check': none, the comparisons alone)")
    ap.add_argument("--reps", type=int, default=6)
    ap.add_argument("--out", default="microbench_pass.json",
                    help="file name under chiprun_out/")
    ap.add_argument("--check", action="store_true",
                    help="first compare the two formulations' histograms")
    ap.add_argument("--interpret", action="store_true",
                    help="Pallas interpret mode: a CPU rehearsal of the "
                         "control flow; its times mean nothing")
    args = ap.parse_args()
    F = args.features
    dev = jax.devices()[0]
    print("# device: %s %s" % (dev.platform, dev.device_kind), flush=True)
    rng = np.random.RandomState(0)
    rows = []
    only = set(filter(None, args.stages.split(",")))
    out = os.path.join(REPO, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    lives = [float(x) for x in args.live.split(",")]
    passes = [(nchan, int(sk))
              for nchan in [int(x) for x in args.nchan.split(",")]
              for sk in args.sk.split(",")]
    if only == {"check"}:
        lives = [lives]           # one build serves every share
    for live, (nchan, sk) in [(x, p) for x in lives for p in passes]:
        print("sk=%d nchan=%d live=%s" % (sk, nchan, live), flush=True)
        if only == {"check"}:
            rows += check_layouts(sk, nchan, args.rows, live,
                                  args.interpret)
        else:
            # the partition's A/B does not depend on the channels
            extras = {"argsort"} if nchan == 5 else set()
            rows.append(bench_pass(sk, nchan, args.rows, args.reps,
                                   args.interpret, rng, extras,
                                   check=args.check, only=only,
                                   live=live))
        with open(os.path.join(out, args.out), "w") as fh:
            json.dump({"platform": dev.platform,
                       "device_kind": dev.device_kind, "rows": rows},
                      fh, indent=1)
    ok = all(r.get("route_exact", True) and r.get("layout_exact", True)
             for r in rows)
    cols = ["sk", "nchan", "live_share", "onehot_ms", "onehot_prepared_ms",
            "grouped_ms", "grouped_prepared_ms", "grouped_rank_ms",
            "route_ms", "partition_stream_ms", "part_ms", "scatter_ms",
            "table_ms", "gather_ms", "kernel_ms", "part_argsort_ms",
            "blocks_used", "agree_rel", "prepared_identical",
            "route_exact", "layout_exact"]
    print("\t".join(cols))
    for r in rows:
        print("\t".join(str(r.get(c, "")) for c in cols))
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
