"""Sort/gather-free tree growth with per-pass-sized MXU histograms.

`grow_tree` (grower.py) runs every growth pass at full frontier capacity
S = num_leaves+1 inside one `lax.while_loop`. On TPU the histogram cost of
the MXU kernel scales linearly with S, and the early passes of a tree have
tiny frontiers (1, 2, 4, ... nodes). This variant unrolls the first
ceil(log2(num_leaves)) passes at doubling capacities S_p = 2^(p+1) — the
total histogram work becomes ~2x the final pass instead of ~P x — and
finishes any data-dependent leftovers (leaves that refused to split on
schedule) with a while_loop at full capacity.

Row bookkeeping never touches a sort, gather or scatter: histograms come
from histogram_mxu.build_histograms_mxu (slot-one-hot matmuls) and rows
advance through route_rows_mxu (packed node-table one-hot lookups), the
TPU reformulation of CUDADataPartition::SplitInner
(cuda_data_partition.cu:288-935).

Feature parity vs grow_tree: numerical + categorical splits, NaN routing,
monotone constraints, interaction constraints, feature_fraction_bynode,
extra_trees, forced splits (forced_splits json), CEGB (eager penalties;
lazy per-row feature penalties still fall back), and distributed growth
(the psum'd histogram merge under data/voting-parallel). The remaining
fallbacks to grow_tree are the ones gbdt._mxu_exclusions enforces:
max_bin > 256, non-basic monotone_constraints_method, CEGB with
cegb_penalty_feature_lazy, and EFB configurations the kernel cannot
route (see that method for the authoritative list).
"""

from __future__ import annotations

import functools
import math
import os
import types
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .grower import _init_tree, TreeArrays
from .histogram import build_histograms
from .histogram_mxu import (_round_up, build_histograms_mxu_auto, fits_v2,
                            fused_route_hist_mxu, hist_num_channels,
                            node_sums_mxu, node_values_mxu,
                            pack_route_tables, prepare_hist_operands,
                            quantize_gradients, route_rows_mxu,
                            unpack_bins_4bit)
from .histogram_pallas import (GROUPED_MIN_WIDTH_COLUMNS,
                               build_histograms_scatter, hist_columns,
                               use_grouped)
from .split import (BestSplits, SplitHyperParams, find_best_splits,
                    leaf_gain, leaf_output, _split_gain)

__all__ = ["grow_tree_mxu", "hist_pass_plan", "operand_builds"]


def _prune_to_best_first(tree: TreeArrays, row_node: jax.Array, *,
                         num_leaves: int, m_grow: int, interpret: bool,
                         aux: Tuple = (), rank_gain=None) -> Tuple:
    """Replay the reference's strict best-first growth order
    (serial_tree_learner.cpp:159-210) over an OVERGROWN tree's recorded
    split gains, keep the winning num_leaves-1 splits, and compact.

    The grower expands ~overshoot*num_leaves leaves in batched passes
    (cheap on the MXU), so every split best-first growth would consider
    has a recorded gain; the greedy heap replay is exact whenever the
    overshoot expanded every node best-first would pick. Runs entirely
    on device: num_leaves-1 argmax steps over [nodes] vectors, then a
    cumsum renumbering. Rows are remapped to their nearest kept-leaf
    ancestor, so callers see a standard (tree, row_node) pair. `aux` is
    a tuple of (array, fill) pairs compacted alongside the tree (e.g.
    monotone constraint bounds for re-clipping recomputed leaf values);
    the compacted arrays come back as a trailing tuple."""
    m1g = m_grow + 1
    mf = 2 * num_leaves - 1
    mf1 = mf + 1
    has_split = tree.left >= 0
    # rank_gain overrides the replay ORDER only (forced splits outrank
    # every gain-chosen candidate, serial_tree_learner.cpp:459); the
    # tree keeps its true recorded gains
    gains = jnp.where(has_split,
                      tree.gain if rank_gain is None else rank_gain,
                      -jnp.inf)

    # greedy selection: pop the max-gain available node, make its
    # children available (the reference's leaf queue, with all gains
    # known up front)
    def sim(i, c):
        avail, sel = c
        j = jnp.argmax(avail)
        ok = avail[j] > -jnp.inf
        sel = sel.at[j].set(sel[j] | ok)
        avail = avail.at[j].set(-jnp.inf)
        cl = jnp.where(ok, jnp.clip(tree.left[j], 0, m_grow), m_grow)
        cr = jnp.where(ok, jnp.clip(tree.right[j], 0, m_grow), m_grow)
        avail = avail.at[cl].set(
            jnp.where(cl < m_grow, gains[cl], -jnp.inf))
        avail = avail.at[cr].set(
            jnp.where(cr < m_grow, gains[cr], -jnp.inf))
        return avail, sel

    avail0 = jnp.full(m1g, -jnp.inf, jnp.float32).at[0].set(gains[0])
    _, sel = jax.lax.fori_loop(0, num_leaves - 1, sim,
                               (avail0, jnp.zeros(m1g, bool)))

    # reachability closure by pointer doubling: a node is kept iff every
    # PROPER ancestor was selected (sel is root-connected by construction
    # of the replay, so this is the whole condition). acc[i] starts as
    # sel[parent[i]] and AND-composes up the parent chain in log2 steps
    # instead of a num_leaves-long sequential fori_loop.
    par = jnp.clip(tree.parent, 0, m_grow)
    ids = jnp.arange(m1g, dtype=jnp.int32)
    is_root = ids == 0  # unused scratch slots also carry parent -1
    ptr = jnp.where(is_root, ids, par)
    acc = jnp.where(is_root, True, sel[par])
    for _ in range(max(1, (m1g - 1).bit_length())):
        acc = acc & acc[ptr]
        ptr = ptr[ptr]
    kept = acc & (is_root | (tree.parent >= 0))
    final_leaf = kept & ~sel

    # rows sit in overgrown leaves; ascend to the nearest kept-leaf
    # ancestor — same log2 pointer doubling (final_leaf cuts every
    # root-to-leaf path, so the fixed point always exists)
    nxt = jnp.where(final_leaf | is_root, ids, par)
    for _ in range(max(1, (m1g - 1).bit_length())):
        nxt = nxt[nxt]
    remap = nxt

    # compact: renumber kept nodes densely (order-preserving, root = 0)
    new_id = jnp.cumsum(kept.astype(jnp.int32)) - 1
    dst = jnp.where(kept, jnp.clip(new_id, 0, mf), mf)

    def compact(arr, fill):
        out = jnp.full((mf1,) + arr.shape[1:], fill, arr.dtype)
        return out.at[dst].set(arr)

    def child_new(c):
        cc = jnp.clip(c, 0, m_grow)
        return jnp.where(sel & (c >= 0), new_id[cc], -1)

    parent_new = jnp.where(tree.parent >= 0, new_id[par], -1)
    pruned = TreeArrays(
        split_feature=compact(
            jnp.where(sel, tree.split_feature, -1), -1),
        threshold_bin=compact(jnp.where(sel, tree.threshold_bin, 0), 0),
        default_left=compact(sel & tree.default_left, False),
        is_cat=compact(sel & tree.is_cat, False),
        cat_bitset=compact(
            jnp.where(sel[:, None], tree.cat_bitset, 0), 0),
        left=compact(child_new(tree.left), -1),
        right=compact(child_new(tree.right), -1),
        parent=compact(parent_new, -1),
        leaf_value=compact(tree.leaf_value, 0.0),
        sum_grad=compact(tree.sum_grad, 0.0),
        sum_hess=compact(tree.sum_hess, 0.0),
        count=compact(tree.count, 0.0),
        gain=compact(jnp.where(sel, tree.gain, 0.0), 0.0),
        depth=compact(tree.depth, 0),
        is_leaf=compact(final_leaf, False),
        num_nodes=jnp.sum(kept.astype(jnp.int32)),
        num_leaves=jnp.sum(final_leaf.astype(jnp.int32)))

    # per-row lookup of the compacted kept-leaf id (exact hi/lo one-hot
    # matmul; ids < 2*num_leaves are f32-exact)
    composed = new_id[remap].astype(jnp.float32)
    row_new = node_values_mxu(row_node, composed,
                              interpret=interpret).astype(jnp.int32)
    if aux:
        return pruned, row_new, tuple(compact(a, fill) for a, fill in aux)
    return pruned, row_new


def _kernel_cap(s: int) -> int:
    """Histogram-kernel slot capacity for a pass scanning `s` slots with
    sibling subtraction: the all-fresh bulk needs s/2 (one slot per smaller
    child), plus slack for stale pairs (leaves split later than the pass
    that scanned them need both children built, 2 slots)."""
    return min(s, s // 2 + 8)


#: index of the done flag in the growth state tuple
_DONE = 9

#: what a tree counts of its own growth, inside the program
#: (grow_tree_mxu(growth_counters=True) returns them as one int32
#: vector, in this order): the passes that RAN, by the formulation of
#: their histogram build (whatever is not one-hot counts as grouped);
#: those of them past the schedule (the bridge, 0 or 1, and the fixup
#: while_loop's iterations); the rows that stood in a slot those passes
#: built (the smaller siblings and the children of stale parents: the
#: mesh's rows under psum_axis, as the tree counts rows); the leaves
#: before the prune to best-first.
GROWTH_COUNTERS = ("onehot_passes", "grouped_passes", "bridge_passes",
                   "fixup_iters", "onehot_rows", "grouped_rows",
                   "leaves_grown")
_INT32_MAX = 2 ** 31 - 1


def _count_pass(counts: jax.Array, kern: jax.Array, form: str,
                stage: str) -> jax.Array:
    """GROWTH_COUNTERS after one pass that ran: one more pass of its
    formulation (and of its stage, past the schedule), and the rows of
    the slots it built. The rows are the count channel of the built
    histogram `kern` [slots, F, B, 3], summed over one column's bins:
    every row of a built slot falls in exactly one of them, and under
    psum_axis the histogram is all-reduced already, so the mesh's rows
    are counted with no collective of their own. The sum is float32,
    like the tree's own counts: exact up to 2^24 live rows a pass,
    rounded beyond. The running total saturates instead of wrapping."""
    live = jnp.minimum(jnp.round(jnp.sum(kern[:, 0, :, 2])),
                       jnp.float32(2 ** 31 - 128)).astype(jnp.int32)
    kind = "onehot" if form == "onehot" else "grouped"
    ran = np.zeros(len(GROWTH_COUNTERS), np.int32)
    ran[GROWTH_COUNTERS.index(kind + "_passes")] = 1
    if stage != "pass":
        ran[GROWTH_COUNTERS.index(
            "bridge_passes" if stage == "bridge" else "fixup_iters")] = 1
    rows_at = np.zeros(len(GROWTH_COUNTERS), np.int32)
    rows_at[GROWTH_COUNTERS.index(kind + "_rows")] = 1
    add = ran + live * rows_at
    total = counts + add
    # two non-negative int32 whose sum wrapped read negative
    return jnp.where(total < 0, _INT32_MAX, total)


def growth_plan(*, num_leaves: int, overshoot: float = 0.0,
                tail_split_cap: int = 0, hist_subtraction: bool = True,
                bridge_gate: float = 0.0):
    """Static growth schedule of grow_tree_mxu.

    Everything here derives from static config only — no array in
    sight — so hist_pass_plan (and through it the booster, on the
    host) reads the same schedule the traced program runs.

    With overshoot the fixup frontier runs FULL-width (s_fix =
    min(512, s_max)): narrow fixup frontiers chasing 65-200 leftover
    splits made late trees slower than early ones; the bridge gate
    (growth_bridge_gate) skips the s_max-wide
    bridge sweep once num_leaves >= gate * L_g, never gating below the
    actual leaf budget so the prune keeps its num_leaves target."""
    over = overshoot if overshoot and overshoot >= 1.0 else 0.0
    if over:
        tail_split_cap = 0
    L_g = int(math.ceil(num_leaves * over)) if over else num_leaves
    m_pad = _round_up(2 * L_g, 128)
    s_max = L_g + 1
    schedule = []
    s_p = 1
    while s_p < s_max and len(schedule) < 32:
        schedule.append(min(max(2 * s_p, 2), s_max))
        s_p *= 2
    if over:
        # 512: the whole frontier of a 255-leaf tree at overshoot 2
        s_fix = min(512, s_max)
        sk_fix = s_fix if hist_subtraction else None
    elif tail_split_cap <= 0:
        s_fix = min(64, s_max)
        sk_fix = _kernel_cap(s_fix) if hist_subtraction else None
    else:
        s_fix = min(s_max, max(16, 2 * tail_split_cap))
        sk_fix = _kernel_cap(s_fix) if hist_subtraction else None
    k_fix = max(1, s_fix // 2)
    if over and bridge_gate > 0:
        gate_leaves = max(int(bridge_gate * L_g), num_leaves)
    else:
        gate_leaves = None

    def m_cap_of(s_p):
        # pass p holds < 2*S_p node ids; slice the route tables to the
        # lane-aligned bound (sweep docstring)
        return min(m_pad, _round_up(max(2 * s_p, 2), 128))

    return types.SimpleNamespace(
        over=over, L_g=L_g, m_pad=m_pad, s_max=s_max, schedule=schedule,
        s_fix=s_fix, sk_fix=sk_fix, k_fix=k_fix, gate_leaves=gate_leaves,
        m_cap_of=m_cap_of, tail_split_cap=tail_split_cap)


def pass_formulation(nslots: int, *, hist_backend: str, nchan: int,
                     rows: int, has_efb: bool = False,
                     columns: int = GROUPED_MIN_WIDTH_COLUMNS) -> str:
    """Which histogram formulation a pass with `nslots` kernel slots
    uses: "onehot" (histogram_mxu: every row against every slot),
    "grouped" (histogram_pallas: live rows partitioned by slot group,
    cost independent of the width) or "scatter" (the XLA segment-sum
    oracle). hist_backend=mxu|pallas|scatter name one for the whole
    run; "auto" asks histogram_pallas.use_grouped per pass, from static
    shapes alone. EFB growth has bundle-space wiring in the one-hot
    sweep only."""
    if has_efb or hist_backend == "mxu":
        return "onehot"
    if hist_backend == "scatter":
        return "scatter"
    if hist_backend == "pallas" or use_grouped(nchan * nslots, rows,
                                               columns=columns):
        return "grouped"
    return "onehot"


def hist_pass_plan(*, rows: int, num_leaves: int, overshoot: float = 0.0,
                   tail_split_cap: int = 0, hist_subtraction: bool = True,
                   bridge_gate: float = 0.0, hist_backend: str = "auto",
                   hist_double_prec: bool = True,
                   quantized_grad: bool = False,
                   const_hessian: float = 0.0, has_efb: bool = False,
                   columns: int = GROUPED_MIN_WIDTH_COLUMNS):
    """The growth program's histogram passes as [(stage, kernel slots,
    formulation)], from static configuration alone: what sweep() will
    decide at trace time, computed by the same function, so a caller can
    record it without a device sync. `rows` is the row count ONE device
    holds, `columns` one slot's histogram columns (hist_columns). Stages: "pass" (the doubling schedule), "bridge", "fixup"
    (the while_loop's body, run as often as the tree needs)."""
    plan = growth_plan(num_leaves=num_leaves, overshoot=overshoot,
                       tail_split_cap=tail_split_cap,
                       hist_subtraction=hist_subtraction,
                       bridge_gate=bridge_gate)
    nchan = hist_num_channels(hist_double_prec, quantized_grad,
                              const_hessian)

    def sk_of(s):
        return _kernel_cap(s) if hist_subtraction else s

    stages = [("pass", sk_of(s)) for s in plan.schedule]
    if plan.schedule:
        stages.append(("bridge", sk_of(plan.s_max)))
    stages.append(("fixup", plan.sk_fix if hist_subtraction
                   else plan.s_fix))
    return [(stage, sk, pass_formulation(
        sk, hist_backend=hist_backend, nchan=nchan, rows=rows,
        has_efb=has_efb, columns=columns)) for stage, sk in stages]


# ---------------------------------------------------------------------------
# per-tree operands, counted in the traced program
# ---------------------------------------------------------------------------

#: the equations that build a kernel operand from what is fixed for a
#: tree (bins, gradients, count weights): what belongs outside every
#: pass. "rank_scatter" is the one row-sized equation a grouped pass
#: keeps by nature, listed so that its count can be held to one a pass.
OPERAND_EQUATIONS = ("bins_row_pad", "bins_lane_pad", "bins_transpose",
                     "channels_stack", "channels_split", "channels_pad",
                     "table_bins", "table_concat")


def _operand_equation(eqn, rows: int) -> Optional[str]:
    """Which of OPERAND_EQUATIONS (or "rank_scatter") `eqn` is, by its
    primitive and its output's shape and dtype; None for the rest.
    Operands with a row of the data per row ([R, k]: the bins) and
    those with the rows along lanes ([k, R]: the channels, the
    transposed bins, the row table) are told apart by where the row
    count sits."""
    out = eqn.outvars[0].aval
    shape = getattr(out, "shape", ())
    if not shape or len(shape) > 2 or max(shape) < rows:
        return None
    name, dt = eqn.primitive.name, out.dtype
    if len(shape) == 1:
        if name == "reduce_precision":
            return "channels_split"        # _hist_channels' hi/lo
        if name == "scatter" and dt == jnp.int32:
            return "rank_scatter"          # partition_rows' inversion
        return None
    if shape[0] < rows:                    # rows along lanes
        if name == "transpose" and jnp.issubdtype(dt, jnp.integer):
            return "bins_transpose"        # the bins' [F, N]
        if dt == jnp.bfloat16:             # _row_table: [W, N (+ 1)]
            if name == "concatenate":
                return "table_concat"      # the table, its padding row
            if name == "convert_element_type" and jnp.issubdtype(
                    eqn.invars[0].aval.dtype, jnp.integer):
                return "table_bins"        # the table's bin rows
            return None
        if shape[0] != 8 or dt != jnp.float32:
            return None
        if name == "concatenate" and shape[1] == rows:
            return "channels_stack"        # _hist_channels' [8, N]
        if name == "pad" and eqn.invars[0].aval.shape == (8, rows):
            return "channels_pad"
        return None
    if name == "pad" and jnp.issubdtype(dt, jnp.integer):
        return "bins_row_pad" if shape[1] == eqn.invars[0].aval.shape[1] \
            else "bins_lane_pad"
    return None


def _id_column(eqn, rows: int) -> bool:
    """Whether `eqn` makes a per-row scalar a COLUMN or reads one back:
    [r] -> [r, 1], or [r', k < 128] -> [r], of a 4-byte type at r >=
    rows. On the TPU an [r, k] array with k < 128 is tiled (8, 128), 512
    bytes a row, so a node or slot id that crosses a kernel boundary
    this way costs a hundred times its content; the kernels take and
    return [1, r] instead."""
    out = eqn.outvars[0].aval
    if not eqn.invars or not hasattr(eqn.invars[0], "aval"):
        return False
    src = eqn.invars[0].aval
    oshape, sshape = getattr(out, "shape", ()), getattr(src, "shape", ())
    if not oshape or oshape[0] < rows or out.dtype.itemsize != 4:
        return False
    if len(sshape) == 1 and oshape == (sshape[0], 1):
        return True                        # x[:, None] into a kernel
    return len(oshape) == 1 and len(sshape) == 2 and \
        sshape[0] >= rows and sshape[1] < 128   # out[:n, c]


def _sub_jaxprs(eqn):
    for val in eqn.params.values():
        for sub in (val if isinstance(val, (list, tuple)) else (val,)):
            while not hasattr(sub, "eqns") and hasattr(sub, "jaxpr"):
                sub = sub.jaxpr
            if hasattr(sub, "eqns"):
                yield sub


def _find_jit(jaxpr, name: str):
    """The body of the first `jit` equation called `name` in `jaxpr` or
    its sub-jaxprs (a scan's body, a shard_map's), else None."""
    for eqn in jaxpr.eqns:
        for sub in _sub_jaxprs(eqn):
            if eqn.primitive.name in ("jit", "pjit") and \
                    eqn.params.get("name") == name:
                return sub
            found = _find_jit(sub, name)
            if found is not None:
                return found
    return None


def operand_builds(jaxpr, rows: Optional[int] = None) -> dict:
    """Where a traced program builds the row-sized kernel operands:
    once per tree, or again in every pass.

    `jaxpr` is any traced program that grows trees with grow_tree_mxu
    (its own jaxpr, the fused scan's, the sharded grower's): the walk
    goes to grow_tree_mxu's body and counts the equations of
    OPERAND_EQUATIONS whose output has at least `rows` rows (default:
    the rows of the body's first argument, the bins), apart for every
    pass body (each branch of a top-level lax.cond, the fixup
    while_loop's body: where XLA shares nothing with the other passes)
    and for what lies outside them. Kernels are not entered.

    Returns {"per_tree": {"bins_pad", "bins_t", "channels",
    "row_table"}: builds outside every pass (the channel operand counts
    2 in the quantized posture: the exact leaf refit stacks its own),
    "per_pass": the most OPERAND_EQUATIONS any one pass body holds (0
    when every operand is prepared per tree), "tree": the raw counts
    outside, "passes": the raw counts of each pass body that has any,
    "id_columns_per_tree" / "id_columns_per_pass": the per-row scalars
    made or read as lane-padded columns (_id_column) outside every pass
    and the most in any one pass body (0 and 0 since the kernels take
    and return them along lanes)}. Static: read from the trace, no
    device involved."""
    while not hasattr(jaxpr, "eqns"):
        jaxpr = jaxpr.jaxpr
    body = _find_jit(jaxpr, "grow_tree_mxu") or jaxpr
    if rows is None:
        rows = body.invars[0].aval.shape[0]
    tree: dict = {}
    passes = []

    def walk(jx, into):
        # the index operand of an XLA scatter or gather is [r, 1] by
        # that operation's own signature: not a kernel boundary
        indices = {id(e.invars[1]) for e in jx.eqns
                   if e.primitive.name.startswith(("scatter", "gather"))}
        for eqn in jx.eqns:
            kind = _operand_equation(eqn, rows) or (
                id(eqn.outvars[0]) not in indices and
                _id_column(eqn, rows) and "id_column")
            if kind:
                into[kind] = into.get(kind, 0) + 1
            name = eqn.primitive.name
            if name == "pallas_call":
                continue
            for sub in _sub_jaxprs(eqn):
                if into is tree and name in ("cond", "while"):
                    passes.append({})
                    walk(sub, passes[-1])
                else:
                    walk(sub, into)

    walk(body, tree)
    return {
        "per_tree": {"bins_pad": tree.get("bins_row_pad", 0),
                     "bins_t": tree.get("bins_transpose", 0),
                     "channels": tree.get("channels_stack", 0),
                     "row_table": tree.get("table_bins", 0)},
        "per_pass": max([sum(c.get(k, 0) for k in OPERAND_EQUATIONS)
                         for c in passes] or [0]),
        "id_columns_per_tree": tree.get("id_column", 0),
        "id_columns_per_pass": max([c.get("id_column", 0)
                                    for c in passes] or [0]),
        "tree": tree, "passes": [c for c in passes if c]}


@functools.partial(
    jax.jit,
    static_argnames=("num_leaves", "max_depth", "hp", "bmax",
                     "interaction_groups", "feature_fraction_bynode",
                     "interpret", "hist_double_prec", "tail_split_cap",
                     "hist_subtraction", "overshoot", "bridge_gate",
                     "psum_axis",
                     "quantized_grad", "packed4",
                     "const_hessian", "hist_backend", "partition_impl",
                     "cegb_cfg", "growth_counters"))
def grow_tree_mxu(bins: jax.Array, grad: jax.Array, hess: jax.Array,
                  cnt_weight: jax.Array, feature_mask: jax.Array,
                  num_bins: jax.Array, missing_is_nan: jax.Array,
                  is_cat_feat: jax.Array, *, num_leaves: int,
                  max_depth: int,
                  hp: SplitHyperParams, bmax: int,
                  monotone: Optional[jax.Array] = None,
                  interaction_groups: Optional[tuple] = None,
                  feature_fraction_bynode: float = 1.0,
                  rng_key: Optional[jax.Array] = None,
                  interpret: bool = False,
                  hist_double_prec: bool = True,
                  tail_split_cap: int = 0,
                  hist_subtraction: bool = True,
                  overshoot: float = 0.0,
                  bridge_gate: float = 0.0,
                  psum_axis: Optional[str] = None,
                  quantized_grad: bool = False,
                  packed4: bool = False,
                  const_hessian: float = 0.0,
                  hist_backend: str = "mxu",
                  partition_impl: str = "auto",
                  efb=None,
                  forced=None,
                  cegb_cfg=None,
                  cegb_state=None,
                  growth_counters: bool = False
                  ) -> Tuple[TreeArrays, jax.Array]:
    """Grow one tree; same contract as grower.grow_tree (serial mode).

    One jit program: the doubling schedule, the bridge pass, the
    data-dependent fixup while_loop and the epilogue (flush + prune +
    exact refit) all run in ONE device dispatch (zero host syncs per
    tree). The serial learner, the fused scan and the sharded grower
    (inside shard_map, psum_axis set) all trace THIS function.

    tail_split_cap > 0 enables hybrid growth: while the leaf budget is
    loose (remaining leaves >= splittable leaves) passes split every
    eligible leaf — the regime where batched and strict best-first growth
    agree — and once the budget binds, passes commit at most
    tail_split_cap splits before re-ranking, approaching the reference's
    strict leaf-wise order (serial_tree_learner.cpp:159-210) as the cap
    shrinks. Retained gains make tail passes cheap: only the new
    children's histograms are built.

    hist_subtraction applies the reference's sibling-histogram trick
    (serial_tree_learner.cpp:311-326): kernel slots are assigned only to
    the SMALLER child of each fresh split; the larger sibling's histogram
    is parent minus smaller, with the parent row pulled from the previous
    pass's scan tensor by an exact one-hot matmul. Nodes split later than
    the pass that scanned them (stale parents) get both children built
    (2 slots), and split selection is throttled so the per-pass slot cost
    fits the kernel capacity (~s/2 instead of s slots per pass).

    packed4=True marks `bins` as 4-bit packed storage (pack_bins_4bit,
    the reference's 4-bit DenseBin, src/io/dense_bin.hpp:42): the kernels
    unpack nibbles in VMEM, so HBM holds half the bin bytes. Exact —
    identical trees to unpacked storage.

    hist_backend selects the histogram formulation (pass_formulation):
    "auto" decides PER PASS from static shapes (the six narrow passes of
    a 255-leaf tree stay on the one-hot kernels, which sit on the MXU's
    floor there; the wide ones route with route_rows_mxu(emit_counts=
    True) and build slot-grouped, histogram_pallas, at a cost
    independent of the width); "mxu", "pallas" and "scatter" (the XLA
    segment-sum oracle) name one formulation for every pass and serve
    as each other's test oracles. The choice is made at trace time, so
    a program holds exactly the kernels its passes use. In the
    quantized posture all formulations produce bit-identical histograms
    (integer sums, order-independent below 2^24), hence byte-identical
    trees. EFB data ignores the selector (bundle-space histograms are a
    one-hot-kernel-only formulation).

    efb (EfbDev, efb.py) marks `bins` as the BUNDLED matrix [N, Fb]:
    histograms build in bundle space ([S, Fb, Bb, 3] — the flop and
    state win on wide-sparse data) and are expanded per pass back to
    original features for the split scan; routing decodes original
    local bins through efb.loc_table inside the kernels. Same math as
    the portable grower's EFB path (grower.py), so trees match it.

    growth_counters=True returns (tree, row_node, counters): what the
    tree ran, counted in the pass state by every pass that runs
    (GROWTH_COUNTERS, one int32 vector). The fused scan asks for them
    with every tree and carries them out with the block
    (boosting/fused.py); the per-iteration callers take the two values
    and their program counts nothing."""
    n = bins.shape[0]
    f = int(num_bins.shape[0]) if (packed4 or efb is not None) \
        else bins.shape[1]
    nf_packed = f if packed4 else 0
    # kernel-space dims: bundle columns/bins when EFB is active
    fk = bins.shape[1] if efb is not None else f
    bk = efb.bundle_bmax if efb is not None else bmax
    loc_tbl = efb.loc_table if efb is not None else None
    # segmented EFB routes by bundle-position RANGES packed into the
    # node tables (histogram_mxu efb_range) — no per-row decode
    efb_seg = efb is not None and efb.scan is not None
    # overshoot > 1 switches to overgrow-and-prune: grow toward
    # overshoot*num_leaves leaves with unthrottled batched passes, then
    # replay the exact best-first selection over the recorded gains
    # (_prune_to_best_first). Replaces the tail throttle entirely.
    plan = growth_plan(num_leaves=num_leaves, overshoot=overshoot,
                       tail_split_cap=tail_split_cap,
                       hist_subtraction=hist_subtraction,
                       bridge_gate=bridge_gate)
    over, L_g, m_pad, s_max = plan.over, plan.L_g, plan.m_pad, plan.s_max
    tail_split_cap = plan.tail_split_cap
    m = 2 * L_g - 1
    m1 = m + 1
    k_top = L_g - 1
    w_cat = (bmax + 31) // 32
    P_all = (s_max + 1) // 2 + 2   # pair-state capacity (subtraction)

    # psum_axis != None runs this grower INSIDE shard_map as the
    # data-parallel learner: rows are sharded, per-pass histograms are
    # all-reduced over ICI (the reference's Reduce-Scatter of histograms,
    # data_parallel_tree_learner.cpp:184-186 — here a psum, with every
    # shard scanning all features), and every shard takes identical
    # split decisions, so the tree is replicated without a sync.
    def _allred(x):
        return jax.lax.psum(x, psum_axis) if psum_axis else x

    # quantized_grad: stochastically-rounded integer grad/hess feed
    # 3-channel histograms (1.67x fewer MXU flops than the 5-channel
    # double-bf16 scheme); the final leaf values are recomputed exactly
    # at the end, so quantization only perturbs the split SEARCH.
    quant = quantized_grad
    # const_hessian != 0: per-row hessians are const x cnt_weight (the
    # reference's IsConstantHessian fast path) — the kernels drop the
    # hessian channel and reconstruct it exactly as const x count, so
    # hessian sums carry NO quantization noise and every histogram dot
    # runs one channel lighter (3 -> 2 quantized, 5 -> 3 exact)
    ch = const_hessian
    root_c = _allred(jnp.sum(cnt_weight))
    if quant:
        qkey = rng_key if rng_key is not None else jax.random.PRNGKey(0)
        qkey = jax.random.fold_in(qkey, 6271)
        # decorrelate rounding noise across trees even when no per-tree
        # key is plumbed (the sharded grower path): fold in gradient
        # bits so each iteration's noise differs — reusing one u per
        # row every tree would make its rounding error systematic in
        # the ensemble
        qkey = jax.random.fold_in(
            qkey, jax.lax.bitcast_convert_type(jnp.sum(grad), jnp.int32))
        h_grad, h_hess, gscale, hscale = quantize_gradients(
            grad, None if ch else hess, qkey, pmax_axis=psum_axis)
        if h_hess is None:
            h_hess = hess  # never read: the channel builder drops it
        hist_scale = jnp.stack([gscale, hscale, jnp.float32(1.0)])
        # hist-consistent root sums (exact integer sums x scale), so
        # right-child = parent - left stays internally consistent
        root_g = _allred(jnp.sum(h_grad)) * gscale
        root_h = jnp.float32(ch) * root_c if ch else \
            _allred(jnp.sum(h_hess)) * hscale
    else:
        h_grad, h_hess = grad, hess
        hist_scale = jnp.ones(3, jnp.float32)   # unused without quant
        root_g = _allred(jnp.sum(grad))
        root_h = jnp.float32(ch) * root_c if ch else \
            _allred(jnp.sum(hess))
    root_val = leaf_output(root_g, root_h, hp.lambda_l1, hp.lambda_l2,
                           hp.max_delta_step)
    tree0 = _init_tree(m, root_g, root_h, root_c, root_val,
                       bitset_words=w_cat)

    best0 = BestSplits(
        gain=jnp.full(m1, -jnp.inf, jnp.float32),
        feature=jnp.full(m1, -1, jnp.int32),
        threshold_bin=jnp.zeros(m1, jnp.int32),
        default_left=jnp.zeros(m1, bool),
        left_grad=jnp.zeros(m1, jnp.float32),
        left_hess=jnp.zeros(m1, jnp.float32),
        left_count=jnp.zeros(m1, jnp.float32),
        left_output=jnp.zeros(m1, jnp.float32),
        right_output=jnp.zeros(m1, jnp.float32),
        per_feature_gain=jnp.zeros((1, 1), jnp.float32),
        cat_bitset=jnp.zeros((m1, w_cat), jnp.uint32))

    use_interaction = interaction_groups is not None and \
        len(interaction_groups) > 0
    if use_interaction:
        gm = np.zeros((len(interaction_groups), f), np.bool_)
        for gi, grp in enumerate(interaction_groups):
            for fi in grp:
                if 0 <= fi < f:
                    gm[gi, fi] = True
        group_masks = jnp.asarray(gm)
        path_mask0 = jnp.zeros((m1, f), bool)
    else:
        group_masks = None
        path_mask0 = jnp.zeros((1, 1), bool)
    use_bynode = feature_fraction_bynode < 1.0 and rng_key is not None
    k_bynode = max(1, int(round(feature_fraction_bynode * f)))

    feat_tbl = jnp.stack([num_bins.astype(jnp.float32),
                          missing_is_nan.astype(jnp.float32)], axis=1)

    # Forced splits (reference SerialTreeLearner::ForceSplits,
    # serial_tree_learner.cpp:459) and CEGB penalties
    # (cost_effective_gradient_boosting.hpp) on the MXU path — same
    # semantics as the portable grower (grower.py:266-300). The lazy
    # per-row CEGB penalty is NOT supported here (it needs an [N, F]
    # charge matrix rebuilt per pass); callers route has_lazy configs to
    # the portable grower.
    use_forced = forced is not None
    if use_forced:
        forced_feat, forced_bin, forced_left, forced_right = forced
        n_spec = forced_feat.shape[0]
    use_cegb = cegb_cfg is not None
    if use_cegb:
        if cegb_cfg.has_lazy:
            raise NotImplementedError(
                "cegb_penalty_feature_lazy runs on the portable grower")
        cegb_coupled, _cegb_lazy, feat_used0, row_feat_used0 = cegb_state
    else:
        feat_used0 = jnp.zeros(1, bool)
    node_force0 = (jnp.full(m1, -1, jnp.int32).at[0].set(0)
                   if use_forced else jnp.full(1, -1, jnp.int32))
    forced_ok0 = jnp.zeros(m1 if use_forced else 1, bool)
    was_forced0 = jnp.zeros(m1 if use_forced else 1, bool)

    nchan = hist_num_channels(hist_double_prec, quant, ch)
    columns = hist_columns(fk, bk)

    # what the kernels read of the rows and that no pass changes (the
    # padded bins, the channel operand, the grouped build's row table)
    # is built HERE, once per tree: every pass below runs in its own
    # lax.cond branch or in the fixup while_loop's body, where XLA
    # shares nothing across passes. Only what the static plan uses is
    # built; the passes close over it.
    forms = {form for _, _, form in hist_pass_plan(
        rows=n, num_leaves=num_leaves, overshoot=overshoot,
        tail_split_cap=tail_split_cap, hist_subtraction=hist_subtraction,
        bridge_gate=bridge_gate, hist_backend=hist_backend,
        hist_double_prec=hist_double_prec, quantized_grad=quant,
        const_hessian=ch, has_efb=efb is not None, columns=columns)}
    ops = prepare_hist_operands(
        bins, h_grad, h_hess, cnt_weight, double_prec=hist_double_prec,
        quantized=quant, const_hess=ch, lanes="onehot" in forms,
        channels="onehot" in forms, table="grouped" in forms, route=True)

    def hist_cfg(s):
        # empirically tuned on v5e: wider feature chunks while the output
        # block fits comfortably in VMEM, narrower for big frontiers
        return dict(row_block=2048, fchunk=7 if s <= 64 else 4)

    def sweep(row_node, tbl_c, member_c, nslots, m_cap=None):
        """Route rows through the previous pass's packed tables and build
        the frontier histograms — fused single sweep when the histogram
        block fits VMEM, else the two-kernel fallback (wide datasets).
        Under psum_axis the local histograms are all-reduced, so the
        subtraction/scan math downstream sees global sums. Returns
        (histograms, row_node, formulation): the last is static.

        m_cap statically slices the node tables: pass p can only hold
        node ids < 2*S_p, so early passes route against a 128-wide
        one-hot instead of the full m_pad (~8x less route work for the
        first ~6 passes of a 255-leaf tree)."""
        if m_cap is not None and m_cap < m_pad:
            tbl_c = tbl_c[:m_cap]
            member_c = member_c[:m_cap]
        form = pass_formulation(nslots, hist_backend=hist_backend,
                                nchan=nchan, rows=n,
                                has_efb=efb is not None, columns=columns)
        if form != "onehot":
            # route + per-slot counts in one sweep, then build from the
            # partitioned live rows (grouped) or by the XLA oracle
            rn, rs, cts = route_rows_mxu(
                None, row_node, tbl_c, member_c, feat_tbl,
                num_features=nf_packed, emit_counts=True,
                num_slots=nslots, has_cat=hp.has_categorical,
                operands=ops, interpret=interpret)
            if form == "grouped":
                h = build_histograms_scatter(
                    None, None, None, None, rs,
                    num_slots=nslots, bmax=bk, num_features=nf_packed,
                    quantized=quant, double_prec=hist_double_prec,
                    const_hess=ch, slot_counts=cts,
                    partition_impl=partition_impl, operands=ops,
                    interpret=interpret)
            else:  # "scatter": the pure-XLA segment-sum oracle
                ub = unpack_bins_4bit(bins, f) if packed4 else bins
                h = build_histograms(ub, h_grad, h_hess, rs, cnt_weight,
                                     num_slots=nslots, bmax=bk)
                if ch:
                    # reconstruct hessian sums exactly as const x count,
                    # matching the kernel backends' channel drop
                    h = h.at[..., 1].set(h[..., 2] * jnp.float32(ch))
            if quant:
                h = h * hist_scale
            return _allred(h), rn, form
        # small frontiers run cheaper at half blocks, large ones
        # prefer the wider block. EFB keeps rb=1024 in BOTH modes:
        # expansion's original-feature route side needs the VMEM
        # headroom (a 2048 block compiled to a real 136 MB OOM at
        # 250-column bundles), and bundle-range mode did not gain from
        # larger adaptive blocks
        rw = f if (efb is not None and not efb_seg) else 0
        if efb is not None:
            rb = 1024
        elif nslots <= 64:
            rb = int(os.environ.get("LGBM_TPU_RB_SMALL", 2048))
        else:
            # large frontiers: the chained per-pass microbench
            # (helpers/microbench_pass.py, v5e round 5) measured 8192
            # fastest at every sk > 64 (sk=72: 20.0 ms vs 26.9 at 4096;
            # sk=136: 34.6 vs 38.9) — fewer grid steps re-visiting the
            # VMEM-resident accumulator. Fall back block-by-block when
            # the bigger input working set would bust the VMEM budget
            # (e.g. 5-channel exact grads at wide frontiers).
            for rb in (int(os.environ.get("LGBM_TPU_RB_LARGE", 8192)),
                       4096, 2048):
                if fits_v2(nslots, fk, bk, hist_double_prec, quant,
                           route_width=rw, row_block=rb, const_hess=ch):
                    break
        if fits_v2(nslots, fk, bk, hist_double_prec, quant,
                   route_width=rw, row_block=rb, const_hess=ch):
            h, rn = fused_route_hist_mxu(
                None, None, None, None, row_node, tbl_c,
                member_c, feat_tbl, num_slots=nslots, bmax=bk,
                has_cat=hp.has_categorical, quantized=quant,
                double_prec=hist_double_prec, num_features=nf_packed,
                loc_table=None if efb_seg else loc_tbl,
                efb_range=efb_seg, row_block=rb, const_hess=ch,
                operands=ops, interpret=interpret)
        else:
            rn, rs = route_rows_mxu(None, row_node, tbl_c, member_c,
                                    feat_tbl, num_features=nf_packed,
                                    has_cat=hp.has_categorical,
                                    loc_table=None if efb_seg
                                    else loc_tbl, efb_range=efb_seg,
                                    operands=ops, interpret=interpret)
            # (the chunked v1 fallback pads to its own selector layout
            # from the plain arguments)
            h = build_histograms_mxu_auto(
                bins, h_grad, h_hess, cnt_weight, rs, num_slots=nslots,
                bmax=bk, interpret=interpret, quantized=quant,
                double_prec=hist_double_prec, num_features=nf_packed,
                const_hess=ch, operands=ops,
                **hist_cfg(nslots))
        if quant:
            h = h * hist_scale  # integer sums -> gradient units
        return _allred(h), rn, form

    def one_pass(s, st, pass_idx, k_cap=None, sk_next=None, m_cap=None,
                 sk_self=None, stage="pass"):
        """One growth pass at scan capacity `s` (python int). sk_next is
        the kernel-slot capacity of the NEXT pass (selection is throttled
        so committed splits' children fit it). `stage` is hist_pass_plan's
        name for it ("pass", "bridge", "fixup"), for the counters."""
        (tree, row_node, tbl_c, member_c, slot_nodes, best, cons_min,
         cons_max, path_mask, done, parent_hist, pair_parent, pair_sleft,
         pair_kstart, node_force, forced_ok_st, feat_used,
         was_forced, counts) = st
        sn = slot_nodes[:s]
        if sk_next is None:
            sk_next = _kernel_cap(min(2 * s, s_max)) if hist_subtraction \
                else min(2 * s, s_max)

        if hist_subtraction:
            # build only the slots assigned by the previous pass (smaller
            # siblings + both children of stale parents) ...
            sk = sk_self if sk_self is not None else _kernel_cap(s)
            kern, row_node, form = sweep(row_node, tbl_c, member_c, sk,
                                         m_cap=m_cap)
            if growth_counters:
                counts = _count_pass(counts, kern, form, stage)
            # ... and reconstruct the full scan tensor [s, F, B, 3] with
            # ONE 0/+-1 selection matmul against [kernel rows ;
            # parent-pair rows]: row s (pair i = s//2, left iff s even)
            # is  +kern[ks_i]                  (smaller side)
            #     +parent_hist[i] - kern[ks_i] (larger side, fresh pair)
            #     +kern[ks_i + 1]              (other side, stale pair).
            # Replaces per-part one-hot pulls + an interleaving stack +
            # a [s_max, F, B, 3] dynamic_update_slice (measured 22.3 ms
            # -> 3.8 ms per pass at the bench shape; the parent rows are
            # carried pair-indexed in parent_hist [P_all, F*B*3], half
            # the old scan_hist state).
            npairs = (s + 1) // 2
            ks = pair_kstart[:npairs]
            pp = pair_parent[:npairs]
            sl = pair_sleft[:npairs]
            stale = pp < 0
            kern2 = kern.reshape(sk, -1)
            sides = jnp.arange(s, dtype=jnp.int32)
            pi = sides // 2
            is_small = (sides % 2 == 0) == sl[pi]
            st_i = stale[pi]
            ks_i = ks[pi]
            iota_k = jnp.arange(sk, dtype=jnp.int32)[None, :]
            hit_small = (ks_i[:, None] == iota_k).astype(jnp.float32)
            # empty pairs carry ks = -1: no column matches either way
            ks2_i = jnp.where(st_i & (ks_i >= 0), ks_i + 1, -1)
            hit_stale2 = (ks2_i[:, None] == iota_k).astype(jnp.float32)
            mk = jnp.where(is_small[:, None], hit_small,
                           jnp.where(st_i[:, None], hit_stale2,
                                     -hit_small))
            iota_p = jnp.arange(P_all, dtype=jnp.int32)[None, :]
            mp = jnp.where((~is_small & ~st_i)[:, None],
                           (pi[:, None] == iota_p).astype(jnp.float32),
                           0.0)
            hist = jax.lax.dot_general(
                jnp.concatenate([mk, mp], axis=1),
                jnp.concatenate([kern2, parent_hist], axis=0),
                dimension_numbers=(((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32) \
                .reshape(s, fk, bk, 3)
        else:
            hist, row_node, form = sweep(row_node, tbl_c, member_c, s,
                                         m_cap=m_cap)
            if growth_counters:
                counts = _count_pass(counts, hist, form, stage)
        if efb is not None and efb.scan is None:
            # expansion fallback: subtraction/parent state live in
            # bundle space (above); the split scan runs on original
            # features — expand here (linear, so it commutes with the
            # psum and the sibling subtraction; efb.expand_histograms)
            from ..efb import expand_histograms
            hist_scan = expand_histograms(hist, efb)
        else:
            # unbundled, or bundled with the segmented scan (which
            # consumes the bundle-space histogram directly)
            hist_scan = hist

        slot_fmask = jnp.broadcast_to(feature_mask[None, :], (s, f))
        if use_bynode:
            ku = jax.random.fold_in(rng_key, pass_idx)
            u = jax.random.uniform(ku, (s, f))
            u = jnp.where(feature_mask[None, :] > 0, u, jnp.inf)
            kth = jnp.sort(u, axis=1)[:, k_bynode - 1][:, None]
            slot_fmask = slot_fmask * (u <= kth)
        if use_interaction:
            pm = path_mask[sn]
            subset = jnp.all((~pm[:, None, :]) | group_masks[None, :, :],
                             axis=2)
            allowed = jnp.einsum("sg,gf->sf", subset.astype(jnp.float32),
                                 group_masks.astype(jnp.float32)) > 0
            allowed = allowed | pm
            slot_fmask = slot_fmask * allowed
        rand_bins = None
        if hp.extra_trees and rng_key is not None:
            kr = jax.random.fold_in(jax.random.fold_in(rng_key, 7919),
                                    pass_idx)
            rand_bins = jax.random.randint(kr, (s, f), 0, bmax)
        if use_cegb:
            # per-(slot, feature) DeltaGain penalty (reference
            # CostEfficientGradientBoosting::DetlaGain; the portable
            # form at grower.py:375-393 minus the lazy term)
            gp = cegb_cfg.tradeoff * cegb_cfg.penalty_split * \
                tree.count[sn][:, None] * jnp.ones((s, f), jnp.float32)
            if cegb_cfg.has_coupled:
                gp += cegb_cfg.tradeoff * cegb_coupled[None, :] * \
                    (~feat_used)[None, :].astype(jnp.float32)
        else:
            gp = None

        if efb is not None and efb.scan is not None:
            # segmented bundle-space scan: [S, Fb, Bb] in, original-
            # feature BestSplits out (split_bundled.py)
            from .split_bundled import find_best_splits_bundled
            bs = find_best_splits_bundled(
                hist_scan, tree.sum_grad[sn], tree.sum_hess[sn],
                tree.count[sn], tree.leaf_value[sn], num_bins,
                missing_is_nan, is_cat_feat, slot_fmask, hp, efb,
                monotone=monotone, cons_min=cons_min[sn],
                cons_max=cons_max[sn], depth=tree.depth[sn],
                rand_bins=rand_bins, gain_penalty=gp)
        else:
            bs = find_best_splits(
                hist_scan, tree.sum_grad[sn], tree.sum_hess[sn],
                tree.count[sn],
                tree.leaf_value[sn], num_bins, missing_is_nan, is_cat_feat,
                slot_fmask, hp, monotone=monotone, cons_min=cons_min[sn],
                cons_max=cons_max[sn], depth=tree.depth[sn],
                rand_bins=rand_bins, gain_penalty=gp,
                cat_columns=hp.cat_columns)

        if use_forced:
            # override gain-chosen splits on forced nodes with the
            # spec's (feature, threshold) — stats gathered from the scan
            # tensor like FeatureHistogram::GatherInfoForThreshold
            # (feature_histogram.hpp:862+; portable form grower.py:456).
            # The sweep already psum'd the histograms, so sums are
            # global here under data-parallel.
            nf_slot = node_force[sn]                         # [S]
            has_f = (nf_slot >= 0) & (sn < m)
            sp = jnp.clip(nf_slot, 0, n_spec - 1)
            ff = jnp.clip(forced_feat[sp], 0, f - 1)         # [S]
            fb_t = forced_bin[sp]
            if efb is not None and efb.scan is not None:
                # bundle-space: expand ONE feature per slot (the same
                # gather + default-mass reconstruction as
                # efb.expand_histograms, restricted to ff[slot])
                bbw = hist_scan.shape[2]
                flath = hist_scan.reshape(s, -1, 3)
                csum_b = jnp.cumsum(hist_scan, axis=2).reshape(s, -1, 3)
                fp = efb.flat_pos[ff]                        # [S, bmax]
                gath = jnp.take_along_axis(flath, fp[..., None], axis=1)
                total_b = jnp.sum(hist_scan[:, 0], axis=1)   # [S, 3]
                colf = efb.col_of_feat[ff]
                hi_i = colf * bbw + efb.seg_hi[ff]
                lo_gate = (efb.seg_lo[ff] > 0)[:, None]
                lo_i = colf * bbw + jnp.maximum(efb.seg_lo[ff] - 1, 0)
                hi_s = jnp.take_along_axis(
                    csum_b, hi_i[:, None, None], axis=1)[:, 0]
                lo_s = jnp.take_along_axis(
                    csum_b, lo_i[:, None, None], axis=1)[:, 0] * lo_gate
                dmass = total_b - (hi_s - lo_s)              # [S, 3]
                hsel = jnp.where(efb.is_valid_pos[ff][..., None], gath,
                                 0.0)
                hsel = jnp.where(efb.is_default_pos[ff][..., None],
                                 dmass[:, None], hsel)       # [S, bmax, 3]
            else:
                hsel = jnp.take_along_axis(
                    hist_scan, ff[:, None, None, None], axis=1)[:, 0]
            lmask = (jnp.arange(hsel.shape[1])[None, :] <=
                     fb_t[:, None]).astype(hsel.dtype)
            lg_f = jnp.sum(hsel[..., 0] * lmask, axis=1)
            lh_f = jnp.sum(hsel[..., 1] * lmask, axis=1)
            lc_f = jnp.sum(hsel[..., 2] * lmask, axis=1)
            pg, ph = tree.sum_grad[sn], tree.sum_hess[sn]
            pc, pout = tree.count[sn], tree.leaf_value[sn]
            rg_f, rh_f, rc_f = pg - lg_f, ph - lh_f, pc - lc_f
            l1_, l2_ = hp.lambda_l1, hp.lambda_l2
            shift = leaf_gain(pg, ph, l1_, l2_, hp.max_delta_step,
                              hp.path_smooth, pc, pout)
            fgain = _split_gain(lg_f, lh_f, lc_f, rg_f, rh_f, rc_f, l1_,
                                l2_, hp, pout) - shift
            lout_f = leaf_output(lg_f, lh_f, l1_, l2_, hp.max_delta_step,
                                 hp.path_smooth, lc_f, pout)
            rout_f = leaf_output(rg_f, rh_f, l1_, l2_, hp.max_delta_step,
                                 hp.path_smooth, rc_f, pout)
            valid_f = has_f & (lc_f > 0) & (rc_f > 0) & \
                (forced_feat[sp] >= 0)
            bs = bs._replace(
                gain=jnp.where(valid_f, fgain, bs.gain),
                feature=jnp.where(valid_f, ff, bs.feature),
                threshold_bin=jnp.where(valid_f, fb_t, bs.threshold_bin),
                default_left=jnp.where(valid_f, False, bs.default_left),
                left_grad=jnp.where(valid_f, lg_f, bs.left_grad),
                left_hess=jnp.where(valid_f, lh_f, bs.left_hess),
                left_count=jnp.where(valid_f, lc_f, bs.left_count),
                left_output=jnp.where(valid_f, lout_f, bs.left_output),
                right_output=jnp.where(valid_f, rout_f, bs.right_output),
                cat_bitset=jnp.where(valid_f[:, None], jnp.uint32(0),
                                     bs.cat_bitset))
            forced_ok_st = forced_ok_st.at[sn].set(valid_f) \
                .at[m].set(False)

        best = BestSplits(*[
            getattr(best, fld).at[sn].set(getattr(bs, fld))
            if fld != "per_feature_gain" else best.per_feature_gain
            for fld in BestSplits._fields])

        # ---- choose splits: top-budget by gain; children fit next pass
        eligible = tree.is_leaf & jnp.isfinite(best.gain) & (best.gain > 0)
        if use_forced:
            # forced nodes split regardless of gain sign and outrank all
            # gain-chosen candidates (serial_tree_learner.cpp:459 BFS)
            eligible = tree.is_leaf & jnp.isfinite(best.gain) & \
                ((best.gain > 0) | forced_ok_st)
        if max_depth > 0:
            eligible &= tree.depth < max_depth
        gains = jnp.where(eligible[:m], best.gain[:m], -jnp.inf)
        if use_forced:
            gains = jnp.where(eligible[:m] & forced_ok_st[:m],
                              1e30 + best.gain[:m], gains)
        budget = L_g - tree.num_leaves
        if k_cap is None:
            k_cap = min(k_top, s)  # children fill the next pass (2*s)
        k_allowed = jnp.minimum(jnp.asarray(k_cap, jnp.int32), budget)
        if tail_split_cap > 0:
            # hybrid growth: once fewer leaves remain than candidates, the
            # commit ORDER matters (a committed split's children would have
            # outranked lower candidates under best-first growth) — throttle
            # to tail_split_cap splits per pass and re-rank
            # >= : even at n_elig == budget the commit order matters (a
            # committed split's children can outrank remaining candidates)
            n_elig = jnp.sum(gains[:m] > -jnp.inf)
            k_allowed = jnp.where(
                n_elig >= budget,
                jnp.minimum(k_allowed, tail_split_cap), k_allowed)
        top_vals, top_idx = jax.lax.top_k(gains, k_top)
        take = (jnp.arange(k_top) < k_allowed) & jnp.isfinite(top_vals)
        ssn = jnp.full(m1, -1, jnp.int32).at[sn].set(
            jnp.arange(s, dtype=jnp.int32)).at[m].set(-1)
        if hist_subtraction:
            # throttle so the selected splits' children fit the next
            # pass's kernel slots: fresh parents cost 1 (smaller child
            # only), stale parents 2 (both children built)
            cand_fresh = ssn[top_idx] >= 0
            cumcost = jnp.cumsum(jnp.where(cand_fresh, 1, 2))
            take &= cumcost <= sk_next
        split_mask = jnp.zeros(m1, bool).at[top_idx].set(take)
        split_mask = split_mask.at[m].set(False)
        k = jnp.sum(split_mask.astype(jnp.int32))

        # ---- apply splits
        order = jnp.cumsum(split_mask.astype(jnp.int32)) - 1
        child_l = jnp.where(split_mask, tree.num_nodes + 2 * order, m)
        child_r = jnp.where(split_mask, tree.num_nodes + 2 * order + 1, m)
        nodes = jnp.arange(m1, dtype=jnp.int32)
        rg = tree.sum_grad - best.left_grad
        rh = tree.sum_hess - best.left_hess
        rc = tree.count - best.left_count
        feat = best.feature
        new_tree = tree._replace(
            split_feature=jnp.where(split_mask, feat, tree.split_feature),
            threshold_bin=jnp.where(split_mask, best.threshold_bin,
                                    tree.threshold_bin),
            default_left=jnp.where(split_mask, best.default_left,
                                   tree.default_left),
            is_cat=jnp.where(split_mask,
                             is_cat_feat[jnp.clip(feat, 0, f - 1)],
                             tree.is_cat),
            cat_bitset=jnp.where(split_mask[:, None], best.cat_bitset,
                                 tree.cat_bitset),
            left=jnp.where(split_mask, child_l, tree.left),
            right=jnp.where(split_mask, child_r, tree.right),
            gain=jnp.where(split_mask, best.gain, tree.gain),
            is_leaf=tree.is_leaf & ~split_mask,
            num_nodes=tree.num_nodes + 2 * k,
            num_leaves=tree.num_leaves + k)

        def scat(arr, lv, rv):
            return arr.at[child_l].set(lv).at[child_r].set(rv)
        neg1 = jnp.full(m1, -1, jnp.int32)
        new_tree = new_tree._replace(
            parent=scat(new_tree.parent, nodes, nodes),
            leaf_value=scat(new_tree.leaf_value, best.left_output,
                            best.right_output),
            sum_grad=scat(new_tree.sum_grad, best.left_grad, rg),
            sum_hess=scat(new_tree.sum_hess, best.left_hess, rh),
            count=scat(new_tree.count, best.left_count, rc),
            depth=scat(new_tree.depth, tree.depth + 1, tree.depth + 1),
            is_leaf=scat(new_tree.is_leaf, split_mask, split_mask),
            split_feature=scat(new_tree.split_feature, neg1, neg1),
            left=scat(new_tree.left, neg1, neg1),
            right=scat(new_tree.right, neg1, neg1))
        new_best = best._replace(
            gain=scat(best.gain, jnp.full(m1, -jnp.inf, jnp.float32),
                      jnp.full(m1, -jnp.inf, jnp.float32)))

        if use_forced:
            # children of an applied forced split inherit the spec's
            # subtree; a node whose forced split was inapplicable stops
            # forcing (the reference halts its BFS there)
            spx = jnp.clip(node_force, 0, n_spec - 1)
            inherit = split_mask & (node_force >= 0) & forced_ok_st
            node_force = scat(node_force,
                              jnp.where(inherit, forced_left[spx], -1),
                              jnp.where(inherit, forced_right[spx], -1))
            was_forced = was_forced | (split_mask & forced_ok_st)
            zb_ = jnp.zeros(m1, bool)
            forced_ok_st = scat(forced_ok_st, zb_, zb_)
        if use_cegb and cegb_cfg.has_coupled:
            feat_used = feat_used.at[jnp.clip(feat, 0, f - 1)].max(
                split_mask)

        if hp.has_monotone:
            mcf = monotone[jnp.clip(feat, 0, f - 1)]
            mid = (best.left_output + best.right_output) * 0.5
            pmin, pmax = cons_min, cons_max
            lmin = jnp.where(mcf < 0, jnp.maximum(pmin, mid), pmin)
            lmax = jnp.where(mcf > 0, jnp.minimum(pmax, mid), pmax)
            rmin = jnp.where(mcf > 0, jnp.maximum(pmin, mid), pmin)
            rmax = jnp.where(mcf < 0, jnp.minimum(pmax, mid), pmax)
            cons_min = scat(cons_min, lmin, rmin)
            cons_max = scat(cons_max, lmax, rmax)
        if use_interaction:
            fsel = (jnp.arange(f)[None, :] ==
                    jnp.clip(feat, 0, f - 1)[:, None]) & split_mask[:, None]
            child_pm = path_mask | fsel
            path_mask = path_mask.at[child_l].set(child_pm) \
                .at[child_r].set(child_pm)

        # ---- scan slots for the children (find_best_splits ordering)
        slot_l = jnp.where(split_mask, 2 * order, -1)
        slot_r = jnp.where(split_mask, 2 * order + 1, -1)
        slot_nodes = jnp.full(s_max + 1, m, jnp.int32) \
            .at[jnp.where(split_mask, slot_l, s_max)].set(
                jnp.where(split_mask, child_l, m)) \
            .at[jnp.where(split_mask, slot_r, s_max)].set(
                jnp.where(split_mask, child_r, m))[:s_max]

        # ---- kernel slots + pair bookkeeping for the next pass
        if hist_subtraction:
            fresh_node = ssn >= 0
            small_left = best.left_count <= rc
            cost_node = jnp.where(split_mask,
                                  jnp.where(fresh_node, 1, 2), 0)
            kstart = jnp.cumsum(cost_node) - cost_node
            route_l = jnp.where(~fresh_node | small_left, kstart, -1)
            route_r = jnp.where(~fresh_node, kstart + 1,
                                jnp.where(small_left, -1, kstart))
            pidx = jnp.where(split_mask, order, P_all)
            pair_parent = jnp.full(P_all + 1, -1, jnp.int32) \
                .at[pidx].set(jnp.where(fresh_node, ssn, -1))[:P_all]
            pair_sleft = jnp.full(P_all + 1, True) \
                .at[pidx].set(fresh_node & small_left | ~fresh_node)[:P_all]
            pair_kstart = jnp.full(P_all + 1, -1, jnp.int32) \
                .at[pidx].set(kstart)[:P_all]
            # carry the fresh pairs' parent scan rows into the next pass
            # (pair-indexed; stale pairs keep zero rows, never read)
            sel_p = (pair_parent[:, None] ==
                     jnp.arange(s, dtype=jnp.int32)[None, :]) \
                .astype(jnp.float32)
            parent_hist = jax.lax.dot_general(
                sel_p, hist.reshape(s, -1),
                dimension_numbers=(((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
        else:
            route_l, route_r = slot_l, slot_r
        slot_of_node = jnp.full(m1, -1, jnp.int32) \
            .at[child_l].set(jnp.where(split_mask, route_l, -1)) \
            .at[child_r].set(jnp.where(split_mask, route_r, -1)) \
            .at[m].set(-1)

        # ---- pack the split tables; the NEXT pass's fused sweep routes
        # rows through them (the final flush after the loops applies the
        # last pass's tables — routing is idempotent, see
        # fused_route_hist_mxu)
        fclip = jnp.clip(feat, 0, f - 1)
        tbl_c, member_c = pack_route_tables(
            split_mask, fclip, best.threshold_bin,
            best.default_left, new_tree.is_cat, child_l, child_r,
            slot_of_node, new_tree.cat_bitset, m_pad, bmax,
            bcol=efb.col_of_feat[fclip] if efb is not None else None,
            efb=efb)

        done = (k == 0) | (new_tree.num_leaves >= L_g)
        return (new_tree, row_node, tbl_c, member_c, slot_nodes, new_best,
                cons_min, cons_max, path_mask, done, parent_hist,
                pair_parent, pair_sleft, pair_kstart, node_force,
                forced_ok_st, feat_used, was_forced, counts)

    # initial tables: nothing split, root (node 0) sits in kernel slot 0,
    # so the first sweep is an identity route + a root histogram. Pair 0
    # of the first pass is the root, built as a "stale" pair so its
    # histogram comes straight from kernel slot 0 (no parent exists)
    tbl0, member0 = pack_route_tables(
        jnp.zeros(m1, bool), jnp.zeros(m1, jnp.int32),
        jnp.zeros(m1, jnp.int32), jnp.zeros(m1, bool),
        jnp.zeros(m1, bool), jnp.full(m1, m, jnp.int32),
        jnp.full(m1, m, jnp.int32),
        jnp.full(m1, -1, jnp.int32).at[0].set(0),
        jnp.zeros((m1, w_cat), jnp.uint32), m_pad, bmax, efb=efb)
    state = (tree0,
             jnp.zeros(n, jnp.int32),                     # row_node
             tbl0, member0,                               # route tables
             jnp.full(s_max, m, jnp.int32).at[0].set(0),  # slot_nodes
             best0,
             jnp.full(m1, -jnp.inf, jnp.float32),
             jnp.full(m1, jnp.inf, jnp.float32),
             path_mask0,
             jnp.asarray(False),
             jnp.zeros((P_all if hist_subtraction else 1,
                        fk * bk * 3 if hist_subtraction else 1),
                       jnp.float32),                       # parent_hist
             jnp.full(P_all, -1, jnp.int32),               # pair_parent
             jnp.full(P_all, True),                        # pair_sleft
             jnp.full(P_all, -1, jnp.int32).at[0].set(0),  # pair_kstart
             node_force0, forced_ok0, feat_used0, was_forced0,
             jnp.zeros(len(GROWTH_COUNTERS), jnp.int32))   # counts

    def cond_pass(s, st, pass_idx, k_cap=None, sk_next=None, m_cap=None,
                  stage="pass"):
        # skip whole passes once growth is done — e.g. the full-capacity
        # bridge pass after a tree that completed on schedule (a free
        # S=s_max histogram otherwise). A skipped pass counts nothing
        return jax.lax.cond(
            st[_DONE], lambda st_: st_,
            lambda st_: one_pass(s, st_, pass_idx, k_cap, sk_next,
                                 m_cap, stage=stage), st)

    # ---- unrolled doubling schedule (growth_plan) ----
    schedule = plan.schedule
    for p, s_p in enumerate(schedule):
        state = cond_pass(s_p, state, jnp.asarray(p, jnp.int32),
                          m_cap=plan.m_cap_of(s_p))

    # ---- fixup loop for off-schedule leftovers ----
    # the best-first tail often splits only a couple of leaves per pass
    # (each new child is the only fresh candidate), so fixup passes run at
    # a small frontier capacity; the inactive-block skip in the histogram
    # kernel makes them cheap. One bridging pass at full capacity first:
    # it scans ALL children of the last scheduled pass (slots up to s_max)
    # while capping its own splits so the children fit the fixup frontier.
    # tail passes are per-pass-floor bound; with a hybrid-growth cap the
    # frontier only ever holds 2*cap fresh children, so shrink the fixup
    # scan capacity accordingly
    # NOTE on gates, two different animals (r3 vs r4):
    # - gating at the TARGET (stop fixups once num_leaves >= num_leaves,
    #   coverage 1.0x) was measured in r3 at +0.85 trees/s but
    #   -3.5e-3 AUC@95 — REJECTED; the replay regularly keeps
    #   fixup-grown splits, so overshoot quality needs most of the
    #   chase. The r3 answer was widening the fixup frontier instead.
    # - gating near the OVERSHOOT (growth_bridge_gate, below: skip the
    #   bridge once num_leaves >= gate*L_g, coverage ~gate*overshoot)
    #   costs only ~2.4e-4 AUC@115 for +6% — the r4 bench posture.
    # fixup capacities and the bridge gate are part of the static
    # growth_plan (see its docstring for the round-3/round-4 tuning
    # history: full-frontier s_fix, growth_bridge_gate)
    s_fix, sk_fix, k_fix = plan.s_fix, plan.sk_fix, plan.k_fix
    if plan.gate_leaves is not None:
        gated = state[_DONE] | (state[0].num_leaves >= plan.gate_leaves)
        state = state[:_DONE] + (gated,) + state[_DONE + 1:]
    if schedule:
        state = cond_pass(s_max, state, len(schedule), k_cap=k_fix,
                          sk_next=sk_fix, stage="bridge")

    first_fix = len(schedule) + 1

    def cond(c):
        st, it = c
        return (~st[_DONE]) & (it < L_g)

    def body(c):
        # one fixup pass at the tail frontier capacity; `it` is the
        # (traced) fixup iteration counter
        st, it = c
        return one_pass(s_fix, st, it + 1000, k_cap=k_fix,
                        sk_next=sk_fix, sk_self=sk_fix,
                        stage="fixup"), it + 1

    state, _ = jax.lax.while_loop(
        cond, body, (state, jnp.asarray(first_fix, jnp.int32)))

    # ---- epilogue: flush routing, prune to best-first, exact refit ----
    # flush the routing of the last pass's splits (sweeps route at the
    # START of a pass, so the final commits have not moved rows yet)
    row_node, _ = route_rows_mxu(None, state[1], state[2], state[3],
                                 feat_tbl, num_features=nf_packed,
                                 has_cat=hp.has_categorical,
                                 loc_table=None if efb_seg else loc_tbl,
                                 efb_range=efb_seg, operands=ops,
                                 interpret=interpret)
    tree_out = state[0]
    cmin, cmax = state[6], state[7]
    if over:
        # forced splits outrank every gain-chosen split in the replay
        # order (their recorded gains stay true)
        rank = (state[0].gain + jnp.where(state[17], 1e30, 0.0)) \
            if use_forced else None
        if quant and hp.has_monotone:
            tree_out, row_node, (cmin, cmax) = _prune_to_best_first(
                tree_out, row_node, num_leaves=num_leaves, m_grow=m,
                interpret=interpret, rank_gain=rank,
                aux=((cmin, -jnp.inf), (cmax, jnp.inf)))
        else:
            tree_out, row_node = _prune_to_best_first(
                tree_out, row_node, num_leaves=num_leaves, m_grow=m,
                interpret=interpret, rank_gain=rank)
    if quant:
        # exact leaf refit: per-leaf double-bf16 sums over the final
        # row->leaf vector, psum'd under data-parallel; quantization
        # then never reaches the fitted outputs (reference closed form,
        # feature_histogram.hpp:737 CalculateSplittedLeafOutput). One
        # caveat: with path_smooth > 0 the parent reference values are
        # the growth-time (quantized) ones — mirroring the reference,
        # which also smooths toward the parent's output as it stood at
        # split time, but those carry rounding noise here.
        nn = tree_out.leaf_value.shape[0]
        sums = _allred(node_sums_mxu(row_node, grad, hess, cnt_weight,
                                     num_nodes=nn, interpret=interpret))
        pout = tree_out.leaf_value[jnp.clip(tree_out.parent, 0, nn - 1)]
        ex_val = leaf_output(sums[:, 0], sums[:, 1], hp.lambda_l1,
                             hp.lambda_l2, hp.max_delta_step,
                             hp.path_smooth, sums[:, 2], pout)
        if hp.has_monotone:
            ex_val = jnp.clip(ex_val, cmin, cmax)
        lf = tree_out.is_leaf
        tree_out = tree_out._replace(
            leaf_value=jnp.where(lf, ex_val, tree_out.leaf_value),
            sum_grad=jnp.where(lf, sums[:, 0], tree_out.sum_grad),
            sum_hess=jnp.where(lf, sums[:, 1], tree_out.sum_hess),
            count=jnp.where(lf, sums[:, 2], tree_out.count))
    if growth_counters:
        # (no caller asks for them beside the CEGB state: the fused
        # scan, which does, is closed to CEGB)
        return tree_out, row_node, state[-1].at[
            GROWTH_COUNTERS.index("leaves_grown")].set(
                state[0].num_leaves)
    if use_cegb:
        # feature-used flags persist across trees (portable contract,
        # grower.py:674); no lazy state here, flags pass through
        return tree_out, row_node, (state[16], row_feat_used0)
    return tree_out, row_node
