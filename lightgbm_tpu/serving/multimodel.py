"""Multi-model forest packing: many models, one fused device dispatch.

A registry full of per-tenant models serializes on the predict path
when every `DeviceForest` dispatches alone — N small models cost N
kernel launches per coalescing window even though each launch moves a
few thousand rows. The Booster accelerator (arXiv:2011.02022) shows
forest traversal is throughput-bound on node-fetch parallelism, and
the GPU tree-boosting line (arXiv:1706.08359) takes its inference wins
from batching many trees into one dense kernel; `ForestPack` applies
both on TPU by padding heterogeneous member forests into ONE
slot-grouped device layout and answering a mixed batch of
(model, rows) pairs in one `predict_packed_forest` launch.

Layout (the PR-6 one-slot-per-block idiom, rotated to serving):

- every member's tree arrays are padded to common pow-2 node/bitset/
  feature extents and concatenated on the tree axis, member trees
  CONTIGUOUS in slot order — so the f32 accumulation order per member
  is identical to its solo `predict_binned_forest` fori-loop, which is
  what makes the packed path bit-identical to the per-model device
  path (and, through the dyadic-booster trick, to host predict);
- `tree_model[t]` maps each packed tree to its member slot; each slot
  owns one `row_block`-row block of the batch at offset
  ``slot * row_block``, so per-row traversal cost is independent of
  how many members are resident;
- slots, trees, nodes and features are padded to powers of two and the
  member count rides a pow-2 slot axis, so a pack REBUILD (member
  evicted / hot-swapped) usually reuses the exact compiled program —
  and the per-dispatch `row_block` goes through the engine's pow-2
  bucket ladder, keeping compiles bounded at
  ``ceil(log2(max_bucket)) + 1`` per *pack*, not per model.

Pad trees are skipped with `lax.cond` (no add at all, not an add of
+0.0) so tree-axis padding cannot perturb signed zeros; pad rows are
masked inert by `row_valid` exactly as in the single-model engine.

`dispatch_pack` is the fused dispatch boundary: a registered fault
site (``serving_pack_predict``) inside the replica retry bracket, so
the chaos harness can kill the fused path and watch the breaker /
failover / host-fallback ladder hold for every member at once.
`PackBatcher` extends the continuous-batching `MicroBatcher` with a
slot-grouped dispatch so one queue (one SLO admission model, one
scheduler) serves the whole pack.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..utils.log import LightGBMError
from ..utils.timer import global_timer
from .batcher import MicroBatcher, _Request
from .engine import next_bucket
from .forest import DeviceForest

__all__ = ["ForestPack", "PackEntry", "PackBatcher", "build_forest_pack",
           "predict_packed_forest", "dispatch_pack"]


def _pow2(n: int) -> int:
    """Smallest power of two >= max(n, 1)."""
    p = 1
    while p < n:
        p <<= 1
    return p


@dataclasses.dataclass
class ForestPack:
    """Several DeviceForests padded into one slot-grouped device layout.

    Presents the same surface the replica fleet needs from a
    DeviceForest (`supported`, `place_on`, `nbytes_device`), so
    `ReplicaSet.build` replicates a pack exactly like a single model.
    """
    name: str
    stacked: object                # TreeArrays, fields [Tp, m1p, ...]
    tree_model: object             # jnp [Tp] i32: packed tree -> slot
    tree_class: object             # jnp [Tp] i32: output column
    num_bins: object               # jnp [Mp, Fp] i32, per-slot tables
    missing_is_nan: object         # jnp [Mp, Fp] bool
    member_names: Tuple[str, ...]  # slot order
    forests: Dict[str, DeviceForest]
    num_slots: int                 # Mp (pow-2 padded member count)
    num_outputs: int               # Kp (pow-2 padded max member outputs)
    num_features: int              # Fp (pow-2 padded max member features)
    num_trees: int                 # real (unpadded) packed tree count

    #: packs only ever contain device-servable members (build_forest_pack
    #: rejects unsupported forests), so the fleet always places them
    supported: bool = True

    def slot_of(self, name: str) -> int:
        return self.member_names.index(name)

    def place_on(self, device) -> "ForestPack":
        """The same logical pack with its device arrays pinned to
        `device` (replica placement; arrays are immutable so replicas
        share nothing mutable)."""
        import jax
        return dataclasses.replace(
            self,
            stacked=jax.device_put(self.stacked, device),
            tree_model=jax.device_put(self.tree_model, device),
            tree_class=jax.device_put(self.tree_class, device),
            num_bins=jax.device_put(self.num_bins, device),
            missing_is_nan=jax.device_put(self.missing_is_nan, device))

    def nbytes_device(self) -> int:
        import jax
        return sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in jax.tree_util.tree_leaves(self.stacked))


def build_forest_pack(members: Sequence[Tuple[str, DeviceForest]],
                      name: str = "pack") -> ForestPack:
    """Pad + concatenate member forests into one ForestPack.

    Tree order inside the pack is member-major in slot order, each
    member's own tree order preserved — the accumulation-order
    invariant behind the bit-identity contract. Raises on empty or
    host-fallback (unsupported) members: those keep serving solo.
    """
    import jax.numpy as jnp
    from ..learner.grower import TreeArrays

    if not members:
        raise LightGBMError("build_forest_pack needs at least one member")
    names = [nm for nm, _ in members]
    if len(set(names)) != len(names):
        raise LightGBMError(f"pack '{name}' has duplicate member names")
    for nm, forest in members:
        if not forest.supported:
            raise LightGBMError(
                f"pack member '{nm}' is not device-servable "
                f"({forest.unsupported_reason}); load it unpacked")

    m = len(members)
    mp = _pow2(m)
    hosts = []            # per member: dict of host numpy tree fields
    for nm, forest in members:
        hosts.append({f: np.asarray(getattr(forest.stacked, f))
                      for f in TreeArrays._fields})
    t_real = sum(h["leaf_value"].shape[0] for h in hosts)
    tp = _pow2(t_real)
    m1p = _pow2(max(h["leaf_value"].shape[1] for h in hosts))
    wp = _pow2(max(h["cat_bitset"].shape[2] for h in hosts))
    fp = _pow2(max(forest.num_features for _, forest in members))
    kp = _pow2(max(forest.num_outputs for _, forest in members))

    def field(fname: str, fill, dtype) -> np.ndarray:
        sample = hosts[0][fname]
        shape = (tp, m1p, wp) if sample.ndim == 3 else \
            ((tp, m1p) if sample.ndim == 2 else (tp,))
        out = np.full(shape, fill, dtype)
        t0 = 0
        for h in hosts:
            a = h[fname]
            t1 = t0 + a.shape[0]
            if a.ndim == 3:
                out[t0:t1, :a.shape[1], :a.shape[2]] = a
            elif a.ndim == 2:
                out[t0:t1, :a.shape[1]] = a
            else:
                out[t0:t1] = a
            t0 = t1
        return out

    # pad trees are single-leaf (split_feature -1 everywhere) AND
    # cond-skipped in the kernel; pad nodes of real trees are
    # unreachable (no child edge points at them)
    stacked = TreeArrays(
        split_feature=field("split_feature", -1, np.int32),
        threshold_bin=field("threshold_bin", 0, np.int32),
        default_left=field("default_left", False, bool),
        is_cat=field("is_cat", False, bool),
        cat_bitset=field("cat_bitset", 0, np.uint32),
        left=field("left", -1, np.int32),
        right=field("right", -1, np.int32),
        parent=field("parent", -1, np.int32),
        leaf_value=field("leaf_value", 0.0, np.float32),
        sum_grad=field("sum_grad", 0.0, np.float32),
        sum_hess=field("sum_hess", 0.0, np.float32),
        count=field("count", 0.0, np.float32),
        gain=field("gain", 0.0, np.float32),
        depth=field("depth", 0, np.int32),
        is_leaf=field("is_leaf", True, bool),
        num_nodes=field("num_nodes", 0, np.int32),
        num_leaves=field("num_leaves", 0, np.int32))
    stacked = TreeArrays(*[jnp.asarray(a) for a in stacked])

    tree_model = np.zeros(tp, np.int32)
    tree_class = np.zeros(tp, np.int32)
    t0 = 0
    for slot, (nm, forest) in enumerate(members):
        t1 = t0 + forest.num_trees
        tree_model[t0:t1] = slot
        tree_class[t0:t1] = np.asarray(forest.tree_class)
        t0 = t1

    # per-slot binning tables; pad slots/features get num_bin 1 (bin 0
    # is their only value, never a NaN bin) and are unreferenced anyway
    num_bins = np.ones((mp, fp), np.int32)
    missing = np.zeros((mp, fp), bool)
    for slot, (nm, forest) in enumerate(members):
        f = forest.num_features
        num_bins[slot, :f] = np.asarray(forest.num_bins)
        missing[slot, :f] = np.asarray(forest.missing_is_nan)

    return ForestPack(
        name=name, stacked=stacked,
        tree_model=jnp.asarray(tree_model),
        tree_class=jnp.asarray(tree_class),
        num_bins=jnp.asarray(num_bins),
        missing_is_nan=jnp.asarray(missing),
        member_names=tuple(names),
        forests={nm: forest for nm, forest in members},
        num_slots=mp, num_outputs=kp, num_features=fp,
        num_trees=t_real)


def _predict_packed_impl(stacked, tree_model, tree_class, t_real,
                         bins, num_bins, missing_is_nan,
                         num_outputs: int, row_block: int, row_valid):
    import jax
    import jax.numpy as jnp

    from ..learner.predict import predict_binned_tree

    tp = stacked.leaf_value.shape[0]
    total = bins.shape[0]
    fp = bins.shape[1]
    valid = row_valid if row_valid is not None else \
        jnp.ones(total, bool)

    def body(i, acc):
        def add(acc):
            tree = jax.tree_util.tree_map(lambda a: a[i], stacked)
            s = tree_model[i]
            off = s * row_block
            rb = jax.lax.dynamic_slice(bins, (off, 0), (row_block, fp))
            rv = jax.lax.dynamic_slice(valid, (off,), (row_block,))
            vals = predict_binned_tree(
                tree, rb, num_bins[s], missing_is_nan[s], row_valid=rv)
            blk = jax.lax.dynamic_slice(
                acc, (off, 0), (row_block, num_outputs))
            blk = blk.at[:, tree_class[i]].add(vals)
            return jax.lax.dynamic_update_slice(acc, blk, (off, 0))

        return jax.lax.cond(i < t_real, add, lambda a: a, acc)

    acc = jnp.zeros((total, num_outputs), jnp.float32)
    return jax.lax.fori_loop(0, tp, body, acc)


_packed_jit = None


def _packed_fn():
    """The jitted fused predictor, built on first use (serving modules
    never import JAX at module load). Tests read `_cache_size()` off
    the returned function for the shape-leak guard."""
    global _packed_jit
    if _packed_jit is None:
        import jax
        _packed_jit = jax.jit(
            _predict_packed_impl,
            static_argnames=("num_outputs", "row_block"))
    return _packed_jit


def predict_packed_forest(stacked, tree_model, tree_class, t_real,
                          bins, num_bins, missing_is_nan,
                          num_outputs: int = 1, row_block: int = 16,
                          row_valid=None):
    """Fused multi-model forest sum: one launch, every resident model.

    bins: [Mp * row_block, Fp] — slot s owns rows
    ``[s*row_block, (s+1)*row_block)``. Each packed tree dynamic-slices
    its slot's row block, traverses it against the SLOT's binning
    tables (exact missing/categorical semantics per member), and
    accumulates into the slot's block of the output — per-member
    accumulation order is the member's own tree order, so every real
    row is bit-identical to the member's solo device predict. Pad
    trees (``i >= t_real``) are `lax.cond`-skipped: no add at all, so
    padding cannot flip signed zeros. `t_real` is a device scalar (not
    a static arg) so rebuilt packs with the same padded shapes reuse
    the compiled program. Returns [Mp * row_block, num_outputs] raw
    f32 scores.
    """
    return _packed_fn()(stacked, tree_model, tree_class, t_real, bins,
                        num_bins, missing_is_nan,
                        num_outputs=num_outputs, row_block=row_block,
                        row_valid=row_valid)


def dispatch_pack(engine, pack: ForestPack,
                  requests: Sequence[Tuple[int, np.ndarray]],
                  metrics_by_slot: Optional[Dict[int, object]] = None,
                  pack_metrics=None) -> np.ndarray:
    """One fused device dispatch answering a mixed (slot, bins) batch.

    Rows are grouped per slot, chunked through the engine's pow-2
    bucket ladder (`row_block` = next_bucket of the largest slot's
    rows this round; a slot with more rows than `max_bucket` takes
    extra rounds), assembled into the slot-grouped layout and scored
    by ONE `predict_packed_forest` launch per round. Returns the raw
    [sum(rows), num_outputs] scores in request order. Compile
    accounting rides the engine's bucket cache keyed on the pack, so
    the ladder bound applies per pack, not per member.
    """
    import jax.numpy as jnp

    from ..reliability import faults

    # registered fault site: the fused multi-model dispatch boundary
    # (replica retry/failover bracket lives in replicas.dispatch)
    faults.inject("serving_pack_predict")

    if not requests:
        return np.zeros((0, pack.num_outputs), np.float32)
    with global_timer.timeit("serve_pack_predict", fine=True):
        by_slot: Dict[int, List[np.ndarray]] = {}
        spans: List[Tuple[int, int, int]] = []   # (slot, start, rows)
        for slot, bins in requests:
            chunks = by_slot.setdefault(slot, [])
            start = sum(c.shape[0] for c in chunks)
            chunks.append(np.asarray(bins, np.int32))
            spans.append((slot, start, bins.shape[0]))
        slot_bins = {s: (c[0] if len(c) == 1 else np.concatenate(c))
                     for s, c in by_slot.items()}
        done: Dict[int, List[np.ndarray]] = {s: [] for s in slot_bins}
        offs = {s: 0 for s in slot_bins}
        while True:
            this_round = {
                s: min(len(b) - offs[s], engine.max_bucket)
                for s, b in slot_bins.items() if offs[s] < len(b)}
            if not this_round:
                break
            block = next_bucket(max(this_round.values()),
                                engine.min_bucket, engine.max_bucket)
            packed = np.zeros((pack.num_slots * block,
                               pack.num_features), np.int32)
            valid = np.zeros(pack.num_slots * block, bool)
            for s, r in this_round.items():
                chunk = slot_bins[s][offs[s]:offs[s] + r]
                packed[s * block:s * block + r, :chunk.shape[1]] = chunk
                valid[s * block:s * block + r] = True
            hit = engine._record(pack, block)
            if metrics_by_slot:
                for s in this_round:
                    m = metrics_by_slot.get(s)
                    if m is not None:
                        m.record_batch(bucket_hit=hit, compiled=not hit)
            # a pack bucket-cache miss makes JAX build the fused
            # predictor for this block shape (the compile ledger books
            # it under this span's name)
            with global_timer.timeit("serve_pack_dispatch", fine=True,
                                     block=block, slots=len(this_round),
                                     rows=sum(this_round.values())):
                raw = predict_packed_forest(
                    pack.stacked, pack.tree_model, pack.tree_class,
                    jnp.int32(pack.num_trees), jnp.asarray(packed),
                    pack.num_bins, pack.missing_is_nan,
                    num_outputs=pack.num_outputs, row_block=block,
                    row_valid=jnp.asarray(valid))
                raw = np.asarray(raw)        # device -> host sync
            if pack_metrics is not None:
                pack_metrics.record_dispatch(
                    rows=sum(this_round.values()),
                    capacity=pack.num_slots * block,
                    slots=len(this_round), compiled=not hit)
            for s, r in this_round.items():
                done[s].append(raw[s * block:s * block + r])
                offs[s] += r
        slot_raw = {s: (c[0] if len(c) == 1 else np.concatenate(c))
                    for s, c in done.items()}
        return np.concatenate(
            [slot_raw[s][start:start + rows]
             for s, start, rows in spans], axis=0)


class PackBatcher(MicroBatcher):
    """One continuous-batching queue for a whole ForestPack.

    Requests carry their member's slot; each coalesced batch becomes
    ONE fused dispatch (`run_pack([(slot, bins), ...]) -> raw rows in
    request order`) instead of one launch per member. Inherits the
    scheduler, SLO admission (rows-aware service model — essential
    here, where members of very different sizes share the queue) and
    drain semantics unchanged.
    """

    def __init__(self, run_pack, **kwargs):
        self._run_pack = run_pack
        super().__init__(run_batch=None, **kwargs)

    def _dispatch(self, batch: List[_Request]) -> None:
        raw = self._run_pack([(r.slot, r.bins) for r in batch])
        lo = 0
        for req in batch:
            hi = lo + len(req.bins)
            req.future.set_result(raw[lo:hi])
            lo = hi


@dataclasses.dataclass
class PackEntry:
    """Shared serving machinery for one resident ForestPack: the fused
    device layout, its replica fleet, the slot-aware batcher and the
    pack-level metrics. Member `ModelEntry`s point here; a rebuild
    (member evict / hot-swap) publishes a NEW PackEntry and drains the
    old batcher through the host path — same semantics as a
    single-model hot swap."""
    name: str
    pack: ForestPack
    replicas: object               # ReplicaSet over the pack
    batcher: Optional[PackBatcher]
    metrics: object                # metrics.PackMetrics
    version: int = 1
    #: slot -> the member ModelEntry's ModelMetrics, filled by the
    #: registry as it publishes member entries (the fused dispatch
    #: records per-member batch/compile counts through it)
    slot_metrics: Dict[int, object] = dataclasses.field(
        default_factory=dict)

    def member_names(self) -> Tuple[str, ...]:
        return self.pack.member_names
