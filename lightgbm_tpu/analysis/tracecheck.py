"""Trace-level contract checking for the device hot path.

The AST rules verify what the source *says*; this module verifies what
the compiler is actually *given*. Every entry in ``TRACE_MANIFEST`` is
a hot entry point traced under abstract inputs (``jax.make_jaxpr`` /
the jit AOT ``.trace`` API) on CPU — no device is touched, nothing
executes — and the resulting jaxpr is asserted against a per-entry
contract (rules_trace.py turns violations into TRACE00x findings):

- **sort-free** (TRACE001): no ``sort`` primitive anywhere in the
  program, including scan/cond/pjit sub-jaxprs. This is the semantic
  version of PERF001's lexical argsort ban — a sort smuggled in through
  any spelling (``jnp.sort``, ``lax.top_k`` lowered via sort, a helper
  module) is caught here.
- **no f64** (TRACE002): entries with ``x64_mode=True`` are traced
  under ``jax.enable_x64`` and must produce no
  strongly-typed float64 avals (weak-typed Python-float constants are
  fine). With x64 off JAX canonicalizes every aval to 32-bit, so the
  check would be vacuous — entries whose programs cannot trace under
  x64 (i32/i64 branch mismatches in lax.cond carry paths) declare
  ``x64_mode=False`` and keep the default-mode tripwire only.
- **no host callbacks** (TRACE003): no ``pure_callback`` /
  ``io_callback`` / ``debug_callback`` / ``debug_print`` primitives —
  each one serializes the dispatch pipeline on a device->host round
  trip.
- **donation consumed** (TRACE004): for entries that declare buffer
  donation, the CPU lowering must carry ``tf.aliasing_output`` — JAX
  silently keeps both buffers when a declared donation is unusable,
  doubling peak memory on exactly the largest arrays.
- **retrace stability** (TRACE005): tracing the jitted entry twice
  with different values for its dispatch-stable scalars (iteration
  counter, live-tree count) must yield byte-identical jaxprs. A
  difference means the scalar is baked into the program — one silent
  recompile per distinct value at serve time.

Coverage (TRACE006): every device entry in FAULT001's
``DISPATCH_MANIFEST`` must be covered by a trace entry or explicitly
waived in ``WAIVERS`` with a reason (host-side IO, multihost-only
collective, delegation to a covered entry).

Everything here imports jax lazily and forces
``jax.default_device(cpu)`` around input construction, so the linter
never takes the accelerator away from the process that holds it.
tests/test_partition_scan.py imports the jaxpr helpers from here so
lint and tests assert one predicate.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Set, Tuple

__all__ = [
    "CALLBACK_PRIMITIVES", "TraceEntry", "TraceReport", "TRACE_MANIFEST",
    "WAIVERS", "iter_primitives", "primitive_names",
    "has_sort_primitive", "callback_primitives",
    "strong_f64_primitives", "donation_consumed", "retrace_stable",
    "build_report",
]

#: jaxpr primitive names that are host callbacks (jax.debug.print
#: traces to its own ``debug_print`` primitive, not ``debug_callback``)
CALLBACK_PRIMITIVES = ("debug_callback", "debug_print", "io_callback",
                       "pure_callback")

_DONATION_MARKER = "tf.aliasing_output"


# ---------------------------------------------------------------------------
# jaxpr walkers (shared with tests — one predicate for lint and pytest)

def _as_jaxpr(obj):
    """Accept a ClosedJaxpr, a Jaxpr, or anything carrying `.jaxpr`."""
    while not hasattr(obj, "eqns") and hasattr(obj, "jaxpr"):
        obj = obj.jaxpr
    return obj


def iter_primitives(jaxpr):
    """Yield every eqn in `jaxpr` and its sub-jaxprs (scan/cond/pjit
    bodies), depth-first."""
    jaxpr = _as_jaxpr(jaxpr)
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (list, tuple)) else (val,)):
                if hasattr(sub, "jaxpr") or hasattr(sub, "eqns"):
                    yield from iter_primitives(sub)


def primitive_names(jaxpr) -> Set[str]:
    return {eqn.primitive.name for eqn in iter_primitives(jaxpr)}


def has_sort_primitive(jaxpr) -> bool:
    """True if any (sub-)jaxpr equation is the `sort` primitive — the
    shared sort-free predicate (TRACE001 and the partition-scan tests)."""
    return any(eqn.primitive.name == "sort"
               for eqn in iter_primitives(jaxpr))


def callback_primitives(jaxpr) -> List[str]:
    """Host-callback primitive names present in the program."""
    return sorted(p for p in primitive_names(jaxpr)
                  if p in CALLBACK_PRIMITIVES)


def strong_f64_primitives(jaxpr) -> List[str]:
    """Primitives emitting a strongly-typed float64 output. Weak-typed
    f64 (bare Python floats before canonicalization) does not count —
    it never survives a binary op against an f32 operand."""
    import numpy as np
    hits: Set[str] = set()
    for eqn in iter_primitives(jaxpr):
        for var in eqn.outvars:
            aval = getattr(var, "aval", None)
            if getattr(aval, "dtype", None) == np.float64 and \
                    not getattr(aval, "weak_type", False):
                hits.add(eqn.primitive.name)
    return sorted(hits)


def donation_consumed(lowered_text: str) -> bool:
    """True when the StableHLO text records an input/output aliasing —
    the only reliable signal that a declared donation was usable."""
    return _DONATION_MARKER in lowered_text


def retrace_stable(jitted, argsets: Sequence,
                   **static_kwargs) -> bool:
    """Trace `jitted` once per argset (same shapes/dtypes, different
    scalar values) and compare jaxpr pretty-prints. Identical text
    means the varied values are not baked into the program — the jit
    cache serves every value with one compile.

    Each argset is either a tuple of positional arguments or a dict of
    keyword arguments (for entry points whose traced inputs are
    keyword-only); dict argsets are merged over `static_kwargs`."""
    texts = []
    for args in argsets:
        if isinstance(args, dict):
            traced = jitted.trace(**{**static_kwargs, **args})
        else:
            traced = jitted.trace(*args, **static_kwargs)
        texts.append(str(traced.jaxpr))
    return all(t == texts[0] for t in texts)


# ---------------------------------------------------------------------------
# manifest machinery

@dataclasses.dataclass
class TraceEntry:
    """One hot entry point plus its contract.

    `build` returns the raw trace materials as a dict with any of:
    ``jaxpr`` (default-mode trace), ``jaxpr_x64`` / ``x64_error``
    (enable_x64 trace, when ``x64_mode``), ``lowered_text`` (when
    ``donate``), ``stable`` (bool, when ``stable_over``). `deps` are
    package-relative source files whose content hashes key the trace
    cache. `line` anchors findings for fixture manifests."""
    name: str
    target_file: str                      # package-relative, findings anchor
    target_fn: str
    build: Callable[[], Dict]
    covers: Tuple[Tuple[str, str, str], ...] = ()
    sort_free: bool = True
    forbid_callbacks: bool = True
    x64_mode: bool = False
    donate: bool = False
    stable_over: Optional[str] = None     # human label of varied scalars
    deps: Tuple[str, ...] = ()
    line: int = 0


@dataclasses.dataclass
class TraceReport:
    """Cacheable result of tracing one entry against its contract."""
    name: str
    prims: List[str] = dataclasses.field(default_factory=list)
    has_sort: bool = False
    callbacks: List[str] = dataclasses.field(default_factory=list)
    f64: List[str] = dataclasses.field(default_factory=list)
    x64_error: Optional[str] = None
    donation_consumed: Optional[bool] = None
    stable: Optional[bool] = None
    error: Optional[str] = None

    def to_dict(self) -> Dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Dict) -> "TraceReport":
        return cls(**d)


def build_report(entry: TraceEntry) -> TraceReport:
    """Trace one entry (CPU, abstract inputs, nothing executes) and
    derive the contract-relevant facts."""
    rep = TraceReport(name=entry.name)
    try:
        import jax
    except Exception as exc:            # pragma: no cover - jax is baked in
        rep.error = f"jax unavailable: {exc}"
        return rep
    try:
        with jax.default_device(jax.devices("cpu")[0]):
            mat = entry.build()
    except Exception as exc:
        rep.error = f"{type(exc).__name__}: {exc}"
        return rep
    jaxpr = mat.get("jaxpr")
    if jaxpr is not None:
        prims = primitive_names(jaxpr)
        rep.prims = sorted(prims)
        rep.has_sort = "sort" in prims
        rep.callbacks = sorted(p for p in prims
                               if p in CALLBACK_PRIMITIVES)
    if entry.x64_mode:
        x64 = mat.get("jaxpr_x64")
        if x64 is not None:
            rep.f64 = strong_f64_primitives(x64)
        else:
            rep.x64_error = mat.get(
                "x64_error", "builder returned no jaxpr_x64")
    elif jaxpr is not None:
        # x64-off canonicalizes avals to 32-bit: vacuous by design, but
        # an honest tripwire if the session runs with x64 globally on
        rep.f64 = strong_f64_primitives(jaxpr)
    if "lowered_text" in mat:
        rep.donation_consumed = donation_consumed(mat["lowered_text"])
    if "stable" in mat:
        rep.stable = bool(mat["stable"])
    return rep


# ---------------------------------------------------------------------------
# builders for the real manifest (tiny concrete inputs, CPU only)

def _tiny_dataset():
    import numpy as np
    from ..data import BinnedDataset, Metadata
    rng = np.random.RandomState(0)
    n = 64
    x = rng.randn(n, 3).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.float32)
    return BinnedDataset.from_raw(x, Metadata(n, label=y), max_bin=15), y


def _tiny_forest(num_models: int = 2, num_nodes: int = 4):
    import jax.numpy as jnp
    from ..learner.grower import TreeArrays

    def mk(value, dtype):
        return jnp.full((num_models, num_nodes), value, dtype)

    return TreeArrays(
        split_feature=mk(0, jnp.int32), threshold_bin=mk(1, jnp.int32),
        default_left=mk(False, bool), is_cat=mk(False, bool),
        cat_bitset=jnp.zeros((num_models, num_nodes, 1), jnp.uint32),
        left=mk(-1, jnp.int32), right=mk(-1, jnp.int32),
        parent=mk(-1, jnp.int32), leaf_value=mk(0.0, jnp.float32),
        sum_grad=mk(0.0, jnp.float32), sum_hess=mk(0.0, jnp.float32),
        count=mk(0.0, jnp.float32), gain=mk(0.0, jnp.float32),
        depth=mk(0, jnp.int32), is_leaf=mk(True, bool),
        num_nodes=jnp.full((num_models,), 1, jnp.int32),
        num_leaves=jnp.full((num_models,), 1, jnp.int32))


def _grower_kwargs(ds):
    from ..learner.split import SplitHyperParams
    return dict(num_leaves=4, max_depth=0,
                hp=SplitHyperParams(min_data_in_leaf=5),
                bmax=int(ds.num_bins.max()), hist_backend="mxu",
                interpret=True)


def _probe_partition_rows() -> Dict:
    import functools
    import jax
    import jax.numpy as jnp
    from ..learner.histogram_pallas import partition_rows
    fn = functools.partial(partition_rows, num_slots=8, row_block=64,
                           impl="rank")
    jaxpr = jax.make_jaxpr(fn)(jax.ShapeDtypeStruct((512,), jnp.int32))
    return {"jaxpr": jaxpr}


def _probe_grow_tree_mxu() -> Dict:
    import jax
    import jax.numpy as jnp
    from ..learner.grower_mxu import grow_tree_mxu
    ds, _y = _tiny_dataset()
    kw = _grower_kwargs(ds)
    bins = jnp.asarray(ds.bins)
    n = bins.shape[0]
    shaped = jax.ShapeDtypeStruct((n,), jnp.float32)

    def grow(grad, hess):
        return grow_tree_mxu(
            bins, grad, hess, jnp.ones(n, jnp.float32),
            jnp.ones(ds.num_features, jnp.float32),
            jnp.asarray(ds.num_bins), jnp.asarray(ds.missing_types == 2),
            jnp.asarray(ds.is_categorical), **kw)

    return {"jaxpr": jax.make_jaxpr(grow)(shaped, shaped)}


def _probe_route_rows_mxu() -> Dict:
    import jax
    import jax.numpy as jnp
    from ..learner.histogram_mxu import pack_route_tables, route_rows_mxu
    m_pad, bmax, feats = 8, 16, 3
    zeros_i = jnp.zeros(m_pad, jnp.int32)
    zeros_b = jnp.zeros(m_pad, bool)
    tbl, member = pack_route_tables(
        zeros_b, zeros_i, zeros_i, zeros_b, zeros_b, zeros_i, zeros_i,
        zeros_i, jnp.zeros((m_pad, 1), jnp.uint32), m_pad, bmax)
    feat_tbl = jnp.stack([jnp.full(feats, float(bmax)),
                          jnp.zeros(feats)], axis=1)

    def route(bins, row_node):
        return route_rows_mxu(bins, row_node, tbl, member, feat_tbl,
                              row_block=256, emit_counts=True,
                              num_slots=8, interpret=True)

    s_bins = jax.ShapeDtypeStruct((256, feats), jnp.int8)
    s_rows = jax.ShapeDtypeStruct((256,), jnp.int32)
    out = {"jaxpr": jax.make_jaxpr(route)(s_bins, s_rows)}
    try:
        with jax.enable_x64(True):
            out["jaxpr_x64"] = jax.make_jaxpr(route)(s_bins, s_rows)
    except Exception as exc:
        out["x64_error"] = f"{type(exc).__name__}: {exc}"
    return out


def _probe_predict_packed() -> Dict:
    import jax
    import jax.numpy as jnp
    from ..serving.multimodel import _packed_fn
    stacked = _tiny_forest()
    fn = _packed_fn()
    zeros2 = jnp.zeros(2, jnp.int32)
    bins = jnp.zeros((32, 4), jnp.int32)
    num_bins = jnp.ones((2, 4), jnp.int32)
    missing = jnp.zeros((2, 4), bool)
    args = (stacked, zeros2, zeros2, 2, bins, num_bins, missing)
    traced = fn.trace(*args, num_outputs=1, row_block=16,
                      row_valid=None)
    # t_real (live-tree count) is deliberately a traced device scalar so
    # rebuilt packs reuse the compiled program — vary it and demand a
    # byte-identical jaxpr (the base trace above doubles as argset 0)
    args_b = (stacked, zeros2, zeros2, 1, bins, num_bins, missing)
    other = fn.trace(*args_b, num_outputs=1, row_block=16,
                     row_valid=None)
    stable = str(traced.jaxpr) == str(other.jaxpr)
    return {"jaxpr": traced.jaxpr, "stable": stable}


def _probe_predict_binned_forest() -> Dict:
    import jax
    import jax.numpy as jnp
    from ..learner.predict import predict_binned_forest
    stacked = _tiny_forest()
    tree_class = jnp.zeros(2, jnp.int32)
    bins = jnp.zeros((32, 4), jnp.int32)
    num_bins = jnp.ones(4, jnp.int32)
    missing = jnp.zeros(4, bool)
    traced = predict_binned_forest.trace(
        stacked, tree_class, bins, num_bins, missing, num_outputs=1)
    return {"jaxpr": traced.jaxpr}


def _probe_fused_train() -> Dict:
    import jax
    import jax.numpy as jnp
    from ..boosting.fused import build_fused_train
    ds, y = _tiny_dataset()
    kw = _grower_kwargs(ds)
    n = ds.bins.shape[0]
    label = jnp.asarray(y)

    class _Objective:
        def get_gradients(self, score):
            return score - label, jnp.ones_like(score)

    run = build_fused_train(
        objective=_Objective(), bins=jnp.asarray(ds.bins),
        feature_mask_fn=lambda it: jnp.ones(ds.num_features,
                                            jnp.float32),
        num_bins=jnp.asarray(ds.num_bins),
        missing_is_nan=jnp.asarray(ds.missing_types == 2),
        is_cat=jnp.asarray(ds.is_categorical), grower_kwargs=kw,
        shrinkage=0.1, extra_seed=3, needs_rng=False)
    score = jnp.zeros(n, jnp.float32)
    keys = jnp.zeros((2, 2), jnp.uint32)     # a block of k=2
    traced = run.program.trace(score, 0, keys, *run.operands)
    # it0 (global iteration offset) must not bake into the program —
    # the base trace above doubles as retrace argset 0
    other = run.program.trace(score, 7, keys, *run.operands)
    stable = str(traced.jaxpr) == str(other.jaxpr)
    lowered = traced.lower().as_text()
    return {"jaxpr": traced.jaxpr, "stable": stable,
            "lowered_text": lowered}


# ---------------------------------------------------------------------------
# the manifest

_GROW_DEPS = ("learner/grower_mxu.py", "learner/histogram_mxu.py",
              "learner/histogram_pallas.py", "learner/split.py",
              "learner/grower.py", "data.py")

TRACE_MANIFEST: Tuple[TraceEntry, ...] = (
    TraceEntry(
        name="partition_rows_rank",
        target_file="learner/histogram_pallas.py",
        target_fn="partition_rows",
        build=_probe_partition_rows,
        deps=("learner/histogram_pallas.py",),
    ),
    TraceEntry(
        name="grow_tree_mxu",
        target_file="learner/grower_mxu.py",
        target_fn="grow_tree_mxu",
        build=_probe_grow_tree_mxu,
        covers=(("gbdt.py", "_grow", "histogram_build"),),
        # the cond-pass carry mixes i32 node counters with i64 under
        # x64; the grow program is x64-off by construction
        x64_mode=False,
        deps=_GROW_DEPS,
    ),
    TraceEntry(
        name="route_rows_mxu",
        target_file="learner/histogram_mxu.py",
        target_fn="route_rows_mxu",
        build=_probe_route_rows_mxu,
        x64_mode=True,
        deps=("learner/histogram_mxu.py",),
    ),
    TraceEntry(
        name="predict_packed_forest",
        target_file="serving/multimodel.py",
        target_fn="_predict_packed_impl",
        build=_probe_predict_packed,
        covers=(("multimodel.py", "dispatch_pack",
                 "serving_pack_predict"),),
        stable_over="t_real (live-tree count)",
        deps=("serving/multimodel.py", "learner/predict.py",
              "learner/grower.py"),
    ),
    TraceEntry(
        name="predict_binned_forest",
        target_file="learner/predict.py",
        target_fn="predict_binned_forest",
        build=_probe_predict_binned_forest,
        covers=(("engine.py", "predict_raw", "serving_device_predict"),),
        deps=("learner/predict.py", "learner/grower.py"),
    ),
    TraceEntry(
        name="fused_train_run",
        target_file="boosting/fused.py",
        target_fn="build_fused_train",
        build=_probe_fused_train,
        covers=(("gbdt.py", "train_many_dispatch", "fused_dispatch"),),
        donate=True,
        stable_over="it0 (iteration offset)",
        deps=_GROW_DEPS + ("boosting/fused.py",),
    ),
)

#: DISPATCH_MANIFEST rows with no device program to trace — each waiver
#: names why. TRACE006 flags any row that is neither covered nor here.
WAIVERS: Dict[Tuple[str, str, str], str] = {
    ("gbdt.py", "_grow", "collective_psum"):
        "multi-device psum across the mesh; COLL004's manifest and the "
        "distributed chaos tier own this barrier — no single-host "
        "abstract trace exists",
    ("replicas.py", "dispatch", "serving_replica_predict"):
        "routing shim; delegates to predict_raw, covered by the "
        "predict_binned_forest entry",
    ("server.py", "hot_swap", "serving_hot_swap"):
        "host-side registry mutation, no device program",
    ("server.py", "hot_swap", "serving_hot_swap_commit"):
        "host-side registry mutation, no device program",
    ("checkpoint.py", "save_checkpoint", "checkpoint_io"):
        "host filesystem IO, no device program",
    ("loader.py", "_ingest_chunk_step", "streaming_ingest"):
        "host-side fault hook around chunk ingest, no device program",
    ("trainer.py", "_publish", "loop_publish"):
        "host-side atomic publish into the serving registry",
    ("comm.py", "guarded_allgather", "collective_psum"):
        "multihost collective; requires a live mesh, watchdog-bracketed "
        "and chaos-tested instead",
    ("hist_agg.py", "build_feature_shards", "distributed_hist_agg"):
        "multihost reduce-scatter; requires a live mesh",
    ("elastic.py", "propose_shrink", "elastic_resize"):
        "host-side membership vote over the heartbeat directory",
}
