"""Fixture trace manifest: one contract violation per TRACE rule.

rules_trace loads this module (any scanned file named
``trace_manifest.py``) instead of the production manifest, so the
TRACE rules can be pinned against known-bad traced programs without
planting violations in the package. Every entry is a tiny
self-contained jax program; `line` anchors the expected finding.
"""

import functools

from lightgbm_tpu.analysis.tracecheck import (TraceEntry,
                                              retrace_stable)


def _shaped(shape, dtype="float32"):
    import jax
    import jax.numpy as jnp
    return jax.ShapeDtypeStruct(shape, getattr(jnp, dtype))


def _probe_sorting():
    import jax
    import jax.numpy as jnp

    def f(x):
        return jnp.sort(x) * 2.0

    return {"jaxpr": jax.make_jaxpr(f)(_shaped((16,)))}


def _probe_f64():
    import warnings

    import jax
    import jax.numpy as jnp

    def f(x):
        return x.astype(jnp.float64) * 2.0

    with warnings.catch_warnings():
        # the default-mode trace truncates f64 -> f32 with a warning;
        # the x64 trace below is the one the rule inspects
        warnings.simplefilter("ignore")
        out = {"jaxpr": jax.make_jaxpr(f)(_shaped((16,)))}
    with jax.enable_x64(True):
        out["jaxpr_x64"] = jax.make_jaxpr(f)(_shaped((16,)))
    return out


def _probe_callback():
    import jax
    import jax.numpy as jnp

    def f(x):
        jax.debug.print("x sum {}", jnp.sum(x))
        return x * 2.0

    return {"jaxpr": jax.make_jaxpr(f)(_shaped((16,)))}


def _probe_dead_donation():
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, donate_argnums=(0,))
    def f(scratch, x):
        # no output matches the donated buffer's shape/dtype: the
        # declared donation is unusable and silently dropped
        return (x * 2.0).astype(jnp.int32)

    traced = f.trace(_shaped((16,)), _shaped((16,)))
    return {"jaxpr": traced.jaxpr,
            "lowered_text": traced.lower().as_text()}


def _probe_baked_scalar():
    import jax

    @functools.partial(jax.jit, static_argnums=(1,))
    def f(x, k):
        return x * k

    traced = f.trace(_shaped((16,)), 2)
    # k is declared dispatch-stable below but marked static here: each
    # value recompiles, so the two traces differ
    stable = retrace_stable(f, [(_shaped((16,)), 2),
                                (_shaped((16,)), 3)])
    return {"jaxpr": traced.jaxpr, "stable": stable}


TRACE_MANIFEST = (
    TraceEntry(name="sorting_entry", target_file="trace_manifest.py",
               target_fn="_probe_sorting", build=_probe_sorting,
               line=94),
    TraceEntry(name="f64_entry", target_file="trace_manifest.py",
               target_fn="_probe_f64", build=_probe_f64,
               x64_mode=True, line=97),
    TraceEntry(name="callback_entry", target_file="trace_manifest.py",
               target_fn="_probe_callback", build=_probe_callback,
               line=100),
    TraceEntry(name="dead_donation_entry",
               target_file="trace_manifest.py",
               target_fn="_probe_dead_donation",
               build=_probe_dead_donation, donate=True, line=103),
    TraceEntry(name="baked_scalar_entry",
               target_file="trace_manifest.py",
               target_fn="_probe_baked_scalar",
               build=_probe_baked_scalar, stable_over="k", line=107),
)

#: one dispatch row with no covering entry and no waiver, plus one
#: waiver naming a row that does not exist (both TRACE006)
DISPATCH_ROWS = (
    ("gbdt.py", "train_many_dispatch", "fused_dispatch"),
)

WAIVERS = {
    ("removed.py", "old_entry", "stale_site"): "row no longer exists",
}
