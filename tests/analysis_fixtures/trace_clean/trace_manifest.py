"""Clean twin of trace_bad: every contract satisfied, zero findings.

One entry exercising every contract flag (sort-free, x64, callbacks,
donation, retrace stability) compliantly, covering the only dispatch
row — the TRACE rules must stay silent here.
"""

import functools

from lightgbm_tpu.analysis.tracecheck import (TraceEntry,
                                              retrace_stable)


def _shaped(shape, dtype="float32"):
    import jax
    import jax.numpy as jnp
    return jax.ShapeDtypeStruct(shape, getattr(jnp, dtype))


def _probe_clean():
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, donate_argnums=(0,))
    def f(acc, x, k):
        # k stays traced (weak scalar): no per-value recompile; the
        # donated accumulator aliases the output
        return acc + x * k

    traced = f.trace(_shaped((16,)), _shaped((16,)), 2)
    stable = retrace_stable(f, [(_shaped((16,)), _shaped((16,)), 2),
                                (_shaped((16,)), _shaped((16,)), 3)])
    out = {"jaxpr": traced.jaxpr,
           "lowered_text": traced.lower().as_text(),
           "stable": stable}
    with jax.enable_x64(True):
        out["jaxpr_x64"] = f.trace(
            _shaped((16,)), _shaped((16,)), 2).jaxpr
    return out


TRACE_MANIFEST = (
    TraceEntry(name="clean_entry", target_file="trace_manifest.py",
               target_fn="_probe_clean", build=_probe_clean,
               covers=(("gbdt.py", "train_many_dispatch",
                        "fused_dispatch"),),
               x64_mode=True, donate=True, stable_over="k", line=43),
)

DISPATCH_ROWS = (
    ("gbdt.py", "train_many_dispatch", "fused_dispatch"),
)

WAIVERS = {}
