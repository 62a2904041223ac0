"""Level-pipelined tree growth: the monolithic grower's passes as
separately-dispatched stage programs with speculative fixup.

``grow_tree_mxu`` runs the doubling schedule, bridge pass and fixup
while_loop as ONE jit program — zero host syncs per tree.  This driver
dispatches the SAME passes (traced from the same ``_make_grow_core``)
as separate stage programs, which buys three things:

- level *k+1*'s histogram build is enqueued before level *k*'s results
  are host-visible (JAX async dispatch keeps the device busy; the host
  never blocks between stages),
- the data-dependent fixup while_loop becomes bounded *speculative*
  host dispatch: chunks of ``lookahead`` fixup stages are enqueued and
  a LAGGED done flag (``copy_to_host_async`` of the previous chunk's
  done bit) decides whether to stop — the host reads a value that is
  already on its way, so polling never stalls the device,
- the host regains a per-level observation point (span traces, stall
  polls, future early-exit heuristics) that the monolithic program
  hides inside the device.

Parity contract: every stage traces ``_make_grow_core`` — the same
code the monolith traces — and a speculative fixup dispatched past the
done flag is an *identity* ``lax.cond`` no-op, exactly like a skipped
``while_loop`` iteration.  Quantized gradients are computed once by the
init stage and threaded through (``quant_state``), so stochastic
rounding bits match the monolith's single quantization.  The retained
``grow_tree_mxu`` is the byte-parity oracle (tests/test_level_pipeline.py
asserts byte-equal model.txt across objectives).

Program count: ``init + len(schedule) passes + bridge + ONE fixup
program (iteration index is a traced scalar) + final`` =
``growth_plan(...).n_stage_programs`` — bounded per (shape, config),
guarded by the compile-accounting entries ``grow_stage_*``.

Ineligible configs fall back to the monolith: ``psum_axis`` (the
sharded grower runs inside shard_map — staged host dispatch would
desynchronize the collective schedule across ranks) and ``debug_info``
(its fixup-iteration count is a device-side while_loop artifact).
"""

from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .grower import TreeArrays
from .grower_mxu import _DONE, _make_grow_core, grow_tree_mxu, growth_plan

__all__ = ["LevelPipelineStats", "grow_tree_pipelined"]

# static argnames mirror grow_tree_mxu's plus the stage selector
@functools.partial(
    jax.jit,
    static_argnames=("stage", "num_leaves", "max_depth", "hp", "bmax",
                     "interaction_groups", "feature_fraction_bynode",
                     "interpret", "hist_double_prec", "tail_split_cap",
                     "hist_subtraction", "overshoot", "bridge_gate",
                     "psum_axis", "quantized_grad", "use_scan_kernel",
                     "packed4", "const_hessian", "hist_backend",
                     "partition_impl", "cegb_cfg", "debug_info"))
def _stage(bins, grad, hess, cnt_weight, feature_mask, num_bins,
           missing_is_nan, is_cat_feat, *, stage,
           state=None, quant_state=None, it=None, fixup_iters=None,
           num_leaves: int, max_depth: int, hp, bmax: int,
           monotone=None, interaction_groups=None,
           feature_fraction_bynode: float = 1.0, rng_key=None,
           interpret: bool = False, hist_double_prec: bool = True,
           tail_split_cap: int = 0, hist_subtraction: bool = True,
           overshoot: float = 0.0, bridge_gate: float = 0.0,
           psum_axis=None, quantized_grad: bool = False,
           use_scan_kernel: bool = False, packed4: bool = False,
           const_hessian: float = 0.0, hist_backend: str = "mxu",
           partition_impl: str = "auto", efb=None, forced=None,
           cegb_cfg=None, cegb_state=None, debug_info: bool = False):
    """One pipeline stage program. `stage` is "init", ("pass", p),
    "bridge", "fixup" (traced `it`) or "final" (traced `fixup_iters`);
    XLA dead-code-eliminates the parts of the shared core a given
    stage doesn't touch."""
    core = _make_grow_core(
        bins, grad, hess, cnt_weight, feature_mask, num_bins,
        missing_is_nan, is_cat_feat, num_leaves=num_leaves,
        max_depth=max_depth, hp=hp, bmax=bmax, monotone=monotone,
        interaction_groups=interaction_groups,
        feature_fraction_bynode=feature_fraction_bynode,
        rng_key=rng_key, interpret=interpret,
        hist_double_prec=hist_double_prec,
        tail_split_cap=tail_split_cap,
        hist_subtraction=hist_subtraction, overshoot=overshoot,
        bridge_gate=bridge_gate, psum_axis=psum_axis,
        quantized_grad=quantized_grad, use_scan_kernel=use_scan_kernel,
        packed4=packed4, const_hessian=const_hessian,
        hist_backend=hist_backend, partition_impl=partition_impl,
        efb=efb, forced=forced, cegb_cfg=cegb_cfg,
        cegb_state=cegb_state, debug_info=debug_info,
        quant_state=quant_state)
    if stage == "init":
        return core.state0, core.quant_state_out
    if isinstance(stage, tuple) and stage[0] == "pass":
        p = stage[1]
        s_p = core.schedule[p]
        return core.cond_pass(s_p, state, jnp.asarray(p, jnp.int32),
                              m_cap=core.m_cap_of(s_p))
    if stage == "bridge":
        st = core.apply_gate(state)
        if core.schedule:
            st = core.cond_pass(core.s_max, st, len(core.schedule),
                                k_cap=core.k_fix, sk_next=core.sk_fix)
        return st
    if stage == "fixup":
        # speculative dispatch past the done flag must be an identity
        # no-op — the exact semantics of a skipped while_loop iteration
        # in the monolith (same cond: (~done) & (it < L_g))
        return jax.lax.cond(
            (~state[_DONE]) & (it < core.L_g),
            lambda st: core.fixup_pass(st, it), lambda st: st, state)
    if stage == "final":
        return core.epilogue(state, fixup_iters)
    raise ValueError(f"unknown stage {stage!r}")


@dataclass
class LevelPipelineStats:
    """Per-tree dispatch accounting for the staged driver.

    ``fixup_speculative`` is a LOWER bound: it counts fixups known (via
    the lagged done poll) to have run as identity no-ops — fixups that
    became no-ops mid-chunk are not separately visible without an extra
    host sync, which is exactly what this driver avoids."""
    stages: int = 0                 # total stage programs dispatched
    fixup_dispatched: int = 0
    fixup_speculative: int = 0
    done_polls: int = 0
    stopped_early: bool = False
    fallback: Optional[str] = None  # set when the monolith ran instead
    lookahead: int = 0
    wall_seconds: float = 0.0
    entries: list = field(default_factory=list)  # stage names, in order


def _dispatch(entry: str, stats: LevelPipelineStats, kwargs):
    """Run one stage. What building each stage program cost is in the
    compile ledger (observability/compiles.py) under `_stage`, from
    JAX's own events: one trace, lowering and compile per static
    stage."""
    out = _stage(**kwargs)
    stats.stages += 1
    stats.entries.append(entry)
    return out


def grow_tree_pipelined(bins, grad, hess, cnt_weight, feature_mask,
                        num_bins, missing_is_nan, is_cat_feat, *,
                        lookahead: int = 4, iteration: int = 0,
                        stats: Optional[LevelPipelineStats] = None,
                        **kw) -> Tuple[TreeArrays, jax.Array]:
    """Grow one tree via staged level-pipelined dispatch; same contract
    (arguments and return value, bit-for-bit) as ``grow_tree_mxu``.

    `lookahead` fixup stages are enqueued per chunk before the host
    consults the previous chunk's (already-in-flight) done flag.
    `stats`, when supplied, receives the dispatch accounting; the
    observability registry's ``level_pipeline`` family is updated
    either way when observability is enabled."""
    if kw.get("psum_axis") is not None or kw.get("debug_info", False):
        # ineligible (module docstring) — the oracle IS the answer
        out = grow_tree_mxu(bins, grad, hess, cnt_weight, feature_mask,
                            num_bins, missing_is_nan, is_cat_feat, **kw)
        if stats is not None:
            stats.fallback = ("psum_axis"
                              if kw.get("psum_axis") is not None
                              else "debug_info")
        return out

    from ..observability import registry as _obs

    st_acc = stats if stats is not None else LevelPipelineStats()
    st_acc.lookahead = lookahead = max(1, int(lookahead))
    plan = growth_plan(
        num_leaves=kw["num_leaves"],
        overshoot=kw.get("overshoot", 0.0),
        tail_split_cap=kw.get("tail_split_cap", 0),
        hist_subtraction=kw.get("hist_subtraction", True),
        bridge_gate=kw.get("bridge_gate", 0.0))
    common = dict(bins=bins, grad=grad, hess=hess,
                  cnt_weight=cnt_weight, feature_mask=feature_mask,
                  num_bins=num_bins, missing_is_nan=missing_is_nan,
                  is_cat_feat=is_cat_feat, **kw)

    t0 = time.time()
    w0 = time.perf_counter()
    state, quant_state = _dispatch(
        "grow_stage_init", st_acc,
        dict(common, stage="init"))
    common["quant_state"] = quant_state
    for p in range(len(plan.schedule)):
        state = _dispatch(
            f"grow_stage_pass_{p}", st_acc,
            dict(common, stage=("pass", p), state=state))
    state = _dispatch(
        "grow_stage_bridge", st_acc,
        dict(common, stage="bridge", state=state))

    # ---- speculative fixup: chunks of `lookahead`, lagged done poll ----
    max_fix = plan.max_fixup_dispatch
    it = len(plan.schedule) + 1
    prev_done = None
    while st_acc.fixup_dispatched < max_fix:
        chunk = min(lookahead, max_fix - st_acc.fixup_dispatched)
        for _ in range(chunk):
            state = _dispatch(
                "grow_stage_fixup", st_acc,
                dict(common, stage="fixup", state=state,
                     it=jnp.asarray(it, jnp.int32)))
            it += 1
            st_acc.fixup_dispatched += 1
        done_ref = state[_DONE]
        try:
            done_ref.copy_to_host_async()
        except AttributeError:
            pass
        if prev_done is not None:
            st_acc.done_polls += 1
            if bool(prev_done):   # lagged read — likely already landed
                st_acc.fixup_speculative += chunk
                st_acc.stopped_early = True
                break
        prev_done = done_ref

    out = _dispatch(
        "grow_stage_final", st_acc,
        dict(common, stage="final", state=state,
             # only consumed under debug_info, which falls back above —
             # the monolith's value would be the executed (not
             # dispatched) fixup count
             fixup_iters=jnp.asarray(st_acc.fixup_dispatched, jnp.int32)))
    st_acc.wall_seconds = time.perf_counter() - w0
    _obs.record_level_pipeline(
        iteration, t0, st_acc.wall_seconds, st_acc.stages,
        st_acc.fixup_dispatched, st_acc.fixup_speculative,
        st_acc.stopped_early)
    return out
