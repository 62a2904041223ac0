"""Level-pipelined grower: parity oracle, compile-count guard,
overlap accounting (`make perf`).

The staged driver (learner/grower_pipeline.py) dispatches the passes of
the shared growth core — ``_make_grow_core``, the same core the
monolithic ``grow_tree_mxu`` traces, collective psum site included —
as separate stage programs with speculative host-side fixup dispatch.
Three contracts are pinned here:

- **byte parity**: ``grow_tree_pipelined`` output is bit-for-bit the
  monolith's, per-tree (slow tier: tobytes over every TreeArrays field
  — NaN leaf values compare equal as bytes) and per-model (slow tier:
  byte-equal model.txt across regression/binary/multiclass); tier-1
  keeps the cheap lookahead-invariance byte check (the monolith oracle
  is a second ~10s interpret-mode compile);
- **compile bound**: distinct stage programs per (shape, config) ==
  ``growth_plan(...).n_stage_programs``, each compiling EXACTLY once —
  a shape leak that recompiled per level or per tree would show up as
  more than that many builds of ``_stage`` in the compile ledger;
- **overlap accounting**: LevelPipelineStats counts (stages, fixup
  dispatch, speculative lower bound, early stop) obey the dispatch
  algebra — count-based, no wall-clock thresholds.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from lightgbm_tpu.data import BinnedDataset, Metadata
from lightgbm_tpu.learner.grower_mxu import (_make_grow_core,  # noqa: F401
                                             grow_tree_mxu, growth_plan)
from lightgbm_tpu.learner.grower_pipeline import (LevelPipelineStats,
                                                  grow_tree_pipelined)
from lightgbm_tpu.learner.split import SplitHyperParams
from lightgbm_tpu.observability import registry as _obs


def _inputs(n=384, f=5, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)
    ds = BinnedDataset.from_raw(X, Metadata(n, label=y), max_bin=15)
    g = jnp.asarray(0.5 - y + 0.01 * rng.randn(n).astype(np.float32))
    h = jnp.full(n, 0.25, jnp.float32)
    return (jnp.asarray(ds.bins), g, h, jnp.ones(n, jnp.float32),
            jnp.ones(ds.num_features, jnp.float32),
            jnp.asarray(ds.num_bins), jnp.asarray(ds.missing_types == 2),
            jnp.asarray(ds.is_categorical))


# interpret-mode programs cost ~10s each to compile on one CPU core, so
# every default-tier test in this file shares ONE (shape, config) cell —
# _inputs() shapes + _KW — and only the data (seed) varies: the compile
# guard below runs first and pays the stage-set compile once, everything
# after it is cache hits plus at most one distinct monolith program.
_KW = dict(num_leaves=7, max_depth=0,
           hp=SplitHyperParams(min_data_in_leaf=20), bmax=15,
           interpret=True)


def _assert_bytes_equal(out_a, out_b):
    t_a, r_a = out_a[0], out_a[1]
    t_b, r_b = out_b[0], out_b[1]
    for fld, x, y in zip(t_a._fields, t_a, t_b):
        assert np.asarray(x).tobytes() == np.asarray(y).tobytes(), fld
    assert np.asarray(r_a).tobytes() == np.asarray(r_b).tobytes()
    for x, y in zip(out_a[2:], out_b[2:]):
        for xi, yi in zip(x, y):
            assert np.asarray(xi).tobytes() == np.asarray(yi).tobytes()


def test_compile_count_bounded_and_no_shape_leak():
    # FIRST test in the file: the shared cell's _stage jit cache must
    # be cold here so compiles are attributable (no other tier-1 file
    # touches grower_pipeline)
    args_a = _inputs(seed=1)
    kw = _KW
    plan = growth_plan(num_leaves=kw["num_leaves"])
    _obs.compiles.reset()

    stats = LevelPipelineStats()
    grow_tree_pipelined(*args_a, lookahead=2, stats=stats, **kw)
    assert set(stats.entries) == ({"grow_stage_init", "grow_stage_bridge",
                                   "grow_stage_fixup", "grow_stage_final"} |
                                  {f"grow_stage_pass_{p}"
                                   for p in range(len(plan.schedule))})
    # one compiled program per stage, as JAX's own events count them
    # (the compile ledger keys them by the jitted function, `_stage`):
    # the fixup program is compiled once and re-dispatched with a
    # traced iteration index
    assert len(set(stats.entries)) == plan.n_stage_programs
    built = _obs.compiles.snapshot()["_stage"]
    assert built["built"] == built["lowered"] == plan.n_stage_programs
    assert built["backend_seconds"] > 0


def test_fixup_program_retrace_stable():
    # shape-leak guard, checked at the trace level instead of by
    # re-dispatching the whole pipeline: the fixup stage's jaxpr must
    # be identical across iteration indices — the retrace_stable
    # helper the TRACE005 lint contract runs over the production
    # manifest. If `it` (or any value derived from it) were baked into
    # the program, each fixup dispatch would recompile and the compile
    # bound above would be a lie. Traces only: nothing executes.
    import functools

    import jax

    from lightgbm_tpu.analysis.tracecheck import retrace_stable
    from lightgbm_tpu.learner import grower_pipeline as gp

    names = ("bins", "grad", "hess", "cnt_weight", "feature_mask",
             "num_bins", "missing_is_nan", "is_cat_feat")
    base = dict(zip(names, _inputs(seed=1)))
    state0, quant0 = jax.eval_shape(
        functools.partial(gp._stage, stage="init", **_KW), **base)
    argsets = [dict(base, stage="fixup", state=state0,
                    quant_state=quant0,
                    it=jnp.asarray(i, jnp.int32), **_KW)
               for i in (3, 9)]
    assert retrace_stable(gp._stage, argsets)


# slow tier: the monolith oracle is a SECOND ~10s interpret-mode
# compile on top of the stage set; tier-1 keeps the compile guard and
# the lookahead-invariance byte check below, while oracle parity runs
# here per-tree and (further down) at model.txt level per objective
@pytest.mark.slow
def test_pipelined_matches_monolith_bytes():
    args = _inputs()
    _assert_bytes_equal(grow_tree_pipelined(*args, lookahead=2, **_KW),
                        grow_tree_mxu(*args, **_KW))


@pytest.mark.perf
class TestOverlapAccounting:
    """Dispatch algebra for the speculative fixup overlap — the
    structure behind the round-6 numbers, count-based only."""

    def test_stage_and_fixup_counts(self):
        args = _inputs(seed=3)
        plan = growth_plan(num_leaves=_KW["num_leaves"])
        stats = LevelPipelineStats()
        grow_tree_pipelined(*args, lookahead=2, stats=stats, **_KW)
        assert stats.fallback is None
        # init + schedule passes + bridge + fixups + final
        assert stats.stages == (len(plan.schedule) + 3 +
                                stats.fixup_dispatched)
        assert 0 <= stats.fixup_speculative <= stats.fixup_dispatched
        assert stats.fixup_dispatched <= plan.max_fixup_dispatch
        assert stats.entries[0] == "grow_stage_init"
        assert stats.entries[-1] == "grow_stage_final"
        assert stats.lookahead == 2
        assert stats.wall_seconds > 0.0

    def test_early_stop_counts_speculative_fixups(self):
        # the tree completes well inside the doubling schedule, so the
        # done flag is set long before max_fixup_dispatch, the lagged
        # poll sees it, and every fixup chunk dispatched past it is
        # known-speculative
        args = _inputs(seed=4)
        plan = growth_plan(num_leaves=_KW["num_leaves"])
        assert plan.max_fixup_dispatch >= 2   # else nothing to stop
        stats = LevelPipelineStats()
        out_p = grow_tree_pipelined(*args, lookahead=1, stats=stats,
                                    **_KW)
        assert stats.stopped_early
        assert stats.fixup_speculative >= 1
        assert stats.fixup_dispatched < plan.max_fixup_dispatch
        assert stats.done_polls >= 1
        # speculative dispatch past done is an identity no-op: the
        # result is invariant under how much the driver speculates
        # (lookahead changes the dispatch pattern, not one byte of the
        # tree; the slow tier pins the same bytes against the monolith)
        _assert_bytes_equal(out_p,
                            grow_tree_pipelined(*args, lookahead=3,
                                                **_KW))

    def test_debug_info_falls_back_to_monolith(self, monkeypatch):
        # debug_info's fixup-iteration count is a device while_loop
        # artifact — the staged driver hands the whole tree to the
        # monolithic oracle, untouched and verbatim (parity is by
        # construction: the fallback IS the monolith call, so stub it
        # out rather than pay its ~10s interpret-mode compile here)
        from lightgbm_tpu.learner import grower_pipeline as gp
        seen = {}

        def spy(*args, **kw):
            seen["args"], seen["kw"] = args, kw
            return "monolith-output"

        monkeypatch.setattr(gp, "grow_tree_mxu", spy)
        args = _inputs(seed=5)
        kw = dict(debug_info=True, **_KW)
        stats = LevelPipelineStats()
        out_p = grow_tree_pipelined(*args, stats=stats, **kw)
        assert out_p == "monolith-output"
        assert stats.fallback == "debug_info"
        assert stats.stages == 0
        assert seen["args"] == tuple(args)
        assert seen["kw"].get("debug_info") is True
        for key, val in _KW.items():
            assert seen["kw"][key] == val, key

    def test_growth_plan_program_bound(self):
        # the static plan both drivers share: program count and fixup
        # dispatch bound are pure functions of the config
        for nl, over, gate in ((31, 1.15, 0.9), (7, 0.0, 0.0),
                               (127, 0.0, 0.0)):
            plan = growth_plan(num_leaves=nl, overshoot=over,
                               bridge_gate=gate)
            assert plan.n_stage_programs == len(plan.schedule) + 4
            assert plan.max_fixup_dispatch == max(
                0, plan.L_g - len(plan.schedule) - 1)
            assert plan.s_max == plan.L_g + 1


@pytest.mark.slow
class TestModelByteParity:
    """level_pipeline=true must be invisible in the trained model:
    byte-equal model.txt across objectives (the monolithic grower is
    the retained oracle)."""

    OBJECTIVES = (
        ("regression", 1, "l2"),
        ("binary", 1, "binary"),
        ("multiclass", 3, "multiclass"),
    )

    def _train(self, objective, num_class, level_pipeline):
        import lightgbm_tpu as lgb
        rng = np.random.RandomState(11)
        X = rng.randn(400, 5).astype(np.float32)
        if objective == "multiclass":
            y = rng.randint(0, num_class, 400).astype(np.float32)
        elif objective == "binary":
            y = (X[:, 0] > 0).astype(np.float32)
        else:
            y = (X[:, 0] + 0.3 * rng.randn(400)).astype(np.float32)
        params = {"objective": objective, "num_leaves": 7,
                  "learning_rate": 0.2, "max_bin": 31, "verbosity": -1,
                  "min_data_in_leaf": 5,
                  "level_pipeline": level_pipeline,
                  "level_pipeline_lookahead": 2}
        if objective == "multiclass":
            params["num_class"] = num_class
        ds = lgb.Dataset(X, label=y, params={"max_bin": 31})
        bst = lgb.Booster(params=params, train_set=ds)
        bst.update()
        g = bst.gbdt
        g._hist_impl = "mxu"
        g._mxu_interpret = True
        g._fused_run = None
        for _ in range(3):
            bst.update()
        return "\n".join(
            ln for ln in bst.model_to_string().splitlines()
            if not ln.startswith("[level_pipeline"))

    @pytest.mark.parametrize("objective,num_class,_name", OBJECTIVES,
                             ids=[o[2] for o in OBJECTIVES])
    def test_byte_identical_models(self, objective, num_class, _name):
        on = self._train(objective, num_class, True)
        off = self._train(objective, num_class, False)
        assert on == off
