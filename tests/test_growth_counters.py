"""What a tree says it ran (grower_mxu.GROWTH_COUNTERS), against a tally
taken from OUTSIDE the growth program.

The counters ride in the pass state and are added to by the pass that
runs, from the count channel of the histogram it built. The tally here
wraps the build entry points the passes call (`fused_route_hist_mxu` for
a one-hot pass; `route_rows_mxu(emit_counts=True)` and
`build_histograms_scatter` for a grouped one) so that every call reports
itself through `jax.debug.callback`, with the rows the ROUTE kernel
counted a slot: another kernel than the one whose histogram the counter
reads. A pass `lax.cond` skips calls nothing, an iteration of the fixup
`while_loop` calls once.

Then the carrying: the fused scan stacks the vector with every tree
([k, C], [k, num_class, C]), and the data-parallel block over four
virtual devices counts what the serial block counts on the same rows.
CPU, Pallas in interpret mode.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.boosting.gbdt import GBDT
from lightgbm_tpu.data import BinnedDataset, Metadata
from lightgbm_tpu.learner import grower_mxu as gm
from lightgbm_tpu.learner.split import SplitHyperParams
from lightgbm_tpu.observability import registry

ROWS = 1500
C = len(gm.GROWTH_COUNTERS)


@pytest.fixture
def mixed_plan(monkeypatch):
    """hist_backend=auto's crossover lowered so that ONE small tree runs
    passes of both formulations (three one-hot, then grouped). Read at
    trace time: compiled programs are dropped around the patch."""
    from lightgbm_tpu.learner import histogram_pallas as hp
    jax.clear_caches()
    monkeypatch.setattr(hp, "GROUPED_MIN_WIDTH", 60)
    monkeypatch.setattr(hp, "GROUPED_MIN_ROWS_PER_PAD", 0)
    yield
    jax.clear_caches()


@pytest.fixture
def tally(monkeypatch, mixed_plan):
    """The calls of the passes' build entry points, in the order the
    program ran them: ("onehot" | "grouped" | "grouped_build", rows in
    the slots the route kernel counted)."""
    calls = []
    real_fused, real_route, real_build = (
        gm.fused_route_hist_mxu, gm.route_rows_mxu,
        gm.build_histograms_scatter)

    def report(kind):
        return lambda cts: calls.append((kind, int(np.sum(cts))))

    def fused(b, g, h, c, row_node, tbl, member, feat_tbl, **kw):
        # the rows a slot holds, asked of the route kernel on the same
        # tables: the fused kernel hands back no slot of a row
        cts = real_route(
            None, row_node, tbl, member, feat_tbl, emit_counts=True,
            **{k: kw[k] for k in ("num_features", "num_slots", "has_cat",
                                  "operands", "interpret")})[2]
        jax.debug.callback(report("onehot"), cts)
        return real_fused(b, g, h, c, row_node, tbl, member, feat_tbl,
                          **kw)

    def route(*a, **kw):
        out = real_route(*a, **kw)
        if kw.get("emit_counts"):
            jax.debug.callback(report("grouped"), out[2])
        return out

    def build(*a, **kw):
        jax.debug.callback(report("grouped_build"), kw["slot_counts"])
        return real_build(*a, **kw)

    monkeypatch.setattr(gm, "fused_route_hist_mxu", fused)
    monkeypatch.setattr(gm, "route_rows_mxu", route)
    monkeypatch.setattr(gm, "build_histograms_scatter", build)
    return calls


def _grow_args(n=ROWS, f=4, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)
    ds = BinnedDataset.from_raw(X, Metadata(n, label=y), max_bin=31)
    p = np.full(n, 0.5, np.float32)
    args = (jnp.asarray(ds.bins), jnp.asarray(p - y),
            jnp.asarray(p * (1 - p)), jnp.ones(n, jnp.float32),
            jnp.ones(f, jnp.float32), jnp.asarray(ds.num_bins),
            jnp.asarray(ds.missing_types == 2),
            jnp.asarray(ds.is_categorical))
    kw = dict(num_leaves=15, max_depth=0,
              hp=SplitHyperParams(min_data_in_leaf=5),
              bmax=int(ds.num_bins.max()), interpret=True,
              hist_backend="auto")
    return args, kw


def _grown(calls, **extra):
    """One tree's counters by name, the tally of its passes, and the
    static plan of its program."""
    args, kw = _grow_args()
    kw.update(extra)
    tree, row_node, counters = gm.grow_tree_mxu(
        *args, growth_counters=True, **kw)
    jax.effects_barrier()
    assert counters.shape == (C,) and counters.dtype == jnp.int32
    plan = gm.hist_pass_plan(
        rows=ROWS, **{k: kw[k] for k in ("num_leaves", "overshoot",
                                         "tail_split_cap", "bridge_gate")
                      if k in kw})
    ran = dict(zip(gm.GROWTH_COUNTERS, np.asarray(counters).tolist()))
    return ran, list(calls), plan, tree


def _held_against_the_tally(ran, calls, plan):
    onehot = [rows for kind, rows in calls if kind == "onehot"]
    grouped = [rows for kind, rows in calls if kind == "grouped"]
    built = [rows for kind, rows in calls if kind == "grouped_build"]
    assert ran["onehot_passes"] == len(onehot)
    assert ran["grouped_passes"] == len(grouped) == len(built)
    assert grouped == built
    assert ran["onehot_rows"] == sum(onehot)
    assert ran["grouped_rows"] == sum(grouped)
    # the root pass builds every row; no pass builds more
    assert onehot[0] == ROWS and max(onehot + grouped) == ROWS
    # the passes past the schedule are what is left of the calls
    scheduled = sum(1 for stage, _, _ in plan if stage == "pass")
    assert ran["bridge_passes"] + ran["fixup_iters"] == \
        len(onehot) + len(grouped) - scheduled


def test_a_tree_on_its_schedule_counts_its_schedule(tally):
    ran, calls, plan, tree = _grown(tally)
    assert [form for stage, _, form in plan if stage == "pass"] == \
        ["onehot", "onehot", "onehot", "grouped"]
    _held_against_the_tally(ran, calls, plan)
    # 15 leaves in four passes: done, so the bridge is skipped and the
    # fixup loop's condition is false at once. Skipped passes call
    # nothing and count nothing
    assert (ran["onehot_passes"], ran["grouped_passes"]) == (3, 1)
    assert ran["bridge_passes"] == 0 and ran["fixup_iters"] == 0
    assert ran["leaves_grown"] == int(tree.num_leaves) == 15
    # a pass after the root builds the smaller sibling of each split:
    # at most half of the rows
    later = [rows for _, rows in calls if _ != "grouped_build"][1:]
    assert all(0 < rows <= ROWS // 2 for rows in later)


def test_a_tree_forced_off_its_schedule_counts_every_fixup(tally):
    # at most two splits a pass once the leaf budget binds: the tree
    # cannot finish on the schedule and the while_loop runs
    ran, calls, plan, tree = _grown(tally, tail_split_cap=2)
    _held_against_the_tally(ran, calls, plan)
    assert ran["bridge_passes"] == 1 and ran["fixup_iters"] > 0
    assert int(tree.num_leaves) == 15


def test_an_overgrown_tree_counts_its_leaves_before_the_prune(tally):
    # overshoot 2: thirty leaves grown, fifteen kept; the gate skips
    # the bridge of a tree that is past gate x 30 leaves by then
    ran, calls, plan, tree = _grown(tally, overshoot=2.0)
    _held_against_the_tally(ran, calls, plan)
    assert ran["leaves_grown"] > int(tree.num_leaves) == 15
    assert ran["bridge_passes"] == 1
    gated, calls_g, plan_g, _ = _grown([], overshoot=2.0,
                                       bridge_gate=0.5)
    assert gated["bridge_passes"] == 0 and gated["fixup_iters"] == 0
    assert gated["grouped_passes"] == ran["grouped_passes"] - 1


def test_the_callers_of_two_values_count_nothing(mixed_plan):
    args, kw = _grow_args()
    tree, row_node = gm.grow_tree_mxu(*args, **kw)
    counted, rows_c, _ = gm.grow_tree_mxu(*args, growth_counters=True,
                                          **kw)
    for fld in tree._fields:
        assert np.asarray(getattr(tree, fld)).tobytes() == \
            np.asarray(getattr(counted, fld)).tobytes(), fld
    assert np.asarray(row_node).tobytes() == np.asarray(rows_c).tobytes()


def test_a_count_saturates_instead_of_wrapping():
    near = jnp.zeros(C, jnp.int32).at[4].set(2 ** 31 - 1000)
    kern = jnp.zeros((2, 1, 4, 3), jnp.float32).at[0, 0, 1, 2].set(600.) \
        .at[1, 0, 3, 2].set(600.)
    out = np.asarray(gm._count_pass(near, kern, "onehot", "fixup"))
    assert out.tolist() == [1, 0, 0, 1, 2 ** 31 - 1, 0, 0]
    out = np.asarray(gm._count_pass(jnp.asarray(out), kern, "grouped",
                                    "bridge"))
    assert out.tolist() == [1, 1, 1, 1, 2 ** 31 - 1, 1200, 0]
    # one pass's rows past int32 (a mesh of 2^24-row shards could)
    big = jnp.full((1, 1, 1, 3), 3e9, jnp.float32)
    out = np.asarray(gm._count_pass(jnp.zeros(C, jnp.int32), big,
                                    "scatter", "pass"))
    assert out[1] == 1 and 2 ** 31 - 129 < out[5] <= 2 ** 31 - 1


# ----------------------------------------------------------------------
# carried out with the block
PARAMS = {"objective": "binary", "num_leaves": 7, "max_bin": 31,
          "min_data_in_leaf": 5, "verbosity": -1}
N, F, NDEV, BLOCK = 2000, 8, 4, 3


def _data(classes=2, seed=3):
    rng = np.random.RandomState(seed)
    X = rng.randn(N, F).astype(np.float32)
    y = (X[:, 0] - X[:, 1] + X[:, 2] * X[:, 3] +
         0.2 * rng.randn(N) > 0).astype(np.float32)
    if classes > 2:
        y = y + (X[:, 4] > 0.5).astype(np.float32)
    return X, y


def _block(monkeypatch, **params):
    """One fused block from iteration 0 on the MXU path (interpreted):
    its counters as the scan stacked them, the attributes its unpack
    span carries, the booster."""
    monkeypatch.setattr(GBDT, "_mxu_interpret", True, raising=False)
    X, y = _data(classes=params.get("num_class", 2))
    g = lgb.Booster(params={**PARAMS, **params},
                    train_set=lgb.Dataset(X, label=y,
                                          params={"max_bin": 31})).gbdt
    if g._learner.device != "mxu":
        g._hist_impl = "mxu"
    assert g._fused_eligible()
    registry.trace.reset()
    handle = g.train_many_dispatch(BLOCK)
    assert handle["mode"] == "fused"
    stacked = np.asarray(handle["counters"])
    assert not g.finalize_block(handle)
    span, = [s for s in registry.trace.spans()
             if s["name"] == "entry.unpack_block"]
    return stacked, span["attrs"], handle, g


def _span_agrees_with_the_stack(stacked, attrs, trees):
    per_tree = stacked.reshape(-1, C)
    assert len(per_tree) == trees == attrs["trees"]
    for i, name in enumerate(gm.GROWTH_COUNTERS):
        assert attrs[name] == int(per_tree[:, i].sum()), name
    assert attrs["passes"] == \
        attrs["onehot_passes"] + attrs["grouped_passes"]
    assert attrs["rows"] == N and attrs["programs"] == 0


def test_the_scan_stacks_them_with_every_tree(monkeypatch):
    jax.clear_caches()
    registry.compiles.reset()
    stacked, attrs, handle, g = _block(monkeypatch)
    assert stacked.shape == (BLOCK, C) and stacked.dtype == np.int32
    _span_agrees_with_the_stack(stacked, attrs, BLOCK)
    # every tree ran its root pass over every row, and grew
    assert (stacked[:, 4] + stacked[:, 5] >= N).all()
    assert (stacked[:, 6] >= 7).all()
    # the handle leaves the view PipelineStats is fed from
    assert handle["ran"]["passes"] == attrs["passes"] > 0
    assert handle["ran"]["fixup_iters"] == attrs["fixup_iters"]
    # ONE fused program and ONE split program, as before the counters
    ledger = registry.compiles.snapshot()
    assert ledger["program"]["built"] == 1
    assert ledger["split_block"]["built"] == 1
    assert "grow_tree_mxu" not in ledger or \
        ledger["grow_tree_mxu"]["built"] == 0


def test_a_tree_per_class_is_a_row_per_class(monkeypatch):
    stacked, attrs, _, g = _block(monkeypatch, objective="multiclass",
                                  num_class=3)
    assert stacked.shape == (BLOCK, 3, C)
    _span_agrees_with_the_stack(stacked, attrs, BLOCK * 3)
    assert len(g.trees) == BLOCK * 3


@pytest.mark.distributed
def test_four_devices_count_what_one_counts(monkeypatch):
    # the same rows, serial and sharded by rows over four devices: the
    # passes are the tree's and the rows the mesh's, so the counts are
    # equal, tree for tree
    serial, attrs_1, _, g1 = _block(monkeypatch)
    assert g1.mesh is None
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    sharded, attrs_4, _, g4 = _block(monkeypatch, tree_learner="data",
                                     num_devices=NDEV)
    assert (g4._learner.mode, g4._learner.device) == ("data", "mxu")
    assert len({s.device.id for s in g4.bins.addressable_shards}) == NDEV
    np.testing.assert_array_equal(serial, sharded)
    assert attrs_4["rows"] == attrs_1["rows"] == N
    _span_agrees_with_the_stack(sharded, attrs_4, BLOCK)
