"""The data-parallel MXU learner runs fused blocks (PR 36).

On the chip `tree_learner=data` resolves ("data", "mxu"): the MXU grower
inside `shard_map`, histograms summed over the mesh in every pass. It
takes the serial learner's own scan (`boosting/fused.py`, `mesh=`), so
a block of ten trees is one dispatch on four devices as on one, the
score update is the one-hot lookup on each device's rows, and a run
builds ONE growth program.

Here: four of the conftest's virtual CPU devices, Pallas in interpret
mode, and the backend NAME a TPU reports while the booster resolves its
learner, so that the spec is the chip's and comes from
`crossbar.resolve_learner` like there. Rows divide the mesh except
where a test says otherwise.
"""

import jax
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.boosting.gbdt import GBDT
from lightgbm_tpu.observability import registry

pytestmark = [pytest.mark.distributed]

N, F, NDEV, BLOCK = 2000, 8, 4, 10
PARAMS = {"objective": "binary", "num_leaves": 7, "max_bin": 31,
          "min_data_in_leaf": 5, "verbosity": -1}
DATA_PARALLEL = {"tree_learner": "data", "num_devices": NDEV}


def _data(n=N, seed=3):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, F).astype(np.float32)
    y = (X[:, 0] - X[:, 1] + X[:, 2] * X[:, 3] +
         0.2 * rng.randn(n) > 0).astype(np.float32)
    return X, y


def _as_on_the_chip(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(GBDT, "_mxu_interpret", True, raising=False)


@pytest.fixture
def as_on_the_chip(monkeypatch):
    """Boosters made under it resolve their learner as a TPU backend
    would, and run their kernels interpreted."""
    _as_on_the_chip(monkeypatch)


def _booster(n=N, **params):
    X, y = _data(n)
    return lgb.Booster(params={**PARAMS, **params},
                       train_set=lgb.Dataset(X, label=y,
                                             params={"max_bin": 31}))


def _blocks(g, blocks):
    for _ in range(blocks):
        assert not g.finalize_block(g.train_many_dispatch(BLOCK))


def _structure(g):
    """(split feature, threshold, leaf count) of every tree, and the
    leaf values."""
    shape, values = [], []
    for t in g.trees:
        nn = int(t.num_nodes)
        shape.append((np.asarray(t.split_feature[:nn]).tolist(),
                      np.asarray(t.threshold_bin[:nn]).tolist(),
                      int(t.num_leaves)))
        values.append(np.asarray(t.leaf_value[:nn]))
    return shape, values


def _row_device_puts(monkeypatch, rows):
    """Counts the `jax.device_put` calls that move a row-sized array
    from here on."""
    calls = []
    real = jax.device_put

    def counting(x, *args, **kwargs):
        if any(getattr(leaf, "shape", ())[:1] >= (rows,)
               for leaf in jax.tree_util.tree_leaves(x)):
            calls.append(np.shape(x))
        return real(x, *args, **kwargs)

    monkeypatch.setattr(jax, "device_put", counting)
    return calls


@pytest.fixture(scope="module")
def two_blocks():
    """A data-parallel MXU booster after two fused blocks from
    iteration 0, with what the compile ledger held after each, the
    spans of the run and the row-sized `device_put`s of the second."""
    jax.clear_caches()
    registry.compiles.reset()
    registry.trace.reset()
    with pytest.MonkeyPatch.context() as monkeypatch:
        _as_on_the_chip(monkeypatch)
        g = _booster(**DATA_PARALLEL).gbdt
        first = g.train_many_dispatch(BLOCK)
        g.finalize_block(first)
        ledger = registry.compiles.snapshot()
        score_between = g.train_score
        puts = _row_device_puts(monkeypatch, N // NDEV)
        second = g.train_many_dispatch(BLOCK)
        puts = list(puts)
        g.finalize_block(second)
    return dict(g=g, first=first, second=second, ledger=ledger,
                ledger_after=registry.compiles.snapshot(),
                spans=registry.trace.spans(),
                score_between=score_between, puts=puts)


def test_the_chips_spec_is_fused_eligible(as_on_the_chip):
    g = _booster(**DATA_PARALLEL).gbdt
    assert (g._learner.mode, g._learner.device) == ("data", "mxu")
    assert g._learner.hist_agg == "psum" and g._bins_ft is None
    assert g._sharded_fused_ok() and g._fused_eligible()


def test_a_run_builds_one_growth_program(two_blocks):
    # (a) the block that starts at iteration 0 runs fused: the scan is
    # the one program that holds the grower, and the per-tree sharded
    # grower ("sharded", parallel/learner.py) is never traced
    r = two_blocks
    assert r["first"]["mode"] == r["second"]["mode"] == "fused"
    assert r["first"]["iter"] == 0 and r["first"]["k"] == BLOCK
    ledger = r["ledger"]
    assert ledger["program"]["built"] == ledger["program"]["lowered"] == 1
    assert ledger["grow_tree_mxu"]["traced"] == 1
    assert ledger["grow_tree_mxu"]["built"] == 0      # inlined in the scan
    assert "sharded" not in ledger
    # and the second block of the same length builds nothing
    after = r["ledger_after"]
    assert after["program"]["built"] == 1
    assert after["grow_tree_mxu"]["traced"] == 1
    assert len(r["g"].trees) == 2 * BLOCK
    assert min(int(t.num_leaves) for t in r["g"].trees) > 1


def test_the_build_span_says_how_many_devices(two_blocks):
    builds = [s["attrs"] for s in two_blocks["spans"]
              if s["name"] == "boosting.build_program"]
    assert [a["program"] for a in builds] == ["fused_train"]
    assert builds[0]["devices"] == NDEV
    assert builds[0]["k"] == BLOCK and builds[0]["hist_plan"]


def test_the_score_stays_on_the_mesh_between_blocks(two_blocks):
    # (c) block 2 takes block 1's score as it came out: row-sharded
    # over the four devices, donated, and nothing row-sized is placed
    r = two_blocks
    for score in (r["score_between"], r["g"].train_score):
        if not score.is_deleted():
            shards = score.addressable_shards
            assert len({s.device for s in shards}) == NDEV
            assert {s.data.shape for s in shards} == {(N // NDEV,)}
    assert r["puts"] == []
    # the row state went onto the mesh when the program was built
    bins, row_state, tables = r["g"]._fused_run.operands
    assert tables == () and row_state
    for arr in (bins,) + row_state:
        assert len({s.device for s in arr.addressable_shards}) == NDEV


@pytest.fixture
def per_iteration_twin(as_on_the_chip):
    """Twenty trees of the same sharded learner, one `train_one_iter`
    each."""
    g = _booster(**DATA_PARALLEL).gbdt
    for _ in range(2 * BLOCK):
        assert not g.train_one_iter()
    return g


def test_two_fused_blocks_equal_twenty_iterations(two_blocks,
                                                  per_iteration_twin):
    # (b) against the per-tree sharded grower (the psum inside it is
    # the same): the trees' structure exactly, their values and the
    # scores to 1e-6
    fused, stepped = two_blocks["g"], per_iteration_twin
    shape_f, values_f = _structure(fused)
    shape_s, values_s = _structure(stepped)
    assert len(shape_f) == 2 * BLOCK and shape_f == shape_s
    for a, b in zip(values_f, values_s):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(fused.train_score),
                               np.asarray(stepped.train_score),
                               rtol=0, atol=1e-6)


def test_two_fused_blocks_equal_the_serial_mxu_learner(two_blocks):
    # (b) and against the un-sharded MXU learner on the gathered rows
    # (the same scan without an axis). Its histograms are summed in
    # another order, so the values are held to 1e-5
    serial = _booster().gbdt
    serial._hist_impl, serial._mxu_interpret = "mxu", True
    assert serial._learner.serial_mxu and serial.mesh is None
    _blocks(serial, 2)
    shape_f, values_f = _structure(two_blocks["g"])
    shape_s, values_s = _structure(serial)
    assert shape_f == shape_s
    for a, b in zip(values_f, values_s):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(two_blocks["g"].train_score),
                               np.asarray(serial.train_score),
                               rtol=0, atol=1e-5)


@pytest.mark.parametrize("extra", [
    {},
    {"bagging_fraction": 0.6, "bagging_freq": 2, "bagging_seed": 5},
], ids=["plain", "bagged"])
def test_rows_that_do_not_divide_the_mesh(as_on_the_chip, extra):
    # 1,998 rows over four devices: the last shard is padded, a padded
    # row has no gradient and no count, and (bagged) every device takes
    # its own rows of the one mask all of them drew
    n = N - 2
    fused = _booster(n, **DATA_PARALLEL, **extra).gbdt
    assert fused._row_pad == 2 and fused._fused_eligible()
    handle = fused.train_many_dispatch(3)
    assert handle["mode"] == "fused"
    fused.finalize_block(handle)
    assert fused.train_score.shape == (n,)
    stepped = _booster(n, **DATA_PARALLEL, **extra).gbdt
    for _ in range(3):
        stepped.train_one_iter()
    shape_f, values_f = _structure(fused)
    shape_s, values_s = _structure(stepped)
    assert shape_f == shape_s
    for a, b in zip(values_f, values_s):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(fused.train_score),
                               np.asarray(stepped.train_score),
                               rtol=0, atol=1e-6)


def test_four_devices_build_the_same_trees_under_every_partition(
        as_on_the_chip):
    # every pass slot-grouped (hist_backend=pallas), so each shard's
    # live rows are moved by the stream partition inside shard_map
    # (partition_impl=auto) or by the retained oracles: one layout, so
    # the same trees to the bit
    grown = {}
    for impl in ("auto", "rank", "argsort"):
        g = _booster(**DATA_PARALLEL, hist_backend="pallas",
                     use_quantized_grad=True, partition_impl=impl).gbdt
        assert g._fused_eligible()
        handle = g.train_many_dispatch(3)
        assert handle["mode"] == "fused"
        g.finalize_block(handle)
        grown[impl] = _structure(g)
    for impl in ("auto", "rank"):
        assert grown[impl][0] == grown["argsort"][0]
        for a, b in zip(grown[impl][1], grown["argsort"][1]):
            np.testing.assert_array_equal(a, b)


def _with(**attrs):
    def change(g):
        for name, value in attrs.items():
            setattr(g, name, value)
    return change


def _ranking(g):
    g.objective.table_state = ("slot_doc",)


@pytest.mark.parametrize("refuse", [
    _with(_nproc=2), _with(_efb=object()), _with(_mono_nonbasic=True),
    _with(num_tree_per_iteration=2), _ranking],
    ids=["two_processes", "efb", "rescan_monotone", "multiclass",
         "ranking_objective"])
def test_what_the_gate_still_refuses(as_on_the_chip, refuse):
    # (d) one fact changed on an eligible booster: per-iteration
    g = _booster(**DATA_PARALLEL).gbdt
    assert g._fused_eligible()
    refuse(g)
    assert not g._sharded_fused_ok() and not g._fused_eligible()


@pytest.mark.parametrize("params", [
    {"tree_learner": "voting", "num_devices": NDEV},
    {**DATA_PARALLEL, "boosting": "goss"},
], ids=["voting", "goss"])
def test_a_refused_spec_runs_per_iteration_as_before(as_on_the_chip,
                                                     params):
    # (d) and such a booster trains one dispatch a tree: no fused
    # handle, no scan built
    g = _booster(**params).gbdt
    assert not g._fused_eligible()
    handle = g.train_many_dispatch(3)
    assert handle["mode"] == "done"
    assert len(g.trees) == 3 and g.iter_ == 3
    assert getattr(g, "_fused_run", None) is None


def test_lgb_train_takes_the_pipelined_executor(as_on_the_chip):
    # engine.train -> run_pipelined: whole blocks of fused_block_size,
    # each enqueued without waiting, as on one chip; a valid set is
    # replayed block by block from the replicated trees
    X, y = _data()
    Xv, yv = _data(400, seed=11)
    dtrain = lgb.Dataset(X, label=y, params={"max_bin": 31})
    evals = {}
    bst = lgb.train({**PARAMS, **DATA_PARALLEL, "fused_block_size": 5,
                     "metric": "auc"}, dtrain, num_boost_round=15,
                    valid_sets=[lgb.Dataset(Xv, label=yv,
                                            reference=dtrain)],
                    valid_names=["held_out"],
                    callbacks=[lgb.record_evaluation(evals)])
    stats = bst.gbdt._pipeline_stats
    assert stats.blocks == 3 and set(stats.block_sizes) == {5}
    assert bst.current_iteration() == 15
    auc = evals["held_out"]["auc"]
    assert len(auc) == 15 and auc[-1] > 0.85
    pred = bst.predict(Xv)
    assert np.mean((pred > 0.5) == (yv > 0.5)) > 0.75
