"""Pipelined training executor (lightgbm_tpu/pipeline/) parity suite.

The acceptance bar: `pipeline=true` must train the byte-identical model
of the serial block loop (`pipeline=false`, the parity oracle) across
the whole matrix — regression/binary/multiclass, bagging, GOSS, early
stop mid-block, checkpoint/resume interop — because the fused scan is
iteration-exact, so any block partition (and any dispatch/finalize
interleaving) trains the same trees. Model comparisons strip the
serialized `[pipeline*` / `[fused_block_size` param lines: dispatch
granularity is config, not model content (same idiom as
tests/test_fused.py).

Eval-path fidelity: `pipeline_device_eval=false` (host metrics) must be
EXACTLY identical to the serial loop, history included; the default
device-eval path computes metric values in f32 where the host path is
f64, so histories agree to ~1e-6 relative while models and
best_iteration stay exact (docs/Performance.md).

The fast half (scheduler, stats, device-eval support matrix, the CPU
per-iteration fallback through the executor) runs in tier 1; the
engine-level matrix forces the MXU interpret path on CPU and is slow.
"""

import numpy as np
import pytest

import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu import callback as cb
from lightgbm_tpu import engine as engine_mod
from lightgbm_tpu.pipeline import (AdaptiveBlockScheduler, PipelineStats,
                                   run_pipelined)
from lightgbm_tpu.pipeline.device_eval import build_device_eval
from lightgbm_tpu.reliability.checkpoint import latest_checkpoint

pytestmark = pytest.mark.pipeline

PARAMS = {"objective": "binary", "num_leaves": 7, "learning_rate": 0.2,
          "max_bin": 31, "verbosity": -1, "min_data_in_leaf": 5}


def _data(n=600, f=5, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)
    return X, y


def _noisy_valid(n=200, f=5, seed=14):
    rng = np.random.RandomState(seed)
    Xv = rng.randn(n, f).astype(np.float32)
    yv = (Xv[:, 0] + 1.5 * rng.randn(n) > 0).astype(np.float32)
    return Xv, yv


def _strip(text):
    """Model text minus the dispatch-granularity params."""
    return [ln for ln in text.splitlines()
            if not ln.startswith("[pipeline")
            and not ln.startswith("[fused_block_size")]


class _MxuBooster(lgb.Booster):
    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        if self.gbdt is not None:  # model-file/str load has no trainer
            self.gbdt._hist_impl = "mxu"   # force fused eligibility on CPU
            self.gbdt._mxu_interpret = True


@pytest.fixture
def mxu_engine(monkeypatch):
    monkeypatch.setattr(engine_mod, "Booster", _MxuBooster)
    return engine_mod


# ----------------------------------------------------------------------
# fast tier: scheduler, stats, device-eval support matrix, CPU fallback
class TestAdaptiveBlockScheduler:
    def test_base_and_remaining_caps(self):
        s = AdaptiveBlockScheduler(5, adaptive=False)
        assert s.next_block(100) == 5
        assert s.next_block(3) == 3
        assert s.next_block(1) == 1

    def test_adaptive_grows_toward_target(self):
        s = AdaptiveBlockScheduler(5, adaptive=True, target_ms=1000.0,
                                   max_block=200)
        s.observe(5, 0.05)          # 100 iterations/sec
        assert s.next_block(1000) == 100   # rate * 1.0 s
        assert s.next_block(30) == 30      # remaining still caps

    def test_max_block_and_stopping_rounds_cap(self):
        s = AdaptiveBlockScheduler(5, adaptive=True, target_ms=1000.0,
                                   max_block=40, stopping_rounds=8)
        s.observe(5, 0.01)          # 500 iterations/sec -> wants 500
        # early-stopping alignment wins over the rate target
        assert s.next_block(1000) == 8
        s2 = AdaptiveBlockScheduler(5, adaptive=True, target_ms=1000.0,
                                    max_block=40)
        s2.observe(5, 0.01)
        assert s2.next_block(1000) == 40

    def test_compile_blocks_excluded_from_rate(self):
        s = AdaptiveBlockScheduler(5, adaptive=True, target_ms=1000.0,
                                   max_block=200)
        s.observe(5, 5.0, compiled=True)   # compile wall: ignored
        assert s.next_block(1000) == 5     # no rate yet -> base
        s.observe(5, 0.05)
        assert s.next_block(1000) == 100

    def test_never_exceeds_remaining_nor_shrinks_below_one(self):
        s = AdaptiveBlockScheduler(5, adaptive=True, target_ms=1.0)
        s.observe(5, 100.0)          # glacial rate -> wants < base
        assert s.next_block(100) == 5   # base is the floor
        assert s.next_block(2) == 2


class TestPipelineStats:
    def test_overlap_frac_and_dict(self):
        # overlap_frac: the share of blocks enqueued while the block
        # before them was still running (the first never is)
        st = PipelineStats()
        st.add(5, host_ms=0.3, device_ms=100.0)
        st.add(5, host_ms=0.2, device_ms=100.0, in_flight=True)
        st.add(5, host_ms=0.2, device_ms=100.0, in_flight=True)
        st.add(5, host_ms=0.2, device_ms=100.0, in_flight=False)
        assert st.blocks == 4 and st.iterations == 20
        assert st.in_flight == 2
        assert st.overlap_frac == pytest.approx(0.5)
        d = st.as_dict()
        assert d["block_sizes"] == [5, 5, 5, 5]
        assert d["host_ms"] == [0.3, 0.2, 0.2, 0.2]
        assert d["device_ms"] == [100.0] * 4
        assert d["in_flight"] == 2
        assert d["overlap_frac"] == pytest.approx(0.5)

    def test_overlap_frac_empty_and_whole(self):
        # no block, no share; and the share is of blocks, whatever the
        # host's milliseconds were (it used to be unpack over wall,
        # clamped at 1, which the chip read as ~1 with nothing hidden)
        st = PipelineStats()
        assert st.overlap_frac == 0.0
        st.add(1, host_ms=500.0, device_ms=100.0)
        assert st.overlap_frac == 0.0
        st.add(1, host_ms=500.0, device_ms=100.0, in_flight=True)
        st.add(1, host_ms=0.0, device_ms=100.0, in_flight=True)
        assert st.overlap_frac == pytest.approx(2 / 3)


class TestDeviceEvalSupport:
    def _valid_booster(self, metric):
        X, y = _data(seed=3)
        Xv, yv = _data(n=200, seed=4)
        ds = lgb.Dataset(X, label=y, params={"max_bin": 31})
        bst = lgb.Booster(params={**PARAMS, "metric": metric},
                          train_set=ds)
        bst.add_valid(lgb.Dataset(Xv, label=yv), "v")
        bst.update()
        return bst

    def test_pointwise_metrics_supported(self):
        bst = self._valid_booster("binary_logloss,binary_error")
        assert build_device_eval(bst) is not None

    def test_rank_family_falls_back_to_host(self):
        # all-or-nothing: one sort-based metric anywhere disables the
        # device path for the whole run
        assert build_device_eval(self._valid_booster("auc")) is None
        assert build_device_eval(
            self._valid_booster("l2,auc")) is None

    def test_device_values_match_host_metrics(self):
        bst = self._valid_booster("binary_logloss,binary_error")
        bst.update()
        dev = build_device_eval(bst)
        vs = jnp.asarray(bst.gbdt.valid_scores[0])
        mx = dev.dispatch([jnp.stack([vs, vs])])
        mhost = [np.asarray(a) for a in mx]
        got = {(vn, mn): v for vn, mn, v, _ in dev.evlist_at(mhost, 1)}
        want = {(vn, mn): v for vn, mn, v, _ in bst.eval_valid()}
        assert set(got) == set(want)
        for key, v in want.items():
            assert got[key] == pytest.approx(v, rel=1e-5, abs=1e-7), key


class TestExecutorCpuFallback:
    """run_pipelined over the ineligible (scatter) path: every dispatch
    degrades to per-iteration handles, the executor must still schedule
    correctly and train the identical model."""

    def test_fallback_parity_and_stats(self):
        X, y = _data(seed=7)
        mk = lambda: lgb.Booster(
            params=dict(PARAMS),
            train_set=lgb.Dataset(X, label=y, params={"max_bin": 31}))
        a, b = mk(), mk()
        run_pipelined(a, start_iter=0, num_boost_round=5, base_block=2,
                      run_callbacks=lambda i, ev: None, has_valid=False)
        b.update_batch(5)
        assert a.current_iteration() == b.current_iteration() == 5
        assert a.model_to_string() == b.model_to_string()
        st = a.gbdt._pipeline_stats
        assert st.blocks >= 1
        assert st.iterations == 5
        assert sum(st.block_sizes) == 5

    def test_callback_cadence_is_per_iteration(self):
        X, y = _data(seed=8)
        bst = lgb.Booster(
            params=dict(PARAMS),
            train_set=lgb.Dataset(X, label=y, params={"max_bin": 31}))
        seen = []
        run_pipelined(bst, start_iter=0, num_boost_round=6, base_block=3,
                      run_callbacks=lambda i, ev: seen.append(i),
                      has_valid=False)
        assert seen == [0, 1, 2, 3, 4, 5]

    def test_observability_pipeline_family(self):
        from lightgbm_tpu.observability import registry as obs
        X, y = _data(seed=9)
        bst = lgb.Booster(
            params=dict(PARAMS),
            train_set=lgb.Dataset(X, label=y, params={"max_bin": 31}))
        obs.reset()
        obs.enable()
        try:
            run_pipelined(bst, start_iter=0, num_boost_round=4,
                          base_block=2,
                          run_callbacks=lambda i, ev: None,
                          has_valid=False)
            snap = obs.snapshot()["pipeline"]
            assert snap["blocks"] >= 1
            assert snap["iterations"] == 4
            assert 0.0 <= snap["overlap_frac"] <= 1.0
            assert "lightgbm_tpu_pipeline" in obs.prometheus_text()
        finally:
            obs.disable()
            obs.reset()

    def test_pipeline_params_defaults(self):
        X, y = _data(seed=10)
        bst = lgb.Booster(
            params=dict(PARAMS),
            train_set=lgb.Dataset(X, label=y, params={"max_bin": 31}))
        cfg = bst.config
        assert cfg.pipeline is True
        assert cfg.pipeline_device_eval is True
        assert cfg.pipeline_adaptive_blocks is True
        assert cfg.pipeline_target_block_ms > 0
        assert cfg.pipeline_max_block >= 1


# ----------------------------------------------------------------------
# the host runs a block ahead (PR 33): what the entry layer does between
# two dispatches of the fused block. Small fused blocks on the forced
# MXU interpret path: seconds each, so they run in tier 1
def _mxu_booster(params, data):
    X, y = data
    return _MxuBooster(params={**PARAMS, **params},
                       train_set=lgb.Dataset(X, label=y,
                                             params={"max_bin": 31}))


def _three_class():
    X, _ = _data(seed=23)
    return X, (X[:, 0] > 0).astype(np.float32) + (X[:, 1] > 0.5)


def _programs_run(fn):
    """How many device programs `fn` dispatched and which: the
    PjitFunction host events of a profiler capture around it."""
    import glob
    import tempfile
    import jax
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with tempfile.TemporaryDirectory() as logdir:
        jax.profiler.start_trace(logdir, profiler_options=opts)
        try:
            fn()
        finally:
            jax.profiler.stop_trace()
        path = glob.glob(logdir + "/**/*.xplane.pb", recursive=True)[0]
        host = next(p for p in ProfileData.from_file(path).planes
                    if p.name == "/host:CPU")
        return [e.name for line in host.lines for e in line.events
                if e.name.startswith("PjitFunction")]


class TestUnpackDispatchesNothing:
    @pytest.mark.parametrize("task,mkdata,kcls", [
        ({}, _data, 1),
        ({"objective": "multiclass", "num_class": 3}, _three_class, 3),
    ], ids=["binary", "multiclass"])
    def test_finalize_block_runs_at_most_one_program(self, task, mkdata,
                                                     kcls):
        import jax
        from lightgbm_tpu.observability import registry as obs
        k = 2
        bst = _mxu_booster({**task, "num_leaves": 4}, mkdata())
        bst.update_batch(k)                     # warm-up: builds
        assert bst.gbdt._fused_warm_at(k), "the fused path did not engage"
        handle = bst.update_batch_dispatch(k)
        assert handle["mode"] == "fused"
        before = len(bst.gbdt.trees)
        obs.trace.reset()

        def unpack():
            # no index (nor anything else) may be put on the device
            with jax.transfer_guard_host_to_device("disallow"):
                bst.finalize_block(handle)

        ran = _programs_run(unpack)
        assert len(ran) <= 1, ran
        assert len(bst.gbdt.trees) == before + k * kcls
        assert bst.gbdt.tree_class[before:] == list(range(kcls)) * k
        (span,) = [s for s in obs.trace.spans()
                   if s["name"] == "entry.unpack_block"]
        assert span["attrs"]["programs"] == len(ran)
        assert span["attrs"]["waited_ms"] >= 0.0
        trees = [s["attrs"]["tree"] for s in obs.trace.spans()
                 if s["name"] == "entry.unpack_tree"]
        assert trees == list(range(before, before + k * kcls))
        # the views are the block's own trees: the model they make is
        # the one the per-tree path would have written
        ref = _mxu_booster({**task, "num_leaves": 4}, mkdata())
        ref.update_batch(k)
        ref.update_batch(k)
        assert bst.model_to_string() == ref.model_to_string()


class TestHostRunsAhead:
    @staticmethod
    def _fed(monkeypatch):
        fed = []
        real = AdaptiveBlockScheduler.observe

        def observe(self, k, wall_s, compiled=False):
            fed.append((k, wall_s, compiled))
            real(self, k, wall_s, compiled)

        monkeypatch.setattr(AdaptiveBlockScheduler, "observe", observe)
        return fed

    def test_scheduler_learns_no_rate_the_device_did_not_show(
            self, mxu_engine, monkeypatch):
        # tiny blocks on the CPU: the device takes some 25 ms over a
        # block of two trees, the host a millisecond or two to enqueue
        # it. Fed the wall of a loop iteration, a scheduler whose host
        # runs ahead reads a thousand trees a second from the first
        # block after any sync and asks for a longer block: a new
        # program (at 10 ms a dispatch the device's own rate, under a
        # hundred trees a second, keeps the base block; the host's
        # would not). Where the host runs ahead the scheduler is fed
        # nothing: the device crosses block boundaries without waiting
        # for the host, so a longer block has nothing to amortise
        fed = self._fed(monkeypatch)
        bst = _train(mxu_engine,
                     {**PARAMS, "num_leaves": 4, "fused_block_size": 2,
                      "pipeline_adaptive_blocks": True,
                      "pipeline_target_block_ms": 10.0,
                      "pipeline_max_block": 200}, _data(seed=29), 16)
        st = bst.gbdt._pipeline_stats
        assert st.block_sizes == [2] * 8
        assert st.iterations == 16
        assert fed == []

    def test_scheduler_learns_where_the_loop_waits_for_every_block(
            self, mxu_engine, monkeypatch):
        # with a valid set the callbacks decide on each block's
        # metrics, so the loop waits for every block at its sync: each
        # block costs a round trip that a longer block amortises, and
        # the wall from dispatch to the end of the sync is the block's
        # time. It is fed every block, and none is in flight
        from lightgbm_tpu.observability import registry as obs
        fed = self._fed(monkeypatch)
        obs.trace.reset()
        bst = _train(mxu_engine,
                     {**PARAMS, "num_leaves": 4, "fused_block_size": 2,
                      "pipeline_adaptive_blocks": False},
                     _data(seed=29), 8, valid=_noisy_valid())
        st = bst.gbdt._pipeline_stats
        assert st.block_sizes == [2] * 4 and st.in_flight == 0
        assert [k for k, _, _ in fed] == [2] * 4
        assert fed[0][2] and not any(c for _, _, c in fed[1:])
        syncs = [s for s in obs.trace.spans()
                 if s["name"] == "entry.sync_metrics"]
        blocks = [s for s in obs.trace.spans()
                  if s["name"] == "entry.block"]
        for (_, wall_s, _), sync, blk in zip(fed, syncs, blocks):
            assert wall_s == pytest.approx(
                sync["ts"] + sync["dur"] - blk["ts"], abs=1e-6)


class TestLaggedStopPoll:
    """A model that stalls (constant labels: no split has a gain) still
    stops. The poll reads the newest leaf count that is already on the
    host and waits for no block in flight, so when the host runs ahead
    the stop is seen one block later than when it waits for every
    block; the extra block is constant trees."""

    def _stalled(self):
        X, _ = _data(seed=37)
        return _mxu_booster(
            {"objective": "regression", "num_leaves": 4,
             "boost_from_average": True},
            (X, np.full(len(X), 3.0, np.float32)))

    def test_train_many_stops_as_before(self):
        # dispatch then finalize at once: every block has been waited
        # for when the next is dispatched, so the poll sees the last
        # block's count, as it always did. Blocks of 8 cross a poll
        # boundary each: block 0 has no count before it, block 1 reads
        # block 0's
        bst = self._stalled()
        stops = [bst.update_batch(8) for _ in range(3)]
        assert stops == [False, True, True]

    def test_one_block_later_when_the_last_block_is_in_flight(
            self, monkeypatch):
        from lightgbm_tpu.boosting.gbdt import GBDT
        bst = self._stalled()
        gb = bst.gbdt
        real = GBDT._is_ready
        # the host a block ahead: whatever was enqueued last is still
        # running when the next dispatch looks
        monkeypatch.setattr(
            GBDT, "_is_ready",
            staticmethod(lambda a: a is not gb._pending_nleaves
                         and real(a)))
        handles, stops = [], []
        for _ in range(4):
            h = bst.update_batch_dispatch(8)
            handles.append(h)
            if len(handles) > 1:
                stops.append(bst.finalize_block(handles[-2]))
        stops.append(bst.finalize_block(handles[-1]))
        # block 1 cannot see block 0's count (in flight) and has none
        # older; block 2 reads block 0's: one block later than above
        assert stops == [False, False, True, True]
        assert [h["in_flight"] for h in handles] == [False, True, True,
                                                     True]
        leaves = [int(t.num_leaves) for t in gb.trees]
        assert len(leaves) == 32 and set(leaves) == {1}


# ----------------------------------------------------------------------
# slow tier: engine-level byte parity on the forced-MXU interpret path
def _train(mxu, params, data, rounds, valid=None, history=None,
           callbacks=None):
    X, y = data
    cbs = list(callbacks or [])
    if history is not None:
        cbs.append(cb.record_evaluation(history))
    return mxu.train(
        params, lgb.Dataset(X, label=y, params={"max_bin": 31}),
        num_boost_round=rounds,
        valid_sets=[lgb.Dataset(valid[0], label=valid[1])]
        if valid is not None else None,
        callbacks=cbs or None)


def _flatten(history):
    return {(vn, mn): vals for vn, d in history.items()
            for mn, vals in d.items()}


@pytest.mark.slow
class TestEnginePipelineParity:
    @pytest.mark.parametrize("task_params,mkdata", [
        (dict(PARAMS), _data),
        ({**PARAMS, "objective": "regression"}, _data),
        ({**PARAMS, "objective": "multiclass", "num_class": 3},
         lambda: (_data()[0],
                  (_data()[0][:, 0] > 0).astype(np.float32) +
                  (_data()[0][:, 1] > 0.5))),
    ], ids=["binary", "regression", "multiclass"])
    def test_model_parity_device_eval(self, mxu_engine, task_params,
                                      mkdata):
        data, valid = mkdata(), _noisy_valid()
        if task_params.get("num_class", 1) > 1:
            rng = np.random.RandomState(15)
            Xv = rng.randn(200, 5).astype(np.float32)
            valid = (Xv, (Xv[:, 0] > 0).astype(np.float32) +
                     (Xv[:, 1] > 0.5))
        models = []
        for pipeline in (True, False):
            bst = _train(mxu_engine,
                         {**task_params, "fused_block_size": 4,
                          "pipeline": pipeline}, data, 10, valid=valid)
            if pipeline:
                st = getattr(bst.gbdt, "_pipeline_stats", None)
                assert st is not None and st.blocks >= 1, \
                    "pipeline did not engage — test is vacuous"
                assert st.iterations == 10
            models.append(bst.model_to_string())
        assert _strip(models[0]) == _strip(models[1])

    @pytest.mark.parametrize("extra", [
        {"bagging_fraction": 0.7, "bagging_freq": 2},
        {"boosting": "goss", "top_rate": 0.3, "other_rate": 0.3},
    ], ids=["bagging", "goss"])
    def test_model_parity_sampling(self, mxu_engine, extra):
        data, valid = _data(seed=6), _noisy_valid(seed=16)
        models = []
        for pipeline in (True, False):
            bst = _train(mxu_engine,
                         {**PARAMS, **extra, "fused_block_size": 4,
                          "pipeline": pipeline}, data, 10, valid=valid)
            models.append(bst.model_to_string())
        assert _strip(models[0]) == _strip(models[1])

    def test_host_eval_mode_exactly_matches_serial(self, mxu_engine):
        # pipeline_device_eval=false routes metrics through the same
        # f64 host path as the oracle: byte parity AND exact history
        data, valid = _data(seed=5), _noisy_valid(seed=17)
        out = []
        for pipeline in (True, False):
            hist = {}
            bst = _train(mxu_engine,
                         {**PARAMS, "fused_block_size": 4,
                          "pipeline": pipeline,
                          "pipeline_device_eval": False},
                         data, 10, valid=valid, history=hist)
            out.append((bst.model_to_string(), hist))
        assert _strip(out[0][0]) == _strip(out[1][0])
        assert out[0][1] == out[1][1]   # float-exact history

    def test_device_eval_history_close_to_host(self, mxu_engine):
        data, valid = _data(seed=5), _noisy_valid(seed=17)
        hists = []
        for device_eval in (True, False):
            hist = {}
            _train(mxu_engine,
                   {**PARAMS, "fused_block_size": 4,
                    "pipeline_device_eval": device_eval},
                   data, 10, valid=valid, history=hist)
            hists.append(_flatten(hist))
        dev, host = hists
        assert set(dev) == set(host)
        for key in host:
            np.testing.assert_allclose(dev[key], host[key], rtol=1e-5,
                                       err_msg=str(key))

    @pytest.mark.parametrize("device_eval", [True, False],
                             ids=["device-eval", "host-eval"])
    def test_early_stop_mid_block_parity(self, mxu_engine, device_eval):
        data, valid = _data(seed=13), _noisy_valid(seed=14)
        results = []
        for pipeline in (True, False):
            bst = _train(mxu_engine,
                         {**PARAMS, "early_stopping_round": 2,
                          "fused_block_size": 5, "pipeline": pipeline,
                          "pipeline_device_eval": device_eval},
                         data, 25, valid=valid)
            results.append(bst)
        a, b = results
        assert a.best_iteration == b.best_iteration
        assert a.current_iteration() == b.current_iteration()
        assert _strip(a.model_to_string()) == _strip(b.model_to_string())
        if device_eval:
            for key in dict(b.best_score):
                assert dict(a.best_score)[key] == pytest.approx(
                    dict(b.best_score)[key], rel=1e-5)
        else:
            assert dict(a.best_score) == dict(b.best_score)
        # the stop must engage before the round budget, mid-block,
        # or this proves nothing about the rollback protocol
        assert a.current_iteration() < 25

    def test_checkpoint_resume_into_pipeline(self, mxu_engine, tmp_path):
        # checkpoint callbacks are not block-safe, so run A trains
        # non-pipelined; resuming WITHOUT the callback re-engages the
        # pipeline for the tail and must land on the byte-identical
        # model of a straight pipelined run
        data = _data(seed=19)
        params = {**PARAMS, "fused_block_size": 4, "seed": 3}
        ref = _train(mxu_engine, params, data, 12)
        st = getattr(ref.gbdt, "_pipeline_stats", None)
        assert st is not None and st.blocks >= 1
        d = str(tmp_path)
        # run A stops at 6 so the resume has a pipelined tail to train
        ck = _train(mxu_engine, params, data, 6,
                    callbacks=[cb.checkpoint(6, d)])
        # the checkpoint callback forced the serial loop on run A
        assert getattr(ck.gbdt, "_pipeline_stats", None) is None
        assert ck.current_iteration() == 6
        found = latest_checkpoint(d)
        assert found is not None
        X, y = data
        resumed = mxu_engine.train(
            dict(params),
            lgb.Dataset(X, label=y, params={"max_bin": 31}),
            num_boost_round=12, resume_from=found)
        st = getattr(resumed.gbdt, "_pipeline_stats", None)
        assert st is not None and st.blocks >= 1
        assert resumed.current_iteration() == 12
        assert _strip(resumed.model_to_string()) == \
            _strip(ref.model_to_string())
