"""Fused multi-tree training (boosting/fused.py, Booster.update_batch).

update_batch(k) must be semantically identical to k update() calls:
- ineligible configs (CPU scatter path here) fall back to a plain loop;
- the fused scan itself must give bit-identical results for one scan of
  k trees vs k scans of 1 tree (scan mechanics, stacking, iteration
  indexing, score carry).
"""

import numpy as np
import pytest

import jax.numpy as jnp

import lightgbm_tpu as lgb


def _data(n=600, f=5, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)
    return X, y


PARAMS = {"objective": "binary", "num_leaves": 7, "learning_rate": 0.2,
          "max_bin": 31, "verbosity": -1, "min_data_in_leaf": 5}


def _booster(X, y):
    ds = lgb.Dataset(X, label=y, params={"max_bin": 31})
    return lgb.Booster(params=dict(PARAMS), train_set=ds)


class TestFallbackLoop:
    def test_update_batch_equals_update_loop(self):
        X, y = _data()
        a = _booster(X, y)
        b = _booster(X, y)
        for _ in range(5):
            a.update()
        b.update_batch(5)
        assert a.current_iteration() == b.current_iteration() == 5
        np.testing.assert_array_equal(
            np.asarray(a.gbdt.train_score), np.asarray(b.gbdt.train_score))
        assert a.model_to_string() == b.model_to_string()


@pytest.mark.slow
class TestFusedScan:
    def _mxu_booster(self, X, y):
        bst = _booster(X, y)
        bst.update()  # iteration 0 runs the normal (scatter) path
        g = bst.gbdt
        g._hist_impl = "mxu"  # force the fused-eligible path on CPU
        g._mxu_interpret = True  # Pallas interpret mode (no TPU here)
        g._fused_run = None
        return bst

    def test_fused_equals_per_iteration_mxu(self):
        # the core contract: the fused scan must grow the SAME trees as
        # k train_one_iter calls through the per-iteration MXU path
        X, y = _data(seed=4)
        a = self._mxu_booster(X, y)
        b = self._mxu_booster(X, y)
        a.update_batch(3)
        for _ in range(3):
            b.update()
        assert a.current_iteration() == b.current_iteration() == 4
        np.testing.assert_array_equal(
            np.asarray(a.gbdt.train_score), np.asarray(b.gbdt.train_score))
        assert a.model_to_string() == b.model_to_string()

    @pytest.mark.parametrize("extra_params", [
        {},                                          # binary, biased
        {"objective": "multiclass", "num_class": 3},
        {"boosting": "goss", "top_rate": 0.3, "other_rate": 0.3},
    ], ids=["binary", "multiclass", "goss"])
    def test_block_from_iteration_zero_equals_per_iteration(
            self, extra_params):
        # a block that starts at iteration 0 runs whole in the fused
        # program (one compiled growth program per run, not two): the
        # boost_from_average constant must land on the scores, on the
        # valid scores and in tree 0's leaves exactly as train_one_iter
        # puts it there
        rng = np.random.RandomState(8)
        X = rng.randn(600, 5).astype(np.float32)
        y = (X[:, 0] + 0.3 * rng.randn(600) > 0.4).astype(np.float32)
        if "num_class" in extra_params:
            y = y + (X[:, 1] > 0.5).astype(np.float32)
        boosters = []
        for _ in range(2):
            ds = lgb.Dataset(X, label=y, params={"max_bin": 31})
            bst = lgb.Booster(params={**PARAMS, **extra_params},
                              train_set=ds)
            bst.add_valid(lgb.Dataset(X[:200], label=y[:200],
                                      reference=ds), "held_out")
            g = bst.gbdt
            g._hist_impl = "mxu"
            g._mxu_interpret = True
            boosters.append(bst)
        a, b = boosters
        assert a.gbdt._fused_eligible()
        a.update_batch(3)
        for _ in range(3):
            b.update()
        assert a.current_iteration() == b.current_iteration() == 3
        np.testing.assert_array_equal(
            np.asarray(a.gbdt.train_score), np.asarray(b.gbdt.train_score))
        np.testing.assert_array_equal(
            np.asarray(a.gbdt.valid_scores[0]),
            np.asarray(b.gbdt.valid_scores[0]))
        assert a.model_to_string() == b.model_to_string()
        # the block left one trajectory point per iteration
        assert a.gbdt._fused_valid_traj[0].shape[0] == 3

    @pytest.mark.parametrize("extra_params", [
        {"bagging_fraction": 0.7, "bagging_freq": 2},          # bagging
        {"boosting": "goss", "top_rate": 0.3, "other_rate": 0.3},  # GOSS
    ])
    def test_fused_sampling_equals_per_iteration(self, extra_params):
        # round-4 eligibility ring: bagging recomputed statelessly
        # in-scan; GOSS rides pre-drawn keys (gbdt._fused_sample_fn)
        X, y = _data(seed=6)
        boosters = []
        for _ in range(2):
            ds = lgb.Dataset(X, label=y, params={"max_bin": 31})
            bst = lgb.Booster(params={**PARAMS, **extra_params},
                              train_set=ds)
            bst.update()
            g = bst.gbdt
            g._hist_impl = "mxu"
            g._mxu_interpret = True
            g._fused_run = None
            boosters.append(bst)
        a, b = boosters
        assert a.gbdt._fused_eligible()
        a.update_batch(3)
        for _ in range(3):
            b.update()
        assert a.current_iteration() == b.current_iteration() == 4
        np.testing.assert_array_equal(
            np.asarray(a.gbdt.train_score), np.asarray(b.gbdt.train_score))
        assert a.model_to_string() == b.model_to_string()

    def test_fused_multiclass_equals_per_iteration(self):
        rng = np.random.RandomState(8)
        X = rng.randn(600, 5).astype(np.float32)
        y = (X[:, 0] + 0.3 * rng.randn(600) > 0).astype(np.float32) + \
            (X[:, 1] > 0.5).astype(np.float32)
        boosters = []
        for _ in range(2):
            ds = lgb.Dataset(X, label=y, params={"max_bin": 31})
            bst = lgb.Booster(
                params={**PARAMS, "objective": "multiclass",
                        "num_class": 3}, train_set=ds)
            bst.update()
            g = bst.gbdt
            g._hist_impl = "mxu"
            g._mxu_interpret = True
            g._fused_run = None
            boosters.append(bst)
        a, b = boosters
        assert a.gbdt._fused_eligible()
        a.update_batch(3)
        for _ in range(3):
            b.update()
        assert a.current_iteration() == b.current_iteration() == 4
        assert len(a.gbdt.trees) == len(b.gbdt.trees) == 12
        assert a.gbdt.tree_class == b.gbdt.tree_class
        np.testing.assert_array_equal(
            np.asarray(a.gbdt.train_score), np.asarray(b.gbdt.train_score))
        assert a.model_to_string() == b.model_to_string()

    def test_scan_of_k_equals_k_scans(self):
        X, y = _data(seed=3)
        a = self._mxu_booster(X, y)
        b = self._mxu_booster(X, y)
        a.update_batch(3)
        for _ in range(3):
            b.update_batch(1)
        assert a.current_iteration() == b.current_iteration() == 4
        np.testing.assert_array_equal(
            np.asarray(a.gbdt.train_score), np.asarray(b.gbdt.train_score))
        for ta, tb in zip(a.gbdt.trees[1:], b.gbdt.trees[1:]):
            for fld in ("split_feature", "threshold_bin", "left", "right"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(ta, fld)),
                    np.asarray(getattr(tb, fld)), err_msg=fld)
            np.testing.assert_array_equal(np.asarray(ta.leaf_value),
                                          np.asarray(tb.leaf_value))
        assert a.model_to_string() == b.model_to_string()


@pytest.mark.slow
class TestFusedValidSets:
    """Round-5 eligibility widening: valid sets ride the fused scan —
    the stacked block is replayed over each valid set after the
    dispatch (fused.stacked_score_traj), so valid scores and the
    per-iteration trajectory match k train_one_iter calls exactly, and
    engine.train's block dispatch early-stops identically to the
    per-iteration loop (reference eval cadence, gbdt.cpp:469-572)."""

    def _mxu_booster(self, X, y, Xv, yv, extra=None):
        ds = lgb.Dataset(X, label=y, params={"max_bin": 31})
        bst = lgb.Booster(params={**PARAMS, **(extra or {})},
                          train_set=ds)
        bst.add_valid(lgb.Dataset(Xv, label=yv), "v")
        bst.update()
        g = bst.gbdt
        g._hist_impl = "mxu"
        g._mxu_interpret = True
        g._fused_run = None
        return bst

    def test_valid_scores_and_trajectory_match_per_iteration(self):
        X, y = _data(seed=11)
        Xv, yv = _data(n=200, seed=12)
        a = self._mxu_booster(X, y, Xv, yv)
        b = self._mxu_booster(X, y, Xv, yv)
        assert a.gbdt._fused_eligible()
        a.update_batch(3)
        traj = a.gbdt._fused_valid_traj
        assert traj is not None and len(traj) == 1
        assert traj[0].shape[0] == 3
        per_iter = []
        for _ in range(3):
            b.update()
            per_iter.append(np.asarray(b.gbdt.valid_scores[0]).copy())
        assert a.current_iteration() == b.current_iteration() == 4
        assert a.model_to_string() == b.model_to_string()
        # final valid scores agree, and every trajectory point equals
        # the per-iteration valid score at that iteration
        np.testing.assert_array_equal(
            np.asarray(a.gbdt.valid_scores[0]), per_iter[-1])
        for j in range(3):
            np.testing.assert_array_equal(
                np.asarray(traj[0][j]), per_iter[j], err_msg=f"iter {j}")

    def test_engine_block_early_stopping_matches_per_iteration(
            self, monkeypatch):
        from lightgbm_tpu import engine as engine_mod

        class _MxuBooster(lgb.Booster):
            def __init__(self, *args, **kw):
                super().__init__(*args, **kw)
                self.gbdt._hist_impl = "mxu"
                self.gbdt._mxu_interpret = True

        monkeypatch.setattr(engine_mod, "Booster", _MxuBooster)
        X, y = _data(seed=13)
        rng = np.random.RandomState(14)
        Xv = rng.randn(200, 5).astype(np.float32)
        yv = (Xv[:, 0] + 1.5 * rng.randn(200) > 0).astype(np.float32)
        results = []
        for block in (1, 5):
            bst = engine_mod.train(
                {**PARAMS, "early_stopping_round": 2,
                 "fused_block_size": block},
                lgb.Dataset(X, label=y, params={"max_bin": 31}),
                num_boost_round=25,
                valid_sets=[lgb.Dataset(Xv, label=yv)])
            results.append(bst)
        a, b = results
        assert a.best_iteration == b.best_iteration
        assert a.current_iteration() == b.current_iteration()
        assert dict(a.best_score) == dict(b.best_score)
        # identical models modulo the serialized fused_block_size param
        # itself (dispatch granularity is config, not model content)
        strip = lambda s: [ln for ln in s.splitlines()
                           if not ln.startswith("[fused_block_size")]
        assert strip(a.model_to_string()) == strip(b.model_to_string())
        # the stop must have engaged before the full round budget,
        # otherwise this test proves nothing about rollback
        assert a.current_iteration() < 25


@pytest.mark.slow
class TestEngineBlockGating:
    def test_custom_callback_forces_per_iteration_cadence(
            self, monkeypatch):
        # a user callback that reads model state is NOT block_safe: the
        # engine must fall back to per-iteration dispatch so the
        # callback never observes future trees (round-5 review finding)
        from lightgbm_tpu import engine as engine_mod

        class _MxuBooster(lgb.Booster):
            def __init__(self, *args, **kw):
                super().__init__(*args, **kw)
                self.gbdt._hist_impl = "mxu"
                self.gbdt._mxu_interpret = True

        monkeypatch.setattr(engine_mod, "Booster", _MxuBooster)
        X, y = _data(seed=21)
        Xv, yv = _data(n=150, seed=22)
        seen = []

        def snoop(env):
            seen.append(env.model.current_iteration())

        bst = engine_mod.train(
            {**PARAMS, "fused_block_size": 4},
            lgb.Dataset(X, label=y, params={"max_bin": 31}),
            num_boost_round=6,
            valid_sets=[lgb.Dataset(Xv, label=yv)],
            callbacks=[snoop])
        # per-iteration cadence: the callback saw every iteration count
        # as it happened, never a block-end state at an inner iteration
        assert seen == [1, 2, 3, 4, 5, 6]
        assert bst.current_iteration() == 6


@pytest.mark.slow
class TestFusedValidMulticlass:
    def test_multiclass_valid_trajectory_matches_per_iteration(self):
        # the stacked_score_traj num_class>1 branch: per-class column
        # updates must reproduce k per-iteration valid updates exactly
        rng = np.random.RandomState(31)
        X = rng.randn(500, 5).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.float32) + (X[:, 1] > 0.5)
        Xv = rng.randn(150, 5).astype(np.float32)
        yv = (Xv[:, 0] > 0).astype(np.float32) + (Xv[:, 1] > 0.5)
        boosters = []
        for _ in range(2):
            ds = lgb.Dataset(X, label=y, params={"max_bin": 31})
            bst = lgb.Booster(params={**PARAMS, "objective": "multiclass",
                                      "num_class": 3}, train_set=ds)
            bst.add_valid(lgb.Dataset(Xv, label=yv), "v")
            bst.update()
            g = bst.gbdt
            g._hist_impl = "mxu"
            g._mxu_interpret = True
            g._fused_run = None
            boosters.append(bst)
        a, b = boosters
        assert a.gbdt._fused_eligible()
        a.update_batch(2)
        # pin that the FUSED dispatch actually ran — a silent
        # per-iteration fallback would make this test pass vacuously
        assert getattr(a.gbdt, "_fused_failures", 0) == 0
        assert not getattr(a.gbdt, "_fused_disabled", False)
        traj = a.gbdt._fused_valid_traj
        assert traj is not None and traj[0].shape[0] == 2
        per_iter = []
        for _ in range(2):
            b.update()
            per_iter.append(np.asarray(b.gbdt.valid_scores[0]).copy())
        np.testing.assert_array_equal(
            np.asarray(a.gbdt.valid_scores[0]), per_iter[-1])
        for j in range(2):
            np.testing.assert_array_equal(
                np.asarray(traj[0][j]), per_iter[j], err_msg=f"iter {j}")
