"""Fault handling around the fused dispatch, and the bench's record.

- train_many catches a fault in a fused dispatch and falls back to the
  per-iteration path with identical results (gbdt.py) — the
  post-compile ladder. A failure on a program's first call, the one
  that compiles it, is not a fault to absorb and surfaces.
- bench.py runs in ONE process, refuses a platform that is not a TPU
  unless told --cpu, and exits non-zero on any exception: a run that
  degraded is not a measurement. Its record names the platform.

Reference analog: tests/distributed/_test_distributed.py runs the
reference CLI in subprocesses so a crash is an assertion, not a lost
round.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.boosting.gbdt import _FAULT_ENV
from lightgbm_tpu.reliability import counters, faults

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _data(n=600, f=5, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)
    return X, y


# retry_max_attempts=1 keeps the original contract under test: a single
# injected fault must reach the degradation ladder (per-iteration
# fallback), not be absorbed by the dispatch retry loop
PARAMS = {"objective": "binary", "num_leaves": 7, "learning_rate": 0.2,
          "max_bin": 31, "verbosity": -1, "min_data_in_leaf": 5,
          "retry_max_attempts": 1}


def _mxu_booster(X, y):
    ds = lgb.Dataset(X, label=y, params={"max_bin": 31})
    bst = lgb.Booster(params=dict(PARAMS), train_set=ds)
    bst.update()  # iteration 0 runs the normal (scatter) path
    g = bst.gbdt
    g._hist_impl = "mxu"  # force the fused-eligible path on CPU
    g._mxu_interpret = True
    g._fused_run = None
    return bst


@pytest.fixture(autouse=True)
def _clean_fault_env():
    faults.clear()
    yield
    os.environ.pop(_FAULT_ENV, None)
    faults.clear()


class TestTrainManyFallback:
    def test_fused_fault_falls_back_per_iteration(self):
        X, y = _data(seed=4)
        a = _mxu_booster(X, y)
        b = _mxu_booster(X, y)
        os.environ[_FAULT_ENV] = "1"
        a.update_batch(3)  # fused dispatch raises -> per-iteration
        # the schedule lives in the in-process registry (the env var is
        # only its seed and is never mutated): fully consumed by now
        assert faults.remaining("fused_dispatch") == (0, 0)
        assert os.environ[_FAULT_ENV] == "1"
        for _ in range(3):
            b.update()
        assert a.current_iteration() == b.current_iteration() == 4
        np.testing.assert_array_equal(
            np.asarray(a.gbdt.train_score), np.asarray(b.gbdt.train_score))
        assert a.model_to_string() == b.model_to_string()
        # one failure does not disable the fused path...
        assert not getattr(a.gbdt, "_fused_disabled", False)

    def test_two_consecutive_faults_disable_fused(self):
        X, y = _data(seed=5)
        a = _mxu_booster(X, y)
        os.environ[_FAULT_ENV] = "2"
        a.update_batch(2)
        a.update_batch(2)
        assert a.gbdt._fused_disabled
        # ...and the disabled path still trains correctly
        a.update_batch(2)
        assert a.current_iteration() == 7


class TestCompileFailureSurfaces:
    """What fails on a program's first call is a broken kernel or
    program, the same on every retry: it leaves lgb.train as it is, it
    is not backed off from, degraded or counted as a fallback."""

    def test_fused_build_failure_propagates(self, monkeypatch):
        X, y = _data(seed=6)
        a = _mxu_booster(X, y)
        counters.reset()

        def refuse():
            raise RuntimeError("Mosaic refused the kernel")

        monkeypatch.setattr(a.gbdt, "_build_fused", refuse)
        a.gbdt.config.retry_max_attempts = 3
        with pytest.raises(RuntimeError, match="Mosaic refused"):
            a.update_batch(3)
        assert getattr(a.gbdt, "_fused_failures", 0) == 0
        assert not getattr(a.gbdt, "_fused_disabled", False)
        snap = counters.snapshot()
        assert snap["fallbacks"] == 0 and snap["device_retries"] == 0

    def test_first_grow_failure_is_not_retried(self, monkeypatch):
        X, y = _data(seed=7)
        ds = lgb.Dataset(X, label=y, params={"max_bin": 31})
        bst = lgb.Booster(params=dict(PARAMS, retry_max_attempts=3),
                          train_set=ds)
        counters.reset()
        calls = []

        def refuse(*args):
            calls.append(1)
            raise RuntimeError("VMEM limit exceeded")

        monkeypatch.setattr(bst.gbdt, "_grow_impl", refuse)
        with pytest.raises(RuntimeError, match="VMEM"):
            bst.update()
        assert len(calls) == 1
        assert counters.snapshot()["device_retries"] == 0

    def test_warm_program_keeps_the_retry_ladder(self, monkeypatch):
        # once the grower has run, a failure is a transient device
        # fault again and is retried like before
        X, y = _data(seed=8)
        ds = lgb.Dataset(X, label=y, params={"max_bin": 31})
        bst = lgb.Booster(params=dict(PARAMS, retry_max_attempts=3,
                                      retry_backoff_ms=1.0),
                          train_set=ds)
        bst.update()
        counters.reset()
        real = bst.gbdt._grow_impl
        calls = []

        def flaky(*args):
            calls.append(1)
            if len(calls) == 1:
                raise RuntimeError("slice preempted")
            return real(*args)

        monkeypatch.setattr(bst.gbdt, "_grow_impl", flaky)
        bst.update()
        assert len(calls) == 2
        assert counters.snapshot()["device_retries"] == 1


def _run_bench(extra_env, timeout=900):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu", "BENCH_ROWS": "1500", "BENCH_LEAVES": "7",
        "BENCH_MAX_BIN": "31", "BENCH_TREES": "4", "BENCH_BLOCK_TREES": "2",
        # the binary headline path only, unless a test asks for more
        "BENCH_TASKS": ""})
    env.update(extra_env)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--cpu"],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.strip().startswith("{")]
    assert lines, f"no JSON line in stdout: {proc.stdout!r}"
    return json.loads(lines[-1]), proc.stderr


@pytest.mark.slow
class TestBenchRecord:
    def test_task_matrix_rows(self):
        # one per-task record (regression, smallest warm-up cost) rides
        # the same JSON line with the documented schema; tiny tree
        # counts can leave no measured block (value 0.0) — the metric
        # must still be real
        parsed, err = _run_bench({"BENCH_TASKS": "regression",
                                  "BENCH_TASK_TREES": "8"})
        # an explicit --cpu run says so in the record
        assert parsed["platform"] == "cpu" and parsed["device_count"] >= 1
        assert parsed["fallbacks"] == 0 and parsed["value"] > 0
        assert len(parsed["tasks"]) == 1, err[-2000:]
        row = parsed["tasks"][0]
        for key in ("task", "value", "unit", "metric", "metric_value",
                    "vs_single_core"):
            assert key in row, key
        assert row["task"] == "regression"
        assert row["metric"] == "rmse"
        assert row["unit"] == "trees/sec"
        assert row["metric_value"] > 0, err[-2000:]
