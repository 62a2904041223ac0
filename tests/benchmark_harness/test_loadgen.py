"""The serving load generator, against a server that is a stub: what is
timed from where, what counts as failed, and that one thread paces."""

import os
import sys
import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import harness  # noqa: E402
from benchmark.runners import serve  # noqa: E402


class _Stub:
    """predict_async answers at once from a timer thread, except that
    call number `stall_at` holds its CALLER for `stall_s` first (a
    stall on the request path, as a slow binning would be) and calls
    in `shed` are refused."""

    def __init__(self, stall_at=None, stall_s=0.0, shed=()):
        self.calls = 0
        self.stall_at, self.stall_s, self.shed = stall_at, stall_s, set(shed)
        self.threads = set()

    def predict_async(self, name, X):
        from lightgbm_tpu.serving import OverloadError
        k, self.calls = self.calls, self.calls + 1
        self.threads.add(threading.current_thread().name)
        if k == self.stall_at:
            time.sleep(self.stall_s)
        if k in self.shed:
            raise OverloadError("full")
        fut = Future()
        fut.set_result(np.zeros(len(X)))
        return fut

    def predict(self, name, X, timeout=None):
        self.threads.add(threading.current_thread().name)
        time.sleep(0.002)
        return np.zeros(len(X))


_OPEN = [{"name": "o", "loop": "open", "rate_per_s": 200.0,
          "sizes": {"dist": "fixed", "rows": 3}}]


def test_latency_is_timed_from_when_a_request_was_due():
    stub = _Stub(stall_at=20, stall_s=0.15)
    X = np.zeros((100, 4), np.float32)
    result = serve.drive(stub, X, _OPEN, seed=3, seconds=1.0,
                         tracer=harness.TracedWindow(False))
    got = serve.summarize(result, _OPEN)
    log = result["log"]
    n = result["n_open"]
    assert got["attempted"] == n == got["ok"] and 150 < n < 260
    late = (log.sent - log.due)[:n]
    lat = (log.done - log.due)[:n]
    # before the stall the generator is on time and answers are instant
    assert np.max(late[:20]) < 0.02 and np.max(lat[:20]) < 0.03
    # the stalled request and those due during the stall were sent late,
    # and each is charged the wait although its own service was instant
    held = (log.due[:n] > log.due[20]) & (log.due[:n] < log.due[20] + 0.1)
    assert held.sum() >= 10
    assert np.all(lat[held] >= late[held]) and np.all(late[held] > 0.04)
    assert lat[20] >= 0.15
    assert np.all((log.done - log.sent)[:n][held] < 0.02)   # from SEND: hidden
    # and the run says how late its generator ran
    assert harness.percentile(got["late_ms"], 99) > 100
    assert got["latency_ms"].max() >= 150
    # one thread paced every request
    assert stub.threads == {threading.current_thread().name}


def test_shed_requests_are_attempted_and_failed_and_have_no_latency():
    stub = _Stub(shed={5, 6, 7})
    X = np.zeros((100, 4), np.float32)
    got = serve.summarize(
        serve.drive(stub, X, _OPEN, seed=4, seconds=0.5,
                    tracer=harness.TracedWindow(False)), _OPEN)
    assert got["shed"] == 3 and got["ok"] == got["attempted"] - 3
    assert len(got["latency_ms"]) == got["ok"]
    # an answer that lands after the window closed is not in it
    assert 3 * (got["ok"] - 2) <= got["rows_answered_in_window"] \
        <= 3 * got["ok"]


def test_a_closed_loop_sends_the_next_request_when_the_last_is_answered():
    streams = [{"name": "c", "loop": "closed", "clients": 3,
                "sizes": {"dist": "log_uniform", "min": 8, "max": 64}}]
    stub = _Stub()
    X = np.zeros((500, 4), np.float32)
    result = serve.drive(stub, X, streams, seed=5, seconds=0.4,
                         tracer=harness.TracedWindow(False))
    got = serve.summarize(result, streams)
    log, used = result["log"], result["used"]
    assert len(stub.threads) == 3 and result["hung_clients"] == 0
    # 2 ms a request, three clients, 0.4 s: some hundreds, none overlapping
    assert 150 < got["attempted"] <= 600 and got["ok"] == got["attempted"]
    assert len(got["latency_ms"]) == 0          # a closed loop has no due time
    per_client = 16384
    for c in range(3):
        mine = np.flatnonzero(used[c * per_client:(c + 1) * per_client])
        sent = log.sent[c * per_client + mine]
        done = log.done[c * per_client + mine]
        assert np.all(sent[1:] >= done[:-1])
    assert got["rows_answered_in_window"] <= log.rows[used].sum()


def test_a_rate_of_zero_is_refused():
    with pytest.raises(ValueError, match="knee"):
        serve.drive(_Stub(), np.zeros((10, 4)), [dict(_OPEN[0], rate_per_s=0)],
                    seed=1, seconds=0.1, tracer=harness.TracedWindow(False))
