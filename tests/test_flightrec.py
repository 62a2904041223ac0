"""Crash flight recorder + cross-rank trace merge + span profiler —
tier-1, subprocess-free.

Every flush trigger is exercised with the real code path and a stubbed
exit: watchdog abort (fake clocks, stubbed abort_fn), injected
rank_death (patched ``os._exit``), a non-finite guard trip, and an
unhandled exception escaping `engine.train`. The true 2-rank kill run
is the chaos harness's job (tests/test_chaos.py, `make postmortem`).
"""

import importlib
import json
import os
import threading

import numpy as np
import pytest

import lightgbm_tpu as lgb

faults_mod = importlib.import_module("lightgbm_tpu.reliability.faults")
profile_mod = importlib.import_module(
    "lightgbm_tpu.observability.profile")
from lightgbm_tpu.observability import merge as merge_mod
from lightgbm_tpu.observability.flightrec import (FlightRecorder,
                                                  POSTMORTEM_PREFIX,
                                                  recorder)
from lightgbm_tpu.observability.profile import profiler
from lightgbm_tpu.observability.registry import registry
from lightgbm_tpu.parallel.comm import guarded_allgather
from lightgbm_tpu.reliability import guards
from lightgbm_tpu.reliability.faults import (RANK_DEATH_EXIT_CODE,
                                             faults)
from lightgbm_tpu.reliability.watchdog import (CollectiveGuard,
                                               shutdown_watchdog)

from conftest import make_regression


@pytest.fixture(autouse=True)
def _clean_state():
    faults.clear()
    recorder.reset()
    recorder.configure(enabled=True, out_dir="")
    profiler.reset()
    yield
    faults.clear()
    recorder.reset()
    recorder.configure(enabled=True, out_dir="")
    profiler.reset()
    shutdown_watchdog()


def _bundle(dirpath, rank=0):
    path = os.path.join(str(dirpath), f"{POSTMORTEM_PREFIX}{rank}.json")
    assert os.path.exists(path), f"no postmortem bundle at {path}"
    with open(path) as fh:
        return json.load(fh)


# ---------------------------------------------------------------------------
# ring semantics

def test_ring_bounded_and_drop_counted():
    rec = FlightRecorder(capacity=16)
    for i in range(40):
        rec.record("span", f"s{i}")
    snap = rec.snapshot()
    assert snap["events"] == 16
    assert snap["dropped"] == 24
    # the ring keeps the NEWEST events
    assert [e["name"] for e in rec.events()][-1] == "s39"
    assert [e["name"] for e in rec.events()][0] == "s24"


def test_disabled_recorder_is_inert(tmp_path):
    rec = FlightRecorder()
    rec.configure(enabled=False)
    rec.record("span", "x")
    assert rec.snapshot()["events"] == 0
    assert rec.flush("watchdog_abort", out_dir=str(tmp_path)) is None
    assert os.listdir(tmp_path) == []


def test_flush_reason_policy(tmp_path, monkeypatch):
    rec = FlightRecorder()
    rec.record("span", "x")
    # non-fatal reason with no destination: no bundle anywhere
    monkeypatch.chdir(tmp_path)
    assert rec.flush("exception") is None
    assert os.listdir(tmp_path) == []
    # fatal reason with no destination: falls back to the cwd
    path = rec.flush("rank_death")
    assert path is not None and os.path.dirname(path) == str(tmp_path)
    bundle = _bundle(tmp_path)
    assert bundle["reason"] == "rank_death"
    assert bundle["events"][0]["name"] == "x"


def test_flush_is_atomic_and_carries_context(tmp_path):
    rec = FlightRecorder()
    rec.record("collective", "gather", phase="enter", deadline_s=5.0)
    path = rec.flush("watchdog_abort", out_dir=str(tmp_path),
                     extra={"diag": "rank 1 gone"})
    assert path is not None
    assert [n for n in os.listdir(tmp_path)
            if n.startswith(POSTMORTEM_PREFIX)] == ["postmortem_0.json"]
    assert not [n for n in os.listdir(tmp_path) if ".tmp" in n]
    bundle = _bundle(tmp_path)
    assert bundle["diag"] == "rank 1 gone"
    assert bundle["rank"] == 0 and bundle["pid"] == os.getpid()
    # best-effort registry context rides along
    assert "collective" in bundle and "clock_skew" in bundle
    assert rec.snapshot()["flushes"] == 1


# ---------------------------------------------------------------------------
# flush triggers, wired for real

def test_watchdog_abort_flushes_bundle(tmp_path):
    recorder.configure(out_dir=str(tmp_path / "bundles"))
    fired = threading.Event()
    g = CollectiveGuard(0.08, rank=0, world=2,
                        heartbeat_dir=str(tmp_path / "hb"),
                        heartbeat_interval_s=0.02,
                        first_deadline_factor=1.0,
                        abort_fn=lambda diag: fired.set())
    g.start()
    try:
        g.enter("gather")
        assert fired.wait(timeout=10.0), "watchdog monitor never fired"
    finally:
        g.exit_()
        g.stop()
    bundle = _bundle(tmp_path / "bundles")
    assert bundle["reason"] == "watchdog_abort"
    kinds = [(e["kind"], e["name"]) for e in bundle["events"]]
    assert ("collective", "gather") in kinds     # the hung bracket
    assert kinds[-1] == ("abort", "watchdog")    # the last word
    abort_ev = bundle["events"][-1]
    assert "gather" in abort_ev["diag"]


def test_watchdog_abort_stub_without_dir_leaves_no_bundle(
        tmp_path, monkeypatch):
    # existing tier-1 watchdog tests stub the abort with no bundle dir
    # configured — they must not litter the cwd with postmortems
    monkeypatch.chdir(tmp_path)
    fired = threading.Event()
    g = CollectiveGuard(0.05, rank=0, world=2,
                        first_deadline_factor=1.0,
                        abort_fn=lambda diag: fired.set())
    g.start()
    try:
        g.enter("gather")
        assert fired.wait(timeout=10.0)
    finally:
        g.exit_()
        g.stop()
    assert not [n for n in os.listdir(tmp_path)
                if n.startswith(POSTMORTEM_PREFIX)]


def test_rank_death_flushes_bundle(tmp_path, monkeypatch):
    recorder.configure(out_dir=str(tmp_path))
    exits = []
    monkeypatch.setattr(faults_mod.os, "_exit", exits.append)
    faults.schedule("collective_psum", fail=1, mode="rank_death")
    faults.inject("collective_psum")
    assert exits == [RANK_DEATH_EXIT_CODE]
    bundle = _bundle(tmp_path)
    assert bundle["reason"] == "rank_death"
    # the fault hit itself is the last recorded event: the bundle names
    # the site the rank died in
    assert bundle["events"][-1]["kind"] == "fault"
    assert bundle["events"][-1]["name"] == "collective_psum"
    assert bundle["events"][-1]["mode"] == "rank_death"


def test_guard_trip_flushes_bundle(tmp_path):
    recorder.configure(out_dir=str(tmp_path))
    guards.trip("gradients", "warn", iteration=7)
    bundle = _bundle(tmp_path)
    assert bundle["reason"] == "guard_nonfinite"
    assert bundle["events"][-1] == {
        **bundle["events"][-1], "kind": "guard", "name": "gradients",
        "policy": "warn", "iteration": 7}


def test_engine_unhandled_exception_flushes_bundle(tmp_path):
    X, y = make_regression(n=200, f=4)
    dtrain = lgb.Dataset(X, label=y)

    def _boom(env):
        raise RuntimeError("callback exploded")

    with pytest.raises(RuntimeError, match="callback exploded"):
        lgb.train({"objective": "regression", "num_leaves": 7,
                   "verbosity": -1, "flightrec_dir": str(tmp_path)},
                  dtrain, 5, callbacks=[_boom])
    bundle = _bundle(tmp_path)
    assert bundle["reason"] == "exception"
    last = bundle["events"][-1]
    assert last["kind"] == "exception" and last["name"] == "engine.train"
    assert last["exc_type"] == "RuntimeError"
    assert "callback exploded" in last["exc"]


def test_cli_failure_before_booster_flushes_bundle(tmp_path):
    # the CLI arms the recorder from the parsed config BEFORE any
    # Booster exists: a bad data path must still honor flightrec_dir=
    import subprocess
    import sys
    proc = subprocess.run(
        [sys.executable, "-m", "lightgbm_tpu.cli", "task=train",
         "data=DOES_NOT_EXIST.csv", "objective=binary",
         f"flightrec_dir={tmp_path}"],
        capture_output=True, text=True, cwd=str(tmp_path),
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": os.path.dirname(os.path.dirname(
                 os.path.abspath(__file__)))},
        timeout=120)
    assert proc.returncode != 0
    assert "DOES_NOT_EXIST.csv" in proc.stderr, proc.stderr
    bundle = _bundle(tmp_path)
    assert bundle["reason"] == "exception"
    assert bundle["events"][-1]["exc_type"] == "FileNotFoundError"


def test_collective_brackets_and_clock_ride_the_ring():
    before = registry.clock_skew_snapshot()["samples"]
    out = guarded_allgather(np.arange(4, dtype=np.float64), "gather")
    # one process stacks a leading rank axis of 1, as N processes stack N
    np.testing.assert_array_equal(
        out, np.arange(4, dtype=np.float64)[None])
    assert registry.clock_skew_snapshot()["samples"] == before + 1
    # single process: no guard bracket (collective_guard no-ops — the
    # bracket records are pinned by the watchdog tests above), but the
    # clock sample piggybacked on the allgather still rides the ring
    kinds = [(e["kind"], e["name"]) for e in recorder.events()]
    assert ("clock", "gather") in kinds
    # single process: one wall stamp, zero skew
    sample = registry.clock_samples()[-1]
    assert sample["site"] == "gather" and len(sample["walls"]) == 1


def test_flightrec_family_in_snapshot_and_prometheus():
    recorder.record("span", "x")
    snap = registry.snapshot()
    assert snap["flightrec"]["events"] >= 1
    assert set(snap["clock_skew"]) == {"samples", "last_skew_s",
                                       "max_skew_s"}
    text = registry.prometheus_text()
    assert "lightgbm_tpu_flightrec_events" in text
    assert "lightgbm_tpu_clock_skew_samples" in text


# ---------------------------------------------------------------------------
# cross-rank merge: synthetic 2-rank traces with a known 5s clock skew

def _rank_trace(rank, epoch_wall, events, clock_samples):
    return {"traceEvents": events, "displayTimeUnit": "ms",
            merge_mod.META_KEY: {"rank": rank, "epoch_wall": epoch_wall,
                                 "clock_samples": clock_samples}}


def test_merge_round_trip_recovers_injected_offset(tmp_path):
    # rank 1's wall clock runs exactly 5.0s ahead of rank 0's; three
    # collective samples carry arrival skews of +0.2s, 0.0s and -0.1s
    samples = [
        {"site": "collective_psum", "walls": [1010.0, 1015.2]},
        {"site": "collective_psum", "walls": [1020.0, 1025.0]},
        {"site": "collective_psum", "walls": [1030.1, 1035.0]},
    ]
    ev0 = [{"name": "train", "ph": "X", "ts": 0.0, "dur": 2e6,
            "pid": 0, "tid": 0}]
    ev1 = [{"name": "train", "ph": "X", "ts": 0.0, "dur": 2e6,
            "pid": 0, "tid": 0}]
    for rank, epoch, ev in ((0, 1000.0, ev0), (1, 1005.0, ev1)):
        with open(tmp_path / f"trace_r{rank}.json", "w") as fh:
            json.dump(_rank_trace(rank, epoch, ev, samples), fh)
    # a non-trace JSON in the same dir must be ignored, not crash
    (tmp_path / "postmortem_0.json").write_text('{"reason": "x"}')

    out, merged = merge_mod.merge_directory(str(tmp_path))
    assert os.path.basename(out) == merge_mod.MERGED_DEFAULT

    info = merged["lightgbm_tpu_merge"]
    assert info["ranks"] == [0, 1] and info["base_rank"] == 0
    # median of (5.2, 5.0, 4.9) recovers the injected 5.0s offset
    assert info["clock_offsets_s"]["1"] == pytest.approx(5.0, abs=1e-6)
    skews = sorted(c["skew_ms"] for c in info["collectives"])
    assert skews == pytest.approx([0.0, 100.0, 200.0], abs=1e-3)

    # both ranks' epochs correct to the same origin: rank 1's "train"
    # slice starts at ts=0 like rank 0's, not 5s later
    starts = {ev["pid"]: ev["ts"] for ev in merged["traceEvents"]
              if ev.get("name") == "train"}
    assert starts[0] == pytest.approx(0.0, abs=1e3)   # us tolerance 1ms
    assert starts[1] == pytest.approx(0.0, abs=1e3)
    skew_events = [ev for ev in merged["traceEvents"]
                   if ev.get("cat") == "lightgbm_tpu_clock"]
    assert len(skew_events) == 3
    assert all(ev["name"] == "skew:collective_psum"
               for ev in skew_events)


def test_merge_cli(tmp_path, capsys):
    from lightgbm_tpu.observability.__main__ import main
    samples = [{"site": "g", "walls": [10.0, 10.5]}]
    for rank in (0, 1):
        with open(tmp_path / f"trace_r{rank}.json", "w") as fh:
            json.dump(_rank_trace(rank, 5.0, [], samples), fh)
    assert main(["merge", str(tmp_path)]) == 0
    outp = capsys.readouterr().out
    assert f"wrote {tmp_path}" in outp.replace(os.sep + 'merged', '/merged') \
        or "wrote" in outp
    assert os.path.exists(tmp_path / merge_mod.MERGED_DEFAULT)
    # empty dir: a clean error, not a traceback
    empty = tmp_path / "empty"
    empty.mkdir()
    assert main(["merge", str(empty)]) == 1
    assert main(["bogus"]) == 2


def test_trace_dump_is_rank_tagged(tmp_path):
    registry.reset()
    registry.enable()
    try:
        with registry.trace.span("unit_work"):
            pass
        path = str(tmp_path / "trace_r0.json")
        registry.dump_trace(path, fmt="chrome")
    finally:
        registry.disable()
        registry.reset()
    doc = merge_mod.load_rank_trace(path)
    assert doc is not None, "dump_trace output not rank-taggged"
    meta = doc[merge_mod.META_KEY]
    assert meta["rank"] == 0 and meta["epoch_wall"] > 0
    merged = merge_mod.merge_traces([path])
    assert any(ev.get("name") == "unit_work"
               for ev in merged["traceEvents"])


# ---------------------------------------------------------------------------
# span profiler: budget + degrade-to-noop

def test_profiler_budget_and_match(tmp_path, monkeypatch):
    started, stopped = [], []
    monkeypatch.setattr(profile_mod, "_start_trace", started.append)
    monkeypatch.setattr(profile_mod, "_stop_trace",
                        lambda: stopped.append(True))
    profiler.configure(spans="sharded_*", out_dir=str(tmp_path),
                       max_captures=2)
    with profiler.capture("unrelated") as live:
        assert live is False
    for _ in range(3):
        with profiler.capture("sharded_grow") as live:
            pass
    snap = profiler.snapshot()
    assert snap["captures"] == 2 and snap["armed"] == 0
    assert len(started) == 2 and len(stopped) == 2
    assert started[0].startswith(str(tmp_path))


def test_profiler_degrades_on_failure(monkeypatch, tmp_path):
    def _boom(log_dir):
        raise RuntimeError("no profiler backend")
    monkeypatch.setattr(profile_mod, "_start_trace", _boom)
    profiler.configure(spans="pipeline_block", out_dir=str(tmp_path),
                       max_captures=4)
    with profiler.capture("pipeline_block") as live:
        assert live is False           # degraded, not raised
    snap = profiler.snapshot()
    assert snap["failed"] == 1 and snap["armed"] == 0
    # once failed, re-configure keeps it disarmed for the process
    profiler.configure(spans="pipeline_block", out_dir=str(tmp_path))
    assert profiler.snapshot()["armed"] == 0
