"""Bench regression sentinel (observability/regress.py + bench.py
--compare) — tier-1. Two halves: (1) the records that remain in the
repo root (the serve and multichip series; no training record has been
taken on the current code) must schema-validate and carry no
regressions; (2) synthetic trajectories prove the detectors fire: a
>10% drop, a broken latest record, a multichip flip, and the --strict
exit code."""

import json
import os
import subprocess
import sys

import pytest

from lightgbm_tpu.observability import regress

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
BENCH = os.path.join(REPO, "bench.py")


def _write(dirpath, name, rec):
    with open(os.path.join(str(dirpath), name), "w") as fh:
        json.dump(rec, fh)


def _bench_rec(value, rc=0, metric="higgs1m_trees_per_sec", **extra):
    parsed = None if value is None else {
        "metric": metric, "unit": "trees/s", "value": value, **extra}
    return {"n": 1, "cmd": "python bench.py", "rc": rc, "tail": "",
            "parsed": parsed}


# ---------------------------------------------------------------------------
# the real trajectory is a checked artifact

def test_real_trajectory_schema_validates():
    traj = regress.load_trajectory(REPO)
    assert traj["multichip"], "no MULTICHIP_r*.json in the repo root"
    assert traj["serve"], "no SERVE_r*.json in the repo root"
    problems = []
    for kind in ("bench", "multichip", "serve"):
        for _, name, rec in traj[kind]:
            problems += regress.validate_record(kind, name, rec)
    assert not problems, "\n".join(problems)


def test_real_trajectory_has_no_regressions():
    result = regress.compare()
    assert result["root"] == REPO
    assert result["regressions"] == [], regress.render_compare(result)
    # the headline metric is tracked with best-so-far context
    assert "serve:serve_sustained_qps_p99lt10ms" in result["metrics"]


def test_real_serve_record_holds_the_slo():
    """The committed SERVE_r*.json must be a usable sample: rc==0, a
    positive sustained QPS, p99 under the 10ms SLO, and zero drops in
    every stage (the bench_serve.py contract the sentinel guards)."""
    (_, name, rec) = regress.load_trajectory(REPO)["serve"][-1]
    assert regress.validate_record("serve", name, rec) == []
    assert rec["rc"] == 0
    parsed = rec["parsed"]
    assert parsed["metric"] == "serve_sustained_qps_p99lt10ms"
    assert parsed["unit"] == "qps" and parsed["value"] > 0
    assert parsed["slo_held"] is True and parsed["p99_ms"] < 10.0
    assert all(s["dropped"] == 0 for s in parsed["stages"])


# ---------------------------------------------------------------------------
# detectors, on synthetic trajectories

def test_drop_beyond_threshold_is_flagged(tmp_path):
    _write(tmp_path, "BENCH_r01.json", _bench_rec(2.0))
    _write(tmp_path, "BENCH_r02.json", _bench_rec(3.0))
    _write(tmp_path, "BENCH_r03.json", _bench_rec(2.5))   # -16.7% vs 3.0
    result = regress.compare(str(tmp_path))
    (reg,) = result["regressions"]
    assert reg["metric"] == "higgs1m_trees_per_sec"
    assert reg["best"] == 3.0 and reg["best_round"] == 2
    assert reg["drop_frac"] == pytest.approx(1 - 2.5 / 3.0, abs=1e-4)
    assert "REGRESSION" in regress.render_compare(result)


def test_drop_within_threshold_is_quiet(tmp_path):
    _write(tmp_path, "BENCH_r01.json", _bench_rec(3.0))
    _write(tmp_path, "BENCH_r02.json", _bench_rec(2.75))  # -8.3%: ok
    result = regress.compare(str(tmp_path))
    assert result["regressions"] == []
    assert result["metrics"]["higgs1m_trees_per_sec"]["delta_frac"] < 0


def test_ratio_side_channels_tracked(tmp_path):
    _write(tmp_path, "BENCH_r01.json", _bench_rec(2.0, vs_baseline=0.8))
    _write(tmp_path, "BENCH_r02.json", _bench_rec(2.1, vs_baseline=0.5))
    result = regress.compare(str(tmp_path))
    (reg,) = result["regressions"]
    assert reg["metric"] == "higgs1m_trees_per_sec:vs_baseline"


def test_unusable_rounds_excluded_from_best(tmp_path):
    # an rc!=0 round and a value<=0 round never become the best bar
    _write(tmp_path, "BENCH_r01.json", _bench_rec(2.0))
    _write(tmp_path, "BENCH_r02.json", _bench_rec(99.0, rc=1))
    _write(tmp_path, "BENCH_r03.json", _bench_rec(0.0))
    _write(tmp_path, "BENCH_r04.json", _bench_rec(2.1))
    result = regress.compare(str(tmp_path))
    assert result["regressions"] == []
    entry = result["metrics"]["higgs1m_trees_per_sec"]
    assert entry["best"] == 2.0 and entry["samples"] == 2


def test_broken_latest_record_is_a_regression(tmp_path):
    _write(tmp_path, "BENCH_r01.json", _bench_rec(2.0))
    _write(tmp_path, "BENCH_r02.json", _bench_rec(None, rc=3))
    result = regress.compare(str(tmp_path))
    (reg,) = result["regressions"]
    assert reg["metric"] == "bench_record"
    assert reg["record"] == "BENCH_r02.json"


def test_skipped_latest_round_is_declared_not_broken(tmp_path):
    # a record carrying skipped=true + a reason (hardware denial) is
    # not a sample and does not trip the unusable-latest
    # rule — unlike an rc=0/value=0 record, which does
    _write(tmp_path, "BENCH_r01.json", _bench_rec(2.0))
    _write(tmp_path, "BENCH_r02.json",
           {**_bench_rec(None), "skipped": True,
            "skip_reason": "device probe timed out"})
    result = regress.compare(str(tmp_path))
    assert result["regressions"] == []
    entry = result["metrics"]["higgs1m_trees_per_sec"]
    assert entry["latest_round"] == 1 and entry["samples"] == 1


def test_skipped_record_requires_a_reason(tmp_path):
    rec = {**_bench_rec(None), "skipped": True}
    problems = regress.validate_record("bench", "BENCH_r09.json", rec)
    assert any("skip_reason" in p for p in problems)
    rec["skip_reason"] = "no accelerator on this host"
    assert regress.validate_record("bench", "BENCH_r09.json", rec) == []


def test_serve_series_regressions_flagged(tmp_path):
    """SERVE_r*.json rides the bench schema: a QPS drop beyond the
    threshold and a broken latest serve round both fire, under the
    'serve:' metric namespace."""
    rec = lambda v, rc=0: _bench_rec(v, rc=rc,
                                     metric="serve_sustained_qps_p99lt10ms")
    _write(tmp_path, "SERVE_r01.json", rec(800.0))
    _write(tmp_path, "SERVE_r02.json", rec(500.0))       # -37.5%
    result = regress.compare(str(tmp_path))
    (reg,) = result["regressions"]
    assert reg["metric"] == "serve:serve_sustained_qps_p99lt10ms"
    assert reg["best"] == 800.0
    # a crashed latest serve bench is itself a regression
    _write(tmp_path, "SERVE_r03.json", rec(None, rc=1))
    result = regress.compare(str(tmp_path))
    assert {r["metric"] for r in result["regressions"]} == {
        "serve:serve_sustained_qps_p99lt10ms", "serve_record"}
    assert result["serve_records"] == 3


def test_multimodel_packed_qps_drop_flagged(tmp_path):
    """The serve record's packed multi-model QPS column is its own
    tracked series: a >10% drop in mm_packed_qps fires even when the
    headline single-model QPS holds steady."""
    rec = lambda mm: _bench_rec(800.0, mm_packed_qps=mm,
                                metric="serve_sustained_qps_p99lt10ms")
    _write(tmp_path, "SERVE_r01.json", rec(400.0))
    _write(tmp_path, "SERVE_r02.json", rec(250.0))       # -37.5%
    result = regress.compare(str(tmp_path))
    (reg,) = result["regressions"]
    assert reg["metric"] == \
        "serve:serve_sustained_qps_p99lt10ms:mm_packed_qps"
    assert reg["best"] == 400.0


def test_multimodel_speedup_within_threshold_quiet(tmp_path):
    """mm_packed_speedup is tracked alongside mm_packed_qps but small
    wobble stays quiet; rounds without the multi-model stage simply
    contribute no sample (no false regression from a missing column)."""
    _write(tmp_path, "SERVE_r01.json",
           _bench_rec(800.0, mm_packed_qps=400.0, mm_packed_speedup=1.5,
                      metric="serve_sustained_qps_p99lt10ms"))
    _write(tmp_path, "SERVE_r02.json",      # no mm stage this round
           _bench_rec(810.0, metric="serve_sustained_qps_p99lt10ms"))
    _write(tmp_path, "SERVE_r03.json",
           _bench_rec(805.0, mm_packed_qps=390.0, mm_packed_speedup=1.45,
                      metric="serve_sustained_qps_p99lt10ms"))
    result = regress.compare(str(tmp_path))
    assert result["regressions"] == []
    spd = result["metrics"][
        "serve:serve_sustained_qps_p99lt10ms:mm_packed_speedup"]
    assert spd["samples"] == 2 and spd["latest"] == 1.45


def test_multichip_flip_is_a_regression(tmp_path):
    mc = {"n_devices": 2, "rc": 0, "ok": True, "skipped": False}
    _write(tmp_path, "MULTICHIP_r01.json", mc)
    _write(tmp_path, "MULTICHIP_r02.json",
           {**mc, "rc": 1, "ok": False})
    result = regress.compare(str(tmp_path))
    (reg,) = result["regressions"]
    assert reg["metric"] == "multichip_ok"
    # skipped rounds are not samples
    _write(tmp_path, "MULTICHIP_r03.json", {**mc, "skipped": True})
    assert regress.compare(str(tmp_path))["metrics"]["multichip_ok"][
        "samples"] == 2


def test_multichip_throughput_drop_is_a_regression(tmp_path):
    """r06+ MULTICHIP records carry real training throughput
    (trees_per_sec / vs_baseline from the 8-device run); a >10% drop
    vs best-so-far fires like any other tracked series, while legacy
    dry-run records (no throughput fields) stay schema-valid and
    contribute no samples."""
    mc = {"n_devices": 8, "rc": 0, "ok": True, "skipped": False,
          "tree_learner": "data"}
    _write(tmp_path, "MULTICHIP_r01.json", mc)    # legacy dry-run round
    _write(tmp_path, "MULTICHIP_r02.json",
           {**mc, "trees_per_sec": 40.0, "vs_baseline": 0.31})
    _write(tmp_path, "MULTICHIP_r03.json",
           {**mc, "trees_per_sec": 30.0, "vs_baseline": 0.23})  # -25%
    for _, name, rec in regress.load_trajectory(
            str(tmp_path))["multichip"]:
        assert regress.validate_record("multichip", name, rec) == []
    result = regress.compare(str(tmp_path))
    metrics = {r["metric"] for r in result["regressions"]}
    assert "multichip_trees_per_sec" in metrics
    assert "multichip_vs_baseline" in metrics
    entry = result["metrics"]["multichip_trees_per_sec"]
    assert entry["best"] == 40.0 and entry["samples"] == 2


def test_multichip_throughput_within_threshold_is_quiet(tmp_path):
    mc = {"n_devices": 8, "rc": 0, "ok": True, "skipped": False,
          "tree_learner": "data"}
    _write(tmp_path, "MULTICHIP_r01.json",
           {**mc, "trees_per_sec": 40.0, "vs_baseline": 0.31})
    _write(tmp_path, "MULTICHIP_r02.json",
           {**mc, "trees_per_sec": 38.0, "vs_baseline": 0.29})  # -5%
    result = regress.compare(str(tmp_path))
    assert result["regressions"] == []
    assert result["metrics"]["multichip_trees_per_sec"]["best"] == 40.0


# ---------------------------------------------------------------------------
# bench.py --compare wiring (subprocess: the real CLI path)

def _run_compare(*argv):
    return subprocess.run(
        [sys.executable, BENCH, "--compare", *argv],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=120)


def test_bench_compare_real_trajectory_passes():
    proc = _run_compare("--strict")
    assert proc.returncode == 0, proc.stderr
    payload = json.loads(proc.stdout.strip().splitlines()[-1])
    assert payload["bench_regressions"]["regressions"] == []
    assert "no regressions" in proc.stderr


def test_bench_compare_strict_fails_on_regression(tmp_path):
    _write(tmp_path, "BENCH_r01.json", _bench_rec(3.0))
    _write(tmp_path, "BENCH_r02.json", _bench_rec(1.0))
    proc = _run_compare("--strict", "--trajectory-dir", str(tmp_path))
    assert proc.returncode == 1
    assert "REGRESSION" in proc.stderr
    # without --strict the same trajectory reports but exits 0
    proc = _run_compare("--trajectory-dir", str(tmp_path))
    assert proc.returncode == 0
    payload = json.loads(proc.stdout.strip().splitlines()[-1])
    assert payload["bench_regressions"]["regressions"]
