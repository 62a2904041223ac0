"""Every cell's control flow, end to end, as the command the driver
gives, at the tiny size its configuration states for a rehearsal and on
the CPU (four virtual devices for the four-chip cell). Proves nothing
about the chip: every record here says "platform": "cpu"."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import harness  # noqa: E402
from test_contract import _run, check_record  # noqa: E402

CELLS = [w["name"] for w in harness.load_benchmark()["workloads"]]


def _rehearse(name, trace, tmp_path):
    if name == "serve_online":
        # its rate is the chip's; offer the CPU a rate it sustains
        # through a copy whose traffic file says so
        import shutil
        shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
        shutil.copytree(os.path.join(REPO, "benchmark"),
                        tmp_path / "benchmark",
                        ignore=shutil.ignore_patterns("__pycache__"))
        path = tmp_path / "benchmark" / "traffic" / "online_poisson.json"
        traffic = json.load(open(path))
        traffic["streams"][0]["rate_per_s"] = 60.0
        json.dump(traffic, open(path, "w"))
        cwd, extra = str(tmp_path), REPO
    else:
        cwd, extra = REPO, None
    proc = _run(["--workload", name, "--seed", "7", "--seconds", "2",
                 "--trace", str(trace), "--rehearse-cpu"], cwd=cwd,
                extra_path=extra)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    assert all(ln.startswith("# ") for ln in lines[:-1])
    return json.loads(lines[-1]), proc.stdout


@pytest.mark.parametrize("name", CELLS)
def test_a_cell_rehearses_end_to_end(name, tmp_path):
    cell = harness.load_cell(name)
    line, out = _rehearse(name, 0, tmp_path)
    assert line["correct"] is True and line["failed"] == 0, out[-3000:]
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] >= cell["chips"]
    assert set(line["metrics"]) == {m["name"] for m in cell["end_to_end"]}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    with pytest.raises(AssertionError, match="names the chip"):
        check_record(line, cell, trace=False)
    # what the last line may not hold is on the lines before it
    if cell["config"]["kind"] == "train":
        for word in ("dataset_", "cache hits", "boundaries (unsynced)",
                     "reference, tree 1", "held-out AUC"):
            assert word in out, word
    else:
        for word in ("leaf_depth_median", "samples beyond the 99th"
                     if name == "serve_online" else "device batches",
                     "buckets compiled inside the window 0",
                     "agreement with forest_numpy"):
            assert word in out, word


@pytest.mark.parametrize("name", ["higgs_dp4_train", "serve_online"])
def test_a_traced_rehearsal_gives_the_per_layer_line(name, tmp_path):
    cell = harness.load_cell(name)
    line, out = _rehearse(name, 1, tmp_path)
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) <= {m["name"] for m in cell["per_layer"]}
    assert line["metrics"], out[-2000:]
    assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"] * \
        max(1, line["device"]["count"])
    assert line["breakdown"]["device_ops"]
    with pytest.raises(AssertionError, match="names the chip"):
        check_record(line, cell, trace=True)
