"""Every cell's control flow, end to end, as the command the driver
gives, at the tiny size its configuration states for a rehearsal and on
the CPU (four virtual devices for the four-chip cell), untraced and
traced. Each is held to what every rehearsal shows and to what the
cell's runner says its rehearsal prints (`test_contract.rehearse`).
Proves nothing about the chip: every record here says "platform":
"cpu"."""

import json
import os
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import harness  # noqa: E402
from test_contract import rehearse  # noqa: E402

CELLS = [w["name"] for w in harness.load_benchmark()["workloads"]]


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("name", CELLS)
def test_a_cell_rehearses_end_to_end(name, trace, tmp_path):
    cell = harness.load_cell(name)
    cwd, extra = REPO, None
    streams = cell["traffic"].get("streams", [])
    if any(s["loop"] == "open" for s in streams):
        # a cell's rate is the chip's: offer the CPU one it sustains,
        # through a copy whose traffic file says so
        shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
        shutil.copytree(os.path.join(REPO, "benchmark"),
                        tmp_path / "benchmark",
                        ignore=shutil.ignore_patterns("__pycache__"))
        path = tmp_path / "benchmark" / "traffic" / (
            cell["traffic_name"] + ".json")
        traffic = json.load(open(path))
        for s in traffic["streams"]:
            if s["loop"] == "open":
                s["rate_per_s"] = min(s["rate_per_s"], 60.0)
        json.dump(traffic, open(path, "w"))
        cwd, extra = str(tmp_path), REPO
    rehearse(name, trace, cwd=cwd, extra_path=extra)
