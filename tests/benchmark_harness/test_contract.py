"""BENCHMARK.json and the files it names, against the driver's contract
and against each other; and the harness taking new cells as data.

CPU only. Nothing here describes a TPU topology or touches a chip; the
subprocesses run `--rehearse-cpu`, whose record says "platform": "cpu"
and is refused as a record by `check_record` below.
"""

import ast
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import harness  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")


def _bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        return json.load(fh)


def _all_cells():
    return [w["name"] for w in harness.load_benchmark()["workloads"]]


def test_benchmark_json_meets_the_contract():
    raw = open(os.path.join(REPO, "BENCHMARK.json"), "rb").read()
    assert len(raw) <= 64 * 1024
    b = json.loads(raw)
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(b["command"]) <= 32
    assert not any(a.startswith("/") or ".." in a for a in b["command"])
    assert 1 <= len(b["paths"]) <= 16
    for p in b["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(REPO, p))
    assert isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51
    # what a full check costs with the full 24 cells has to fit
    assert (2 + 14 * 24) * (b["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    names = [e["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for e in b[key]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert 1 <= len(b["configs"]) <= 24 and 2 <= len(b["workloads"]) <= 24
    used = {w["config"] for w in b["workloads"]}
    files = set()
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used, "%s is used by no cell" % c["name"]
        assert c["source"].startswith("http")
        assert any(c["file"].startswith(p + "/") for p in b["paths"])
        assert PATH.match(c["file"]) and c["file"] not in files
        files.add(c["file"])
        assert len(c["why"]) <= 200
        with open(os.path.join(REPO, c["file"])) as fh:
            cfg = json.load(fh)
        # every key named as reduced is in the file, with its reason
        assert set(c["reduced"]) == set(cfg["reduced"])
        assert all(k in cfg for k in c["reduced"])
    pairs = [(w["config"], w["traffic"]) for w in b["workloads"]]
    assert len(pairs) == len(set(pairs))
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert w["config"] in {c["name"] for c in b["configs"]}
    four = sum(w["chips"] == 4 for w in b["workloads"])
    assert four <= max(1, len(b["workloads"]) // 4)
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert 1 <= len(e2e) <= 16 and 1 <= len(b["per_layer"]) <= 128
    assert e2e["setup_s"]["bound"] == 0.1 and "workloads" not in e2e["setup_s"]
    for m in b["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
        assert m["better"] in ("higher", "lower")
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and m["source"] in harness.SOURCES
        assert m["name"].endswith("_roofline") == \
            (m["unit"] == "%" and "roofline" in m["name"])


@pytest.mark.parametrize("name", _all_cells())
def test_every_cells_files_exist_and_parse(name):
    cell = harness.load_cell(name)
    cfg, traffic = cell["config"], cell["traffic"]
    for key in ("name", "kind", "source", "deployment", "reduced",
                "assumed", "expect", "rehearsal"):
        assert key in cfg, key
    assert cfg["name"] == cell["config_entry"]["name"]
    assert traffic["loop"] in ("job", "open", "closed") and traffic["who"]
    runner = harness.load_runner(cfg["kind"])
    assert callable(runner.run)
    # it owes the set-up time, one more end-to-end metric, and per-layer
    # metrics that each have a reader which states what the entry states
    owed = {m["name"] for m in cell["end_to_end"]}
    assert "setup_s" in owed and len(owed) >= 2
    assert cell["per_layer"]
    readers = harness.layer_metric_readers()
    for m in cell["per_layer"]:
        mod = readers[m["name"]]
        assert (mod.UNIT, mod.BETTER, mod.LAYER, mod.SOURCE, mod.MOVES) == \
            (m["unit"], m["better"], m["layer"], m["source"], m["moves"])
        if not m.get("held_back"):
            assert mod.WORKLOADS == _bench_entry(m["name"]).get("workloads")
        assert mod.read({}) is None      # nothing to read: nothing given


def _bench_entry(name):
    return next(m for m in _bench()["per_layer"] if m["name"] == name)


def test_every_reader_is_listed_somewhere():
    listed = {m["name"] for m in harness.load_benchmark()["per_layer"]}
    assert set(harness.layer_metric_readers()) == listed


def test_nothing_here_imports_the_programs_own_benchmarks():
    banned = ("bench", "bench_serve", "helpers", "chip_smoke")
    for base, _, files in os.walk(os.path.join(REPO, "benchmark")):
        for f in files:
            if not f.endswith(".py"):
                continue
            tree = ast.parse(open(os.path.join(base, f)).read())
            for node in ast.walk(tree):
                if isinstance(node, ast.Import):
                    mods = [a.name for a in node.names]
                elif isinstance(node, ast.ImportFrom) and not node.level:
                    mods = [node.module or ""]
                else:
                    continue
                for mod in mods:
                    assert mod.split(".")[0] not in banned, (f, mod)
                    assert not mod.startswith("lightgbm_tpu.testing"), \
                        (f, mod)


def test_peaks_name_the_v5e_and_nothing_by_default():
    assert harness.peak_of_kind("TPU v5 lite")["bf16_tflops"] == 197.0
    assert harness.peak_of_kind("TPU v5 lite")["hbm_gb_per_s"] == 819.0
    assert harness.peak_of_kind("cpu") is None
    assert harness.peak_of_kind("TPU v9 imaginary") is None


def test_percentile_is_a_value_that_was_measured():
    xs = list(range(1, 1001))
    assert harness.percentile(xs, 50) == 500
    assert harness.percentile(xs, 99) == 990      # ten samples beyond it
    assert harness.percentile([7.0], 99) == 7.0


# ----------------------------------------------------------------------
# subprocesses: the command as the driver gives it
def _run(args, cwd=REPO, extra_path=None, timeout=300):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "XLA_FLAGS")}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (extra_path, env.get("PYTHONPATH")) if p)
    return subprocess.run([sys.executable, "-m", "benchmark"] + args,
                          capture_output=True, text=True, timeout=timeout,
                          env=env, cwd=cwd)


def check_record(line, cell, trace):
    """What the driver reads off a run's last line. A rehearsal's line
    has every key and is refused here for its platform."""
    assert set(line) - {"compared"} == {
        "correct", "attempted", "failed", "metrics", "device"} | (
        {"breakdown"} if trace else set())
    # the numbers `correct` compared, each beside its limit, come last
    assert "compared" not in line or list(line)[-1] == "compared"
    for got in line.get("compared", {}).values():
        assert set(got) in ({"value", "max"}, {"value", "min"}), got
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= \
        set(line["device"])
    owed = cell["per_layer"] if trace else cell["end_to_end"]
    assert set(line["metrics"]) <= {m["name"] for m in owed}
    for m in owed:
        if m["name"] in line["metrics"]:
            got = line["metrics"][m["name"]]
            assert set(got) == {"value", "unit"} and got["unit"] == m["unit"]
            assert isinstance(got["value"], float)
    if trace:
        assert line["device"]["busy_s"] > 0 and line["device"]["window_s"] > 0
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        assert all(len(v) <= 10 for v in line["breakdown"].values())
    else:
        assert set(line["metrics"]) == {m["name"] for m in owed}
    assert line["device"]["platform"] == "tpu", \
        "a record names the chip it was measured on"


def _what_the_runner_says(name, cwd, extra_path):
    """The cell as the benchmark in `cwd` loads it, and what its runner
    says a rehearsal prints (`rehearsal_says`: words of the earlier
    lines; `rehearsal_reads`: per-layer metrics of a traced line, each
    with the range (lo, hi] it lies in)."""
    code = ("import json; from benchmark import harness; "
            "c = harness.load_cell(%r); "
            "r = harness.load_runner(c['config']['kind']); "
            "print(json.dumps(dict(chips=c['chips'], "
            "end_to_end=c['end_to_end'], per_layer=c['per_layer'], "
            "says=r.rehearsal_says(c), reads=r.rehearsal_reads(c))))" % name)
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        p for p in (extra_path, os.environ.get("PYTHONPATH")) if p))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120, env=env, cwd=cwd)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def rehearse(name, trace, cwd=REPO, extra_path=None, seed="7"):
    """One rehearsal of a cell as the benchmark's command runs it, held to
    what every cell's rehearsal shows and to what its runner says it
    prints. Names no kind and no cell."""
    proc = _run(["--workload", name, "--seed", seed, "--seconds", "2",
                 "--trace", str(trace), "--rehearse-cpu"], cwd=cwd,
                extra_path=extra_path)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    assert all(ln.startswith("# ") for ln in lines[:-1])
    line, out = json.loads(lines[-1]), proc.stdout
    cell = _what_the_runner_says(name, cwd, extra_path)
    assert line["correct"] is True and line["failed"] == 0, out[-3000:]
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] >= cell["chips"]
    if trace:
        assert set(line["metrics"]) <= {m["name"] for m in cell["per_layer"]}
        assert line["metrics"], out[-2000:]
        for metric, (lo, hi) in cell["reads"].items():
            value = line["metrics"][metric]["value"]
            assert lo < value and (hi is None or value <= hi), (metric, value)
        assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"] * \
            max(1, line["device"]["count"])
        assert line["breakdown"]["device_ops"]
    else:
        assert set(line["metrics"]) == {m["name"] for m in cell["end_to_end"]}
        assert all(m["value"] > 0 for m in line["metrics"].values())
    # the numbers compared, each beside its limit: last in the line, and
    # the last lines of stderr
    assert list(line)[-1] == "compared" and line["compared"]
    tail = proc.stderr.strip().splitlines()[-len(line["compared"]):]
    assert [ln.split()[1] for ln in tail] == list(line["compared"])
    with pytest.raises(AssertionError, match="names the chip"):
        check_record(line, cell, trace=bool(trace))
    # what the last line may not hold is on the lines before it
    for word in cell["says"]:
        assert word in out, word
    return line, out


def test_a_platform_that_is_not_a_tpu_ends_the_run_with_no_record():
    proc = _run(["--workload", "higgs_train", "--seed", "1", "--seconds",
                 "1", "--trace", "0"])
    assert proc.returncode != 0
    assert "not a TPU" in proc.stderr
    assert not [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]


def _copy_of_the_benchmark(tmp_path):
    """BENCHMARK.json and the directories under `paths`, and nothing
    else of the repo (the program comes in through PYTHONPATH)."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path


def test_without_the_program_there_is_no_record(tmp_path):
    root = _copy_of_the_benchmark(tmp_path)
    proc = _run(["--workload", "higgs_train", "--seed", "1", "--seconds",
                 "1", "--trace", "0", "--rehearse-cpu"], cwd=str(root))
    assert proc.returncode != 0
    assert "lightgbm_tpu" in proc.stderr
    assert not [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]


_NEW_READER = '''"""A throw-away per-layer metric."""
NAME = "throwaway.trees_in_window"
UNIT = "trees"
BETTER = "higher"
LAYER = "entry"
SOURCE = "program_counter"
MOVES = "trees_per_s"
WORKLOADS = ["throwaway_train", "throwaway_noop"]


def read(r):
    return r.get("window_trees")
'''

_NEW_RUNNER = '''"""A throw-away kind of configuration: measures nothing."""


def run(cell, *, seed, seconds, trace, rehearsal):
    return {"correct": True, "attempted": cell["config"]["things"],
            "failed": 0,
            "end_to_end": {"trees_per_s": 1.5, "setup_s": 0.25},
            "readings": {"window_trees": 3, "memory_peak_bytes": None,
                         "trace": {"busy_s": 0.5, "window_s": 1.0,
                                   "mosaic_s": 0.25, "collective_s": 0.0,
                                   "devices": 1,
                                   "device_ops": [["op", 0.5]],
                                   "idle_gaps": []}}}
'''


#: a throw-away TRAINING kind, written as a new deployment is: a task on
#: the training core with its own data and its own reference check
_NEW_TRAINING_RUNNER = '''"""A throw-away training kind: least squares on
rows near a plane, each checked tree held against the labels' mean."""

import numpy as np

from ..harness import say
from ..training import Task


def leaves(structure):
    """(rows, value) of every leaf of a dumped tree."""
    if "left_child" not in structure:
        return [(structure["leaf_count"], structure["leaf_value"])]
    return leaves(structure["left_child"]) + leaves(structure["right_child"])


class Plane(Task):
    kind = "throwaway_l2"
    reference = "the labels' mean"
    flatten_tree = staticmethod(leaves)
    limits = (("mean_gap", "mean_gap"),)
    quality, quality_trees = "R2", "r2_trees"

    def draw(self, cfg, seed):
        rng = np.random.default_rng(seed)
        n, m = int(cfg["num_data"]), int(cfg["held_out_rows"])
        X = rng.standard_normal((n + m, int(cfg["num_features"])),
                                dtype=np.float32)
        y = (2.0 * X[:, 0] - X[:, 1] + 0.1 * rng.standard_normal(n + m)
             ).astype(np.float32)
        return {"X": X[:n], "y": y[:n], "Xho": X[n:], "yho": y[n:]}

    def say_data(self, cfg, seed, data, data_s, binning_s, binned):
        say("data: %d x %d rows near a plane %s"
            % (*data["X"].shape, binned))

    def check_step(self, k, trees, data, bins, cfg, resolved, routed):
        # under least squares every tree keeps the rows' mean: tree 0's
        # leaves carry the labels' mean, a later tree's sum to nothing
        y = data["y"].astype(np.float64)
        mean = sum(c * v for c, v in trees[k]) / len(y)
        return {"mean_gap": abs(mean - (y.mean() if k == 0 else 0.0))
                / y.std()}

    def held_out(self, bst, data, n, expect):
        yho = data["yho"].astype(np.float64)
        r2 = 1.0 - np.mean((bst.predict(data["Xho"], num_iteration=n)
                            - yho) ** 2) / yho.var()
        say("held-out R2 after %d trees: %.5f" % (n, r2))
        return "R2", r2, expect["r2_floor"]

    def rehearsal_says(self, cell):
        return super().rehearsal_says(cell) + ("rows near a plane",)


TASK = Plane()
'''


def test_a_cell_a_configuration_a_traffic_mix_a_runner_and_a_metric_are_new_files(
        tmp_path):
    """What a later PR does: new files under benchmark/, new entries in
    BENCHMARK.json, no edit to any file that was there."""
    root = _copy_of_the_benchmark(tmp_path)
    before = {p: open(p, "rb").read()
              for base, _, fs in os.walk(root / "benchmark")
              for p in (os.path.join(base, f) for f in fs)}
    bench_dir = root / "benchmark"
    cfg = json.load(open(bench_dir / "configs" / "higgs_share4.json"))
    cfg.update(name="throwaway_shape", num_data=4000)
    cfg["rehearsal"] = dict(cfg["rehearsal"], num_data=6000, num_leaves=7)
    json.dump(cfg, open(bench_dir / "configs" / "throwaway_shape.json", "w"))
    json.dump({"name": "throwaway_thing", "kind": "noop", "things": 5},
              open(bench_dir / "configs" / "throwaway_thing.json", "w"))
    traffic = json.load(open(bench_dir / "traffic" / "train_plain.json"))
    traffic.update(who="nobody", params={"min_data_in_leaf": 30},
                   trace_trees_per_iteration_path=4)
    json.dump(traffic, open(bench_dir / "traffic" / "throwaway_mix.json",
                            "w"))
    (bench_dir / "layer_metrics" / "throwaway_trees.py").write_text(
        _NEW_READER)
    (bench_dir / "runners" / "noop.py").write_text(_NEW_RUNNER)
    (bench_dir / "runners" / "throwaway_l2.py").write_text(
        _NEW_TRAINING_RUNNER)
    json.dump({"name": "throwaway_plane", "kind": "throwaway_l2",
               "objective": "regression", "num_data": 3000,
               "num_features": 4, "num_leaves": 7, "max_bin": 31,
               "learning_rate": 0.1, "held_out_rows": 1000, "params": {},
               "expect": {"check_trees": [0, 1], "mean_gap": 1e-4,
                          "r2_trees": 20, "r2_floor": 0.5},
               "rehearsal": {}},
              open(bench_dir / "configs" / "throwaway_plane.json", "w"))
    bench = json.load(open(root / "BENCHMARK.json"))
    bench["configs"] += [
        {"name": "throwaway_shape", "source": "https://example.org/a",
         "file": "benchmark/configs/throwaway_shape.json",
         "reduced": ["num_data", "num_iterations"], "why": "a test"},
        {"name": "throwaway_thing", "source": "https://example.org/b",
         "file": "benchmark/configs/throwaway_thing.json", "reduced": [],
         "why": "a test"},
        {"name": "throwaway_plane", "source": "https://example.org/c",
         "file": "benchmark/configs/throwaway_plane.json", "reduced": [],
         "why": "a test"}]
    bench["workloads"] += [
        {"name": "throwaway_train", "config": "throwaway_shape",
         "traffic": "throwaway_mix", "chips": 1, "why": "a test"},
        {"name": "throwaway_noop", "config": "throwaway_thing",
         "traffic": "throwaway_mix", "chips": 1, "why": "a test"},
        {"name": "throwaway_l2_train", "config": "throwaway_plane",
         "traffic": "throwaway_mix", "chips": 1, "why": "a test"}]
    bench["per_layer"].append(
        {"name": "throwaway.trees_in_window", "unit": "trees",
         "better": "higher", "source": "program_counter", "layer": "entry",
         "moves": "trees_per_s",
         "workloads": ["throwaway_train", "throwaway_noop"]})
    json.dump(bench, open(root / "BENCHMARK.json", "w"))

    def last_line(args):
        proc = _run(args + ["--rehearse-cpu"], cwd=str(root),
                    extra_path=REPO)
        assert proc.returncode == 0, proc.stderr[-3000:]
        return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout

    # a new kind of configuration through a new runner
    line, _ = last_line(["--workload", "throwaway_noop", "--seed", "1",
                         "--seconds", "1", "--trace", "1"])
    assert line["attempted"] == 5
    assert line["metrics"]["throwaway.trees_in_window"] == \
        {"value": 3.0, "unit": "trees"}
    # a new configuration under a new traffic mix through the train
    # runner that was there, end to end and traced
    line, out = last_line(["--workload", "throwaway_train", "--seed", "1",
                           "--seconds", "1", "--trace", "0"])
    assert "6000 x 28" in out and "'min_data_in_leaf': 30" in out
    assert line["correct"] and line["failed"] == 0
    assert set(line["metrics"]) == {"trees_per_s", "setup_s"}
    line, _ = last_line(["--workload", "throwaway_train", "--seed", "2",
                         "--seconds", "1", "--trace", "1"])
    assert line["metrics"]["throwaway.trees_in_window"]["value"] == 4.0
    assert "growth.device_ms_per_tree" in line["metrics"]
    with pytest.raises(AssertionError, match="names the chip"):
        check_record(line, {"per_layer": bench["per_layer"],
                            "end_to_end": bench["end_to_end"]}, trace=True)
    # a new KIND of training deployment, a task on the training core,
    # through the same assertions as every cell's rehearsal
    for trace in (0, 1):
        line, _ = rehearse("throwaway_l2_train", trace, cwd=str(root),
                           extra_path=REPO)
        assert line["compared"]["tree1.mean_gap"]["value"] < 1e-6
    # and no file that was there has changed
    after = {p: open(p, "rb").read() for p in before}
    assert after == before
