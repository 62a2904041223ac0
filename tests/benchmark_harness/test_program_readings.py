"""The per-layer readers that take their number from inside the program
(its span ring and compile ledger), and the two that read the host's
hold on the chip off the trace (`entry.gap_ms_per_tree`,
`device.idle_share`): each against hand-made readings, against a
program that has no ring or ledger, and in the rehearsals of the two
Higgs cells."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import harness, program_readings  # noqa: E402
from test_contract import _run  # noqa: E402

#: what is left of the six entries appended with the span ring and the
#: compile ledger, in their order
NEW = ["boosting.trace_s", "boosting.lower_s", "boosting.programs_built",
       "boosting.init_s"]
#: retired: a span around three list appends, the benchmark's own syncs
#: inside `entry.callbacks`, and a residual that NEW's first two replaced
RETIRED = ["entry.unpack_ms_per_tree", "entry.host_ms_per_tree",
           "boosting.trace_lower_s"]
#: what reads the host's hold on the chip in their place
HOLD = ["entry.gap_ms_per_tree", "device.idle_share"]
READERS = harness.layer_metric_readers()


def _span(i, name, ts, dur, parent=0, **attrs):
    return {"name": name, "id": i, "parent_id": parent, "ts": ts,
            "dur": dur, "attrs": attrs}


def _event(kind, fun, seconds, ts):
    return {"kind": kind, "fun": fun, "seconds": seconds, "ts": ts,
            "span": "boosting.build_program"}


#: two warm-up blocks of two trees, then a window of two blocks; the
#: window's first span begins at 10.0
FUSED_RING = [
    _span(1, "boosting.init", 0.0, 1.5, rows=100, devices=1),
    _span(2, "entry.block", 2.0, 4.0, iter=0, k=2),
    _span(3, "entry.block", 6.0, 4.0, iter=2, k=2),
    _span(4, "entry.block", 10.0, 5.0, iter=4, k=2),
    _span(5, "entry.unpack_block", 10.1, 4.5, 4, iter=2, k=2),
    # the first unpack_tree of a block waits out the block in flight
    _span(6, "entry.unpack_tree", 10.1, 4.4, 5, iter=2, k=2, tree=2),
    _span(7, "entry.unpack_tree", 14.5, 0.1, 5, iter=2, k=2, tree=3),
    _span(8, "entry.block", 15.0, 5.0, iter=6, k=2),
    _span(9, "entry.unpack_block", 15.1, 4.5, 8, iter=4, k=2),
    _span(10, "entry.unpack_tree", 15.1, 4.48, 9, iter=4, k=2, tree=4),
    _span(11, "entry.unpack_tree", 19.58, 0.02, 9, iter=4, k=2, tree=5),
    _span(12, "entry.sync_metrics", 19.7, 0.01, 8, iter=6, k=2),
    _span(13, "entry.callbacks", 19.8, 0.03, 8, iter=6, k=2),
    _span(14, "entry.unpack_tree", 20.5, 0.016, 0, iter=6, k=2, tree=6),
    _span(15, "entry.unpack_tree", 20.6, 0.018, 0, iter=6, k=2, tree=7),
]
LEDGER = [
    _event("trace", "program", 3.0, 3.1),
    _event("lower", "program", 2.0, 5.1),
    _event("backend", "program", 0.5, 5.6),
    _event("hits", "program", 0.0, 5.5),
    _event("trace", "squeeze", 0.25, 7.0),
    _event("lower", "squeeze", 0.125, 7.2),
    _event("backend", "squeeze", 0.1, 7.3),
    # inside the window: a correct run has none, and none is counted
    _event("trace", "late", 9.0, 12.0),
    _event("backend", "late", 9.0, 13.0),
]
FUSED = {"kind": "train", "warm_trees": 4, "window_trees": 4,
         "timers": {"boosting.init": 1.5, "dataset_sample": 0.1},
         "spans": FUSED_RING, "compile_events": LEDGER}

#: the per-iteration path: three trees of a window that opens at tree 2
TREE_RING = [_span(1, "entry.tree", 1.0, 1.0, iter=1)]
for n, it in enumerate((2, 3, 4)):
    base, t0 = 100 * (n + 1), 10.0 + n
    TREE_RING += [
        _span(base, "entry.tree", t0, 0.9, iter=it),
        _span(base + 1, "boosting.gradients", t0, 0.001, base, iter=it),
        _span(base + 2, "boosting.bagging", t0 + .01, 0.002, base, iter=it),
        _span(base + 3, "entry.dispatch", t0 + .02, 0.5, base, iter=it),
        _span(base + 4, "entry.wait_device", t0 + .53, 0.3, base, iter=it),
        _span(base + 5, "boosting.shrink", t0 + .84, 0.003, base, iter=it),
        _span(base + 6, "boosting.update_score", t0 + .85, 0.004 + n,
              base, iter=it),
        _span(base + 7, "entry.append_tree", t0 + .86, 0.0, base, iter=it),
        _span(base + 8, "entry.callbacks", t0 + .87, 0.004, base, iter=it),
        # a grandchild is not counted twice
        _span(base + 9, "boosting.linear_fit", t0 + .84, 0.002, base + 5,
              iter=it),
    ]
TREES = {"kind": "train", "warm_trees": 2, "window_trees": 3,
         "timers": {}, "spans": TREE_RING, "compile_events": []}
#: the fused window, traced: the device ran 9.96 s of its 10
TRACED = dict(FUSED, trace={"window_s": 10.0, "busy_s": 9.96})


def _read(name, readings):
    return READERS[name].read(readings)


@pytest.mark.parametrize("name,readings,want", [
    ("boosting.trace_s", FUSED, 3.25),
    ("boosting.lower_s", FUSED, 2.125),
    ("boosting.programs_built", FUSED, 2),
    ("boosting.init_s", FUSED, 1.5),
    # the idle 40 ms of the window over its four trees
    ("entry.gap_ms_per_tree", TRACED, 10.0),
    ("device.idle_share", TRACED, 0.4),
    # the per-iteration window was preceded by no lowering at all
    ("boosting.lower_s", TREES, 0.0),
    ("boosting.programs_built", TREES, 0),
    ("boosting.trace_s", TREES, 0.0),
])
def test_a_reader_against_a_hand_made_ring_and_ledger(name, readings, want):
    assert _read(name, readings) == pytest.approx(want)


@pytest.mark.parametrize("name", NEW + HOLD)
def test_a_reader_with_nothing_to_read_gives_none(name, monkeypatch):
    # no readings at all; a serving run; a training run whose window
    # holds no span of the kind; a run with no window
    assert _read(name, {}) is None
    assert _read(name, {"kind": "serve", "spans": FUSED_RING,
                        "compile_events": LEDGER,
                        "timers": {"boosting.init": 1.0}}) is None
    empty = {"kind": "train", "warm_trees": 4, "window_trees": 4,
             "timers": {}, "spans": [], "compile_events": []}
    assert _read(name, empty) is None
    assert _read(name, dict(FUSED, warm_trees=None, timers={})) is None
    # a program with no span ring or compile ledger to read (the parent
    # of the PR that added them): nothing, and no exception
    monkeypatch.setattr(program_readings, "_registry", lambda: None)
    bare = {"kind": "train", "warm_trees": 4, "window_trees": 4,
            "timers": {"dataset_sample": 0.1}}
    assert _read(name, bare) is None


def test_the_readers_say_what_benchmark_json_says():
    entries = {m["name"]: m for m in
               json.load(open(os.path.join(REPO, "BENCHMARK.json")))
               ["per_layer"]}
    # appended, in this order: a subsequence, since later entries are
    # appended after them
    order = [name for name in entries if name in NEW]
    assert order == NEW
    for name, m in entries.items():
        mod = READERS[name]
        assert (mod.UNIT, mod.BETTER, mod.LAYER, mod.SOURCE, mod.MOVES,
                mod.WORKLOADS) == (m["unit"], m["better"], m["layer"],
                                   m["source"], m["moves"],
                                   m.get("workloads"))
    assert all(READERS[name].SOURCE in ("program_span", "program_counter")
               for name in NEW)
    for name in RETIRED:
        assert name not in entries and name not in READERS


def test_the_live_ring_and_ledger_feed_the_readers():
    """A fused, pipelined lgb.train in this process (data-parallel over
    four virtual devices, which takes the fused scan on the CPU): the
    readers find the program's own spans and ledger with no help."""
    import numpy as np
    import lightgbm_tpu as lgb
    from lightgbm_tpu.observability import registry as obs
    from lightgbm_tpu.utils.timer import global_timer
    obs.disable()
    obs.reset()
    rng = np.random.RandomState(5)
    X = rng.randn(1200, 6).astype(np.float32)
    y = (X[:, 0] + X[:, 1] > 0).astype(np.float32)
    init0 = global_timer.totals().get("boosting.init", 0.0)
    lgb.train({"objective": "binary", "num_leaves": 7, "max_bin": 31,
               "verbosity": -1, "tree_learner": "data", "num_devices": 4,
               "fused_block_size": 4, "min_data_in_leaf": 5},
              lgb.Dataset(X, label=y), 16)
    r = {"kind": "train", "warm_trees": 8, "window_trees": 8,
         "timers": global_timer.totals()}
    got = {name: _read(name, r) for name in NEW}
    assert got["boosting.init_s"] > init0
    assert got["boosting.programs_built"] >= 2
    assert got["boosting.trace_s"] > 0 and got["boosting.lower_s"] > 0
    spans = obs.trace.spans()
    unpacked = [s["dur"] for s in spans if s["name"] == "entry.unpack_tree"
                and 8 <= s["attrs"]["iter"] < 16]
    assert len(unpacked) == 8
    # what was built before the window is what the ledger holds from
    # before the block at iteration 8 began
    cut = next(s["ts"] for s in spans if s["name"] == "entry.block"
               and s["attrs"]["iter"] == 8)
    assert got["boosting.programs_built"] == sum(
        1 for e in obs.compiles.events()
        if e["kind"] == "backend" and e["ts"] < cut)
    obs.reset()


@pytest.fixture(scope="module")
def traced_lines():
    lines = {}
    for cell in ("higgs_train", "higgs_dp4_train"):
        proc = _run(["--workload", cell, "--seed", str(2**31 + 11),
                     "--seconds", "2", "--trace", "1", "--rehearse-cpu"],
                    cwd=REPO)
        assert proc.returncode == 0, proc.stderr[-3000:]
        lines[cell] = json.loads(proc.stdout.strip().splitlines()[-1])
    return lines


def test_the_rehearsals_print_the_new_metrics(traced_lines):
    # (g). On the CPU higgs_train takes the portable grower and runs per
    # iteration, higgs_dp4_train runs fused blocks there: both lines
    # carry the inside numbers, and the host's hold on the chip is read
    # off the trace, not off the program's spans
    one, four = (traced_lines[c]["metrics"] for c in
                 ("higgs_train", "higgs_dp4_train"))
    for metrics in (one, four):
        assert set(NEW) | set(HOLD) <= set(metrics)
        assert not set(RETIRED) & set(metrics)
        assert metrics["boosting.programs_built"]["value"] >= 10
        assert metrics["boosting.programs_built"]["unit"] == "count"
        for name in NEW[2:] + HOLD[:1]:
            assert metrics[name]["value"] > 0
        assert metrics["boosting.trace_s"]["value"] + \
            metrics["boosting.lower_s"]["value"] > 0


def test_idle_gaps_are_named_by_program_spans(traced_lines):
    labels = [g[0] for line in traced_lines.values()
              for g in line["breakdown"]["idle_gaps"]]
    assert any(lb.split(" / ")[1].startswith(("entry.", "boosting."))
               for lb in labels), labels
