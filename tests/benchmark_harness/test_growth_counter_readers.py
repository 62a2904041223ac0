"""The four readers of the growth counters
(`benchmark/layer_metrics/growth_*passes_per_tree.py`,
`growth_*_live_row_share.py`): each against a hand-made ring, against a
program whose spans lack the attributes (the parent's), against what
`BENCHMARK.json` says of it, and fed by one live `lgb.train` through
the fused MXU path with no help."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import harness, program_readings  # noqa: E402

NEW = ["growth.passes_per_tree", "growth.offschedule_passes_per_tree",
       "growth.onehot_live_row_share", "growth.grouped_live_row_share"]
READERS = harness.layer_metric_readers()


def _unpack(i, iter_, k=2, **ran):
    return {"name": "entry.unpack_block", "id": i, "parent_id": 0,
            "ts": float(i), "dur": 0.5,
            "attrs": dict(iter=iter_, k=k, programs=0, waited_ms=400.0,
                          nodes=28, cat_nodes=0, **ran)}


def _ran(onehot, grouped, bridge, fixup, onehot_rows, grouped_rows,
         trees=2, rows=1000):
    return dict(passes=onehot + grouped, onehot_passes=onehot,
                grouped_passes=grouped, bridge_passes=bridge,
                fixup_iters=fixup, onehot_rows=onehot_rows,
                grouped_rows=grouped_rows, leaves_grown=60, trees=trees,
                rows=rows)


#: a warm-up block of two trees (off its schedule, and not counted),
#: then a window of two blocks: four trees, 12 + 10 one-hot and 7 + 11
#: grouped passes, 1 + 2 bridges, 0 + 3 fixup iterations
RING = [
    _unpack(1, 0, **_ran(12, 30, 2, 20, 9000, 2000)),
    {"name": "entry.block", "id": 2, "parent_id": 0, "ts": 2.0,
     "dur": 1.0, "attrs": {"iter": 2, "k": 2}},
    _unpack(3, 2, **_ran(12, 7, 1, 0, 5400, 1400)),
    _unpack(4, 4, **_ran(10, 11, 2, 3, 5600, 400)),
]
WINDOW = {"kind": "train", "warm_trees": 2, "window_trees": 4,
          "timers": {}, "spans": RING, "compile_events": []}


def _read(name, readings):
    return READERS[name].read(readings)


@pytest.mark.parametrize("name,want", [
    ("growth.passes_per_tree", (19 + 21) / 4),
    ("growth.offschedule_passes_per_tree", (1 + 0 + 2 + 3) / 4),
    ("growth.onehot_live_row_share", 100.0 * 11000 / (22 * 1000)),
    ("growth.grouped_live_row_share", 100.0 * 1800 / (18 * 1000)),
])
def test_a_reader_against_a_hand_made_ring(name, want):
    assert _read(name, WINDOW) == pytest.approx(want)


def test_a_share_weighs_each_block_by_its_own_rows():
    # (a reader needs nothing but the span: `rows` rides on each)
    ring = [_unpack(1, 2, **_ran(2, 1, 0, 0, 1500, 100, rows=1000)),
            _unpack(2, 4, **_ran(4, 1, 0, 0, 600, 30, rows=100))]
    r = dict(WINDOW, spans=ring)
    assert _read("growth.onehot_live_row_share", r) == \
        pytest.approx(100.0 * 2100 / (2 * 1000 + 4 * 100))
    assert _read("growth.grouped_live_row_share", r) == \
        pytest.approx(100.0 * 130 / (1000 + 100))


def test_a_window_with_no_pass_of_a_formulation_has_no_share_of_it():
    ring = [_unpack(1, 2, **_ran(12, 0, 0, 0, 5000, 0))]
    r = dict(WINDOW, spans=ring)
    assert _read("growth.grouped_live_row_share", r) is None
    assert _read("growth.onehot_live_row_share", r) == \
        pytest.approx(100.0 * 5000 / 12000)
    assert _read("growth.passes_per_tree", r) == 6.0
    assert _read("growth.offschedule_passes_per_tree", r) == 0.0


@pytest.mark.parametrize("name", NEW)
def test_a_reader_with_nothing_to_read_gives_none(name, monkeypatch):
    # the parent's program: the span is there, the counters are not
    bare = [dict(s, attrs={k: v for k, v in s["attrs"].items()
                           if k in ("iter", "k", "programs", "waited_ms",
                                    "nodes", "cat_nodes")})
            for s in RING]
    assert _read(name, dict(WINDOW, spans=bare)) is None
    # one block of the window without them is no reading either
    assert _read(name, dict(WINDOW, spans=RING[:3] + bare[3:])) is None
    # no readings at all; a serving run; a window that holds no block;
    # a run with no window
    assert _read(name, {}) is None
    assert _read(name, dict(WINDOW, kind="serve")) is None
    assert _read(name, dict(WINDOW, spans=[])) is None
    assert _read(name, dict(WINDOW, warm_trees=8)) is None
    assert _read(name, dict(WINDOW, warm_trees=None)) is None
    assert _read(name, dict(WINDOW, window_trees=0)) is None
    # a program with no span ring to read: nothing, and no exception
    monkeypatch.setattr(program_readings, "_registry", lambda: None)
    assert _read(name, {"kind": "train", "warm_trees": 2,
                        "window_trees": 4, "timers": {}}) is None


def test_the_readers_say_what_benchmark_json_says():
    entries = {m["name"]: m for m in
               json.load(open(os.path.join(REPO, "BENCHMARK.json")))
               ["per_layer"]}
    assert [n for n in entries if n in NEW] == NEW      # in this order
    for name in NEW:
        mod, m = READERS[name], entries[name]
        assert (mod.UNIT, mod.BETTER, mod.LAYER, mod.SOURCE, mod.MOVES,
                mod.WORKLOADS) == (m["unit"], m["better"], m["layer"],
                                   m["source"], m["moves"],
                                   m.get("workloads"))
        assert mod.SOURCE == "program_counter"
        # every training cell runs the grower
        assert "workloads" not in m


def test_the_live_ring_feeds_the_readers(monkeypatch):
    """A fused, pipelined lgb.train in this process on the growth
    program the chip runs (the MXU grower, interpreted; the learner
    resolved under the backend name a TPU reports): the readers find
    the counters on the program's own spans with no help, and the
    executor's view of the run carries them block by block."""
    import jax
    import numpy as np
    import lightgbm_tpu as lgb
    from lightgbm_tpu.boosting.gbdt import GBDT
    from lightgbm_tpu.observability import registry as obs
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(GBDT, "_mxu_interpret", True, raising=False)
    obs.disable()
    obs.reset()
    rng = np.random.RandomState(5)
    X = rng.randn(1200, 6).astype(np.float32)
    y = (X[:, 0] + X[:, 1] > 0).astype(np.float32)
    bst = lgb.train({"objective": "binary", "num_leaves": 7, "max_bin": 31,
                     "verbosity": -1, "fused_block_size": 3,
                     "min_data_in_leaf": 5},
                    lgb.Dataset(X, label=y), 9)
    r = {"kind": "train", "warm_trees": 3, "window_trees": 6,
         "timers": {}}
    got = {name: _read(name, r) for name in NEW}
    blocks = [s["attrs"] for s in obs.trace.spans()
              if s["name"] == "entry.unpack_block"]
    assert [a["iter"] for a in blocks] == [0, 3, 6]
    assert all(a["trees"] == 3 and a["rows"] == 1200 for a in blocks)
    window = blocks[1:]
    passes = sum(a["passes"] for a in window)
    # a tree of seven leaves runs its root pass and more
    assert got["growth.passes_per_tree"] == passes / 6 > 1
    assert got["growth.offschedule_passes_per_tree"] == sum(
        a["bridge_passes"] + a["fixup_iters"] for a in window) / 6
    assert passes == sum(a["onehot_passes"] + a["grouped_passes"]
                         for a in window)
    # (at this size every pass is one-hot: the root pass sweeps live
    # rows only, a later pass at most half)
    assert all(a["grouped_passes"] == 0 for a in window)
    assert got["growth.grouped_live_row_share"] is None
    assert 100.0 / (passes / 6) <= got["growth.onehot_live_row_share"] < 100
    # PipelineStats: an entry carries the counts of the block unpacked
    # inside it (the block before); the run's last block is unpacked
    # after the loop and is on the ring alone
    stats = bst.gbdt._pipeline_stats.as_dict()
    assert stats["blocks"] == 3 and len(stats["device_ms"]) == 3
    assert stats["passes"] == [None, blocks[0]["passes"],
                               blocks[1]["passes"]]
    assert stats["fixup_iters"] == [None, blocks[0]["fixup_iters"],
                                    blocks[1]["fixup_iters"]]
    obs.reset()
