"""trace_reduce against a recorded trace and against arithmetic by hand.

benchmark/testdata/trace_v5e_train_slice.json.gz is 34 ms of a capture
taken on a TPU v5e in PR 22 (lgb.train, 200,000 x 28, 31 leaves): the
end of one fused block, the gap in which the host unpacks its trees,
and the start of the next, cut with `python3 -m benchmark.trace_reduce
<capture> --cut 318:352 <out>`.
"""

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import trace_reduce as tr  # noqa: E402

RECORDED = os.path.join(REPO, "benchmark", "testdata",
                        "trace_v5e_train_slice.json.gz")


@pytest.fixture(scope="module")
def recorded():
    return tr.load(RECORDED)


def test_the_recorded_trace_gives_its_known_numbers(recorded):
    got = tr.reduce_trace(recorded)
    assert got["devices"] == 1 and got["events"] == 2467
    assert got["busy_s"] == pytest.approx(27_524_820e-9, rel=1e-12)
    assert got["span_s"] == pytest.approx(33_680_500e-9, rel=1e-12)
    # the busy share of the slice: 81.7%
    assert got["busy_s"] / got["span_s"] == pytest.approx(0.81724, abs=1e-5)
    assert got["mosaic_s"] == pytest.approx(19_929_916e-9, rel=1e-12)
    assert got["collective_s"] == 0.0
    top = got["device_ops"]
    assert [n for n, _ in top[:4]] == ["fused_route_hist_mxu", "copy",
                                       "pad", "slice_reduce_fusion"]
    assert top[0][1] == pytest.approx(18_384_212e-9, rel=1e-12)
    assert len(top) == 10
    # the idle time goes to what the host was doing: unpacking trees
    labels = dict(got["idle_gaps"])
    assert labels["- / PjitFunction(squeeze)"] == pytest.approx(1_074_855e-9)
    assert sum(labels.values()) == pytest.approx(
        got["span_s"] - got["busy_s"], rel=1e-9)


def test_the_reduction_agrees_with_a_rasterisation(recorded):
    """The same numbers the slow way: one cell a nanosecond, each owned
    by the shortest operation that covers it."""
    ops = recorded["devices"][0]["ops"]
    lo = min(s for _, s, _ in ops)
    hi = max(s + d for _, s, d in ops)
    owner = np.full(int(hi - lo) + 1, -1)
    owned_for = np.full(len(owner), np.inf)
    for i, (_, s, d) in enumerate(ops):
        a, b = int(s - lo), int(s - lo + d)
        inner = owned_for[a:b] > d
        owner[a:b][inner] = i
        owned_for[a:b][inner] = d
    cells = np.bincount(owner[owner >= 0], minlength=len(ops))
    by_name = {}
    for (text, _, _), c in zip(ops, cells):
        by_name[tr.op_name(text)] = by_name.get(tr.op_name(text), 0) + c
    got = tr.reduce_trace(recorded, top=1000)
    assert got["busy_s"] * 1e9 == pytest.approx((owner >= 0).sum())
    for name, seconds in got["device_ops"]:
        assert seconds * 1e9 == pytest.approx(by_name[name]), name
    mosaic = sum(c for (text, _, _), c in zip(ops, cells)
                 if 'custom_call_target="tpu_custom_call"' in text)
    assert got["mosaic_s"] * 1e9 == pytest.approx(mosaic)


def test_compact_form_round_trips(recorded, tmp_path):
    path = str(tmp_path / "again.json.gz")
    tr.dump_compact(recorded, path)
    assert tr.reduce_trace(tr.load(path)) == tr.reduce_trace(recorded)
    half = tr.cut(recorded, 318e6, 335e6)
    assert 0 < len(half["devices"][0]["ops"]) < 2467


_WHILE = ("%while.324 = (s32[]{:T(128)}, f32[200000]{0:T(1024)S(1)}) "
          "while((s32[]{:T(128)}, f32[200000]{0}) %tuple.1), "
          "condition=%cond, body=%body")
_KERNEL = ("%fused_route_hist_mxu.32 = (f32[1,10,7168]{2,1,0:T(8,128)S(1)}, "
           "s32[200704,2]{1,0:T(8,128)}) custom-call(s32[200704,1]{1,0} "
           '%copy.1448), custom_call_target="tpu_custom_call"')
_ALLOC = ('%custom-call.346 = u32[10,62,8]{1,2,0:T(8,128)} custom-call(), '
          'custom_call_target="AllocateBuffer"')
_AR = ("%all-reduce.7 = f32[256,28,256]{2,1,0:T(8,128)} all-reduce("
       "f32[256,28,256]{2,1,0} %fusion.3), replica_groups={{0,1,2,3}}, "
       "to_apply=%add")
_ARS = ("%all-reduce-start.2 = (f32[8]{0}, f32[8]{0}) all-reduce-start("
        "f32[8]{0} %x), replica_groups={{0,1,2,3}}, to_apply=%add")


def test_names_and_opcodes_of_hlo_text():
    assert tr.op_name(_WHILE) == "while" and tr.opcode(_WHILE) == "while"
    assert tr.op_name(_KERNEL) == "fused_route_hist_mxu"
    assert tr.opcode(_KERNEL) == "custom-call"
    assert tr.op_name(_ALLOC) == "custom-call"
    assert tr.opcode(_AR) == "all-reduce" and tr.is_collective(_AR)
    assert tr.is_collective(_ARS) and not tr.is_collective(_KERNEL)
    # a name that is no HLO text (XLA:CPU's thunks in a rehearsal)
    assert tr.op_name("dot_general.1") == "dot_general"
    assert tr.opcode("dot_general.1") == ""


def test_self_time_union_collectives_and_gaps_by_hand():
    ms = 1e6
    chip0 = {"id": 0, "async": [(_ARS, 12 * ms, 6 * ms)], "ops": [
        (_WHILE, 0, 10 * ms),                 # 10 ms, two children
        (_KERNEL, 1 * ms, 4 * ms),
        (_ALLOC, 6 * ms, 1 * ms),
        (_AR, 14 * ms, 2 * ms),               # inside the async span
        (_KERNEL, 30 * ms, 5 * ms)]}
    chip1 = {"id": 1, "async": [], "ops": [(_KERNEL, 0, 20 * ms)]}
    host = [("bench.train.after_tree", 5 * ms, 30 * ms),
            ("PjitFunction(squeeze)", 19 * ms, 4 * ms),
            ("bench.loadgen.wait", 11 * ms, 0.5 * ms)]
    got = tr.reduce_trace({"devices": [chip0, chip1], "host": host})
    assert got["busy_s_by_device"] == pytest.approx([0.017, 0.020])
    assert got["busy_s"] == pytest.approx(0.0185)          # the mean
    ops = dict(got["device_ops"])                          # per chip, mean
    assert ops["while"] == pytest.approx((10 - 4 - 1) / 2 / 1e3)
    assert ops["fused_route_hist_mxu"] == pytest.approx((4 + 5 + 20) / 2e3)
    assert ops["custom-call"] == pytest.approx(1 / 2e3)
    assert got["mosaic_s"] == pytest.approx(29 / 2e3)      # not AllocateBuffer
    # chip 0: all-reduce 14-16 lies inside all-reduce-start 12-18: 6 ms
    assert got["collective_s"] == pytest.approx(0.006)
    # chip 0's gaps: 10-14 (host: our span only) and 16-30 (its middle,
    # 23 ms, is just past the unpacking: our span, no JAX event)
    assert got["idle_gaps"] == [
        ["bench.train.after_tree / -", pytest.approx(0.018)]]
    assert got["longest_gap_s"] == pytest.approx(0.014)
    assert tr.reduce_trace({"devices": [], "host": []})["busy_s"] == 0.0
    assert tr.union([(0, 2), (1, 3), (5, 6), (6, 6)]) == [(0, 3), (5, 6)]
    # a child that overruns its parent is clipped to it
    assert tr.self_times([("a", 0, 10), ("b", 8, 5)]) == [8, 2]
