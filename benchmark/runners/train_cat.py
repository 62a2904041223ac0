"""Runner for configurations of kind `train_cat`: one `lgb.train` call
on data with categorical columns, passed as `categorical_feature=`,
measured between block boundaries.

It does what `train.py` does and borrows what is the same: the callback
`_Window` (block boundaries, two syncs), the clocks, the last line; from
`rank.py` the traced window that keeps its capture. Its own are the
data (generators/expo.py: six integer-coded categorical columns beside
eleven numerical ones; level frequencies and label model fixed; the
training rows one fixed table of flights, the configuration's
`train_rows_seed`, under the SEED's codes for its carriers and
airports, which move no bin, so that every seed gives a run the same
trees to grow; the held-out rows the seed's own), the reference (reference/gbdt_cat_numpy.py: trees
0 and 1 at full size, the root over all columns, every leaf, and every
node that decides on a categorical column against the published rule
on that node's own histogram; held-out AUC), and one more reduction of
the capture: the device time of the operations the program names
`split.categorical`. Its readings have train.py's keys, `"kind":
"train"` among them, so every per-layer reader of a training cell reads
this one unchanged.

Before it draws a row it asks the program whether its model dump says
which category values a categorical node sends left
(`tree.HostTree.cat_values_left`: the reference's public JSON form,
`"threshold": "3||17||40"`). A program that cannot (the one before this
cell was added writes the index of the node's bitset there) cannot be
held against the reference, and is refused at once, with an error and
no record.
"""

from __future__ import annotations

import shutil
import time
from typing import Optional, Set

import numpy as np

from .. import harness, program_readings, trace_reduce
from ..generators.expo import (CATEGORICAL, COLUMNS, level_codes,
                               make_expo_like)
from ..harness import say
from ..reference import gbdt_cat_numpy
from .rank import _INSTRUCTION
from .rank import _TracedWindow as _KeptWindow
from .train import _Window

#: streams of the data generator: training rows, held-out rows
_TRAIN, _HELD_OUT = 0, 1
#: how the program names the categorical half of its split search: a
#: `jax.named_scope` in learner/split.py
CATEGORICAL_SCOPE = "split.categorical"


def scoped_instructions(gb, block: int, scope: str) -> Optional[Set[str]]:
    """The instructions of the fused block's compiled program whose
    `op_name` carries `scope`, by name (a chip capture names operations
    by their HLO text alone: `rank.objective_instructions` says why the
    program is lowered once more, after the window). None where the run
    had no fused block."""
    run = getattr(gb, "_fused_run", None)
    if run is None:
        return None
    t0 = time.perf_counter()
    import jax.numpy as jnp
    text = run.program.lower(*run.arguments(
        gb.train_score, jnp.asarray(0, jnp.int32),
        k=block)).compile().as_text()
    names = set()
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m and scope in m.group(2):
            names.add(m.group(1))
    say("scope %r: %d instructions of the fused block carry it (read in "
        "%.1fs)" % (scope, len(names), time.perf_counter() - t0))
    return names


def scope_busy_s(path: str, cpu_rehearsal: bool, instructions: Set[str]
                 ) -> Optional[float]:
    """Seconds, averaged over the chips, in which an instruction of
    `instructions` ran: the union of their events' intervals on each
    chip's operations line (an enclosing `while` covers its body once),
    as `rank.objective_busy_s` takes them; says which kinds of operation
    the time went to. None where nothing matches."""
    from collections import Counter
    from jax.profiler import ProfileData
    per_device, by_kind = [], Counter()
    for plane in ProfileData.from_file(path).planes:
        on_chip = trace_reduce._DEVICE_PLANE.match(plane.name)
        if not on_chip and not (cpu_rehearsal and
                                plane.name == trace_reduce._HOST_PLANE):
            continue
        spans = []
        for line in plane.lines:
            if not (line.name == trace_reduce._OPS_LINE if on_chip else
                    line.name.startswith("tf_XLAPjRtCpuClient")):
                continue
            for e in line.events:
                name = e.name.split(" = ", 1)[0].lstrip("%")
                if e.duration_ns > 0 and name in instructions:
                    spans.append((e.start_ns, e.start_ns + e.duration_ns))
                    by_kind[trace_reduce.op_name(e.name)] += e.duration_ns
        if spans:
            per_device.append(
                sum(b - a for a, b in trace_reduce.union(spans)) / 1e9)
    say("scope: device seconds by kind of the counted operations %s"
        % {k: round(v / 1e9, 4) for k, v in by_kind.most_common(6)})
    return sum(per_device) / len(per_device) if per_device else None


class _TracedWindow(_KeptWindow):
    """The traced window, read once more for a scope's device time."""

    def scope_s(self, gb, block: Optional[int], scope: str
                ) -> Optional[float]:
        """Read once, after lgb.train has returned; deletes the
        capture."""
        if self.path is None:
            return None
        try:
            names = scoped_instructions(gb, block, scope) if block else None
            return scope_busy_s(self.path, self.cpu_rehearsal,
                                names) if names else None
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)


def ask_the_program() -> None:
    """The one question (module docstring)."""
    from lightgbm_tpu.tree import HostTree
    if not hasattr(HostTree, "cat_values_left"):
        raise harness.BenchmarkError(
            "this program's dump_model does not say which category values "
            "a categorical node sends left (no tree.HostTree."
            "cat_values_left: its \"threshold\" there is the index of the "
            "node's bitset, where the reference's JSON gives \"a||b||c\"), "
            "so its trees cannot be held against gbdt_cat_numpy. The cell "
            "is not run on it.")


def _rule_params(resolved) -> dict:
    return dict(learning_rate=float(resolved.learning_rate),
                min_data_in_leaf=int(resolved.min_data_in_leaf),
                min_sum_hessian_in_leaf=float(
                    resolved.min_sum_hessian_in_leaf),
                lambda_l2=float(resolved.lambda_l2),
                cat_smooth=float(resolved.cat_smooth),
                cat_l2=float(resolved.cat_l2),
                max_cat_threshold=int(resolved.max_cat_threshold),
                max_cat_to_onehot=int(resolved.max_cat_to_onehot),
                min_data_per_group=int(resolved.min_data_per_group))


def agrees(got: dict, expect: dict) -> bool:
    """Whether one step's readings are inside the cell's limits."""
    return (got["root_gain_shortfall"] <= expect["root_gain_rtol"]
            and got["leaf_sum_err_root_ulps"]
            <= expect["leaf_sum_err_root_ulps"]
            and got["empty_leaves"] == 0
            and got["cat_infeasible_nodes"] == 0
            and got["cat_gain_shortfall_ulps"]
            <= expect["cat_gain_shortfall_ulps"])


def _check_against_reference(bst, X, y, bins, categorical, cfg, resolved
                             ) -> bool:
    """Boosting steps `expect.check_trees` against gbdt_cat_numpy, at
    full size: says what it found, returns whether every step agrees."""
    expect = cfg["expect"]
    steps = sorted(expect["check_trees"])
    if not steps:
        return True
    dump = bst.dump_model(num_iteration=max(steps) + 1)
    trees = [gbdt_cat_numpy.flatten_tree(t["tree_structure"])
             for t in dump["tree_info"]]
    ok, routed = True, {}
    for k in steps:
        t0 = time.perf_counter()
        got = gbdt_cat_numpy.check_step(
            k, trees, X, y, bins, categorical, routed=routed,
            slack_ulps=float(expect["cat_prefix_slack_ulps"]),
            **_rule_params(resolved))
        step_ok = agrees(got, expect)
        ok = ok and step_ok
        say("reference, tree %d (%.1fs): %s %s"
            % (k, time.perf_counter() - t0,
               "agrees" if step_ok else "DISAGREES", got))
    return ok


def run(cell: dict, *, seed: int, seconds: float, trace: bool,
        rehearsal: bool) -> dict:
    cfg, traffic = cell["config"], cell["traffic"]
    if traffic["loop"] != "job" or traffic.get("valid_rows"):
        raise harness.BenchmarkError(
            "a train_cat configuration runs a job with no valid set, not "
            "%r" % traffic)
    if rehearsal:
        cfg, traffic = harness.rehearsal_overlay(cfg, traffic)
    expect = {**cfg["expect"], **traffic.get("expect", {})}
    cfg = {**cfg, "expect": expect}
    clock = harness.start_clocks(rehearsal)
    tracer = _TracedWindow(trace, cpu_rehearsal=rehearsal)

    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.reliability import counters
    from lightgbm_tpu.utils.timer import global_timer
    say("imports done")
    ask_the_program()

    params = {"objective": cfg["objective"],
              "num_leaves": cfg["num_leaves"], "max_bin": cfg["max_bin"],
              "learning_rate": cfg["learning_rate"], "verbosity": -1}
    params.update(cfg.get("params", {}))
    params.update(traffic.get("params", {}))
    resolved = Config(dict(params))
    say("params %s" % params)

    # ---- data (module docstring), binned by the program
    rows = int(cfg["num_data"])
    categorical = [int(c) for c in cfg["categorical_feature"]]
    if int(cfg["num_features"]) != len(COLUMNS) or \
            categorical != list(CATEGORICAL):
        raise harness.BenchmarkError(
            "the configuration's columns are not the generator's: %d "
            "columns, categorical %s" % (len(COLUMNS), list(CATEGORICAL)))
    t0 = time.perf_counter()
    rows_seed = int(cfg["train_rows_seed"])
    X, y, threshold = make_expo_like(rows, seed, stream=_TRAIN,
                                     rows_seed=rows_seed)
    Xho, yho, _ = make_expo_like(int(cfg["held_out_rows"]), seed,
                                 stream=_HELD_OUT, threshold=threshold)
    data_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    dtrain = lgb.Dataset(X, label=y, categorical_feature=categorical,
                         params={"max_bin": cfg["max_bin"]})
    dtrain.construct()
    binning_s = time.perf_counter() - t0
    ingest = [sp["attrs"] for sp in program_readings.spans(
        {"kind": "train"}) or [] if sp["name"] == "ingest.construct"]
    say("data: %d x %d float32 (%d categorical; training rows of the "
        "fixed table %d, codes of seed %d: carriers %s..), label share "
        "%.4f, drawn in %.2fs, binned in %.2fs %s; ingest.construct %s"
        % (rows, X.shape[1], len(categorical), rows_seed, seed,
           level_codes(seed)["carrier"][:4].tolist(), float(y.mean()),
           data_s, binning_s,
           {k: round(v, 3) for k, v in global_timer.totals().items()
            if k.startswith("dataset_")}, ingest[-1:] or None))

    # ---- the job
    window = _Window(seconds, traffic, clock, tracer)
    t_call = time.perf_counter()
    bst = lgb.train(dict(params), dtrain, num_boost_round=1_000_000,
                    callbacks=[window])
    if window.t_end is None:
        raise harness.BenchmarkError(
            "lgb.train returned after %d trees before the window closed"
            % bst.current_iteration())
    gb = bst.gbdt
    window_s = window.t_end - window.t_start
    window_trees = window.trees_end - window.trees_start
    setup_s = window.t_start - harness.T0
    stats = getattr(gb, "_pipeline_stats", None)
    walls = np.diff([window.t_start] + [t for _, t in window.boundaries])
    say("window: %d trees in %.3fs after %d warm-up trees; host clock "
        "between its %d boundaries (unsynced): %s"
        % (window_trees, window_s, window.trees_start, len(walls),
           " ".join("%.2f" % w for w in walls)))
    c0, c1 = window.compiles_start, window.compiles_end
    say("compile: set-up %.1fs in %d programs (cache hits %d, misses %d); "
        "inside the window %d programs"
        % (c0["seconds"], c0["programs"], c1["hits"], c1["misses"],
           c1["programs"] - c0["programs"]))
    if stats is not None:
        say("pipeline: %s" % stats.as_dict())
    built = [sp["attrs"] for sp in program_readings.spans(
        {"kind": "train"}) or [] if sp["name"] == "boosting.build_program"]
    say("the growth program: %s" % (
        {k: built[-1].get(k) for k in ("hist_plan", "has_cat",
                                       "cat_columns", "cat_bins")}
        if built else None))

    # ---- attempted, failed
    leaves = np.asarray(jax.numpy.stack([t.num_leaves for t in gb.trees]))
    unpacked = [sp["attrs"] for sp in program_readings.spans(
        {"kind": "train"}) or [] if sp["name"] == "entry.unpack_block"]
    snap = counters.snapshot()
    degraded = int(getattr(gb, "_fused_failures", 0)) + \
        int(bool(getattr(gb, "_fused_disabled", False)))
    failed = int(snap["fallbacks"]) + int(snap["device_retries"]) + \
        degraded + int((leaves <= 1).sum())
    say("trees %d (min leaves %d; %s of %s nodes of the unpacked blocks "
        "decide on a categorical column), reliability counters %s, "
        "degraded blocks %d"
        % (len(leaves), leaves.min(),
           sum(a.get("cat_nodes", 0) for a in unpacked),
           sum(a.get("nodes", 0) for a in unpacked), snap, degraded))

    # ---- correct
    problems = []
    if c1["programs"] != c0["programs"]:
        problems.append("%d programs were built inside the window"
                        % (c1["programs"] - c0["programs"]))
    if expect.get("fused_pipelined"):
        if stats is None or not stats.blocks or \
                set(stats.block_sizes) != {int(resolved.fused_block_size)}:
            problems.append("not every block went through the fused, "
                            "pipelined executor at fused_block_size: %s"
                            % (stats and stats.block_sizes))
    if not _check_against_reference(bst, X, y, dtrain._binned.bins,
                                    categorical, cfg, resolved):
        problems.append("a checked tree disagrees with gbdt_cat_numpy")
    n_auc = int(expect["auc_trees"])
    if bst.current_iteration() < n_auc:
        problems.append("only %d trees, the AUC check wants %d"
                        % (bst.current_iteration(), n_auc))
    else:
        auc = gbdt_cat_numpy.auc(yho, bst.predict(
            Xho, num_iteration=n_auc, raw_score=True))
        say("held-out AUC after %d trees on %d rows: %.5f (floor %s)"
            % (n_auc, len(yho), auc, expect["auc_floor"]))
        if not auc > expect["auc_floor"]:
            problems.append("held-out AUC %.5f is not above %s"
                            % (auc, expect["auc_floor"]))
    for p in problems:
        say("NOT CORRECT: " + p)

    readings = {
        "kind": "train", "window_s": window_s, "window_trees": window_trees,
        "warm_trees": window.trees_start, "binning_s": binning_s,
        "data_s": data_s, "timers": global_timer.totals(),
        "compile_setup_s": c0["seconds"],
        "train_call_to_window_s": window.t_start - t_call,
        "trace": tracer.reduced,
        "memory_peak_bytes": harness.memory_peak_bytes(),
        "categorical_busy_s": tracer.scope_s(
            gb, window.block if window.fused else None, CATEGORICAL_SCOPE),
    }
    return {"correct": not problems, "attempted": int(window.trees_end),
            "failed": failed,
            "end_to_end": {"trees_per_s": window_trees / window_s,
                           "setup_s": setup_s},
            "readings": readings}
