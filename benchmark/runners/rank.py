"""Runner for configurations of kind `rank`: one `lgb.train` call of a
learning-to-rank job (`objective=lambdarank`, `group=` the query sizes)
on data from the seed, measured between block boundaries.

It does what `train.py` does, for a ranking job, and borrows from it
what is the same: the callback `_Window` (block boundaries, two syncs),
the clocks, the traced window, the last line. Its own are the data
(generators/mslr.py: the same multiset of query lengths and the same
label shares in every seed), the reference (reference/lambdarank_numpy.py:
trees 0 and 1 at full size under the ranking gradients, held-out
NDCG@10), and one more reduction of the capture: the device time of the
operations the program names `objective.<name>`, which the ten names a
breakdown keeps cannot carry. Its readings have train.py's keys,
`"kind": "train"` among them, so every per-layer reader of a training
cell reads this one unchanged.

Before it draws a row it asks the program what a tree's gradients will
compute over these query lengths (`objectives_rank.pair_slots`), and a
program that cannot say is refused there, with an error and no record:
the program before PR 32 pads every query to the longest, 2.96e10 pair
slots a tree here, and its cold run of this cell took 398 s (PERF.md
section 5), more than the driver gives a run.
"""

from __future__ import annotations

import os
import re
import shutil
import time
from typing import Optional, Set

import numpy as np

from .. import harness, program_readings, trace_reduce
from ..generators.mslr import make_mslr_like, query_lengths
from ..harness import say
from ..reference import gbdt_numpy, lambdarank_numpy
from .train import _Window

#: streams of the data generator: training queries, held-out queries
_TRAIN, _HELD_OUT = 0, 1
#: how the program names the gradient computation: a
#: `jax.named_scope("objective.<name>")` around it in the fused scan
#: (boosting/fused.py), and a jitted function of its own,
#: `objective_gradients`, where a tree is a dispatch of its own
OBJECTIVE_SCOPE = "objective."
OBJECTIVE_MODULE = "jit_objective_"
_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*metadata=\{[^}]*op_name="([^"]*)"')


def objective_instructions(gb, block: int) -> Optional[Set[str]]:
    """The instructions of the fused block's compiled program that the
    objective's scope produced, by name. A capture of the chip names
    every operation by its HLO text and carries no `op_name`, so the
    scope is read where it is kept: in the metadata of the compiled
    program, which is traced and lowered once more here (after the
    window; the compile is a cache hit). None where the run had no
    fused block."""
    run = getattr(gb, "_fused_run", None)
    if run is None:
        return None
    t0 = time.perf_counter()
    import jax.numpy as jnp
    # the arguments of a block's own dispatch, so that the compile is the
    # cache's entry and not a second one
    text = run.program.lower(*run.arguments(
        gb.train_score, jnp.asarray(0, jnp.int32),
        k=block)).compile().as_text()
    names = set()
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m and OBJECTIVE_SCOPE in m.group(2):
            names.add(m.group(1))
    say("objective: %d instructions of the fused block carry the scope "
        "%r (read in %.1fs)" % (len(names), OBJECTIVE_SCOPE,
                                time.perf_counter() - t0))
    return names


def objective_busy_s(path: str, cpu_rehearsal: bool,
                     instructions: Optional[Set[str]]) -> Optional[float]:
    """Seconds, averaged over the chips, in which the objective ran: the
    union of the intervals of the operations named in `instructions`
    (an enclosing `while` covers its body once) and of the programs
    whose own name carries OBJECTIVE_MODULE. Instruction names are one
    program's: an operation of another program under the same name is
    counted too (the programs beside a fused block are its trees' slices,
    microseconds each). None where nothing matches, as on a program
    that names no scope; a CPU rehearsal reads the thunks' events the
    same way."""
    from jax.profiler import ProfileData
    instructions = instructions or set()
    per_device = []
    for plane in ProfileData.from_file(path).planes:
        on_chip = trace_reduce._DEVICE_PLANE.match(plane.name)
        if not on_chip and not (cpu_rehearsal and
                                plane.name == trace_reduce._HOST_PLANE):
            continue
        spans = []
        for line in plane.lines:
            if on_chip and line.name == "XLA Modules":
                spans += [(e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events
                          if e.name.startswith(OBJECTIVE_MODULE)]
            elif on_chip and line.name == trace_reduce._OPS_LINE:
                spans += [(e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events
                          if e.name.split(" = ", 1)[0].lstrip("%")
                          in instructions]
            elif not on_chip and line.name.startswith("tf_XLAPjRtCpuClient"):
                for e in line.events:
                    module = str(dict(e.stats).get("hlo_module", ""))
                    if e.duration_ns > 0 and (
                            module.startswith(OBJECTIVE_MODULE) or
                            (e.name in instructions and module)):
                        spans.append((e.start_ns,
                                      e.start_ns + e.duration_ns))
        if spans:
            per_device.append(
                sum(b - a for a, b in trace_reduce.union(spans)) / 1e9)
    return sum(per_device) / len(per_device) if per_device else None


def pair_slots_a_tree(lengths: np.ndarray, truncation_level: int) -> int:
    """Elements of the pair tensors a tree's gradients will form, asked
    of the program before anything is drawn or built."""
    try:
        from lightgbm_tpu.objectives_rank import pair_slots
    except ImportError:
        longest = int(lengths.max())
        raise harness.BenchmarkError(
            "this program cannot say what its ranking objective computes a "
            "tree (no lightgbm_tpu.objectives_rank.pair_slots). Padded to "
            "the longest query, as before PR 32, these %d queries are "
            "%.3g pair slots; at the cell's own size, 2.96e10, that was "
            "3.3 s a tree and 398 s a cold run (PERF.md section 5), over "
            "a run's time limit. The cell is not run on it."
            % (len(lengths), len(lengths) * longest * longest)) from None
    return int(pair_slots(lengths, truncation_level))


class _TracedWindow(harness.TracedWindow):
    """The traced window, which keeps its capture until the run has read
    the objective's share off it too (`objective_s`)."""

    path: Optional[str] = None

    def stop(self) -> None:
        if not self.enabled or self._t0 is None or self._t1 is not None:
            return
        import jax
        self._t1 = time.perf_counter()
        jax.profiler.stop_trace()
        self.path = trace_reduce.find_xplane(self._dir)
        say("trace: %s (%.1f MB), window %.3f s"
            % (os.path.basename(self.path),
               os.path.getsize(self.path) / 1e6, self._t1 - self._t0))
        self.reduced = trace_reduce.reduce_trace(
            trace_reduce.load_xplane(self.path, self.cpu_rehearsal))
        self.reduced["window_s"] = self._t1 - self._t0

    def objective_s(self, gb, block: Optional[int]) -> Optional[float]:
        """Read once, after lgb.train has returned; deletes the capture."""
        if self.path is None:
            return None
        try:
            return objective_busy_s(
                self.path, self.cpu_rehearsal,
                objective_instructions(gb, block) if block else None)
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)


def _check_against_reference(bst, X, y, sizes, bins, cfg, resolved) -> bool:
    """Boosting steps `expect.check_trees` against lambdarank_numpy, at
    full size: says what it found, returns whether every step agrees."""
    expect = cfg["expect"]
    steps = sorted(expect["check_trees"])
    if not steps:
        return True
    dump = bst.dump_model(num_iteration=max(steps) + 1)
    trees = [gbdt_numpy.flatten_tree(t["tree_structure"])
             for t in dump["tree_info"]]
    ok, routed = True, {}
    for k in steps:
        t0 = time.perf_counter()
        got = lambdarank_numpy.check_step(
            k, trees, X, y, sizes, bins,
            learning_rate=float(resolved.learning_rate),
            min_data_in_leaf=int(resolved.min_data_in_leaf),
            min_sum_hessian_in_leaf=float(resolved.min_sum_hessian_in_leaf),
            lambda_l2=float(resolved.lambda_l2),
            sigmoid=float(resolved.sigmoid),
            truncation_level=int(resolved.lambdarank_truncation_level),
            norm=bool(resolved.lambdarank_norm), routed=routed)
        step_ok = (got["root_gain_shortfall"] <= expect["root_gain_rtol"]
                   and got["leaf_sum_err_root_ulps"]
                   <= expect["leaf_sum_err_root_ulps"]
                   and got["empty_leaves"] == 0)
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
            "a rank configuration runs a job with no valid set, not %r"
            % traffic)
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

    params = {"objective": cfg["objective"],
              "num_leaves": cfg["num_leaves"], "max_bin": cfg["max_bin"],
              "learning_rate": cfg["learning_rate"], "verbosity": -1}
    params.update(cfg.get("params", {}))
    params.update(traffic.get("params", {}))
    resolved = Config(dict(params))
    say("params %s" % params)

    # ---- the queries, and what the program will compute over them
    rows, nf = int(cfg["num_data"]), int(cfg["num_features"])
    lengths = query_lengths(int(cfg["num_queries"]), rows,
                            int(cfg["longest_query"]))
    say("objective: %.4g pair slots a tree over %d queries of %d to %d "
        "documents"
        % (pair_slots_a_tree(lengths,
                             int(resolved.lambdarank_truncation_level)),
           len(lengths), lengths.min(), lengths.max()))

    # ---- data from the seed, binned by the program
    t0 = time.perf_counter()
    X, y, sizes, cuts = make_mslr_like(lengths, nf, seed, stream=_TRAIN)
    n_ho = int(cfg["held_out_queries"])
    Xho, yho, sizes_ho, _ = make_mslr_like(
        lengths[(np.arange(n_ho) * len(lengths)) // n_ho], nf, seed,
        stream=_HELD_OUT, cuts=cuts)
    data_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    dtrain = lgb.Dataset(X, label=y, group=sizes,
                         params={"max_bin": cfg["max_bin"]})
    dtrain.construct()
    binning_s = time.perf_counter() - t0
    say("data: %d x %d float32 under %d queries of %d to %d documents, "
        "label shares %s, drawn in %.2fs, binned in %.2fs %s"
        % (rows, nf, len(sizes), sizes.min(), sizes.max(),
           np.round(np.bincount(y.astype(np.int64)) / rows, 4).tolist(),
           data_s, binning_s,
           {k: round(v, 3) for k, v in global_timer.totals().items()
            if k.startswith("dataset_")}))

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
    plan = [sp["attrs"].get("hist_plan") for sp in program_readings.spans(
        {"kind": "train"}) or [] if sp["name"] == "boosting.build_program"]
    say("histogram passes of the growth program: %s" % (plan[-1:] or None))

    # ---- attempted, failed
    leaves = np.asarray(jax.numpy.stack([t.num_leaves for t in gb.trees]))
    snap = counters.snapshot()
    degraded = int(getattr(gb, "_fused_failures", 0)) + \
        int(bool(getattr(gb, "_fused_disabled", False)))
    failed = int(snap["fallbacks"]) + int(snap["device_retries"]) + \
        degraded + int((leaves <= 1).sum())
    say("trees %d (min leaves %d), reliability counters %s, degraded "
        "blocks %d" % (len(leaves), leaves.min(), snap, degraded))

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
    if not _check_against_reference(bst, X, y, sizes, dtrain._binned.bins,
                                    cfg, resolved):
        problems.append("a checked tree disagrees with lambdarank_numpy")
    n_ndcg = int(expect["ndcg_trees"])
    if bst.current_iteration() < n_ndcg:
        problems.append("only %d trees, the NDCG check wants %d"
                        % (bst.current_iteration(), n_ndcg))
    else:
        at = int(expect["ndcg_at"])
        ndcg = lambdarank_numpy.ndcg_at_k(
            yho, bst.predict(Xho, num_iteration=n_ndcg, raw_score=True),
            sizes_ho, at)
        say("held-out NDCG@%d after %d trees on %d queries (%d documents): "
            "%.5f (floor %s; unranked %.5f)"
            % (at, n_ndcg, len(sizes_ho), len(yho), ndcg,
               expect["ndcg_floor"],
               lambdarank_numpy.ndcg_at_k(yho, np.zeros(len(yho)),
                                          sizes_ho, at)))
        if not ndcg > expect["ndcg_floor"]:
            problems.append("held-out NDCG@%d %.5f is not above %s"
                            % (at, ndcg, expect["ndcg_floor"]))
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
        "objective_busy_s": tracer.objective_s(
            gb, window.block if window.fused else None),
    }
    return {"correct": not problems, "attempted": int(window.trees_end),
            "failed": failed,
            "end_to_end": {"trees_per_s": window_trees / window_s,
                           "setup_s": setup_s},
            "readings": readings}
