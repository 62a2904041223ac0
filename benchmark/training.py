"""The training job that every runner of a training kind shares: one
`lgb.train` call on data from the seed, measured between block
boundaries, its first trees held against a plain reference.

The job is the one a user starts: `lgb.Dataset(X, label=y, ...)`,
`lgb.train(params, ...)` with a `num_boost_round` it never reaches, and
one callback, this benchmark's, which reads the clock and ends the run
with EarlyStopException. The callback carries `block_safe = True`
(engine.train drops to one dispatch per tree for any callback that does
not) and acts only at block boundaries, so the run compiles no tail
length. It syncs twice, on the training scores: where the window opens
(after the blocks that compile: the first builds the growth program,
the host work beside the second builds the small programs that unpack
a block's trees) and where it closes (the first boundary the host
reaches at or after --seconds; the device finishes the block in flight
and that block counts). Between the two it reads the host clock and
nothing else.

A runner of a training kind is a `Task`: what its deployment brings
and nothing more (a question for the program before any row is drawn,
its data and the `lgb.Dataset` arguments, extra checks, its reference
on the checked trees, its held-out quality, what it reads off the
traced window, what its rehearsal prints) as `TASK`, an instance, which
`harness.load_runner` returns for the kind. `Task.run` is the job. The
readings carry `"kind": "train"` and the same keys under every task, so
every per-layer reader of a training cell reads each one alike.
"""

from __future__ import annotations

import os
import re
import shutil
import time
from collections import Counter
from typing import Dict, List, Optional, Set, Tuple

import numpy as np

from . import harness, trace_reduce
from .harness import say

#: an instruction of a compiled program's text, by name, with the
#: `op_name` of its metadata
INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*metadata=\{[^}]*op_name="([^"]*)"')


class Window:
    """The benchmark's callback (see the module docstring)."""

    block_safe = True
    before_iteration = False
    order = 0

    def __init__(self, seconds: float, traffic: dict, clock, tracer):
        self.seconds = seconds
        self.traffic = traffic
        self.clock = clock
        self.tracer = tracer
        self.block: Optional[int] = None
        self.fused = False
        self.warm_trees = 0
        self.trace_trees = 0
        self.t_start = self.t_end = None
        self.trees_start = self.trees_end = 0
        self.compiles_start = self.compiles_end = None
        self.boundaries: List[tuple] = []      # (trees done, host clock)
        self._span = None

    def _first_call(self, env) -> None:
        gb = env.model.gbdt
        # run_pipelined attaches its stats before the first dispatch;
        # the per-iteration loop never does
        self.fused = getattr(gb, "_pipeline_stats", None) is not None
        if self.fused:
            self.block = int(env.model.config.fused_block_size)
            self.warm_trees = self.block * int(self.traffic["warmup_blocks"])
            self.trace_trees = self.block * int(self.traffic["trace_blocks"])
        else:
            self.block = 1
            self.warm_trees = int(
                self.traffic["warmup_trees_per_iteration_path"])
            self.trace_trees = int(
                self.traffic["trace_trees_per_iteration_path"])

    def _next_span(self, done: Optional[int]) -> None:
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None
        if done is not None and self.tracer.enabled:
            self._span = self.tracer.span("bench.train.after_tree",
                                          tree=done)
            self._span.__enter__()

    def __call__(self, env) -> None:
        import jax
        from lightgbm_tpu.callback import EarlyStopException
        if self.block is None:
            self._first_call(env)
        done = env.iteration + 1
        if done % self.block:
            return
        gb = env.model.gbdt
        if self.t_start is None:
            if done < self.warm_trees:
                return
            jax.block_until_ready(gb.train_score)
            self.compiles_start = self.clock.read()
            self.trees_start = done
            self.tracer.start()
            self.t_start = time.perf_counter()
            self._next_span(done)
            return
        now = time.perf_counter()
        self.boundaries.append((done, now))
        self._next_span(done)
        if self.tracer.enabled:
            over = done - self.trees_start >= self.trace_trees
        else:
            over = now - self.t_start >= self.seconds
        if over:
            jax.block_until_ready(gb.train_score)
            self.t_end = time.perf_counter()
            self._next_span(None)
            self.tracer.stop()
            self.trees_end = done
            self.compiles_end = self.clock.read()
            raise EarlyStopException(env.iteration, [])


# ----------------------------------------------------------------------
# the device time of a named scope, read off the traced window
class KeptTrace(harness.TracedWindow):
    """The traced window, which keeps its capture until the run has read
    a scope's device time off it too (`read`)."""

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

    def read(self, fn):
        """fn(path of the capture), once, after lgb.train has returned;
        deletes the capture. None where nothing was traced."""
        if self.path is None:
            return None
        try:
            return fn(self.path)
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)


def scoped_instructions(gb, block: int, scope: str
                        ) -> Optional[Tuple[Set[str], float]]:
    """The instructions of the fused block's compiled program whose
    `op_name` carries `scope`, by name, and the seconds it took to read
    them. A capture of the chip names every operation by its HLO text and
    carries no `op_name`, so the scope is read where it is kept: in the
    metadata of the compiled program, which is traced and lowered once
    more here (after the window; the compile is a cache hit). None where
    the run had no fused block."""
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
        m = INSTRUCTION.match(line)
        if m and scope in m.group(2):
            names.add(m.group(1))
    return names, time.perf_counter() - t0


def scope_busy_s(path: str, cpu_rehearsal: bool, instructions: Set[str],
                 module_prefix: Optional[str] = None
                 ) -> Tuple[Optional[float], Counter]:
    """Seconds, averaged over the chips, in which the scope ran: the union
    of the intervals of the operations named in `instructions` (an
    enclosing `while` covers its body once) and of the programs whose own
    name starts with `module_prefix`; and the device nanoseconds of the
    named operations by kind of operation. Instruction names are one
    program's: an operation of another program under the same name is
    counted too (the programs beside a fused block are microseconds
    each). None where nothing matches, as on a program that names no
    scope; a CPU rehearsal reads the thunks' events the same way."""
    from jax.profiler import ProfileData
    per_device, by_kind = [], Counter()
    for plane in ProfileData.from_file(path).planes:
        on_chip = trace_reduce._DEVICE_PLANE.match(plane.name)
        if not on_chip and not (cpu_rehearsal and
                                plane.name == trace_reduce._HOST_PLANE):
            continue
        spans = []
        for line in plane.lines:
            if on_chip and line.name == "XLA Modules":
                spans += [(e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events if module_prefix
                          and e.name.startswith(module_prefix)]
                continue
            if not (line.name == trace_reduce._OPS_LINE if on_chip else
                    line.name.startswith("tf_XLAPjRtCpuClient")):
                continue
            for e in line.events:
                name = e.name.split(" = ", 1)[0].lstrip("%")
                module = "" if on_chip else \
                    str(dict(e.stats).get("hlo_module", ""))
                if e.duration_ns > 0 and (name in instructions or (
                        module_prefix and module.startswith(module_prefix))):
                    spans.append((e.start_ns, e.start_ns + e.duration_ns))
                    by_kind[trace_reduce.op_name(e.name)] += e.duration_ns
        if spans:
            per_device.append(
                sum(b - a for a, b in trace_reduce.union(spans)) / 1e9)
    return (sum(per_device) / len(per_device) if per_device else None,
            by_kind)


# ----------------------------------------------------------------------
# the job
class Task:
    """One kind of training deployment. A subclass states what it brings
    (the methods below that raise or return nothing here); `run` is the
    job, the same for every kind."""

    kind = "train"
    #: whether a traffic mix may ask for a valid set (`valid_set`)
    takes_valid_set = False
    #: the traced window's class (`KeptTrace` where `readings` reads it)
    tracer = harness.TracedWindow
    #: the plain reference, by name, and how it reads a dumped tree
    reference = ""
    flatten_tree = None
    #: what one checked tree is held to: (reading of `check_step`, key of
    #: `expect` that holds its limit, or None for 0)
    limits = (("root_gain_shortfall", "root_gain_rtol"),
              ("leaf_sum_err_root_ulps", "leaf_sum_err_root_ulps"),
              ("empty_leaves", None))
    #: the held-out quality, and the key of `expect` that says after how
    #: many trees it is read
    quality, quality_trees = "AUC", "auc_trees"

    # ---- what a task brings
    def preflight(self, cfg: dict, resolved) -> None:
        """A question for the program before any row is drawn; raises
        harness.BenchmarkError where it cannot say."""

    def draw(self, cfg: dict, seed: int) -> dict:
        """The data from the seed: `X`, `y`, `dataset` (further
        `lgb.Dataset` arguments) and whatever the checks need."""
        raise NotImplementedError

    def say_data(self, cfg, seed, data, data_s, binning_s, binned) -> None:
        """The run's `data:` line."""
        raise NotImplementedError

    def valid_set(self, cfg, traffic, seed, data):
        """(X, y) of the valid set a traffic mix asks for."""
        raise NotImplementedError

    def say_program(self) -> None:
        """Lines on the growth program, after the window's."""

    def leaves_note(self) -> str:
        """Said beside the trees' least leaf count."""
        return ""

    def problems(self, gb, expect: dict) -> List[str]:
        """Checks of `correct` besides the job's own."""
        return []

    def check_step(self, k, trees, data, bins, cfg, resolved, routed
                   ) -> dict:
        """The reference's readings of boosting step k."""
        raise NotImplementedError

    def held_out(self, bst, data, n: int, expect: dict
                 ) -> Tuple[str, float, float]:
        """The held-out quality after n trees (its full name, value and
        floor); says it."""
        raise NotImplementedError

    def readings(self, gb, window: Window, tracer) -> dict:
        """Readings beyond the job's own."""
        return {}

    # ---- what a rehearsal prints, asked of the runner by its tests; a
    # task adds its own to these
    def rehearsal_says(self, cell: dict) -> Tuple[str, ...]:
        """Words of a rehearsal's earlier lines."""
        cfg, _ = harness.rehearsal_overlay(cell["config"], cell["traffic"])
        expect = {**cfg["expect"], **cell["traffic"].get("expect", {})}
        return ("cache hits", "boundaries (unsynced)",
                "held-out %s" % self.quality) + tuple(
            "reference, tree %d" % k for k in expect["check_trees"])

    def rehearsal_reads(self, cell: dict) -> Dict[str, tuple]:
        """Per-layer metrics a traced rehearsal reports, each with the
        range (lo, hi] its value lies in (hi None: no upper end)."""
        return {name: (0, None) for name in (
            "growth.device_ms_per_tree", "boosting.programs_built",
            "boosting.init_s", "ingest.binning_s")}

    # ---- the job
    def agrees(self, got: dict, expect: dict) -> bool:
        """Whether one checked tree's readings are inside its limits."""
        return all(got[key] <= (expect[limit] if limit else 0)
                   for key, limit in self.limits)

    def check_trees(self, bst, data, bins, cfg, resolved,
                    compared: Optional[list] = None) -> bool:
        """Boosting steps `expect.check_trees` against the reference, at
        full size: says what it found, returns whether every step
        agrees."""
        expect = cfg["expect"]
        steps = sorted(expect["check_trees"])
        if not steps:
            return True
        dump = bst.dump_model(num_iteration=max(steps) + 1)
        trees = [self.flatten_tree(t["tree_structure"])
                 for t in dump["tree_info"]]
        ok, routed = True, {}
        for k in steps:
            t0 = time.perf_counter()
            got = self.check_step(k, trees, data, bins, cfg, resolved,
                                  routed)
            step_ok = self.agrees(got, expect)
            ok = ok and step_ok
            say("reference, tree %d (%.1fs): %s %s"
                % (k, time.perf_counter() - t0,
                   "agrees" if step_ok else "DISAGREES", got))
            if compared is not None:
                compared += [("tree%d.%s" % (k, key), got[key], "max",
                              expect[limit] if limit else 0)
                             for key, limit in self.limits]
        return ok

    def run(self, cell: dict, *, seed: int, seconds: float, trace: bool,
            rehearsal: bool) -> dict:
        cfg, traffic = cell["config"], cell["traffic"]
        if traffic["loop"] != "job" or (traffic.get("valid_rows") and
                                        not self.takes_valid_set):
            raise harness.BenchmarkError(
                "a %s configuration runs a job%s, not %r"
                % (self.kind, "" if self.takes_valid_set else
                   " with no valid set", traffic))
        if rehearsal:
            cfg, traffic = harness.rehearsal_overlay(cfg, traffic)
        # a mix that changes what a tree is (bagging, feature sampling)
        # says which checks still hold for it
        expect = {**cfg["expect"], **traffic.get("expect", {})}
        cfg = {**cfg, "expect": expect}
        clock = harness.start_clocks(rehearsal)
        tracer = self.tracer(trace, cpu_rehearsal=rehearsal)

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
        self.preflight(cfg, resolved)

        # ---- data from the seed, binned by the program
        t0 = time.perf_counter()
        data = self.draw(cfg, seed)
        data_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        dtrain = lgb.Dataset(data["X"], label=data["y"],
                             params={"max_bin": cfg["max_bin"]},
                             **data.get("dataset", {}))
        dtrain.construct()
        binning_s = time.perf_counter() - t0
        self.say_data(cfg, seed, data, data_s, binning_s,
                      {k: round(v, 3) for k, v in global_timer.totals().items()
                       if k.startswith("dataset_")})
        valid_sets = None
        if traffic.get("valid_rows"):
            Xva, yva = self.valid_set(cfg, traffic, seed, data)
            valid_sets = [lgb.Dataset(Xva, label=yva, reference=dtrain)]
            valid_sets[0].construct()

        # ---- the job
        window = Window(seconds, traffic, clock, tracer)
        t_call = time.perf_counter()
        bst = lgb.train(dict(params), dtrain, num_boost_round=1_000_000,
                        valid_sets=valid_sets, callbacks=[window])
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
        say("compile: set-up %.1fs in %d programs (cache hits %d, misses "
            "%d); inside the window %d programs"
            % (c0["seconds"], c0["programs"], c1["hits"], c1["misses"],
               c1["programs"] - c0["programs"]))
        if stats is not None:
            say("pipeline: %s" % stats.as_dict())
        self.say_program()

        # ---- attempted, failed
        leaves = np.asarray(jax.numpy.stack([t.num_leaves for t in gb.trees]))
        snap = counters.snapshot()
        degraded = int(getattr(gb, "_fused_failures", 0)) + \
            int(bool(getattr(gb, "_fused_disabled", False)))
        failed = int(snap["fallbacks"]) + int(snap["device_retries"]) + \
            degraded + int((leaves <= 1).sum())
        say("trees %d (min leaves %d%s), reliability counters %s, degraded "
            "blocks %d" % (len(leaves), leaves.min(), self.leaves_note(),
                           snap, degraded))

        # ---- correct: each number compared, with its limit
        problems: List[str] = []
        compared = [("programs_in_window", c1["programs"] - c0["programs"],
                     "max", 0)]
        if c1["programs"] != c0["programs"]:
            problems.append("%d programs were built inside the window"
                            % (c1["programs"] - c0["programs"]))
        if expect.get("fused_pipelined"):
            if stats is None or not stats.blocks or \
                    set(stats.block_sizes) != {int(resolved.fused_block_size)}:
                problems.append("not every block went through the fused, "
                                "pipelined executor at fused_block_size: %s"
                                % (stats and stats.block_sizes))
        problems += self.problems(gb, expect)
        if not self.check_trees(bst, data, dtrain._binned.bins, cfg,
                                resolved, compared):
            problems.append("a checked tree disagrees with %s"
                            % self.reference)
        n = int(expect[self.quality_trees])
        if bst.current_iteration() < n:
            problems.append("only %d trees, the %s check wants %d"
                            % (bst.current_iteration(), self.quality, n))
        else:
            label, value, floor = self.held_out(bst, data, n, expect)
            compared.append(("held_out_" + self.quality.lower(), value,
                             "min", floor))
            if not value > floor:
                problems.append("held-out %s %.5f is not above %s"
                                % (label, value, floor))
        for p in problems:
            say("NOT CORRECT: " + p)

        readings = {
            "kind": "train", "window_s": window_s,
            "window_trees": window_trees,
            "warm_trees": window.trees_start, "binning_s": binning_s,
            "data_s": data_s, "timers": global_timer.totals(),
            "compile_setup_s": c0["seconds"],
            "train_call_to_window_s": window.t_start - t_call,
            "trace": tracer.reduced,
            "memory_peak_bytes": harness.memory_peak_bytes(),
        }
        readings.update(self.readings(gb, window, tracer))
        return {"correct": not problems, "attempted": int(window.trees_end),
                "failed": failed,
                "end_to_end": {"trees_per_s": window_trees / window_s,
                               "setup_s": setup_s},
                "readings": readings, "compared": compared}
