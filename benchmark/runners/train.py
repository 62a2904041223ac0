"""Runner for configurations of kind `train`: one `lgb.train` call on
data from the seed, measured between block boundaries.

The job is the one a user starts: `lgb.Dataset(X, label=y)`,
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
"""

from __future__ import annotations

import time
from typing import List, Optional

import numpy as np

from .. import harness
from ..generators.higgs import make_higgs_like
from ..harness import say
from ..reference import gbdt_numpy

#: streams of the data generator: training rows, held-out rows, and the
#: valid set a traffic mix may ask for
_TRAIN, _HELD_OUT, _VALID = 0, 1, 2


class _Window:
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


def _check_against_reference(bst, X, y, bins, cfg, params) -> bool:
    """Boosting steps `expect.check_trees` against gbdt_numpy, at full
    size: says what it found, returns whether every step agrees."""
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
        got = gbdt_numpy.check_step(
            k, trees, X, y, bins, learning_rate=params["learning_rate"],
            min_data_in_leaf=params["min_data_in_leaf"],
            min_sum_hessian_in_leaf=params["min_sum_hessian_in_leaf"],
            lambda_l2=params["lambda_l2"], routed=routed)
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
    if traffic["loop"] != "job":
        raise harness.BenchmarkError(
            "a train configuration runs a job, not a %r loop"
            % traffic["loop"])
    if rehearsal:
        cfg, traffic = harness.rehearsal_overlay(cfg, traffic)
    # a mix that changes what a tree is (bagging, feature sampling) says
    # which checks still hold for it
    expect = {**cfg["expect"], **traffic.get("expect", {})}
    cfg = {**cfg, "expect": expect}
    clock = harness.start_clocks(rehearsal)
    tracer = harness.TracedWindow(trace, cpu_rehearsal=rehearsal)

    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.reliability import counters
    from lightgbm_tpu.utils.timer import global_timer
    say("imports done")

    # ---- data from the seed, binned by the program
    rows, nf = int(cfg["num_data"]), int(cfg["num_features"])
    t0 = time.perf_counter()
    X, y, threshold = make_higgs_like(rows, nf, seed, stream=_TRAIN)
    Xho, yho, _ = make_higgs_like(int(cfg["held_out_rows"]), nf, seed,
                                  stream=_HELD_OUT, threshold=threshold)
    data_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    dtrain = lgb.Dataset(X, label=y, params={"max_bin": cfg["max_bin"]})
    dtrain.construct()
    binning_s = time.perf_counter() - t0
    say("data: %d x %d float32 drawn in %.2fs, binned in %.2fs %s"
        % (rows, nf, data_s, binning_s,
           {k: round(v, 3) for k, v in global_timer.totals().items()
            if k.startswith("dataset_")}))

    params = {"objective": cfg["objective"],
              "num_leaves": cfg["num_leaves"], "max_bin": cfg["max_bin"],
              "learning_rate": cfg["learning_rate"], "verbosity": -1}
    params.update(cfg.get("params", {}))
    params.update(traffic.get("params", {}))
    valid_sets = None
    if traffic.get("valid_rows"):
        Xva, yva, _ = make_higgs_like(int(traffic["valid_rows"]), nf, seed,
                                      stream=_VALID, threshold=threshold)
        valid_sets = [lgb.Dataset(Xva, label=yva, reference=dtrain)]
        valid_sets[0].construct()
    resolved = Config(dict(params))
    say("params %s" % params)

    # ---- the job
    window = _Window(seconds, traffic, clock, tracer)
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
    say("compile: set-up %.1fs in %d programs (cache hits %d, misses %d); "
        "inside the window %d programs"
        % (c0["seconds"], c0["programs"], c1["hits"], c1["misses"],
           c1["programs"] - c0["programs"]))
    if stats is not None:
        say("pipeline: %s" % stats.as_dict())

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
    if expect.get("row_shards"):
        owners = {s.device.id for s in gb.bins.addressable_shards}
        say("rows live on devices %s" % sorted(owners))
        if len(owners) != int(expect["row_shards"]):
            problems.append("rows on %d devices, not %d"
                            % (len(owners), expect["row_shards"]))
    if not _check_against_reference(
            bst, X, y, dtrain._binned.bins, cfg,
            {"learning_rate": float(resolved.learning_rate),
             "min_data_in_leaf": int(resolved.min_data_in_leaf),
             "min_sum_hessian_in_leaf":
                 float(resolved.min_sum_hessian_in_leaf),
             "lambda_l2": float(resolved.lambda_l2)}):
        problems.append("a checked tree disagrees with gbdt_numpy")
    n_auc = int(expect["auc_trees"])
    if bst.current_iteration() < n_auc:
        problems.append("only %d trees, the AUC check wants %d"
                        % (bst.current_iteration(), n_auc))
    else:
        auc = gbdt_numpy.auc(yho, bst.predict(Xho, num_iteration=n_auc,
                                              raw_score=True))
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
    }
    return {"correct": not problems, "attempted": int(window.trees_end),
            "failed": failed,
            "end_to_end": {"trees_per_s": window_trees / window_s,
                           "setup_s": setup_s},
            "readings": readings}
