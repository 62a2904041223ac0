"""Runner for configurations of kind `rank`: the training job of
`benchmark/training.py` for a learning-to-rank job
(`objective=lambdarank`, `group=` the query sizes). Its own are the data
(generators/mslr.py: the same multiset of query lengths and the same
label shares in every seed), the reference (reference/lambdarank_numpy.py:
trees 0 and 1 at full size under the ranking gradients, held-out
NDCG@10), and one more reduction of the capture: the device time of the
operations the program names `objective.<name>`, which the ten names a
breakdown keeps cannot carry (`objective_busy_s`).

Before it draws a row it asks the program what a tree's gradients will
compute over these query lengths (`objectives_rank.pair_slots`), and a
program that cannot say is refused there, with an error and no record:
the program before PR 32 pads every query to the longest, 2.96e10 pair
slots a tree here, and its cold run of this cell took 398 s (PERF.md
section 5), more than the driver gives a run.
"""

from __future__ import annotations

import numpy as np

from .. import harness, program_readings, training
from ..generators.mslr import make_mslr_like, query_lengths
from ..harness import say
from ..reference import gbdt_numpy, lambdarank_numpy
from ..training import Task

#: streams of the data generator: training queries, held-out queries
_TRAIN, _HELD_OUT = 0, 1
#: how the program names the gradient computation: a
#: `jax.named_scope("objective.<name>")` around it in the fused scan
#: (boosting/fused.py), and a jitted function of its own,
#: `objective_gradients`, where a tree is a dispatch of its own
OBJECTIVE_SCOPE = "objective."
OBJECTIVE_MODULE = "jit_objective_"


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


def _lengths(cfg: dict) -> np.ndarray:
    return query_lengths(int(cfg["num_queries"]), int(cfg["num_data"]),
                         int(cfg["longest_query"]))


class Rank(Task):
    kind = "rank"
    tracer = training.KeptTrace
    reference = "lambdarank_numpy"
    flatten_tree = staticmethod(gbdt_numpy.flatten_tree)
    quality, quality_trees = "NDCG", "ndcg_trees"

    def preflight(self, cfg, resolved):
        lengths = _lengths(cfg)
        say("objective: %.4g pair slots a tree over %d queries of %d to %d "
            "documents"
            % (pair_slots_a_tree(lengths,
                                 int(resolved.lambdarank_truncation_level)),
               len(lengths), lengths.min(), lengths.max()))

    def draw(self, cfg, seed):
        lengths, nf = _lengths(cfg), int(cfg["num_features"])
        X, y, sizes, cuts = make_mslr_like(lengths, nf, seed, stream=_TRAIN)
        n_ho = int(cfg["held_out_queries"])
        Xho, yho, sizes_ho, _ = make_mslr_like(
            lengths[(np.arange(n_ho) * len(lengths)) // n_ho], nf, seed,
            stream=_HELD_OUT, cuts=cuts)
        return {"X": X, "y": y, "sizes": sizes, "dataset": {"group": sizes},
                "Xho": Xho, "yho": yho, "sizes_ho": sizes_ho}

    def say_data(self, cfg, seed, data, data_s, binning_s, binned):
        rows, sizes = len(data["y"]), data["sizes"]
        say("data: %d x %d float32 under %d queries of %d to %d documents, "
            "label shares %s, drawn in %.2fs, binned in %.2fs %s"
            % (rows, data["X"].shape[1], len(sizes), sizes.min(), sizes.max(),
               np.round(np.bincount(data["y"].astype(np.int64)) / rows,
                        4).tolist(), data_s, binning_s, binned))

    def say_program(self):
        plan = [sp["attrs"].get("hist_plan") for sp in program_readings.spans(
            {"kind": "train"}) or [] if sp["name"] == "boosting.build_program"]
        say("histogram passes of the growth program: %s" % (plan[-1:] or None))

    def check_step(self, k, trees, data, bins, cfg, resolved, routed):
        return lambdarank_numpy.check_step(
            k, trees, data["X"], data["y"], data["sizes"], bins,
            learning_rate=float(resolved.learning_rate),
            min_data_in_leaf=int(resolved.min_data_in_leaf),
            min_sum_hessian_in_leaf=float(resolved.min_sum_hessian_in_leaf),
            lambda_l2=float(resolved.lambda_l2),
            sigmoid=float(resolved.sigmoid),
            truncation_level=int(resolved.lambdarank_truncation_level),
            norm=bool(resolved.lambdarank_norm), routed=routed)

    def held_out(self, bst, data, n, expect):
        at, yho, sizes_ho = int(expect["ndcg_at"]), data["yho"], \
            data["sizes_ho"]
        ndcg = lambdarank_numpy.ndcg_at_k(
            yho, bst.predict(data["Xho"], num_iteration=n, raw_score=True),
            sizes_ho, at)
        say("held-out NDCG@%d after %d trees on %d queries (%d documents): "
            "%.5f (floor %s; unranked %.5f)"
            % (at, n, len(sizes_ho), len(yho), ndcg, expect["ndcg_floor"],
               lambdarank_numpy.ndcg_at_k(yho, np.zeros(len(yho)), sizes_ho,
                                          at)))
        return "NDCG@%d" % at, ndcg, expect["ndcg_floor"]

    def readings(self, gb, window, tracer):
        def objective_s(path):
            names = None
            got = training.scoped_instructions(
                gb, window.block, OBJECTIVE_SCOPE) if window.fused else None
            if got is not None:
                names = got[0]
                say("objective: %d instructions of the fused block carry "
                    "the scope %r (read in %.1fs)"
                    % (len(names), OBJECTIVE_SCOPE, got[1]))
            return training.scope_busy_s(path, tracer.cpu_rehearsal,
                                         names or set(), OBJECTIVE_MODULE)[0]
        return {"objective_busy_s": tracer.read(objective_s)}

    def rehearsal_says(self, cell):
        cfg, _ = harness.rehearsal_overlay(cell["config"], cell["traffic"])
        # the generator's lengths run from 1 to the longest query
        return super().rehearsal_says(cell) + (
            "pair slots a tree over %d queries" % int(cfg["num_queries"]),
            "under %d queries of 1 to %d documents"
            % (int(cfg["num_queries"]), int(cfg["longest_query"])),
            "label shares", "histogram passes of the growth program")

    def rehearsal_reads(self, cell):
        # most of a rehearsal's pair slots hold a pair, and none holds two
        return {**super().rehearsal_reads(cell),
                "objective.device_ms_per_tree": (0, None),
                "objective.pair_fill": (25, 100)}


TASK = Rank()
