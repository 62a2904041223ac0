"""Runner for configurations of kind `train_cat`: the training job of
`benchmark/training.py` on data with categorical columns, passed as
`categorical_feature=`. Its own are the data (generators/expo.py: six
integer-coded categorical columns beside eleven numerical ones; level
frequencies and label model fixed; the training rows one fixed table of
flights, the configuration's `train_rows_seed`, under the SEED's codes
for its carriers and airports, which move no bin, so that every seed
gives a run the same trees to grow; the held-out rows the seed's own),
the reference (reference/gbdt_cat_numpy.py: trees 0 and 1 at full size,
the root over all columns, every leaf, and every node that decides on a
categorical column against the published rule on that node's own
histogram; held-out AUC), and one more reduction of the capture: the
device time of the operations the program names `split.categorical`
(`categorical_busy_s`).

Before it draws a row it asks the program whether its model dump says
which category values a categorical node sends left
(`tree.HostTree.cat_values_left`: the reference's public JSON form,
`"threshold": "3||17||40"`). A program that cannot (the one before this
cell was added writes the index of the node's bitset there) cannot be
held against the reference, and is refused at once, with an error and
no record.
"""

from __future__ import annotations

import numpy as np

from .. import harness, program_readings, training
from ..generators.expo import (CATEGORICAL, COLUMNS, level_codes,
                               make_expo_like)
from ..harness import say
from ..reference import gbdt_cat_numpy
from ..training import Task

#: streams of the data generator: training rows, held-out rows
_TRAIN, _HELD_OUT = 0, 1
#: how the program names the categorical half of its split search: a
#: `jax.named_scope` in learner/split.py
CATEGORICAL_SCOPE = "split.categorical"


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


def _spans(name: str) -> list:
    return [sp["attrs"] for sp in program_readings.spans(
        {"kind": "train"}) or [] if sp["name"] == name]


class TrainCat(Task):
    kind = "train_cat"
    tracer = training.KeptTrace
    reference = "gbdt_cat_numpy"
    flatten_tree = staticmethod(gbdt_cat_numpy.flatten_tree)
    limits = Task.limits + (
        ("cat_infeasible_nodes", None),
        ("cat_gain_shortfall_ulps", "cat_gain_shortfall_ulps"))

    def preflight(self, cfg, resolved):
        ask_the_program()

    def draw(self, cfg, seed):
        categorical = [int(c) for c in cfg["categorical_feature"]]
        if int(cfg["num_features"]) != len(COLUMNS) or \
                categorical != list(CATEGORICAL):
            raise harness.BenchmarkError(
                "the configuration's columns are not the generator's: %d "
                "columns, categorical %s" % (len(COLUMNS), list(CATEGORICAL)))
        X, y, threshold = make_expo_like(
            int(cfg["num_data"]), seed, stream=_TRAIN,
            rows_seed=int(cfg["train_rows_seed"]))
        Xho, yho, _ = make_expo_like(int(cfg["held_out_rows"]), seed,
                                     stream=_HELD_OUT, threshold=threshold)
        return {"X": X, "y": y, "categorical": categorical,
                "dataset": {"categorical_feature": categorical},
                "Xho": Xho, "yho": yho}

    def say_data(self, cfg, seed, data, data_s, binning_s, binned):
        X, y = data["X"], data["y"]
        say("data: %d x %d float32 (%d categorical; training rows of the "
            "fixed table %d, codes of seed %d: carriers %s..), label share "
            "%.4f, drawn in %.2fs, binned in %.2fs %s; ingest.construct %s"
            % (len(y), X.shape[1], len(data["categorical"]),
               int(cfg["train_rows_seed"]), seed,
               level_codes(seed)["carrier"][:4].tolist(), float(y.mean()),
               data_s, binning_s, binned,
               _spans("ingest.construct")[-1:] or None))

    def say_program(self):
        built = _spans("boosting.build_program")
        say("the growth program: %s" % (
            {k: built[-1].get(k) for k in ("hist_plan", "has_cat",
                                           "cat_columns", "cat_bins")}
            if built else None))

    def leaves_note(self):
        unpacked = _spans("entry.unpack_block")
        return ("; %s of %s nodes of the unpacked blocks decide on a "
                "categorical column"
                % (sum(a.get("cat_nodes", 0) for a in unpacked),
                   sum(a.get("nodes", 0) for a in unpacked)))

    def check_step(self, k, trees, data, bins, cfg, resolved, routed):
        return gbdt_cat_numpy.check_step(
            k, trees, data["X"], data["y"], bins, data["categorical"],
            routed=routed,
            slack_ulps=float(cfg["expect"]["cat_prefix_slack_ulps"]),
            **_rule_params(resolved))

    def held_out(self, bst, data, n, expect):
        auc = gbdt_cat_numpy.auc(data["yho"], bst.predict(
            data["Xho"], num_iteration=n, raw_score=True))
        say("held-out AUC after %d trees on %d rows: %.5f (floor %s)"
            % (n, len(data["yho"]), auc, expect["auc_floor"]))
        return "AUC", auc, expect["auc_floor"]

    def readings(self, gb, window, tracer):
        def categorical_s(path):
            got = training.scoped_instructions(
                gb, window.block, CATEGORICAL_SCOPE) if window.fused else None
            if not got:
                return None
            say("scope %r: %d instructions of the fused block carry it "
                "(read in %.1fs)" % (CATEGORICAL_SCOPE, len(got[0]), got[1]))
            if not got[0]:
                return None
            seconds, by_kind = training.scope_busy_s(
                path, tracer.cpu_rehearsal, got[0])
            say("scope: device seconds by kind of the counted operations %s"
                % {k: round(v / 1e9, 4) for k, v in by_kind.most_common(6)})
            return seconds
        return {"categorical_busy_s": tracer.read(categorical_s)}

    def rehearsal_says(self, cell):
        cfg, _ = harness.rehearsal_overlay(cell["config"], cell["traffic"])
        # the program's ingest counts every level the rehearsal's rows
        # hold (its sample is all of them); a seed's codes rename the
        # levels and add none
        X, _, _ = make_expo_like(int(cfg["num_data"]), 0, stream=_TRAIN,
                                 rows_seed=int(cfg["train_rows_seed"]))
        levels = sum(len(np.unique(X[:, j])) for j in CATEGORICAL)
        return super().rehearsal_says(cell) + (
            "(%d categorical; training rows of the fixed table %d, codes "
            "of seed " % (len(cfg["categorical_feature"]),
                          cfg["train_rows_seed"]),
            "ingest.construct", "'levels': %d," % levels, "'has_cat': True",
            "'cat_infeasible_nodes': 0", "decide on a categorical column")


TASK = TrainCat()
