"""Runner for configurations of kind `train`: the training job of
`benchmark/training.py` on Higgs-like rows from the seed
(generators/higgs.py), trees 0 and 1 held against gbdt_numpy at full
size, held-out AUC. The one kind that takes a valid set from its
traffic mix, and checks where the rows live (`expect.row_shards`)."""

from __future__ import annotations

from ..generators.higgs import make_higgs_like
from ..harness import say
from ..reference import gbdt_numpy
from ..training import Task

#: streams of the data generator: training rows, held-out rows, and the
#: valid set a traffic mix may ask for
_TRAIN, _HELD_OUT, _VALID = 0, 1, 2


class Train(Task):
    takes_valid_set = True
    reference = "gbdt_numpy"
    flatten_tree = staticmethod(gbdt_numpy.flatten_tree)

    def draw(self, cfg, seed):
        nf = int(cfg["num_features"])
        X, y, threshold = make_higgs_like(int(cfg["num_data"]), nf, seed,
                                          stream=_TRAIN)
        Xho, yho, _ = make_higgs_like(int(cfg["held_out_rows"]), nf, seed,
                                      stream=_HELD_OUT, threshold=threshold)
        return {"X": X, "y": y, "threshold": threshold, "Xho": Xho,
                "yho": yho}

    def say_data(self, cfg, seed, data, data_s, binning_s, binned):
        say("data: %d x %d float32 drawn in %.2fs, binned in %.2fs %s"
            % (*data["X"].shape, data_s, binning_s, binned))

    def valid_set(self, cfg, traffic, seed, data):
        X, y, _ = make_higgs_like(int(traffic["valid_rows"]),
                                  int(cfg["num_features"]), seed,
                                  stream=_VALID, threshold=data["threshold"])
        return X, y

    def problems(self, gb, expect):
        if not expect.get("row_shards"):
            return []
        owners = {s.device.id for s in gb.bins.addressable_shards}
        say("rows live on devices %s" % sorted(owners))
        if len(owners) != int(expect["row_shards"]):
            return ["rows on %d devices, not %d"
                    % (len(owners), expect["row_shards"])]
        return []

    def check_step(self, k, trees, data, bins, cfg, resolved, routed):
        return gbdt_numpy.check_step(
            k, trees, data["X"], data["y"], bins,
            learning_rate=float(resolved.learning_rate),
            min_data_in_leaf=int(resolved.min_data_in_leaf),
            min_sum_hessian_in_leaf=float(resolved.min_sum_hessian_in_leaf),
            lambda_l2=float(resolved.lambda_l2), routed=routed)

    def held_out(self, bst, data, n, expect):
        auc = gbdt_numpy.auc(data["yho"], bst.predict(
            data["Xho"], num_iteration=n, raw_score=True))
        say("held-out AUC after %d trees on %d rows: %.5f (floor %s)"
            % (n, len(data["yho"]), auc, expect["auc_floor"]))
        return "AUC", auc, expect["auc_floor"]

    def rehearsal_says(self, cell):
        return super().rehearsal_says(cell) + ("dataset_",)


TASK = Train()
