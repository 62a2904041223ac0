"""Runner for configurations of kind `train_missing`: the training job
of `benchmark/training.py` on a wide table most of whose values are
missing. Its own are the data (generators/bosch.py: 968 columns, a
part's columns NaN at every station its route skips, four in five of
them; the training rows ONE fixed table, the generator's
`TRAIN_ROWS_SEED`, so that every seed grows the same trees; the
held-out rows the seed's), the reference
(reference/gbdt_missing_numpy.py: trees 0 and 1 at full size, the root
over every column and both directions, every leaf, and every node that
splits on a column with a NaN bin against the rule on that node's own
histogram; held-out AUC), and what it reads off
the capture: the device time of the feature-tiled kernels of a wide
pass (`wide_pass_s`), and of the tiled one-hot sweep the operations and
bytes it issued (`wide_onehot`, kernels_wide_hist_roofline).

Before it draws a row it asks the program whether it builds a pass too
wide for one histogram block over the feature axis in tiles
(`learner.histogram_mxu.feature_tile`). A program that cannot (the one
before this cell was added) falls to a chunked kernel and a separate
route in every wide pass and compiles the wide kernels whole: its cold
run does not end in a run's time, so it is refused at once, with an
error and no record.
"""

from __future__ import annotations

import numpy as np

from .. import harness, program_readings, training
from ..generators.bosch import (NUM_FEATURES, TRAIN_ROWS_SEED,
                               make_bosch_like)
from ..harness import say
from ..layer_metrics import kernels_wide_hist_roofline as roofline
from ..reference import gbdt_missing_numpy
from ..training import Task

#: streams of the data generator: training rows, held-out rows
_TRAIN, _HELD_OUT = 0, 1
#: the kernels of a pass built over the feature axis in tiles, by the
#: names their pallas_calls give them (learner/histogram_mxu.py,
#: learner/histogram_pallas.py)
WIDE_KERNELS = ("fused_route_hist_tiled", "grouped_hist_tiled")


def ask_the_program() -> None:
    """The one question (module docstring)."""
    from lightgbm_tpu.learner import histogram_mxu
    if not hasattr(histogram_mxu, "feature_tile"):
        raise harness.BenchmarkError(
            "this program builds no pass over the feature axis in tiles "
            "(no learner.histogram_mxu.feature_tile): at 968 columns its "
            "wide passes fall to the chunked kernel and a separate route, "
            "and its cold run does not end in a run's time. The cell is "
            "not run on it.")


def _spans(name: str) -> list:
    return [sp["attrs"] for sp in program_readings.spans(
        {"kind": "train"}) or [] if sp["name"] == name]


def wide_kernel_calls(path: str):
    """Every call of WIDE_KERNELS in a capture: (kernel, HLO text,
    device seconds of its own), averaged over the chips."""
    from jax.profiler import ProfileData
    from .. import trace_reduce
    calls, chips = [], 0
    for plane in ProfileData.from_file(path).planes:
        if not trace_reduce._DEVICE_PLANE.match(plane.name):
            continue
        chips += 1
        for line in plane.lines:
            if line.name != trace_reduce._OPS_LINE:
                continue
            events = [(e.name, e.start_ns, e.duration_ns)
                      for e in line.events]
            for (text, _, _), own in zip(
                    events, trace_reduce.self_times(events)):
                kernel = trace_reduce.op_name(text)
                if kernel in WIDE_KERNELS:
                    calls.append((kernel, text, own / 1e9))
    return [(k, t, s / max(chips, 1)) for k, t, s in calls]


class TrainMissing(Task):
    kind = "train_missing"
    tracer = training.KeptTrace
    reference = "gbdt_missing_numpy"
    flatten_tree = staticmethod(gbdt_missing_numpy.flatten_tree)
    limits = Task.limits + (
        ("nan_direction_flips", None),
        ("nan_gain_shortfall_ulps", "nan_gain_shortfall_ulps"),
        ("binning_disagreements", None))

    def preflight(self, cfg, resolved):
        ask_the_program()

    def draw(self, cfg, seed):
        if int(cfg["num_features"]) != NUM_FEATURES:
            raise harness.BenchmarkError(
                "the configuration's columns are not the generator's: %d"
                % NUM_FEATURES)
        X, y, threshold = make_bosch_like(
            int(cfg["num_data"]), TRAIN_ROWS_SEED, stream=_TRAIN)
        Xho, yho, _ = make_bosch_like(int(cfg["held_out_rows"]), seed,
                                      stream=_HELD_OUT, threshold=threshold)
        return {"X": X, "y": y, "Xho": Xho, "yho": yho}

    def say_data(self, cfg, seed, data, data_s, binning_s, binned):
        X, y = data["X"], data["y"]
        say("data: %d x %d float32 (training rows of the fixed table %d, "
            "held-out rows of seed %d), missing share %.4f, failure share "
            "%.5f, drawn in %.2fs, binned in %.2fs %s"
            % (len(y), X.shape[1], TRAIN_ROWS_SEED, seed,
               float(np.isnan(X[:20000]).mean()), float(y.mean()), data_s,
               binning_s, binned))

    def say_program(self):
        built = _spans("boosting.build_program")
        say("the growth program: %s" % (
            {k: built[-1].get(k) for k in ("hist_plan", "partition")}
            if built else None))

    def leaves_note(self):
        unpacked = _spans("entry.unpack_block")
        return ("; %s of %s rows routed at the unpacked blocks' splits "
                "went by their node's default direction"
                % (sum(a.get("default_rows", 0) for a in unpacked),
                   sum(a.get("routed_rows", 0) for a in unpacked)))

    def check_step(self, k, trees, data, bins, cfg, resolved, routed):
        if "bins_t" not in data:
            data["bins_t"] = np.ascontiguousarray(np.asarray(bins).T)
        return gbdt_missing_numpy.check_step(
            k, trees, data["X"], data["y"], bins, routed=routed,
            bins_t=data["bins_t"],
            learning_rate=float(resolved.learning_rate),
            min_data_in_leaf=int(resolved.min_data_in_leaf),
            min_sum_hessian_in_leaf=float(resolved.min_sum_hessian_in_leaf),
            lambda_l2=float(resolved.lambda_l2),
            slack_ulps=float(cfg["expect"]["nan_slack_ulps"]))

    def held_out(self, bst, data, n, expect):
        auc = gbdt_missing_numpy.auc(data["yho"], bst.predict(
            data["Xho"], num_iteration=n, raw_score=True))
        say("held-out AUC after %d trees on %d rows: %.5f (floor %s)"
            % (n, len(data["yho"]), auc, expect["auc_floor"]))
        return "AUC", auc, expect["auc_floor"]

    def readings(self, gb, window, tracer):
        import jax

        def wide(path):
            calls = wide_kernel_calls(path)
            if not calls:
                return None
            onehot = [(t, s) for k, t, s in calls
                      if k == "fused_route_hist_tiled"]
            work = [roofline.onehot_work(t, NUM_FEATURES) for t, _ in onehot]
            say("wide passes: %d calls of the tiled kernels, %s s by kernel"
                % (len(calls), {k: round(sum(s for kk, _, s in calls
                                             if kk == k), 4)
                                for k in WIDE_KERNELS}))
            return {"wide_pass_s": sum(s for _, _, s in calls),
                    "wide_onehot": {
                        "seconds": sum(s for _, s in onehot),
                        "flops": sum(w[0] for w in work),
                        "bytes": sum(w[1] for w in work)}}

        got = tracer.read(wide) or {}
        got["device_kind"] = jax.devices()[0].device_kind
        return got

    def rehearsal_says(self, cell):
        return super().rehearsal_says(cell) + (
            "missing share", "went by their node's default direction",
            "'nan_direction_flips': 0", "'binning_disagreements': 0")


TASK = TrainMissing()
