"""Command-line application (reference src/application/, src/main.cpp).

Same invocation contract as the reference CLI:
    lightgbm-tpu config=train.conf [key=value ...]
with tasks train / predict / refit / save_binary / convert_model
(application.cpp:85-269) and `key=value` config files ('#' comments,
CLI overrides file — application.cpp:50-83).
"""

from __future__ import annotations

import sys
from typing import Dict, List, Optional

import numpy as np

from .basic import Booster, Dataset
from .callback import log_evaluation
from .config import Config, parse_config_file
from .engine import train as train_fn
from .utils.log import Log
from .utils.file_io import open_file, _scheme_of

__all__ = ["main", "Application"]


def _parse_argv(argv: List[str]) -> Dict[str, str]:
    params: Dict[str, str] = {}
    for arg in argv:
        if "=" not in arg:
            Log.warning("Unknown CLI argument %s (expected key=value)", arg)
            continue
        key, value = arg.split("=", 1)
        params[key.strip()] = value.strip()
    file_params: Dict[str, str] = {}
    if "config" in params or "config_file" in params:
        path = params.get("config") or params.get("config_file")
        file_params = parse_config_file(path)
    # CLI overrides config file (application.cpp:75-80)
    file_params.update(params)
    return file_params


def _load_text_data(path: str, cfg: Config):
    """Load CSV/TSV/LibSVM training file.

    Reference Parser auto-detection (src/io/parser.cpp): tab/comma sniffing,
    label in column `label_column` (default 0).
    """
    with open_file(path) as fh:
        first = fh.readline().strip()
    if ":" in first.split(" ")[-1] and "," not in first:
        # LibSVM format: label idx:val idx:val ...
        return _load_libsvm(path)
    delim = "\t" if "\t" in first else ","
    skip = 1 if cfg.header else 0
    from . import cext
    # the native parser mmaps local files; URI paths use the virtual FS
    data = None if _scheme_of(path) else \
        cext.parse_delimited(path, delim, skip)
    if data is None:
        with open_file(path) as fh:
            data = np.loadtxt(fh, delimiter=delim, skiprows=skip, ndmin=2)
    label_col = 0
    if cfg.label_column.startswith("name:"):
        Log.fatal("label_column=name: requires header parsing; use index")
    elif cfg.label_column:
        label_col = int(cfg.label_column)
    y = data[:, label_col].astype(np.float32)
    X = np.delete(data, label_col, axis=1)
    return X, y


def _load_libsvm(path: str):
    rows = []
    labels = []
    max_idx = -1
    with open_file(path) as fh:
        for line in fh:
            parts = line.strip().split()
            if not parts:
                continue
            labels.append(float(parts[0]))
            feats = {}
            for tok in parts[1:]:
                i, v = tok.split(":")
                feats[int(i)] = float(v)
                max_idx = max(max_idx, int(i))
            rows.append(feats)
    X = np.zeros((len(rows), max_idx + 1))
    for r, feats in enumerate(rows):
        for i, v in feats.items():
            X[r, i] = v
    return X, np.asarray(labels, np.float32)


def _maybe_load_group(data_path: str) -> Optional[np.ndarray]:
    """LightGBM reads <data>.query / <data>.group side files."""
    import os
    for ext in (".query", ".group"):
        p = data_path + ext
        if os.path.exists(p):
            return np.loadtxt(p, dtype=np.int64, ndmin=1)
    return None


def _maybe_load_weight(data_path: str) -> Optional[np.ndarray]:
    import os
    p = data_path + ".weight"
    if os.path.exists(p):
        return np.loadtxt(p, dtype=np.float32, ndmin=1)
    return None


class Application:
    """Task dispatcher (reference application.cpp:31-269)."""

    def __init__(self, argv: List[str]):
        self.params = _parse_argv(argv)
        self.config = Config(self.params)
        Log.set_verbosity(self.config.verbosity)
        # arm the flight recorder as soon as the config exists — a
        # failure before any Booster is built (bad data path, schema
        # error) must still honor flightrec_dir= for its bundle
        from .observability.registry import registry
        registry.configure_from_config(self.config)

    def run(self) -> None:
        task = self.config.task
        if task == "train" and self.config.num_machines > 1:
            # before any data/backend work, like the reference's
            # Network::Init at InitTrain start (application.cpp:165)
            from .parallel import setup_multihost
            setup_multihost(self.config.num_machines, self.config.machines,
                            self.config.machine_list_filename,
                            self.config.local_listen_port)
        if task == "train":
            self.train()
        elif task in ("predict", "prediction", "test"):
            self.predict()
        elif task == "refit":
            self.refit()
        elif task == "convert_model":
            self.convert_model()
        elif task == "save_binary":
            self.save_binary()
        elif task == "serve":
            self.serve()
        elif task == "loop":
            self.loop()
        else:
            Log.fatal("Unknown task %s", task)

    # ------------------------------------------------------------------
    def _load_train_dataset(self) -> Dataset:
        cfg = self.config
        from .data import BinnedDataset
        if BinnedDataset.is_binary_file(cfg.data):
            return Dataset(cfg.data, params=dict(self.params))
        if cfg.stream_input:
            # out-of-core ingestion (docs/Streaming.md): the text/npy
            # file is never materialized — Dataset.construct streams it
            # through the two-pass loader. Row partitioning happens at
            # file granularity (pre_partition), so the shared-file
            # auto-split path falls back to in-memory loading.
            if cfg.num_machines > 1 and not cfg.pre_partition:
                Log.warning(
                    "stream_input with num_machines > 1 requires "
                    "pre_partition=true (each machine streams its own "
                    "file); falling back to in-memory loading")
            else:
                return Dataset(
                    cfg.data, group=_maybe_load_group(cfg.data),
                    weight=_maybe_load_weight(cfg.data),
                    params=dict(self.params))
        X, y = _load_text_data(cfg.data, cfg)
        group = _maybe_load_group(cfg.data)
        weight = _maybe_load_weight(cfg.data)
        X, y, group, weight = self._partition_rows(X, y, group, weight)
        return Dataset(X, label=y, group=group, weight=weight,
                       params=dict(self.params))

    def _partition_rows(self, X, y, group, weight):
        """Multi-machine row assignment (reference
        dataset_loader.cpp:560-592): with pre_partition=false every
        machine reads the shared file and keeps its contiguous block —
        query-granular when ranking groups exist, so no query spans
        machines (dataset_loader.cpp:569-590). pre_partition=true means
        each machine's file already IS its partition."""
        cfg = self.config
        if cfg.num_machines <= 1 or cfg.pre_partition:
            return X, y, group, weight
        import jax
        nproc, rank = jax.process_count(), jax.process_index()
        if nproc <= 1:
            return X, y, group, weight
        n = len(y)
        if group is not None:
            bounds = np.concatenate([[0], np.cumsum(group)])
            qlo = len(group) * rank // nproc
            qhi = len(group) * (rank + 1) // nproc
            lo, hi = int(bounds[qlo]), int(bounds[qhi])
            group = group[qlo:qhi]
        else:
            lo, hi = n * rank // nproc, n * (rank + 1) // nproc
        X, y = X[lo:hi], y[lo:hi]
        if weight is not None:
            weight = weight[lo:hi]
        return X, y, group, weight

    def train(self) -> None:
        cfg = self.config
        if not cfg.data:
            Log.fatal("No training data: set data=<file>")
        dtrain = self._load_train_dataset()
        valid_sets, valid_names = [], []
        if cfg.valid:
            for i, vpath in enumerate(str(cfg.valid).split(",")):
                vgroup = _maybe_load_group(vpath)
                if cfg.stream_input:
                    # stream the valid file too, aligned with the
                    # training dataset's frozen bin mappers
                    valid_sets.append(Dataset(vpath, group=vgroup,
                                              reference=dtrain,
                                              params=dict(self.params)))
                else:
                    vX, vy = _load_text_data(vpath, cfg)
                    valid_sets.append(Dataset(vX, label=vy, group=vgroup,
                                              reference=dtrain))
                valid_names.append(f"valid_{i + 1}")
        callbacks = [log_evaluation(cfg.metric_freq)]
        if cfg.snapshot_freq > 0:
            # periodic model snapshots (reference gbdt.cpp:279-283:
            # "snapshot_iter_<n>" files every snapshot_freq iterations)
            out_model = cfg.output_model

            def _snapshot(env):
                it = env.iteration + 1
                if it % cfg.snapshot_freq == 0:
                    path = f"{out_model}.snapshot_iter_{it}"
                    env.model.save_model(path)
                    Log.info("Saved snapshot to %s", path)

            callbacks.append(_snapshot)
        init_model = cfg.input_model if cfg.input_model else None
        resume_from = None
        if cfg.checkpoint_period > 0 and cfg.checkpoint_dir:
            # auto-resume (docs/Reliability.md): a killed task=train run
            # rerun with the same conf picks up at its last checkpoint;
            # engine.train adds the periodic checkpoint callback itself
            from .reliability.checkpoint import latest_checkpoint
            found = latest_checkpoint(cfg.checkpoint_dir)
            if found is not None:
                resume_from = found
                init_model = None
                Log.info("Auto-resuming from checkpoint %s", found)
        msrv = None
        if cfg.observe and cfg.observe_metrics_port > 0:
            # live Prometheus scrape surface for the duration of the run
            from .observability import MetricsHTTPServer
            from .observability import registry as _obs
            msrv = MetricsHTTPServer(_obs.prometheus_text, _obs.snapshot,
                                     port=cfg.observe_metrics_port)
            Log.info("observability metrics at %s", msrv.url)
        try:
            booster = train_fn(dict(self.params), dtrain,
                               num_boost_round=cfg.num_iterations,
                               valid_sets=valid_sets or None,
                               valid_names=valid_names or None,
                               callbacks=callbacks,
                               init_model=init_model,
                               resume_from=resume_from)
        finally:
            if msrv is not None:
                msrv.close()
        st = getattr(getattr(dtrain, "_binned", None), "stream_stats", None)
        if st is not None and st.chunks and cfg.stream_input:
            Log.info("streamed ingest: %d chunks / %d rows, %.1f%% "
                     "parse/bin overlap, %.0f rows/s",
                     st.chunks, st.rows, 100.0 * st.overlap_frac,
                     st.rows_per_sec)
        stats = getattr(getattr(booster, "gbdt", None),
                        "_pipeline_stats", None)
        if stats is not None and stats.blocks:
            # the share of blocks the host enqueued while the block
            # before was still running: the boundaries the device
            # crossed without waiting for the host (none with valid
            # sets, whose callbacks wait for every block's metrics)
            Log.info("pipelined executor: %d blocks / %d iterations, "
                     "%.1f%% of the blocks enqueued behind a running "
                     "one, %.1f ms a block of host work putting trees "
                     "on the list",
                     stats.blocks, stats.iterations,
                     100.0 * stats.overlap_frac,
                     sum(stats.host_ms) / max(len(stats.host_ms), 1))
        booster.save_model(cfg.output_model)
        Log.info("Finished training, model saved to %s", cfg.output_model)
        if cfg.observe and cfg.observe_trace_file:
            from .observability import registry as _obs
            fmt = _obs.dump_trace(cfg.observe_trace_file)
            Log.info("Wrote %s span trace to %s", fmt,
                     cfg.observe_trace_file)

    def predict(self) -> None:
        cfg = self.config
        if not cfg.data:
            Log.fatal("No prediction data: set data=<file>")
        if not cfg.input_model:
            Log.fatal("No model file: set input_model=<file>")
        booster = Booster(model_file=cfg.input_model)
        X, _ = _load_text_data(cfg.data, cfg)
        pred = booster.predict(
            X, raw_score=cfg.predict_raw_score,
            pred_leaf=cfg.predict_leaf_index,
            pred_contrib=cfg.predict_contrib,
            start_iteration=cfg.start_iteration_predict,
            num_iteration=cfg.num_iteration_predict,
            pred_early_stop=cfg.pred_early_stop,
            pred_early_stop_freq=cfg.pred_early_stop_freq,
            pred_early_stop_margin=cfg.pred_early_stop_margin)
        out = np.asarray(pred)
        if out.ndim == 1:
            out = out[:, None]
        np.savetxt(cfg.output_result, out, delimiter="\t", fmt="%.18g")
        Log.info("Finished prediction, results saved to %s",
                 cfg.output_result)

    def refit(self) -> None:
        cfg = self.config
        booster = Booster(model_file=cfg.input_model)
        X, y = _load_text_data(cfg.data, cfg)
        new_booster = booster.refit(X, y, decay_rate=cfg.refit_decay_rate)
        new_booster.save_model(cfg.output_model)
        Log.info("Finished refit, model saved to %s", cfg.output_model)

    def save_binary(self) -> None:
        """task=save_binary: quantize the data once, cache to <data>.bin
        (reference application.cpp save_binary task)."""
        cfg = self.config
        if not cfg.data:
            Log.fatal("No training data: set data=<file>")
        dtrain = self._load_train_dataset()
        out = cfg.data + ".bin"
        dtrain.save_binary(out)
        Log.info("Dataset saved to binary file %s", out)

    def serve(self) -> None:
        """task=serve: score a request file through the serving engine.

        Unlike task=predict, rows go through the device-resident
        `serving.Server` — registry load, shape-bucketed compiled
        predictor, micro-batching — as a mixed-size request stream, and
        a metrics snapshot (QPS, latency percentiles, bucket cache
        hits, sheds) lands next to the predictions."""
        import json
        cfg = self.config
        if not cfg.data:
            Log.fatal("No request data: set data=<file>")
        if not cfg.input_model:
            Log.fatal("No model file: set input_model=<file>")
        from .serving import Server
        X, _ = _load_text_data(cfg.data, cfg)
        with Server.from_config(cfg) as server:
            if cfg.observe:
                from .observability import registry as _obs
                _obs.enable(ring=cfg.observe_ring)
                msrv = server.start_metrics_server(
                    port=cfg.observe_metrics_port)
                Log.info("observability metrics at %s", msrv.url)
            server.load_model("default", model_file=cfg.input_model)
            # mixed-size request stream: walk the file in growing chunks
            # so the bucket cache sees many batch shapes, like live
            # traffic would produce
            futures = []
            lo, step = 0, 1
            while lo < len(X):
                hi = min(lo + step, len(X))
                futures.append(server.predict_async(
                    "default", X[lo:hi], raw_score=cfg.predict_raw_score))
                lo = hi
                step = min(step * 2, max(cfg.serve_max_batch_size, 1))
            preds = [np.asarray(f.result()) for f in futures]
            out = np.concatenate(
                [p[:, None] if p.ndim == 1 else p for p in preds], axis=0)
            np.savetxt(cfg.output_result, out, delimiter="\t", fmt="%.18g")
            snapshot = server.metrics_snapshot()
        metrics_path = cfg.serve_metrics_file or \
            cfg.output_result + ".metrics.json"
        with open_file(metrics_path, "w") as fh:
            json.dump(snapshot, fh, indent=2)
            fh.write("\n")
        m = snapshot["models"]["default"]
        Log.info("Finished serving %d requests (%d rows, %d compiled "
                 "buckets), results saved to %s, metrics to %s",
                 m["requests"], m["rows"], m["buckets_compiled"],
                 cfg.output_result, metrics_path)
        if cfg.observe and cfg.observe_trace_file:
            from .observability import registry as _obs
            fmt = _obs.dump_trace(cfg.observe_trace_file)
            Log.info("Wrote %s span trace to %s", fmt,
                     cfg.observe_trace_file)

    def loop(self) -> None:
        """task=loop: the continuous train -> refresh -> serve loop
        (docs/Continuous.md).

        Windows of `loop_window_chunks` stream chunks are pulled from
        `data`, each refresh continues boosting from the live model,
        and every new generation is checkpointed under `loop_dir` and
        hot-swapped into a serving entry under live traffic. The loop
        is kill-survivable at every seam: rerunning the same conf
        resumes from the GENERATION marker."""
        import json
        cfg = self.config
        if not cfg.data:
            Log.fatal("No streaming data: set data=<file>")
        if not cfg.loop_dir:
            Log.fatal("No loop state dir: set loop_dir=<dir>")
        from .continuous import ContinuousTrainer
        from .serving import Server
        from .streaming import source_from_path
        if cfg.label_column.startswith("name:"):
            Log.fatal("label_column=name: requires header parsing; "
                      "use index")
        source = source_from_path(cfg.data,
                                  chunk_rows=cfg.stream_chunk_rows,
                                  label_col=cfg.label_column or 0,
                                  header=cfg.header)
        with Server.from_config(cfg) as server:
            if cfg.observe:
                from .observability import registry as _obs
                _obs.enable(ring=cfg.observe_ring)
                msrv = server.start_metrics_server(
                    port=cfg.observe_metrics_port)
                Log.info("observability metrics at %s", msrv.url)
            trainer = ContinuousTrainer(cfg, source, server,
                                        params=dict(self.params))
            published = trainer.run()
            snapshot = server.metrics_snapshot()
        if trainer._live_model_str is not None:
            with open_file(cfg.output_model, "w") as fh:
                fh.write(trainer._live_model_str)
        from .observability import registry as _obs
        fresh = _obs.freshness_snapshot()
        snapshot["freshness"] = fresh
        metrics_path = cfg.serve_metrics_file or \
            cfg.output_model + ".metrics.json"
        with open_file(metrics_path, "w") as fh:
            json.dump(snapshot, fh, indent=2)
            fh.write("\n")
        Log.info("Finished loop: %d generations published (live "
                 "generation %d, %d quarantined windows, last "
                 "data-to-serve %.3fs), model saved to %s, metrics "
                 "to %s", published, fresh["generation"],
                 fresh["quarantined_windows"], fresh["data_to_serve_s"],
                 cfg.output_model, metrics_path)
        if cfg.observe and cfg.observe_trace_file:
            fmt = _obs.dump_trace(cfg.observe_trace_file)
            Log.info("Wrote %s span trace to %s", fmt,
                     cfg.observe_trace_file)

    def convert_model(self) -> None:
        cfg = self.config
        booster = Booster(model_file=cfg.input_model)
        model = booster._host_model()
        code = _model_to_if_else(model)
        with open_file(cfg.convert_model, "w") as fh:
            fh.write(code)
        Log.info("Model converted to %s", cfg.convert_model)


def _model_to_if_else(model) -> str:
    """C++ if-else codegen (reference SaveModelToIfElse,
    gbdt_model_text.cpp:286 / Tree::ToIfElse tree.cpp)."""
    lines = ["#include <cmath>", "#include <cstdint>", "",
             "// generated by lightgbm_tpu convert_model", ""]
    for ti, t in enumerate(model.trees):
        lines.append(f"double PredictTree{ti}(const double* arr) {{")

        def emit(node, indent):
            pad = "  " * indent
            if node < 0:
                return [f"{pad}return {float(t.leaf_value[~node])!r};"]
            f = int(t.split_feature[node])
            thr = float(t.threshold[node])
            dt = int(t.decision_type[node])
            if dt & 1:
                # categorical: threshold is a cat_boundaries index; decode
                # the category-value bitset into an explicit membership
                # test (reference Tree::ToIfElse CategoricalDecision /
                # FindInBitset, tree.cpp)
                ci = int(thr)
                lo = int(t.cat_boundaries[ci])
                hi = int(t.cat_boundaries[ci + 1])
                vals = [(w - lo) * 32 + b for w in range(lo, hi)
                        for b in range(32)
                        if (int(t.cat_threshold[w]) >> b) & 1]
                in_set = " || ".join(f"v{node} == {v}" for v in vals) \
                    or "false"
                # non-finite / negative / huge values go right like
                # HostTree.predict_rows (tree.py) — also keeps the
                # double->int cast defined
                cond = (f"std::isfinite(arr[{f}]) && arr[{f}] >= 0.0 && "
                        f"arr[{f}] < 2147483647.0 && "
                        f"[&]{{ int v{node} = static_cast<int>(arr[{f}]); "
                        f"return {in_set}; }}()")
            else:
                # numerical; mirror HostTree.predict_rows / reference
                # NumericalDecision (tree.h:335-412): missing_type NAN
                # routes NaN by default_left; NONE/ZERO first map NaN->0,
                # then ZERO routes |v|<=kZeroThreshold by default_left
                mt = (dt >> 2) & 3
                dl = bool(dt & 2)
                if mt == 2:
                    if dl:
                        cond = (f"std::isnan(arr[{f}]) || "
                                f"arr[{f}] <= {thr!r}")
                    else:
                        cond = (f"!std::isnan(arr[{f}]) && "
                                f"arr[{f}] <= {thr!r}")
                elif mt == 1:
                    cond = (f"[&]{{ double u{node} = std::isnan(arr[{f}])"
                            f" ? 0.0 : arr[{f}]; "
                            f"return std::fabs(u{node}) <= 1e-35 ? "
                            f"{str(dl).lower()} : u{node} <= {thr!r}; }}()")
                else:
                    cond = (f"(std::isnan(arr[{f}]) ? 0.0 : arr[{f}])"
                            f" <= {thr!r}")
            out = [f"{pad}if ({cond}) {{"]
            out += emit(int(t.left_child[node]), indent + 1)
            out += [f"{pad}}} else {{"]
            out += emit(int(t.right_child[node]), indent + 1)
            out += [f"{pad}}}"]
            return out

        if t.num_leaves <= 1:
            lines.append(f"  return {float(t.leaf_value[0])!r};")
        else:
            lines.extend(emit(0, 1))
        lines.append("}")
        lines.append("")
    n = len(model.trees)
    lines.append("double Predict(const double* arr) {")
    lines.append("  double sum = 0.0;")
    for ti in range(n):
        lines.append(f"  sum += PredictTree{ti}(arr);")
    lines.append("  return sum;")
    lines.append("}")
    return "\n".join(lines)


def main(argv: Optional[List[str]] = None) -> int:
    argv = argv if argv is not None else sys.argv[1:]
    from .utils.compile_cache import configure_compile_cache
    configure_compile_cache()
    try:
        Application(argv).run()
    except Exception as e:  # mirror main.cpp catch-all
        Log.warning("Met Exceptions: %s", str(e))
        from .observability.flightrec import recorder as _flightrec
        _flightrec.record_exception("cli.main", e)
        _flightrec.flush("exception")
        raise
    return 0


if __name__ == "__main__":
    sys.exit(main())
