"""Process-global observability registry: one surface over the parts.

The registry COMPOSES the pre-existing fragments instead of replacing
them: `registry.timer` IS utils.timer.global_timer and
`registry.counters` IS reliability.counters.counters (same objects, so
every existing call site keeps working and feeds the unified snapshot),
plus the new components owned here — the span trace, the per-iteration
training telemetry, compile accounting and device-utilization (MFU)
accounting.

Everything is off by default. `enable()` flips one flag; instrumented
hot paths check `registry.enabled` (a single attribute read + branch)
and do nothing else when off, keeping the disabled-path overhead in
the noise (<2% of an iteration — tests/test_observability.py smokes
this).

The `record_train_iteration` / `record_fused_block` helpers keep the
gbdt.py hook sites to a couple of lines: they derive trees-per-
iteration, the analytic MAC estimate for MFU (MXU path only — other
kernels have no closed-form MAC model, so MFU reads as unavailable
rather than invented), fold in reliability-counter deltas, and mirror
the iteration into the span trace.
"""

from __future__ import annotations

import collections as _collections
import threading
from typing import Dict, List, Optional

from ..reliability.counters import counters as _rel_counters
from ..utils.timer import global_timer as _global_timer
from .compiles import ledger as _compile_ledger
from .export import render_prometheus
from .flightrec import current_rank, recorder as _flightrec
from .mfu import DeviceUtilization, tree_macs
from .profile import profiler as _profiler
from .telemetry import TrainingTelemetry
from .trace import tracer as _tracer

__all__ = ["ObservabilityRegistry", "registry"]


class ObservabilityRegistry:
    """One process-global surface over tracing/telemetry/MFU/compiles
    plus the shared timer and reliability counters."""

    def __init__(self):
        self._lock = threading.Lock()
        self.enabled = False
        self.record_norms = False   # host-sync stats (norms, leaves)
        # the process-global span trace (trace.py) and compile ledger
        # (compiles.py): the same objects `observability.span`,
        # `global_timer` and JAX's monitoring listeners feed
        self.trace = _tracer
        self.training = TrainingTelemetry()
        self.compiles = _compile_ledger
        self.mfu = DeviceUtilization()
        # pipelined-executor aggregates (pipeline/executor.py): the
        # blocks enqueued behind a running one, the host's own work in
        # the unpacking and the block walls
        self._pipeline = {"blocks": 0, "iterations": 0, "in_flight": 0,
                          "host_seconds": 0.0, "wall_seconds": 0.0}
        # streamed-ingestion aggregates (streaming/loader.py): chunk and
        # byte volume per pass plus the frozen sketch sample size
        self._streaming = {"chunks": 0, "rows": 0, "bytes": 0,
                           "wall_seconds": 0.0, "sample_rows": 0,
                           "exact": 0}
        # histogram-backend resolution (boosting/gbdt.py
        # _resolved_hist_backend): the pinned choice + the growth
        # program's static per-pass plan
        self._hist_backend = {"choice": "", "plan": []}
        # collective-watchdog aggregates (reliability/watchdog.py):
        # guarded brackets, deadline overruns, aborts and the worst
        # peer heartbeat age observed while diagnosing
        self._collective = {"guarded": 0, "wall_seconds": 0.0,
                            "timeouts": 0, "aborts": 0,
                            "heartbeat_age_max_s": 0.0, "world": 0}
        # cross-rank clock-offset samples piggybacked on guarded
        # collectives (parallel/comm.py): aggregates for /metrics plus
        # a bounded sample ring the trace dump embeds for the merge CLI
        self._clock_skew = {"samples": 0, "last_skew_s": 0.0,
                            "max_skew_s": 0.0}
        self._clock_samples: "collections.deque" = \
            _collections.deque(maxlen=512)
        # distributed-training aggregates (distributed/): crossbar mesh
        # setup (world size, reduce-scatter feature shard width) and the
        # binning sketch volume merged through mapper_sync
        self._distributed = {"world": 0, "feature_shard_width": 0,
                             "setup_wall_seconds": 0.0,
                             "sketch_rows": 0, "sketch_merges": 0}
        # continuous-loop freshness watchdog (continuous/trainer.py):
        # data-to-serving latency of the live generation plus the loop's
        # incident counters — torn publishes discarded on recovery and
        # poison windows quarantined after crash-looping
        self._freshness = {"generation": 0, "publishes": 0,
                           "data_to_serve_s": 0.0,
                           "max_data_to_serve_s": 0.0,
                           "staleness_slo_s": 0.0, "slo_alarm": 0,
                           "slo_breaches": 0, "torn_publishes": 0,
                           "quarantined_windows": 0}
        # elastic membership (distributed/elastic.py): the epoch/world
        # this rank currently believes, shrink/join commits observed,
        # and the wall spent rebuilding shards after a resize
        self._membership = {"epoch": 0, "world": 0, "resizes": 0,
                            "shrinks": 0, "joins": 0,
                            "reshard_wall_s": 0.0, "resharded_loads": 0}
        # shared singletons, NOT copies — existing call sites in
        # serving/, reliability/ and the phase timeits keep writing to
        # the same objects this registry reads.
        self.timer = _global_timer
        self.counters = _rel_counters

    # -- lifecycle ------------------------------------------------------
    def enable(self, ring: Optional[int] = None,
               norms: Optional[bool] = None) -> None:
        with self._lock:
            self.enabled = True
            self.trace.enabled = True
            if ring:
                self.trace.set_capacity(ring)
                self.training.set_capacity(ring)
            if norms is not None:
                self.record_norms = bool(norms)

    def disable(self) -> None:
        with self._lock:
            self.enabled = False
            self.trace.enabled = False

    def configure_from_config(self, cfg) -> None:
        """Wire the whole observability surface from a resolved Config
        (Booster.__init__): registry enable flag, flight-recorder ring
        and bundle directory (falling back to the checkpoint directory
        so multihost post-mortems land on shared storage), and the
        device span profiler."""
        if cfg.observe:
            self.enable(ring=cfg.observe_ring,
                        norms=cfg.observe_norms)
        _flightrec.configure(
            enabled=bool(cfg.flightrec),
            capacity=int(cfg.flightrec_ring),
            out_dir=cfg.flightrec_dir or cfg.checkpoint_dir or "")
        if cfg.profile_spans:
            _profiler.configure(spans=cfg.profile_spans,
                                out_dir=cfg.profile_dir,
                                max_captures=cfg.profile_max_captures)

    def reset(self) -> None:
        """Clear observability-owned state. The shared timer and
        reliability counters are left alone — they predate this
        subsystem and other code depends on their accumulation."""
        self.trace.reset()
        self.training.reset()
        self.compiles.reset()
        self.mfu.reset()
        with self._lock:
            self._pipeline = {"blocks": 0, "iterations": 0, "in_flight": 0,
                              "host_seconds": 0.0, "wall_seconds": 0.0}
            self._streaming = {"chunks": 0, "rows": 0, "bytes": 0,
                               "wall_seconds": 0.0, "sample_rows": 0,
                               "exact": 0}
            self._hist_backend = {"choice": "", "plan": []}
            self._collective = {"guarded": 0, "wall_seconds": 0.0,
                                "timeouts": 0, "aborts": 0,
                                "heartbeat_age_max_s": 0.0, "world": 0}
            self._clock_skew = {"samples": 0, "last_skew_s": 0.0,
                                "max_skew_s": 0.0}
            self._clock_samples = _collections.deque(maxlen=512)
            self._distributed = {"world": 0, "feature_shard_width": 0,
                                 "setup_wall_seconds": 0.0,
                                 "sketch_rows": 0, "sketch_merges": 0}
            self._freshness = {"generation": 0, "publishes": 0,
                               "data_to_serve_s": 0.0,
                               "max_data_to_serve_s": 0.0,
                               "staleness_slo_s": 0.0, "slo_alarm": 0,
                               "slo_breaches": 0, "torn_publishes": 0,
                               "quarantined_windows": 0}
            self._membership = {"epoch": 0, "world": 0, "resizes": 0,
                                "shrinks": 0, "joins": 0,
                                "reshard_wall_s": 0.0,
                                "resharded_loads": 0}

    # -- exporters ------------------------------------------------------
    def pipeline_snapshot(self) -> Dict:
        with self._lock:
            p = dict(self._pipeline)
        frac = p["in_flight"] / p["blocks"] if p["blocks"] else 0.0
        return {"blocks": p["blocks"], "iterations": p["iterations"],
                "in_flight": p["in_flight"],
                "host_seconds": round(p["host_seconds"], 6),
                "wall_seconds": round(p["wall_seconds"], 6),
                "overlap_frac": round(frac, 4)}

    def streaming_snapshot(self) -> Dict:
        with self._lock:
            s = dict(self._streaming)
        rps = s["rows"] / s["wall_seconds"] if s["wall_seconds"] > 0 else 0.0
        return {"chunks": s["chunks"], "rows": s["rows"],
                "bytes": s["bytes"], "sample_rows": s["sample_rows"],
                "exact": s["exact"],
                "wall_seconds": round(s["wall_seconds"], 6),
                "rows_per_sec": round(rps, 1)}

    def hist_backend_snapshot(self) -> Dict:
        """The pinned histogram backend and the growth program's
        per-pass plan as an exportable mapping. `plan` lists every
        histogram pass of one tree as {stage, sk, formulation}
        (grower_mxu.hist_pass_plan: static, read without a device
        sync); `grouped_passes_per_tree` counts the scheduled passes
        and the bridge built slot-grouped (the fixup body runs as often
        as a tree needs, so it is listed and not counted); `partition`
        is what partition_impl resolved to for the grouped passes
        (stream | rank | argsort); `onehot_bin_rows`, present only where
        one-hot passes are built in feature tiles, the rows a feature's
        bin one-hot takes there (64 at 63 bins). Once the
        growth program has been traced, `operand_builds_per_tree`
        ({bins_pad, bins_t, channels, row_table}) and
        `operand_builds_per_pass`
        say where that program builds its row-sized kernel operands
        (grower_mxu.operand_builds: counted in the trace). The strings
        ride the JSON snapshot/bench tail; the Prometheus exporter
        skips them, so the choice is ALSO one-hot encoded (is_auto/
        is_mxu/is_pallas/is_scatter) for scrapers."""
        with self._lock:
            hb = dict(self._hist_backend)
        plan = [dict(p) for p in hb["plan"]]
        out: Dict = {"choice": hb["choice"], "plan": plan,
                     "partition": hb.get("partition", "")}
        if "onehot_bin_rows" in hb:
            out["onehot_bin_rows"] = hb["onehot_bin_rows"]
        if "operand_builds_per_pass" in hb:
            out["operand_builds_per_tree"] = \
                dict(hb["operand_builds_per_tree"])
            out["operand_builds_per_pass"] = hb["operand_builds_per_pass"]
        for name in ("auto", "mxu", "pallas", "scatter"):
            out["is_" + name] = int(hb["choice"] == name)
        for form in ("onehot", "grouped", "scatter"):
            out[form + "_passes_per_tree"] = sum(
                p["formulation"] == form and p["stage"] != "fixup"
                for p in plan)
        return out

    def collective_snapshot(self) -> Dict:
        with self._lock:
            c = dict(self._collective)
        c["wall_seconds"] = round(c["wall_seconds"], 6)
        c["heartbeat_age_max_s"] = round(c["heartbeat_age_max_s"], 3)
        return c

    def distributed_snapshot(self) -> Dict:
        with self._lock:
            d = dict(self._distributed)
        d["setup_wall_seconds"] = round(d["setup_wall_seconds"], 6)
        return d

    def freshness_snapshot(self) -> Dict:
        with self._lock:
            f = dict(self._freshness)
        f["data_to_serve_s"] = round(f["data_to_serve_s"], 6)
        f["max_data_to_serve_s"] = round(f["max_data_to_serve_s"], 6)
        return f

    def membership_snapshot(self) -> Dict:
        with self._lock:
            m = dict(self._membership)
        m["reshard_wall_s"] = round(m["reshard_wall_s"], 6)
        return m

    def clock_skew_snapshot(self) -> Dict:
        with self._lock:
            s = dict(self._clock_skew)
        s["last_skew_s"] = round(s["last_skew_s"], 6)
        s["max_skew_s"] = round(s["max_skew_s"], 6)
        return s

    def clock_samples(self) -> List[Dict]:
        """The bounded ring of piggybacked clock-offset samples
        ({"site", "walls"}) that the chrome trace dump embeds for
        ``python -m lightgbm_tpu.observability merge``."""
        with self._lock:
            return list(self._clock_samples)

    def snapshot(self) -> Dict:
        return {
            "enabled": self.enabled,
            "clock_skew": self.clock_skew_snapshot(),
            "collective": self.collective_snapshot(),
            "distributed": self.distributed_snapshot(),
            "freshness": self.freshness_snapshot(),
            "membership": self.membership_snapshot(),
            "flightrec": _flightrec.snapshot(),
            "profiler": _profiler.snapshot(),
            "hist_backend": self.hist_backend_snapshot(),
            "pipeline": self.pipeline_snapshot(),
            "streaming": self.streaming_snapshot(),
            "training": self.training.snapshot(),
            "compiles": {"entries": self.compiles.snapshot(),
                         **self.compiles.totals()},
            "device_utilization": self.mfu.snapshot(),
            "counters": self.counters.snapshot(),
            "timers": {k: round(float(v), 6)
                       for k, v in self.timer.totals().items()},
            "trace": {"spans_buffered": len(self.trace),
                      "dropped": self.trace.dropped},
        }

    def prometheus_text(self) -> str:
        snap = self.snapshot()
        training = dict(snap["training"])
        training.pop("last", None)   # unbounded-cardinality record
        return render_prometheus([
            ({"enabled": snap["enabled"]}, "lightgbm_tpu_observability",
             None),
            (training, "lightgbm_tpu_training", None),
            (snap["compiles"], "lightgbm_tpu_compiles", None),
            (snap["device_utilization"], "lightgbm_tpu_device", None),
            (snap["counters"], "lightgbm_tpu_reliability", None),
            (snap["collective"], "lightgbm_tpu_collective", None),
            (snap["distributed"], "lightgbm_tpu_distributed", None),
            (snap["freshness"], "lightgbm_tpu_freshness", None),
            (snap["membership"], "lightgbm_tpu_membership", None),
            (snap["clock_skew"], "lightgbm_tpu_clock_skew", None),
            (snap["flightrec"], "lightgbm_tpu_flightrec", None),
            (snap["hist_backend"], "lightgbm_tpu_hist_backend", None),
            (snap["pipeline"], "lightgbm_tpu_pipeline", None),
            (snap["streaming"], "lightgbm_tpu_streaming", None),
            (snap["timers"], "lightgbm_tpu_timer_seconds", None),
            (snap["trace"], "lightgbm_tpu_trace", None),
        ])

    def dump_trace(self, path: str, fmt: Optional[str] = None) -> str:
        return self.trace.dump(path, fmt, rank=current_rank(),
                               clock_samples=self.clock_samples())

    # -- training hooks (called from boosting/gbdt.py) ------------------
    def record_hist_plan(self, choice: str, plan, partition: str = "",
                         onehot_bin_rows: int = 0) -> None:
        """Pin the resolved histogram backend, the growth program's
        per-pass plan, [(stage, kernel slots, formulation)], the
        partition its grouped passes are built with and, where its
        one-hot passes are built in feature tiles, a feature's one-hot
        height there (0: none is). Recorded
        even when disabled: this is one-shot startup configuration, not
        per-iteration telemetry, and the bench JSON tail reads it
        regardless of the enable flag."""
        with self._lock:
            self._hist_backend = {
                "choice": str(choice), "partition": str(partition),
                "plan": [{"stage": str(st), "sk": int(sk),
                          "formulation": str(form)}
                         for st, sk, form in plan]}
            if onehot_bin_rows:
                self._hist_backend["onehot_bin_rows"] = int(onehot_bin_rows)

    def record_operand_builds(self, per_tree: Dict, per_pass: int) -> None:
        """What the traced growth program builds of its row-sized
        kernel operands once per tree and (still) in every pass; beside
        the plan it belongs to, and recorded like it."""
        with self._lock:
            self._hist_backend["operand_builds_per_tree"] = {
                str(k): int(v) for k, v in per_tree.items()}
            self._hist_backend["operand_builds_per_pass"] = int(per_pass)

    # -- collective-watchdog hooks (reliability/watchdog.py) ------------
    # recorded even when disabled, like record_hist_plan: watchdog
    # events are rare, high-value incident forensics — the last thing
    # the run prints before aborting must not depend on an enable flag
    def record_collective_guard(self, wall_seconds: float) -> None:
        with self._lock:
            self._collective["guarded"] += 1
            self._collective["wall_seconds"] += float(wall_seconds)

    def record_collective_timeout(self) -> None:
        with self._lock:
            self._collective["timeouts"] += 1

    def record_collective_abort(self) -> None:
        with self._lock:
            self._collective["aborts"] += 1

    def record_heartbeat_age(self, age_s: float) -> None:
        with self._lock:
            self._collective["heartbeat_age_max_s"] = max(
                self._collective["heartbeat_age_max_s"], float(age_s))

    def record_collective_world(self, world: int) -> None:
        with self._lock:
            self._collective["world"] = int(world)

    # -- elastic-membership hooks (distributed/elastic.py) --------------
    # recorded even when disabled, like the watchdog hooks: a resize is
    # an incident, and the metrics tail is the only record a
    # reincarnated process has of the world it came from
    def record_membership(self, epoch: int, world: int) -> None:
        """This rank's current membership belief (set at distributed
        init and again after every epoch adoption)."""
        with self._lock:
            self._membership["epoch"] = int(epoch)
            self._membership["world"] = int(world)

    def record_membership_resize(self, kind: str, epoch: int,
                                 world: int, joined: int = 0) -> None:
        """One committed membership change: `kind` is "shrink" or
        "join"; `world`/`epoch` are the NEW values the record names."""
        with self._lock:
            m = self._membership
            m["resizes"] += 1
            if kind == "shrink":
                m["shrinks"] += 1
            m["joins"] += int(joined)
            m["epoch"] = int(epoch)
            m["world"] = int(world)

    def record_membership_reshard(self, wall_s: float) -> None:
        """One topology-flexible checkpoint load (W-rank bundle read by
        a W'-rank world): the elasticity cost the bench sentinel
        watches."""
        with self._lock:
            self._membership["resharded_loads"] += 1
            self._membership["reshard_wall_s"] += float(wall_s)

    def record_clock_sample(self, site: str, walls) -> None:
        """One piggybacked clock-offset sample from a guarded collective
        (parallel/comm.py): every rank's pre-collective wall stamp, one
        float per rank, moved by the SAME allgather as the payload.
        Recorded even when disabled, like the other collective hooks —
        skew forensics must survive the enable flag."""
        w = [float(v) for v in walls]
        if not w:
            return
        skew = (max(w) - min(w)) if len(w) > 1 else 0.0
        with self._lock:
            self._clock_skew["samples"] += 1
            self._clock_skew["last_skew_s"] = skew
            self._clock_skew["max_skew_s"] = max(
                self._clock_skew["max_skew_s"], skew)
            self._clock_samples.append({"site": str(site), "walls": w})
        _flightrec.record_clock_sample(site, w)

    # -- continuous-loop hooks (continuous/trainer.py) ------------------
    # recorded even when disabled, like the watchdog hooks: the
    # freshness SLO alarm and the loop's incident counters (torn
    # publishes, quarantines) are the forensics the chaos protocol
    # reads from metrics alone — they must not depend on an enable flag
    def record_freshness_publish(self, generation: int,
                                 data_to_serve_s: float,
                                 slo_s: float = 0.0) -> None:
        """One published generation: `data_to_serve_s` is the wall from
        first row of the window entering ingest to the hot-swap landing
        (data-to-serving latency). `slo_s` > 0 arms the staleness
        alarm: the gauge latches 1 whenever the latest publish blew the
        budget and clears on the next in-budget one."""
        lat = float(data_to_serve_s)
        breach = int(slo_s > 0 and lat > float(slo_s))
        with self._lock:
            f = self._freshness
            f["generation"] = int(generation)
            f["publishes"] += 1
            f["data_to_serve_s"] = lat
            f["max_data_to_serve_s"] = max(f["max_data_to_serve_s"], lat)
            f["staleness_slo_s"] = float(slo_s)
            f["slo_alarm"] = breach
            f["slo_breaches"] += breach

    def record_freshness_recover(self, generation: int) -> None:
        """Loop recovery re-read the GENERATION marker: seed the live
        generation gauge so a restarted process that publishes nothing
        (exhausted stream, serve-only restart) still reports the
        generation it is actually serving, not 0. Publish counters are
        untouched — only publishes move them."""
        with self._lock:
            f = self._freshness
            f["generation"] = max(f["generation"], int(generation))

    def record_freshness_torn_publish(self, generation: int) -> None:
        """A half-built generation found ahead of the marker on
        recovery — the torn-publish twin of streaming's torn
        stream-state pairs — detected and discarded."""
        with self._lock:
            self._freshness["torn_publishes"] += 1

    def record_freshness_quarantine(self, window: int) -> None:
        """A poison window skipped after crash-looping the cycle past
        its retry budget."""
        with self._lock:
            self._freshness["quarantined_windows"] += 1

    def tree_macs_for(self, gbdt) -> int:
        """Analytic per-tree MAC estimate for this booster's config;
        cached on the booster. 0 off the MXU path (no MAC model) and
        wherever a pass of the growth program is built slot-grouped or
        by the XLA oracle: the model counts the one-hot kernel's MACs,
        which such a pass does not do, so MFU then reads as unavailable
        rather than invented (docs/Observability.md)."""
        cached = getattr(gbdt, "_obs_tree_macs", None)
        if cached is not None:
            return cached
        macs = 0
        all_onehot = all(form == "onehot" for _, _, form in
                         getattr(gbdt, "_hist_plan", None) or ())
        if getattr(gbdt, "_hist_impl", None) == "mxu" and all_onehot:
            cfg = gbdt.config
            macs = tree_macs(
                num_leaves=cfg.num_leaves, num_rows=gbdt.num_data,
                num_features=int(gbdt.num_bins_d.shape[0]),
                bmax=gbdt.bmax, double_prec=cfg.gpu_use_dp,
                quantized=cfg.use_quantized_grad,
                const_hess=bool(gbdt._const_hessian()),
                hist_subtraction=cfg.hist_subtraction,
                overshoot=cfg.growth_overshoot,
                bridge_gate=cfg.growth_bridge_gate)
        gbdt._obs_tree_macs = macs
        return macs

    def record_train_iteration(self, gbdt, iteration: int,
                               wall_s: float,
                               phases: Optional[Dict[str, float]] = None,
                               gradients=None, hessians=None,
                               tree=None) -> None:
        """One telemetry record for a per-iteration boosting step.
        `wall_s` and `phases` are the durations of the iteration's own
        spans (boosting/gbdt.py train_one_iter), not clocks read here."""
        if not self.enabled:
            return
        trees = int(getattr(gbdt, "num_tree_per_iteration", 1))
        macs = self.tree_macs_for(gbdt) * trees
        extra: Dict = {}
        if self.record_norms:
            import numpy as np
            if gradients is not None:
                extra["grad_norm"] = float(
                    np.linalg.norm(np.asarray(gradients)))
            if hessians is not None:
                extra["hess_norm"] = float(
                    np.linalg.norm(np.asarray(hessians)))
            if tree is not None:
                # host sync on the fresh tree — norms-gated for a reason
                # (see gbdt.py's lagged stall poll)
                extra["leaves"] = int(np.asarray(tree.num_leaves))
        self.training.record_iteration(
            iteration, wall_s, phases=phases, trees=trees,
            bagging_fraction=float(gbdt.config.bagging_fraction),
            macs=macs or None, counters=self.counters.snapshot(), **extra)
        if macs:
            self.mfu.add(macs, wall_s, trees)

    def record_fused_block(self, gbdt, iteration: int, k: int,
                           wall_s: float) -> None:
        """One record for a k-iteration fused scan dispatch (no host
        boundary inside the block). `wall_s` is the host clock from this
        block's dispatch to the next one's (the first record of a run
        has none and is not made): the device works through blocks back
        to back, so that is what a block costs, and nothing syncs to
        measure it. What building the program cost is in the compile
        ledger, from JAX's own events."""
        if not self.enabled:
            return
        kcls = int(getattr(gbdt, "num_tree_per_iteration", 1))
        trees = int(k) * kcls
        macs = self.tree_macs_for(gbdt) * trees
        self.training.record_iteration(
            iteration, wall_s, trees=trees, iterations=int(k), fused=True,
            bagging_fraction=float(gbdt.config.bagging_fraction),
            macs=macs or None, counters=self.counters.snapshot())
        if macs:
            self.mfu.add(macs, wall_s, trees)

    def record_pipeline_block(self, k: int, wall_s: float, host_s: float,
                              in_flight: bool = False) -> None:
        """One pipelined-executor block, from its `entry.block` span:
        wall_s runs from the dispatch to the end of the metric sync,
        host_s is the host's own work unpacking the previous block's
        trees inside it (its wait for that block left out), in_flight
        whether the block was enqueued while the one before it was
        still running. `overlap_frac` of the snapshot is the share of
        blocks for which it was."""
        if not self.enabled:
            return
        with self._lock:
            p = self._pipeline
            p["blocks"] += 1
            p["iterations"] += int(k)
            p["in_flight"] += bool(in_flight)
            p["host_seconds"] += float(host_s)
            p["wall_seconds"] += float(wall_s)

    def record_streaming_chunk(self, phase: str, chunk_index: int,
                               t0: float, wall_s: float, rows: int,
                               nbytes: int) -> None:
        """One ingested chunk from streaming/loader.py: `phase` is
        "sketch" (pass 1) or "bin" (pass 2); wall_s covers the chunk's
        host work including any overlapped parse it absorbed."""
        if not self.enabled:
            return
        with self._lock:
            s = self._streaming
            s["chunks"] += 1
            if phase == "bin":   # pass 2 re-streams the same rows
                s["rows"] += int(rows)
            s["bytes"] += int(nbytes)
            s["wall_seconds"] += float(wall_s)

    def record_streaming_sketch(self, sample_rows: int,
                                exact: bool) -> None:
        """The frozen pass-1 reservoir: its row count and whether it
        held the whole stream (exact => bit-parity boundaries)."""
        if not self.enabled:
            return
        with self._lock:
            self._streaming["sample_rows"] = int(sample_rows)
            self._streaming["exact"] = int(bool(exact))

    def record_distributed_setup(self, world: int,
                                 feature_shard_width: int,
                                 wall_seconds: float) -> None:
        """Crossbar mesh resolution (boosting/gbdt.py _setup_parallel):
        device-mesh world size, the reduce-scatter feature shard width
        (0 = psum full-histogram aggregation), and the setup wall."""
        if not self.enabled:
            return
        with self._lock:
            d = self._distributed
            d["world"] = int(world)
            d["feature_shard_width"] = int(feature_shard_width)
            d["setup_wall_seconds"] += float(wall_seconds)

    def record_distributed_sketch(self, rows: int) -> None:
        """One per-rank sketch merged through the distributed-binning
        mapper_sync (distributed/binning.py)."""
        if not self.enabled:
            return
        with self._lock:
            d = self._distributed
            d["sketch_rows"] += int(rows)
            d["sketch_merges"] += 1


#: process-global singleton; `lightgbm_tpu.observability.registry`.
registry = ObservabilityRegistry()
