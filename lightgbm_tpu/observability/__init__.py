"""Unified observability: one span primitive, a compile ledger fed by
JAX's own events, telemetry, MFU, exporters.

One process-global registry (`registry`) over:

- THE timed region, `span(name, **attrs)` (trace.py): a
  ``jax.profiler.TraceAnnotation`` (so it lies on any capture's clock
  beside the device's operations), a record in a bounded in-memory ring
  with span and parent ids (JSONL / Chrome-Perfetto export,
  `registry.dump_trace(path)`), and a per-name total.
  `utils.timer.global_timer.timeit`, `registry.trace.span` and
  `observability.span` are the same function. Names are
  ``<layer>.<phase>`` with the layer names of PERF.md section 3;
- the compile ledger (compiles.py): per jitted function, what JAX's
  monitoring events say its tracing, lowering and backend compile (or
  cache retrieval) cost, and inside which span it was built;
- per-iteration training telemetry (iteration wall time, phase split,
  grad/hess norms, leaves grown, bagging fraction, reliability-counter
  deltas) hooked into `boosting/gbdt.py`, read off the spans;
- device-utilization accounting: achieved MACs from the MXU histogram
  kernel dimensions (nchan * S * N * F * B, learner/histogram_mxu.py)
  turned into achieved-TFLOP/s and model-flops-utilization (MFU);
- exporters: `registry.snapshot()` JSON dict, Prometheus text format
  (served from `serving/server.py` at /metrics), `dump_trace(path)`;
- a crash flight recorder (`recorder`, flightrec.py): bounded ring of
  recent spans / collective brackets / fault hits / guard trips,
  flushed as an atomic ``postmortem_<rank>.json`` on fatal paths;
- budgeted device-profiler capture (`profiler`, profile.py) bracketing
  jax.profiler traces around spans matching ``profile_spans``;
- cross-rank trace merge (merge.py, ``python -m
  lightgbm_tpu.observability merge <dir>``) aligning per-rank clocks
  from samples piggybacked on guarded collectives;
- the bench regression sentinel (regress.py, ``bench.py --compare``)
  checking the BENCH_r*/MULTICHIP_r* trajectory for perf drops.

Always on: the annotation, the totals, the compile ledger, and the ring
record of PHASE-level spans (a set-up step, a block, a tree and its
phases), which is what gives the flight recorder its spans. The
`observe` parameter (config.py, or `registry.enable()`) adds the FINE
spans (per request, per batch, per chunk) to the ring, the per-
iteration telemetry and MFU records. No span syncs the device, moves
data or builds a program, and `observe` changes neither the model nor
the number of device syncs (docs/Observability.md).

Reference analog: Common::Timer / FunctionTimer RAII accumulators
printed under USE_TIMETAG (include/LightGBM/utils/common.h:973) — here
the accumulators are structured, exportable, and device-aware.
"""

from __future__ import annotations

from . import mfu
from .compiles import CompileAccounting
from .export import MetricsHTTPServer, prometheus_lines
from .flightrec import FlightRecorder, recorder
from .merge import merge_traces
from .profile import SpanProfiler, profiler
from .registry import ObservabilityRegistry, registry
from .telemetry import TrainingTelemetry
from .trace import Span, Trace, span

__all__ = [
    "registry", "ObservabilityRegistry", "Trace", "Span",
    "TrainingTelemetry", "CompileAccounting", "MetricsHTTPServer",
    "prometheus_lines", "mfu", "span", "snapshot", "dump_trace",
    "prometheus_text", "enable", "disable",
    "FlightRecorder", "recorder", "SpanProfiler", "profiler",
    "merge_traces",
]

# module-level conveniences bound to the process-global registry
snapshot = registry.snapshot
dump_trace = registry.dump_trace
prometheus_text = registry.prometheus_text
enable = registry.enable
disable = registry.disable
