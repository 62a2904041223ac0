"""What every runner shares: finding a cell's files by name, refusing
anything but the chip, the clocks and counters taken from JAX itself,
the traced window, and the one line a run ends with."""

from __future__ import annotations

import contextlib
import importlib
import json
import os
import pkgutil
import shutil
import threading
import time
from typing import Any, Dict, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

#: read at import, which __main__ does before anything heavy: the
#: process's own start, to within the interpreter's tens of milliseconds
T0 = time.perf_counter()

SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class BenchmarkError(Exception):
    """The run cannot give a result: no record is printed."""


def say(msg: str) -> None:
    """An earlier line: what the last line may not hold."""
    print("# [%7.2fs] %s" % (time.perf_counter() - T0, msg), flush=True)


# ----------------------------------------------------------------------
# cells are data
def _load_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_benchmark() -> dict:
    """BENCHMARK.json, with the cells this directory holds back (built,
    rehearsed, not admitted: benchmark/held_back.json says why) listed
    under the same keys and marked `held_back`."""
    bench = _load_json(os.path.join(ROOT, "BENCHMARK.json"))
    held_path = os.path.join(BENCH_DIR, "held_back.json")
    if os.path.exists(held_path):
        held = _load_json(held_path)
        admitted = [w["name"] for w in bench["workloads"]]
        for key in ("end_to_end", "per_layer"):
            # a metric that names no cells means the admitted ones
            # (but every cell there ever is owes the set-up time)
            for m in bench[key]:
                if m["name"] != "setup_s":
                    m.setdefault("workloads", admitted)
        for key in ("configs", "workloads", "end_to_end", "per_layer"):
            have = {e["name"] for e in bench[key]}
            for entry in held.get(key, []):
                if entry["name"] not in have:
                    bench[key].append(dict(entry, held_back=True))
    return bench


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str) -> dict:
    """One cell with its configuration, its traffic mix and the metrics
    it owes, each found by the name BENCHMARK.json gives it."""
    bench = load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchmarkError("no workload %r; there are: %s"
                             % (name, ", ".join(sorted(cells))))
    cell = dict(cells[name])
    entry = next((c for c in bench["configs"]
                  if c["name"] == cell["config"]), None)
    if entry is None:
        raise BenchmarkError("workload %r names configuration %r, which "
                             "BENCHMARK.json does not list"
                             % (name, cell["config"]))
    cell["config_entry"] = entry
    cell["config"] = _load_json(os.path.join(ROOT, entry["file"]))
    cell["traffic_name"] = cell["traffic"]
    cell["traffic"] = _load_json(os.path.join(
        BENCH_DIR, "traffic", cell["traffic_name"] + ".json"))
    cell["end_to_end"] = [m for m in bench["end_to_end"]
                          if _applies(m, name)]
    moved = {m["name"] for m in cell["end_to_end"]}
    cell["per_layer"] = [m for m in bench["per_layer"]
                         if _applies(m, name) and m["moves"] in moved]
    return cell


def rehearsal_overlay(cfg: dict, traffic: dict):
    """(configuration, traffic) at the tiny size the configuration's
    `rehearsal` states: its keys laid over the configuration's (one
    level into dicts, so `expect` keeps what it does not override), its
    `traffic` over the mix's."""
    over = cfg["rehearsal"]
    out = dict(cfg)
    for key, value in over.items():
        if key == "traffic":
            continue
        if isinstance(value, dict) and isinstance(out.get(key), dict):
            out[key] = {**out[key], **value}
        else:
            out[key] = value
    return out, {**traffic, **over.get("traffic", {})}


def load_runner(kind: str):
    """The runner of a kind: `runners/<kind>.py`'s `TASK` where it states
    one (a training task, benchmark/training.py), else the module. Either
    has `run`, `rehearsal_says` and `rehearsal_reads`."""
    try:
        mod = importlib.import_module("benchmark.runners." + kind)
    except ModuleNotFoundError as exc:
        if exc.name != "benchmark.runners." + kind:
            raise
        raise BenchmarkError("no runner for configurations of kind %r "
                             "(benchmark/runners/%s.py)" % (kind, kind))
    return getattr(mod, "TASK", mod)


def layer_metric_readers() -> Dict[str, Any]:
    """Every module in benchmark/layer_metrics/, by the NAME it states."""
    from . import layer_metrics
    readers = {}
    for info in pkgutil.iter_modules(layer_metrics.__path__):
        mod = importlib.import_module(
            "benchmark.layer_metrics." + info.name)
        if mod.NAME in readers:
            raise BenchmarkError("two per-layer readers state the name %r"
                                 % mod.NAME)
        if mod.SOURCE not in SOURCES:
            raise BenchmarkError("%s: SOURCE %r is not one of %s"
                                 % (info.name, mod.SOURCE, SOURCES))
        readers[mod.NAME] = mod
    return readers


# ----------------------------------------------------------------------
# the device
def load_peaks() -> dict:
    return _load_json(os.path.join(BENCH_DIR, "peaks.json"))


def peak_of_kind(device_kind: str, peaks: Optional[dict] = None):
    """The peaks entry whose `match` is in `device_kind`, or None: a
    device that is not in the table is an error, not a default."""
    kind = str(device_kind).lower()
    for entry in (peaks or load_peaks())["devices"]:
        if any(pat in kind for pat in entry["match"]):
            return entry
    return None


def claim_device(chips: int, rehearsal: bool) -> dict:
    """The device record of the last line; anything but a TPU that
    peaks.json knows, or fewer chips than the cell asks for, ends the
    run here with no record. A rehearsal pins the process to the CPU
    first (with `chips` virtual devices) and says so in the record."""
    if rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if chips > 1 and "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=%d"
                % chips).strip()
    import jax
    if rehearsal:
        jax.config.update("jax_platforms", "cpu")
    devs = jax.devices()
    rec = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if rehearsal:
        return rec
    if rec["platform"] != "tpu":
        raise BenchmarkError(
            "JAX found platform %r (%s x%d), not a TPU; nothing was run. "
            "--rehearse-cpu checks a cell's control flow on a CPU."
            % (rec["platform"], rec["kind"], rec["count"]))
    if peak_of_kind(rec["kind"]) is None:
        raise BenchmarkError("device_kind %r is not in benchmark/peaks.json"
                             % rec["kind"])
    if rec["count"] < chips:
        raise BenchmarkError("the cell needs %d chip(s), JAX sees %d"
                             % (chips, rec["count"]))
    return rec


def memory_peak_bytes() -> Optional[int]:
    """Peak device memory on the fullest chip: the live buffers at their
    peak plus the largest scratch a program reserved. On this runtime
    `peak_bytes_in_use` leaves a running program's temporaries out (a
    sort with 0.5 GB of them moved it by 6 MB, and `peak_bytes_reserved`
    by the 0.5 GB: chip probe, PR 22), and a cell holds both at once."""
    import jax
    worst = None
    for dev in jax.local_devices():
        stats = dev.memory_stats() or {}
        if "peak_bytes_in_use" not in stats:
            continue
        total = int(stats["peak_bytes_in_use"]) + \
            int(stats.get("peak_bytes_reserved", 0))
        say("memory %s: peak_bytes_in_use %d + peak_bytes_reserved %d of "
            "%s" % (dev, stats["peak_bytes_in_use"],
                    stats.get("peak_bytes_reserved", 0),
                    stats.get("bytes_limit")))
        worst = total if worst is None else max(worst, total)
    return worst


class CompileClock:
    """Backend compilations as JAX's own monitoring events count them:
    how many programs this process had to build or fetch (`programs`: a
    hit of the persistent cache still means the program was traced and
    lowered here), the seconds the backend spent on them, and the
    cache's hits and misses. Copied from chip_smoke.py; tracing and
    lowering are not in `seconds`."""

    def __init__(self):
        import jax
        self._lock = threading.Lock()
        self.seconds = 0.0
        self.programs = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_):
        if event == _BACKEND_COMPILE:
            with self._lock:
                self.seconds += secs
                self.programs += 1

    def _event(self, event, **_):
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

    def read(self) -> dict:
        with self._lock:
            return {"seconds": self.seconds, "programs": self.programs,
                    "hits": self.hits, "misses": self.misses}


def start_clocks(rehearsal: bool) -> CompileClock:
    """The program's own cache placement (JAX_COMPILATION_CACHE_DIR if
    set, else <checkout>/.jax_cache), every program kept however fast it
    compiled, and the compile clock."""
    import jax
    from lightgbm_tpu.utils.compile_cache import configure_compile_cache
    cache_dir = None if rehearsal else configure_compile_cache()
    if cache_dir:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    entries = 0
    if cache_dir and os.path.isdir(cache_dir):
        entries = sum(1 for n in os.listdir(cache_dir)
                      if n.endswith("-cache"))
    say("compile cache: %s (%d entries)" % (cache_dir, entries))
    return CompileClock()


# ----------------------------------------------------------------------
# the traced window
class TracedWindow:
    """Brackets jax.profiler around a few seconds of the steady window
    and hands the capture to trace_reduce. The Python tracer is off (it
    costs host time per call and the reduction does not read it); the
    host tracer stays on for this benchmark's own spans and JAX's."""

    def __init__(self, enabled: bool, cpu_rehearsal: bool = False):
        self.enabled = enabled
        self.cpu_rehearsal = cpu_rehearsal
        self.reduced: Optional[dict] = None
        self._dir = os.path.join(
            os.environ.get("TMPDIR") or os.path.join(ROOT, ".bench_tmp"),
            "lgbm_tpu_bench_trace_%d" % os.getpid())
        self._t0 = self._t1 = None

    def start(self) -> None:
        if not self.enabled:
            return
        import jax
        shutil.rmtree(self._dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self._dir, profiler_options=opts)
        self._t0 = time.perf_counter()

    def stop(self) -> None:
        if not self.enabled or self._t0 is None or self._t1 is not None:
            return
        import jax
        self._t1 = time.perf_counter()
        jax.profiler.stop_trace()
        from . import trace_reduce
        try:
            path = trace_reduce.find_xplane(self._dir)
            say("trace: %s (%.1f MB), window %.3f s"
                % (os.path.basename(path), os.path.getsize(path) / 1e6,
                   self._t1 - self._t0))
            self.reduced = trace_reduce.reduce_trace(
                trace_reduce.load_xplane(path, self.cpu_rehearsal))
            self.reduced["window_s"] = self._t1 - self._t0
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)

    def span(self, name: str, **kw):
        """A host span in the capture (a no-op context when not
        tracing)."""
        if not self.enabled:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name, **kw)


# ----------------------------------------------------------------------
# the last line
def percentile(samples, q: float) -> float:
    """Nearest-rank percentile of a non-empty sequence (no
    interpolation: the value is one that was measured)."""
    xs = sorted(samples)
    if not xs:
        raise BenchmarkError("a percentile of no samples")
    k = max(0, min(len(xs) - 1, int(-(-q * len(xs) // 100)) - 1))
    return float(xs[k])


def result_line(cell: dict, run: dict, device: dict, trace: bool) -> dict:
    """The contract's last line from a runner's readings: with tracing
    off the cell's end-to-end metrics, with it on its per-layer metrics
    (each from its own reader; one that finds nothing to read is left
    out) and the device's busy seconds; and the numbers `correct`
    compared (`compared`: (name, value, "max" | "min", limit))."""
    metrics: Dict[str, dict] = {}
    if not trace:
        for m in cell["end_to_end"]:
            if m["name"] not in run["end_to_end"]:
                raise BenchmarkError(
                    "the %s runner gave no %r, which %s owes"
                    % (cell["config"]["kind"], m["name"], cell["name"]))
            metrics[m["name"]] = {"value": run["end_to_end"][m["name"]],
                                  "unit": m["unit"]}
    else:
        readers = layer_metric_readers()
        for m in cell["per_layer"]:
            mod = readers.get(m["name"])
            if mod is None:
                raise BenchmarkError(
                    "per-layer metric %r has no reader under "
                    "benchmark/layer_metrics/" % m["name"])
            value = mod.read(run["readings"])
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    dev = dict(device)
    dev["memory_peak_bytes"] = run["readings"].get("memory_peak_bytes")
    line = {"correct": bool(run["correct"]),
            "attempted": int(run["attempted"]),
            "failed": int(run["failed"]), "metrics": metrics,
            "device": dev}
    if trace:
        reduced = run["readings"].get("trace")
        if not reduced or not reduced["busy_s"] > 0:
            raise BenchmarkError("the traced window holds no device "
                                 "operation")
        dev["busy_s"] = reduced["busy_s"]
        dev["window_s"] = reduced["window_s"]
        line["breakdown"] = {"device_ops": reduced["device_ops"][:10],
                             "idle_gaps": reduced["idle_gaps"][:10]}
    # what `correct` compared, each number beside its limit ("max": at
    # most, "min": above), last
    if "compared" in run:
        line["compared"] = {name: {"value": float(value), bound: float(limit)}
                            for name, value, bound, limit in run["compared"]}
    return line
