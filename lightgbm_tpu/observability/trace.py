"""The one timed region of the package: `span(name, **attrs)`.

A span is one timed region of host code with attributes. Every use

- opens a ``jax.profiler.TraceAnnotation`` of the same name and
  attributes, so inside ANY ``jax.profiler`` capture (the benchmark's
  traced window, an operator's own) the span lies on the capture's
  clock beside the device's operations; with no capture running that is
  one inactive check;
- adds its duration and a count to the per-name totals
  (`Trace.totals()`, which is what ``utils.timer.global_timer.totals()``
  returns): never evicted, always on;
- lands in one lock-guarded ring (`collections.deque(maxlen=...)`,
  oldest evicted) with its name, start, duration, a span id, the ID of
  the span that was open around it on the same thread (0 for none), the
  thread and its attributes, and from there in the crash flight
  recorder.

The ring takes PHASE-level spans unconditionally (a set-up step, a
block, a tree and its phases: a handful per block or per tree), which
is what gives a post-mortem its last spans with the default
``observe=false``. A FINE span (`fine=True`: per request, per batch,
per chunk — every site under serving/, streaming/ and continuous/)
enters the ring only while `Trace.enabled` (``observe=true``), so a
server does not flush a trainer's set-up out of the ring; its
annotation and its totals are unconditional like any other.

Nesting is tracked with a per-thread stack (`threading.local`), so
concurrent threads — the serving micro-batcher workers, checkpoint
writers — interleave freely. A span never syncs the device, moves data
or builds a program: it reads the host clock twice.

Clocks. Ring timestamps are ``time.perf_counter()`` less the trace's
epoch, in seconds; `Trace.epoch_wall` is the wall clock at that same
instant. A capture's host events are on the profiler's clock, which
differs from (epoch_wall + ts) by one constant per capture (where the
capture's zero lies): `capture_agreement` measures how closely.

Export formats:
- JSONL: one span dict per line (jq/pandas-friendly);
- Chrome/Perfetto `trace_event` JSON ("ph": "X" complete events with
  microsecond ts/dur), loadable in chrome://tracing or ui.perfetto.dev.
"""

from __future__ import annotations

import collections
import itertools
import json
import os
import threading
import time
from typing import Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation

from .flightrec import recorder as _flightrec
from .profile import profiler as _profiler

__all__ = ["Span", "Trace", "tracer", "span"]


class Span:
    """One live timed region; books itself on __exit__."""

    __slots__ = ("_trace", "_ann", "name", "attrs", "fine", "start",
                 "duration", "depth", "id", "parent_id", "parent")

    def __init__(self, trace: "Trace", name: str, attrs: Dict,
                 fine: bool = False):
        self._trace = trace
        self._ann = None
        self.name = name
        self.attrs = attrs
        self.fine = fine
        self.start = 0.0
        self.duration = 0.0
        self.depth = 0
        self.id = 0
        self.parent_id = 0
        self.parent: Optional[str] = None

    @property
    def end(self) -> float:
        return self.start + self.duration

    def __enter__(self) -> "Span":
        trace = self._trace
        stack = trace._stack()
        self.depth = len(stack)
        if stack:
            self.parent_id = stack[-1].id
            self.parent = stack[-1].name
        self.id = next(trace._ids)
        stack.append(self)
        # a TraceMe starts its clock when it is built, not when entered
        self._ann = TraceAnnotation(self.name, **self.attrs)
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.duration = time.perf_counter() - self.start
        self._ann.__exit__(*exc)
        stack = self._trace._stack()
        # balanced exit is the overwhelmingly common case; an exception
        # unwinding several spans at once still pops each in turn
        if stack and stack[-1] is self:
            stack.pop()
        else:  # pragma: no cover - unbalanced enter/exit
            try:
                stack.remove(self)
            except ValueError:
                pass
        self._trace._book(self)
        return False


class Trace:
    """Span factory, completed-span ring and per-name totals.
    Thread-safe."""

    def __init__(self, capacity: int = 4096):
        #: ``observe=true``: fine spans enter the ring too
        self.enabled = False
        self._lock = threading.Lock()
        self._ring = collections.deque(maxlen=max(int(capacity), 16))
        self._local = threading.local()
        self._ids = itertools.count(1)     # next() is atomic under the GIL
        # per-name [seconds, count]: what global_timer.totals() reads;
        # never evicted, untouched by reset()
        self._totals: Dict[str, List] = {}
        # open-span stacks by thread id (the same list objects as the
        # threading.local stacks) so the watchdog's heartbeat thread can
        # name another thread's innermost open span
        self._open: Dict[int, List["Span"]] = {}
        self._epoch = time.perf_counter()
        self._epoch_wall = time.time()   # same instant, wall clock —
        self.dropped = 0          # spans evicted from the ring

    # ------------------------------------------------------------------
    def span(self, name: str, *, fine: bool = False, **attrs):
        """Context manager timing a region (module docstring). `fine`
        marks a per-request / per-batch / per-chunk site, kept out of
        the ring unless `enabled`. When the device profiler is armed for
        this span name, the region is additionally bracketed in a
        jax.profiler capture of its own."""
        sp = Span(self, name, attrs, fine)
        if _profiler.armed and _profiler.matches(name):
            return _ProfiledSpan(sp, name)
        return sp

    # ------------------------------------------------------------------
    def _stack(self) -> List[Span]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
            with self._lock:
                self._open[threading.get_ident()] = stack
        return stack

    def current(self) -> Optional[Span]:
        """The innermost span open on the CALLING thread, or None (the
        compile ledger names it as the place a program was built)."""
        stack = getattr(self._local, "stack", None)
        return stack[-1] if stack else None

    def innermost_open(self) -> Tuple[str, float]:
        """(name, age_s) of the most recently opened span still open on
        ANY thread; ("", 0.0) when nothing is open. Read cross-thread
        for the watchdog heartbeat payload: stacks are only appended/
        popped under the GIL, so a stale read costs at most one span of
        accuracy in a diagnostic."""
        with self._lock:
            stacks = list(self._open.values())
        best: Optional[Span] = None
        for stack in stacks:
            if stack:
                top = stack[-1]
                if best is None or top.start > best.start:
                    best = top
        if best is None:
            return "", 0.0
        return best.name, max(0.0, time.perf_counter() - best.start)

    def now(self) -> float:
        """The host clock on the ring's timeline (seconds since the
        trace epoch): what a record's `ts` would be for a span starting
        now."""
        with self._lock:
            return time.perf_counter() - self._epoch

    def _book(self, sp: Span) -> None:
        """A closed span: totals always, ring and flight recorder
        unless it is a fine span with the trace disabled."""
        keep = self.enabled or not sp.fine
        rec = None
        if keep:
            rec = {
                "name": sp.name,
                "id": sp.id,
                "parent_id": sp.parent_id,
                "dur": sp.duration,              # seconds
                "tid": threading.get_ident(),
                "depth": sp.depth,
            }
            if sp.parent is not None:
                rec["parent"] = sp.parent
            if sp.attrs:
                rec["attrs"] = sp.attrs
        with self._lock:
            tot = self._totals.get(sp.name)
            if tot is None:
                self._totals[sp.name] = [sp.duration, 1]
            else:
                tot[0] += sp.duration
                tot[1] += 1
            if rec is not None:
                # epoch read under the lock: reset() rebinds it
                rec["ts"] = sp.start - self._epoch   # s since the epoch
                if len(self._ring) == self._ring.maxlen:
                    self.dropped += 1
                self._ring.append(rec)
        if rec is not None:
            # span-close tap for the crash flight recorder (bounded
            # ring, survives as the postmortem timeline — flightrec.py)
            _flightrec.record_span(sp.name, sp.start, sp.duration,
                                   sp.depth, sp.parent)

    # ------------------------------------------------------------------
    def totals(self) -> Dict[str, float]:
        """Seconds spent under each span name since the process began
        (or since reset_totals)."""
        with self._lock:
            return {k: v[0] for k, v in self._totals.items()}

    def counts(self) -> Dict[str, int]:
        with self._lock:
            return {k: v[1] for k, v in self._totals.items()}

    def reset_totals(self) -> None:
        with self._lock:
            self._totals.clear()

    # ------------------------------------------------------------------
    def set_capacity(self, capacity: int) -> None:
        with self._lock:
            self._ring = collections.deque(self._ring,
                                           maxlen=max(int(capacity), 16))

    def spans(self) -> List[Dict]:
        with self._lock:
            return list(self._ring)

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)

    def reset(self) -> None:
        with self._lock:
            self._ring.clear()
            self.dropped = 0
            self._epoch = time.perf_counter()
            self._epoch_wall = time.time()

    @property
    def epoch_wall(self) -> float:
        """Wall-clock instant of the trace epoch: the anchor the
        cross-rank merge (observability/merge.py) uses to place this
        rank's perf_counter-relative timestamps on a shared timeline."""
        with self._lock:
            return self._epoch_wall

    # ------------------------------------------------------------------
    # export
    def to_chrome_trace(self, rank: Optional[int] = None,
                        clock_samples: Optional[List[Dict]] = None
                        ) -> Dict:
        """Chrome/Perfetto `trace_event` format: "X" complete events,
        microsecond timestamps (chrome://tracing, ui.perfetto.dev).
        With `rank`, the document gains rank-tagged process_name
        metadata and a ``lightgbm_tpu_meta`` block (rank, wall-clock
        epoch, piggybacked clock-offset samples) that
        ``python -m lightgbm_tpu.observability merge`` consumes."""
        pid = os.getpid()
        events = []
        if rank is not None:
            events.append({"name": "process_name", "ph": "M", "pid": pid,
                           "tid": 0,
                           "args": {"name": f"lightgbm_tpu rank {rank}"}})
        for rec in self.spans():
            ev = {
                "name": rec["name"],
                "ph": "X",
                "ts": round(rec["ts"] * 1e6, 3),
                "dur": round(rec["dur"] * 1e6, 3),
                "pid": pid,
                "tid": rec["tid"],
                "cat": "lightgbm_tpu",
            }
            args = dict(rec.get("attrs", ()))
            args["span_id"] = rec["id"]
            if rec["parent_id"]:
                args["parent_id"] = rec["parent_id"]
                args["parent"] = rec["parent"]
            if args:
                ev["args"] = args
            events.append(ev)
        doc: Dict = {"traceEvents": events, "displayTimeUnit": "ms"}
        if rank is not None:
            doc["lightgbm_tpu_meta"] = {
                "rank": int(rank),
                "epoch_wall": self.epoch_wall,
                "clock_samples": list(clock_samples or ()),
            }
        return doc

    def to_jsonl(self) -> str:
        return "\n".join(json.dumps(rec) for rec in self.spans())

    def dump(self, path: str, fmt: Optional[str] = None,
             rank: Optional[int] = None,
             clock_samples: Optional[List[Dict]] = None) -> str:
        """Write the ring to `path`. fmt: "jsonl" | "chrome"; default
        by extension (.jsonl -> JSONL, anything else -> Chrome JSON).
        Returns the format written."""
        if fmt is None:
            fmt = "jsonl" if str(path).endswith(".jsonl") else "chrome"
        with open(path, "w") as fh:
            if fmt == "jsonl":
                fh.write(self.to_jsonl())
                fh.write("\n")
            else:
                json.dump(self.to_chrome_trace(
                    rank=rank, clock_samples=clock_samples), fh)
                fh.write("\n")
        return fmt


class _ProfiledSpan:
    """A Span whose region is additionally captured by the device
    profiler (observability/profile.py). Entering starts the
    jax.profiler trace first so it covers the whole span."""

    __slots__ = ("_span", "_name", "_started")

    def __init__(self, span: Span, name: str):
        self._span = span
        self._name = name
        self._started = False

    def __enter__(self) -> Span:
        self._started = _profiler.begin(self._name)
        return self._span.__enter__()

    def __exit__(self, *exc) -> bool:
        out = self._span.__exit__(*exc)
        if self._started:
            _profiler.end()
        return out


#: the process-global trace: `registry.trace`, `global_timer` and the
#: module-level `span` are all this object
tracer = Trace()
span = tracer.span


def capture_agreement(spans: List[Dict], events: List[Tuple],
                      epoch_wall: float) -> Dict:
    """How closely the ring and a jax.profiler capture agree on the
    same spans. `spans` are ring records, `events` the capture's host
    events as (name, start_ns, duration_ns); both are matched per name
    in order of start. A capture's clock starts where the capture does,
    so one constant (the median difference of the starts) is taken out
    first. Returns the number matched, that constant (`offset_s`) and
    the largest remaining disagreement of a start or an end
    (`max_err_s`); names whose counts differ are listed under
    `unmatched`."""
    by_name: Dict[str, List] = {}
    for name, start_ns, dur_ns in events:
        by_name.setdefault(name, []).append((start_ns, dur_ns))
    pairs, unmatched = [], []
    ring: Dict[str, List[Dict]] = {}
    for rec in spans:
        ring.setdefault(rec["name"], []).append(rec)
    for name, recs in ring.items():
        evs = sorted(by_name.get(name, ()))
        if len(evs) != len(recs):
            unmatched.append(name)
            continue
        for rec, (start_ns, dur_ns) in zip(
                sorted(recs, key=lambda r: r["ts"]), evs):
            wall = epoch_wall + rec["ts"]
            pairs.append((wall - start_ns / 1e9,
                          wall + rec["dur"] - (start_ns + dur_ns) / 1e9))
    if not pairs:
        return {"matched": 0, "offset_s": None, "max_err_s": None,
                "unmatched": sorted(unmatched)}
    starts = sorted(p[0] for p in pairs)
    offset = starts[len(starts) // 2]
    err = max(max(abs(a - offset), abs(b - offset)) for a, b in pairs)
    return {"matched": len(pairs), "offset_s": offset, "max_err_s": err,
            "unmatched": sorted(unmatched)}
