"""Compile ledger: what JAX itself timed, per function name.

JAX times every trace of a jitted function
(``/jax/core/compile/jaxpr_trace_duration``), every lowering to MLIR
(``.../jaxpr_to_mlir_module_duration``) and every backend compile or
fetch from the persistent cache (``.../backend_compile_duration``), each
with ``fun_name=``, and announces each of those when it STARTS as a
scalar event of the same name; it counts the persistent cache's hits
and misses as plain events. This module listens to all of them, once,
from import, and keeps per function name

    traced   trace_seconds      times traced; a nested jitted function
                                is traced (or found in the trace cache,
                                ~0 s) once per enclosing trace
    lowered  lower_seconds
    built    backend_seconds    compile, or retrieval on a cache hit
    hits     misses             of the persistent cache: an event
                                belongs to the backend compile it falls
                                inside on its thread
    span                        the innermost span (observability/
                                trace.py) open on the thread when the
                                program was last built

Seconds are SELF times: an event's duration less the events nested in
it on the same thread (a trace inside a trace, a trace inside a
lowering), so the seconds of all entries add up to wall time spent and
nothing is counted twice. ``jit(f)`` (lowering, backend) and ``f``
(trace) are one entry, ``f``.

The ledger also keeps the last `capacity` OUTERMOST events one by one
(`events()`: kind, function, the event's whole duration with what
nested in it, the ring-timeline instant it ended and the open span;
tracing a grower fires thousands of nested events, which would flush a
bounded list), which is how a reader tells the programs built before a
given moment from those built after. Nothing is bracketed by
hand any more: a program's first dispatch is not its compile time.
"""

from __future__ import annotations

import collections
import threading
from typing import Dict, List

from jax import monitoring as _monitoring

from .trace import tracer as _tracer

__all__ = ["CompileAccounting", "ledger"]

_KINDS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_CACHE = {"/jax/compilation_cache/cache_hits": "hits",
          "/jax/compilation_cache/cache_misses": "misses"}
#: kind -> (count key, seconds key) of an entry
_KEYS = {"trace": ("traced", "trace_seconds"),
         "lower": ("lowered", "lower_seconds"),
         "backend": ("built", "backend_seconds")}


def _plain(fun_name) -> str:
    """'jit(f)' / 'pmap(f)' -> 'f': one entry per function."""
    name = str(fun_name)
    head, sep, rest = name.partition("(")
    if sep and rest.endswith(")") and head in ("jit", "pjit", "pmap"):
        return rest[:-1]
    return name


def _new_entry() -> Dict:
    return {"traced": 0, "trace_seconds": 0.0, "lowered": 0,
            "lower_seconds": 0.0, "built": 0, "backend_seconds": 0.0,
            "hits": 0, "misses": 0, "span": ""}


class CompileAccounting:
    """Thread-safe per-function ledger fed by JAX's monitoring events
    (module docstring). `listen()` registers the listeners; the
    process-global `ledger` below does so at import."""

    def __init__(self, capacity: int = 4096):
        self._lock = threading.Lock()
        self._entries: Dict[str, Dict] = {}
        self._events = collections.deque(maxlen=max(int(capacity), 16))
        self._local = threading.local()
        self._listening = False

    def listen(self) -> "CompileAccounting":
        with self._lock:
            first, self._listening = not self._listening, True
        if first:
            _monitoring.register_scalar_listener(self._on_start)
            _monitoring.register_event_duration_secs_listener(self._on_end)
            _monitoring.register_event_listener(self._on_event)
        return self

    # -- listeners ------------------------------------------------------
    def _open(self) -> List:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _on_start(self, event, value=None, fun_name="", **_) -> None:
        kind = _KINDS.get(event)
        if kind is not None:
            # [kind, function, seconds of the events nested in it]
            self._open().append([kind, _plain(fun_name), 0.0])

    def _on_end(self, event, seconds, fun_name="", **_) -> None:
        kind = _KINDS.get(event)
        if kind is None:
            return
        fun = _plain(fun_name)
        stack = self._open()
        own = float(seconds)
        # the matching start is the top of the stack unless an event
        # was abandoned above it (JAX skips the end at interpreter exit)
        for i in range(len(stack) - 1, -1, -1):
            if stack[i][0] == kind and stack[i][1] == fun:
                own = max(0.0, own - stack[i][2])
                del stack[i:]
                break
        outermost = not stack
        if not outermost:
            stack[-1][2] += float(seconds)
        sp = _tracer.current()
        where = sp.name if sp is not None else ""
        n_key, s_key = _KEYS[kind]
        ts = _tracer.now()
        with self._lock:
            rec = self._entries.get(fun)
            if rec is None:
                rec = self._entries[fun] = _new_entry()
            rec[n_key] += 1
            rec[s_key] += own
            if kind != "trace" or not rec["span"]:
                rec["span"] = where
            if outermost:
                self._events.append(
                    {"kind": kind, "fun": fun, "seconds": float(seconds),
                     "ts": ts, "span": where})

    def _on_event(self, event, **_) -> None:
        key = _CACHE.get(event)
        if key is None:
            return
        stack = self._open()
        fun = next((e[1] for e in reversed(stack) if e[0] == "backend"),
                   "")
        ts = _tracer.now()
        with self._lock:
            rec = self._entries.get(fun)
            if rec is None:
                rec = self._entries[fun] = _new_entry()
            rec[key] += 1
            self._events.append({"kind": key, "fun": fun, "seconds": 0.0,
                                 "ts": ts, "span": ""})

    # -- readers --------------------------------------------------------
    def snapshot(self) -> Dict[str, Dict]:
        with self._lock:
            return {k: dict(v) for k, v in self._entries.items()}

    def events(self) -> List[Dict]:
        """The last events, oldest first; `ts` is on the span ring's
        timeline (seconds since the trace epoch)."""
        with self._lock:
            return list(self._events)

    def totals(self) -> Dict:
        snap = self.snapshot().values()
        return {
            "programs_built": sum(v["built"] for v in snap),
            "trace_seconds": round(sum(v["trace_seconds"] for v in snap), 6),
            "lower_seconds": round(sum(v["lower_seconds"] for v in snap), 6),
            "backend_seconds": round(
                sum(v["backend_seconds"] for v in snap), 6),
            "cache_hits": sum(v["hits"] for v in snap),
            "cache_misses": sum(v["misses"] for v in snap),
        }

    def reset(self) -> None:
        with self._lock:
            self._entries.clear()
            self._events.clear()


#: the process-global ledger (`registry.compiles`), listening from import
ledger = CompileAccounting().listen()
