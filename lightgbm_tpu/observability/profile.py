"""Device profiler capture: real XLA timelines for named spans.

Host-side spans (observability/trace.py) time the *dispatch*; the
device work behind it — the MXU histogram matmuls vs the scatter
kernels that the two-point protocol wants to attribute
(docs/Performance.md) — only shows up in a ``jax.profiler`` trace.
This module brackets ``jax.profiler.start_trace``/``stop_trace``
around spans whose name matches the ``profile_spans`` glob(s), with a
hard capture budget (``profile_max_captures``) so a long run collects
a handful of representative windows instead of gigabytes.

Config surface (config.py): ``profile_spans`` (comma-separated
fnmatch globs, e.g. ``pipeline_block,sharded_grow``), ``profile_dir``
(one subdirectory per capture), ``profile_max_captures``.

Degrades to a logged no-op wherever the profiler is unavailable
(missing tensorboard plugin, unsupported backend, a second profiler
already attached): the first failure disarms the profiler for the
rest of the process and training continues untouched.
"""

from __future__ import annotations

import fnmatch
import os
import re
import threading
from contextlib import contextmanager
from typing import Tuple

from ..utils.log import Log

__all__ = ["SpanProfiler", "profiler"]


def _start_trace(log_dir: str) -> None:
    """Indirection over jax.profiler.start_trace (tests stub this)."""
    import jax.profiler
    jax.profiler.start_trace(log_dir)


def _stop_trace() -> None:
    import jax.profiler
    jax.profiler.stop_trace()


class SpanProfiler:
    """Budgeted jax.profiler bracketing for matching span names."""

    def __init__(self):
        self._lock = threading.Lock()
        self.armed = False          # fast-path flag: one attr read
        self.patterns: Tuple[str, ...] = ()
        self.out_dir = ""
        self.max_captures = 0
        self.captures = 0
        self._active = False        # jax.profiler allows ONE live trace
        self._failed = False

    def configure(self, spans: str = "", out_dir: str = "",
                  max_captures: int = 4) -> None:
        with self._lock:
            self.patterns = tuple(
                p.strip() for p in str(spans or "").split(",") if p.strip())
            self.out_dir = str(out_dir or "")
            self.max_captures = max(0, int(max_captures))
            self.armed = bool(self.patterns and not self._failed and
                              self.max_captures > self.captures)

    def reset(self) -> None:
        with self._lock:
            self.armed = False
            self.patterns = ()
            self.out_dir = ""
            self.max_captures = 0
            self.captures = 0
            self._active = False
            self._failed = False

    # ------------------------------------------------------------------
    def matches(self, name: str) -> bool:
        return any(fnmatch.fnmatchcase(name, p) for p in self.patterns)

    def begin(self, name: str) -> bool:
        """Start a device trace for `name` if it matches, budget
        remains, and no capture is live. True iff a trace started —
        the caller owes a matching `end()`."""
        if not self.armed or not self.matches(name):
            return False
        with self._lock:
            if (self._active or self._failed or
                    self.captures >= self.max_captures):
                return False
            self._active = True
            self.captures += 1
            n = self.captures
            if self.captures >= self.max_captures:
                self.armed = False      # budget spent
        safe = re.sub(r"[^A-Za-z0-9_.-]", "_", name)
        log_dir = os.path.join(self.out_dir or "jax_profile",
                               f"{safe}_{n:03d}")
        try:
            os.makedirs(log_dir, exist_ok=True)
            _start_trace(log_dir)
        except Exception as exc:
            with self._lock:
                self._active = False
                self._failed = True
                self.armed = False
            Log.warning("span profiler unavailable (%s: %s); device "
                        "capture disabled for this process",
                        type(exc).__name__, exc)
            return False
        Log.info("span profiler: capturing %r -> %s (%d/%d)",
                 name, log_dir, n, self.max_captures)
        return True

    def end(self) -> None:
        try:
            _stop_trace()
        except Exception as exc:
            with self._lock:
                self._failed = True
                self.armed = False
            Log.warning("span profiler: stop_trace failed (%s: %s); "
                        "device capture disabled", type(exc).__name__, exc)
        finally:
            with self._lock:
                self._active = False

    @contextmanager
    def capture(self, name: str):
        """Bracket a region; yields True iff a device trace is live
        (callers use it to add a block_until_ready so the capture
        window covers the async device work, at zero cost when no
        capture is running)."""
        started = self.begin(name)
        try:
            yield started
        finally:
            if started:
                self.end()

    def snapshot(self) -> dict:
        with self._lock:
            return {"armed": int(self.armed),
                    "captures": self.captures,
                    "max_captures": self.max_captures,
                    "failed": int(self._failed)}


#: process-wide singleton, configured from Config by the Booster
profiler = SpanProfiler()
