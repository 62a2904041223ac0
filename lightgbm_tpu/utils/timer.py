"""Named-phase timers: the old name of the package's one timed region.

Reference: Common::Timer / FunctionTimer RAII profiling accumulators
(include/LightGBM/utils/common.h:973,1037; printed at exit under USE_TIMETAG)
plus one process-global registry `global_timer` (src/boosting/gbdt.cpp:20).

`global_timer.timeit(name)` IS `lightgbm_tpu.observability.span(name)`
(observability/trace.py): a host annotation in any jax.profiler
capture, a record in the span ring and a per-name total. This module
keeps the name every call site and the benchmark use, and holds no
clock or accumulator of its own; `totals()` reads the trace's.
"""

from __future__ import annotations

from typing import Dict


_TRACER = None


def _tracer():
    # resolved at the first call: observability imports this module
    global _TRACER
    if _TRACER is None:
        from ..observability.trace import tracer
        _TRACER = tracer
    return _TRACER


class Timer:
    def timeit(self, name: str, **attrs):
        """`with global_timer.timeit(name):` — a span (pass `fine=True`
        at a per-request or per-batch site)."""
        return _tracer().span(name, **attrs)

    def totals(self) -> Dict[str, float]:
        return _tracer().totals()

    def report(self) -> str:
        totals, counts = _tracer().totals(), _tracer().counts()
        lines = ["LightGBM-TPU phase timings:"]
        for name in sorted(totals, key=totals.get, reverse=True):
            lines.append(f"  {name}: {totals[name]:.3f}s "
                         f"(x{counts.get(name, 0)})")
        return "\n".join(lines)

    def reset(self) -> None:
        _tracer().reset_totals()


global_timer = Timer()
