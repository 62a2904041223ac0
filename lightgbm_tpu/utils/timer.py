"""Named-phase accumulating timers.

Reference: Common::Timer / FunctionTimer RAII profiling accumulators
(include/LightGBM/utils/common.h:973,1037; printed at exit under USE_TIMETAG)
plus one process-global registry `global_timer` (src/boosting/gbdt.cpp:20).
This host timer brackets whole phases the same way the reference brackets
CUDA phases (cuda_single_gpu_tree_learner.cpp:112-169). For the device
side, `lightgbm_tpu/observability/profile.py` brackets real
``jax.profiler`` captures around named spans (``profile_spans=`` globs,
e.g. ``pipeline_block,sharded_grow`` — the two-point attribution
protocol in docs/Performance.md).
"""

from __future__ import annotations

import contextlib
import time
from collections import defaultdict
from typing import Dict


class Timer:
    def __init__(self) -> None:
        self._acc: Dict[str, float] = defaultdict(float)
        self._count: Dict[str, int] = defaultdict(int)

    @contextlib.contextmanager
    def timeit(self, name: str):
        start = time.perf_counter()
        try:
            yield
        finally:
            self._acc[name] += time.perf_counter() - start
            self._count[name] += 1

    def add(self, name: str, seconds: float) -> None:
        self._acc[name] += seconds
        self._count[name] += 1

    def totals(self) -> Dict[str, float]:
        return dict(self._acc)

    def report(self) -> str:
        lines = ["LightGBM-TPU phase timings:"]
        for name in sorted(self._acc, key=self._acc.get, reverse=True):
            lines.append(f"  {name}: {self._acc[name]:.3f}s "
                         f"(x{self._count[name]})")
        return "\n".join(lines)

    def reset(self) -> None:
        self._acc.clear()
        self._count.clear()


global_timer = Timer()
