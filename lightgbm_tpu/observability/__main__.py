"""CLI for the observability toolbox.

Subcommands:

``merge <dir> [-o OUT]``
    Merge every rank-tagged Perfetto trace under <dir> (the chrome
    dumps each rank writes via ``observe_trace_file``) into one
    clock-aligned trace with ``pid = rank`` and per-collective skew
    instants — see observability/merge.py and docs/Observability.md
    ("Cross-rank tracing").

``clock [N]``
    Open N spans (default 200) around a small device program inside a
    jax.profiler capture of its own and print, as one JSON object, how
    closely the span ring and the capture agree on this machine (the
    largest disagreement of a start or an end once the capture's zero
    is taken out) and what one span costs with the capture running and
    without one.
"""

from __future__ import annotations

import sys
from typing import List, Optional

from .merge import merge_directory, merge_summary

USAGE = ("usage: python -m lightgbm_tpu.observability "
         "merge <trace_dir> [-o OUT] | clock [N]")


def _span_cost_us(trace, n: int, **kw) -> float:
    import time
    t0 = time.perf_counter()
    for i in range(n):
        with trace.span("clock.cost", iter=i, **kw):
            pass
    return (time.perf_counter() - t0) / n * 1e6


def clock(n: int = 200) -> dict:
    """See the module docstring. Uses a Trace of its own, so the
    process's ring is left alone."""
    import glob
    import tempfile

    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from .trace import Trace, capture_agreement
    trace = Trace(capacity=max(4 * n, 16))
    step = jax.jit(lambda x: x @ x)
    x = step(jnp.eye(128)).block_until_ready()
    out = {"platform": jax.devices()[0].platform, "spans": n,
           "span_us_no_capture": _span_cost_us(Trace(), 20000),
           "fine_span_us_no_capture": _span_cost_us(Trace(), 20000,
                                                    fine=True)}
    with tempfile.TemporaryDirectory() as logdir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(logdir, profiler_options=opts)
        try:
            for i in range(n):
                with trace.span("clock.outer", iter=i):
                    with trace.span("clock.inner", iter=i):
                        x = step(x).block_until_ready()
            out["span_us_in_capture"] = _span_cost_us(Trace(), 20000)
        finally:
            jax.profiler.stop_trace()
        path = glob.glob(logdir + "/**/*.xplane.pb", recursive=True)[0]
        events = [(e.name, e.start_ns, e.duration_ns)
                  for plane in ProfileData.from_file(path).planes
                  if plane.name == "/host:CPU"
                  for line in plane.lines for e in line.events
                  if e.name.startswith("clock.")
                  and e.name != "clock.cost"]
    out.update(capture_agreement(trace.spans(), events, trace.epoch_wall))
    return out


def main(argv: Optional[List[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help"):
        print(USAGE)
        return 0 if argv else 2
    cmd, rest = argv[0], argv[1:]
    if cmd == "clock":
        import json
        print(json.dumps(clock(int(rest[0]) if rest else 200)))
        return 0
    if cmd != "merge":
        print(f"unknown command {cmd!r}\n{USAGE}", file=sys.stderr)
        return 2
    out = None
    if "-o" in rest:
        i = rest.index("-o")
        if i + 1 >= len(rest):
            print(f"-o needs a path\n{USAGE}", file=sys.stderr)
            return 2
        out = rest[i + 1]
        del rest[i:i + 2]
    if len(rest) != 1:
        print(USAGE, file=sys.stderr)
        return 2
    try:
        path, merged = merge_directory(rest[0], out=out)
    except (ValueError, OSError) as exc:
        print(f"merge failed: {exc}", file=sys.stderr)
        return 1
    print(f"wrote {path}")
    print(merge_summary(merged))
    return 0


if __name__ == "__main__":
    sys.exit(main())
