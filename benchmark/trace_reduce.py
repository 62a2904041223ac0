"""From a jax.profiler capture to numbers: the one reduction every PR uses.

A capture is an `.xplane.pb`. On a TPU it holds one plane per chip
(`/device:TPU:<n>`) whose line `XLA Ops` carries every HLO operation
the chip ran, nested (a `while` encloses its body's operations), under
the operation's full HLO text, e.g.

    %fused_route_hist_mxu.32 = (f32[1,10,7168]{...}, ...) custom-call(...),
        custom_call_target="tpu_custom_call", ...

`Async XLA Ops` carries the spans of asynchronous copies and
collectives, and the host plane's lines of threads that run Python
carry JAX's own host events (`PjitFunction(...)`, `np.asarray(jax.Array)`) beside this
benchmark's spans (`bench.*`, jax.profiler.TraceAnnotation).

What is read off it (chip probe, PR 22, for the layout):

- busy: the union of the intervals in which an operation ran on a chip,
  averaged over the chips; the idle share is 1 - busy / window;
- an operation's time is its SELF time (its interval less what its
  children cover), so a `while` does not count its body twice; the
  operations are grouped by the name before `=`, less the `%` and the
  trailing `.<n>`;
- the Mosaic share: self time of custom calls whose target is
  `tpu_custom_call` (the Pallas kernels), over busy;
- the collective share: the union of the intervals of all-reduce,
  all-gather, reduce-scatter, all-to-all and collective-permute
  operations (synchronous or asynchronous) on chip 0, over the window;
- chip 0's idle time between its first and last operation, by what the
  host was doing: each gap goes to the benchmark span and the innermost
  JAX host event open at its middle (the five longest gaps said nothing:
  a block's unpacking alone cuts the idle time into hundreds of gaps
  under 2 ms, chip probe, PR 22).

`python3 -m benchmark.trace_reduce <capture>` prints the reduction of
any capture; `--cut a:b out.json.gz` keeps the milliseconds [a, b) of
it in the compact form the recorded traces under testdata/ have.
"""

from __future__ import annotations

import functools
import gzip
import json
import os
import re
import sys
from typing import Dict, List, Tuple

Event = Tuple[str, float, float]          # name, start_ns, duration_ns

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OPS_LINE, _ASYNC_LINE = "XLA Ops", "Async XLA Ops"
_HOST_PLANE = "/host:CPU"
#: a host line is a thread that runs Python if it holds one of these (the
#: line's own name is the thread's, "python" or "python3" or a worker's)
_PYTHON_EVENTS = ("bench.", "PjitFunction(")
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute", "collective-broadcast")
_MOSAIC = 'custom_call_target="tpu_custom_call"'
SPAN_PREFIX = "bench."


# ----------------------------------------------------------------------
# reading
def find_xplane(trace_dir: str) -> str:
    """The newest .xplane.pb under a jax.profiler log directory."""
    found = []
    for base, _, files in os.walk(trace_dir):
        found += [os.path.join(base, f) for f in files
                  if f.endswith(".xplane.pb")]
    if not found:
        raise FileNotFoundError("no .xplane.pb under %s" % trace_dir)
    return max(found, key=os.path.getmtime)


def load_xplane(path: str, cpu_rehearsal: bool = False) -> dict:
    """{"devices": [{"id", "ops": [Event], "async": [Event]}],
    "host": [Event]} of a capture. `cpu_rehearsal` reads XLA:CPU's
    thunk events as if they were one chip's, so that a rehearsal can
    walk the same code; its numbers mean nothing."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            dev = {"id": int(m.group(1)), "ops": [], "async": []}
            for line in plane.lines:
                key = {_OPS_LINE: "ops", _ASYNC_LINE: "async"}.get(line.name)
                if key:
                    dev[key] = [(e.name, e.start_ns, e.duration_ns)
                                for e in line.events]
            devices.append(dev)
        elif plane.name == _HOST_PLANE:
            cpu_ops = []
            for line in plane.lines:
                events = [(e.name, e.start_ns, e.duration_ns)
                          for e in line.events] \
                    if not line.name.startswith("tf_") else []
                if any(n.startswith(_PYTHON_EVENTS) for n, _, _ in events):
                    host += events
                elif cpu_rehearsal and \
                        line.name.startswith("tf_XLAPjRtCpuClient"):
                    cpu_ops += [(e.name, e.start_ns, e.duration_ns)
                                for e in line.events
                                if "::" not in e.name
                                and not e.name.startswith("end: ")
                                and e.duration_ns > 0]
            if cpu_rehearsal and cpu_ops:
                devices.append({"id": 0, "ops": cpu_ops, "async": []})
    devices.sort(key=lambda d: d["id"])
    return {"devices": devices, "host": host}


def dump_compact(trace: dict, path: str) -> None:
    """The recorded form: names once, events as [name, start, ns]."""
    names: Dict[str, int] = {}

    def pack(events):
        return [[names.setdefault(n, len(names)), int(s), int(d)]
                for n, s, d in events]

    body = {"devices": [{"id": d["id"], "ops": pack(d["ops"]),
                         "async": pack(d["async"])}
                        for d in trace["devices"]],
            "host": pack(trace["host"])}
    body["names"] = list(names)
    with gzip.open(path, "wt") as fh:
        json.dump(body, fh, separators=(",", ":"))


def load_compact(path: str) -> dict:
    with gzip.open(path, "rt") as fh:
        body = json.load(fh)
    names = body["names"]

    def unpack(events):
        return [(names[n], float(s), float(d)) for n, s, d in events]

    return {"devices": [{"id": d["id"], "ops": unpack(d["ops"]),
                         "async": unpack(d["async"])}
                        for d in body["devices"]],
            "host": unpack(body["host"])}


def load(path: str) -> dict:
    if path.endswith(".json.gz"):
        return load_compact(path)
    if os.path.isdir(path):
        path = find_xplane(path)
    return load_xplane(path)


def cut(trace: dict, lo_ns: float, hi_ns: float) -> dict:
    """The events that start in [lo_ns, hi_ns)."""
    def keep(events):
        return [e for e in events if lo_ns <= e[1] < hi_ns]
    return {"devices": [{"id": d["id"], "ops": keep(d["ops"]),
                         "async": keep(d["async"])}
                        for d in trace["devices"]],
            "host": keep(trace["host"])}


# ----------------------------------------------------------------------
# names
@functools.lru_cache(maxsize=None)
def op_name(text: str) -> str:
    """'%fusion.13 = s32[512]{0} fusion(...)' -> 'fusion'."""
    head = text.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"\.\d+$", "", head)


@functools.lru_cache(maxsize=None)
def opcode(text: str) -> str:
    """The HLO opcode of an operation's text ('' when it has none, as
    for a name that is not HLO text)."""
    _, sep, rest = text.partition(" = ")
    if not sep:
        return ""
    i = 0
    if rest.startswith("("):            # a tuple shape: skip to its end
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        i += 1
    else:
        i = rest.find(" ")
        if i < 0:
            return ""
    m = re.match(r"\s*([A-Za-z][\w\-]*)\(", rest[i:])
    return m.group(1) if m else ""


def is_collective(text: str) -> bool:
    return opcode(text).startswith(_COLLECTIVES)


# ----------------------------------------------------------------------
# arithmetic on intervals
def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Disjoint, sorted cover of (start, end) pairs."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def self_times(events: List[Event]) -> List[float]:
    """Each event's duration less what the events nested in it cover,
    in the order of `events`. Events of one line nest or are disjoint;
    a child that overruns its parent is clipped to it."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    selfs = [0.0] * len(events)
    stack: List[Tuple[float, int]] = []       # (end, index)
    for i in order:
        _, start, dur = events[i]
        end = start + dur
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack:
            end = min(end, stack[-1][0])
            selfs[stack[-1][1]] -= end - start
        selfs[i] += end - start
        stack.append((end, i))
    return selfs


def _label_at(spans: List[Event], times: List[float]) -> List[str]:
    """For each time (ascending), the name of the innermost (shortest)
    span open then, '-' for none. One sweep: spans nest a few deep."""
    todo = sorted(spans, key=lambda e: e[1])
    active: List[Event] = []
    labels, nxt = [], 0
    for t in times:
        while nxt < len(todo) and todo[nxt][1] <= t:
            active.append(todo[nxt])
            nxt += 1
        active = [e for e in active if e[1] + e[2] > t]
        labels.append(min(active, key=lambda e: e[2])[0] if active
                      else "-")
    return labels


# ----------------------------------------------------------------------
def reduce_trace(trace: dict, top: int = 10) -> dict:
    """The numbers of the module docstring. Seconds throughout; a
    capture with no chip in it gives busy_s 0."""
    devices = trace["devices"]
    n = len(devices)
    out = {"devices": n, "events": sum(len(d["ops"]) for d in devices),
           "busy_s": 0.0, "busy_s_by_device": [], "mosaic_s": 0.0,
           "collective_s": 0.0, "span_s": 0.0, "device_ops": [],
           "idle_gaps": []}
    if not n:
        return out
    by_name: Dict[str, float] = {}
    mosaic_ns = 0.0
    for dev in devices:
        ops = dev["ops"]
        busy = union([(s, s + d) for _, s, d in ops])
        out["busy_s_by_device"].append(sum(b - a for a, b in busy) / 1e9)
        for (text, _, _), own in zip(ops, self_times(ops)):
            name = op_name(text)
            by_name[name] = by_name.get(name, 0.0) + own
            if _MOSAIC in text:
                mosaic_ns += own
    out["busy_s"] = sum(out["busy_s_by_device"]) / n
    out["mosaic_s"] = mosaic_ns / n / 1e9
    out["device_ops"] = [
        [name, ns / n / 1e9] for name, ns in
        sorted(by_name.items(), key=lambda kv: -kv[1])[:top] if ns > 0]
    # chip 0: collectives, and the gaps with what the host was doing
    first = devices[0]
    coll = union([(s, s + d) for text, s, d in first["ops"] + first["async"]
                  if is_collective(text)])
    out["collective_s"] = sum(b - a for a, b in coll) / 1e9
    busy0 = union([(s, s + d) for _, s, d in first["ops"]])
    if busy0:
        out["span_s"] = (busy0[-1][1] - busy0[0][0]) / 1e9
    holes = [(a1, b2) for (_, a1), (b2, _) in zip(busy0, busy0[1:])]
    mids = [(a + b) / 2 for a, b in holes]
    ours = _label_at([e for e in trace["host"]
                      if e[0].startswith(SPAN_PREFIX)], mids)
    theirs = _label_at([e for e in trace["host"]
                        if not e[0].startswith(SPAN_PREFIX)], mids)
    idle: Dict[str, float] = {}
    for (a, b), mine, jaxs in zip(holes, ours, theirs):
        label = "%s / %s" % (mine, jaxs)
        idle[label] = idle.get(label, 0.0) + (b - a)
    out["idle_gaps"] = [[label, ns / 1e9] for label, ns in
                        sorted(idle.items(), key=lambda kv: -kv[1])[:top]]
    out["longest_gap_s"] = max((b - a for a, b in holes), default=0.0) / 1e9
    return out


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("capture", help=".xplane.pb, a jax.profiler log "
                                    "directory, or a recorded .json.gz")
    ap.add_argument("--cut", nargs=2, metavar=("FROM:TO_MS", "OUT"),
                    help="write the events starting in that range of "
                         "milliseconds to OUT (.json.gz)")
    args = ap.parse_args(argv)
    trace = load(args.capture)
    if args.cut:
        lo, hi = (float(v) * 1e6 for v in args.cut[0].split(":"))
        trace = cut(trace, lo, hi)
        dump_compact(trace, args.cut[1])
    print(json.dumps(reduce_trace(trace), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
