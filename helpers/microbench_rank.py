"""The ranking objective alone, at the MSLR cell's shape (2,270,296
documents under 18,919 heavy-tailed queries): what a tree pays for its
gradients, by stage, on the chip.

  gather              scores into the bucket tables (one gather a slot)
  gather_and_buckets  that, the per-bucket sorts and the pair tensors
  write               the two writes back to document order
  write_as_gather     the alternative that lost: each document's slot
                      gathered
  whole               LambdarankNDCG / RankXENDCG.get_gradients

Each stage is its own jit, timed as the median of --reps dispatches
after one that compiles (a stage takes milliseconds to tens of
milliseconds, far over a dispatch's cost). With --check the whole
objective's output is first compared with
benchmark/reference/lambdarank_numpy.py on the first --check-queries
queries: a time for something that computes something else is worth
nothing. With --profile DIR one dispatch of `whole` inside a
named scope is captured and the device events' names and statistics are
listed, which is how benchmark/runners/rank.py learnt where a capture
says which operations are the objective's.

Usage: python helpers/microbench_rank.py [--queries Q --documents N
       --longest L] [--reps K] [--check] [--profile DIR] [--cpu]
Writes chiprun_out/microbench_rank.json beside the lines it prints.
"""

import argparse
import functools
import json
import os
import sys
import time
from statistics import median

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


class _Meta:
    weight = None


def _timed(fn, *args, reps):
    import jax
    jax.block_until_ready(fn(*args))
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        out.append((time.perf_counter() - t0) * 1e3)
    return median(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--queries", type=int, default=18919)
    ap.add_argument("--documents", type=int, default=2270296)
    ap.add_argument("--longest", type=int, default=1251)
    ap.add_argument("--reps", type=int, default=7)
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--check-queries", type=int, default=600)
    ap.add_argument("--profile", default=None)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    from benchmark.generators.mslr import LABEL_SHARES, query_lengths
    from lightgbm_tpu.config import Config
    from lightgbm_tpu import objectives_rank as rank
    from lightgbm_tpu.objectives_rank import LambdarankNDCG, RankXENDCG

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.cpu:
        print("not a TPU (%s); --cpu runs a small size on the CPU" % dev)
        return 2
    rng = np.random.default_rng(7)
    sizes = rng.permutation(
        query_lengths(args.queries, args.documents, args.longest))
    n = int(sizes.sum())
    meta = _Meta()
    meta.query_boundaries = np.concatenate([[0], np.cumsum(sizes)])
    meta.label = rng.choice(5, n, p=LABEL_SHARES).astype(np.float32)
    # 255 distinct scores, as after one tree: ties inside every query
    score = jnp.asarray((rng.integers(0, 255, n) / 64.0 - 2.0)
                        .astype(np.float32))
    report = {"device": str(dev), "documents": n, "queries": len(sizes)}

    for cls in (LambdarankNDCG, RankXENDCG):
        obj = cls(Config({"objective": cls.name}))
        t0 = time.perf_counter()
        obj.init(meta, n)
        row = {"init_s": time.perf_counter() - t0,
               "buckets": [tuple(b) for b in obj.plan.buckets],
               "slots": int(obj.slot_doc.shape[0])}
        whole = jax.jit(obj.get_gradients)
        if args.check and cls is LambdarankNDCG:
            from benchmark.reference.lambdarank_numpy import \
                lambdarank_gradients
            m = int(meta.query_boundaries[args.check_queries])
            g, h = (np.asarray(a[:m], np.float64) for a in whole(score))
            gr, hr = lambdarank_gradients(
                np.asarray(score[:m]), meta.label[:m],
                sizes[:args.check_queries])
            row["check_max_abs_err"] = [float(np.abs(g - gr).max()),
                                        float(np.abs(h - hr).max())]
            row["check_max_abs"] = [float(np.abs(gr).max()),
                                    float(np.abs(hr).max())]
        row["whole_ms"] = _timed(whole, score, reps=args.reps)

        tables = tuple(getattr(obj, name) for name in obj.table_state)
        key = obj._next_key()
        pad = jnp.concatenate([score, jnp.full(1, -1e30, score.dtype)])
        row["gather_ms"] = _timed(jax.jit(lambda p: p[obj.slot_doc]), pad,
                                  reps=args.reps)
        slots = jax.jit(functools.partial(rank.slot_gradients, obj.plan))
        row["gather_and_buckets_ms"] = _timed(slots, score, tables, key,
                                              reps=args.reps)
        lam, hes, doc = slots(score, tables, key)
        write = jax.jit(lambda a, b, d: (rank.to_documents(n, a, d),
                                         rank.to_documents(n, b, d)))
        row["write_ms"] = _timed(write, lam, hes, doc, reps=args.reps)
        # the alternative that lost (59 ms against 39, my chip run, PR
        # 32): a document's slot gathered, which would also need a second
        # sort to put every row back into document order first
        slot_of_doc = np.empty(n + 1, np.int32)
        slot_of_doc[np.asarray(obj.slot_doc)] = np.arange(
            obj.slot_doc.shape[0], dtype=np.int32)
        slot_of_doc = jnp.asarray(slot_of_doc[:n])
        row["write_as_gather_ms"] = _timed(
            jax.jit(lambda a, b: (a[slot_of_doc], b[slot_of_doc])), lam, hes,
            reps=args.reps)
        stats = dev.memory_stats() or {}
        row["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
        row["peak_bytes_reserved"] = stats.get("peak_bytes_reserved")
        report[cls.name] = row
        print(cls.name, json.dumps(row), flush=True)

        if args.profile and cls is LambdarankNDCG:
            report["profile"] = _profile(whole, score, args.profile)

    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out", "microbench_rank.json"),
              "w") as fh:
        json.dump(report, fh, indent=1)
    return 0


def _profile(whole, score, out_dir):
    """One dispatch under a named scope in a capture; the device
    events' names and statistics, a few of each operation."""
    import jax
    from jax.profiler import ProfileData
    from benchmark import trace_reduce

    @jax.jit
    def scoped(s):
        with jax.named_scope("objective.lambdarank"):
            g, h = whole(s)
        return g + h

    jax.block_until_ready(scoped(score))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    jax.block_until_ready(scoped(score))
    jax.profiler.stop_trace()
    data = ProfileData.from_file(trace_reduce.find_xplane(out_dir))
    seen, listing = {}, []
    for plane in data.planes:
        print("plane", plane.name, [ln.name for ln in plane.lines],
              {k: str(v)[:80] for k, v in plane.stats})
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                key = (line.name, trace_reduce.op_name(e.name))
                seen[key] = seen.get(key, 0) + 1
                if seen[key] <= 1:
                    rec = {"line": line.name, "name": e.name[:300],
                           "ms": e.duration_ns / 1e6,
                           "stats": {k: str(v)[:400] for k, v in e.stats}}
                    listing.append(rec)
                    print(json.dumps(rec))
    return listing


if __name__ == "__main__":
    sys.exit(main())
