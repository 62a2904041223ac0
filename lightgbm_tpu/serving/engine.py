"""Shape-bucketed device predictor: bounded XLA compilations.

`predict_binned_forest` is jit-compiled per (batch-shape, forest-shape)
pair, so an unconstrained request stream — batch sizes 1, 2, 3, ... —
would recompile on every new size and the compile queue, not the MXU,
would set the latency floor (the launch/compile overhead both GPU
tree-inference papers in PAPERS.md identify as the real bottleneck).

The engine therefore pads every batch up to a power-of-two row bucket
in [min_bucket, max_bucket]: after warmup a model can be hit by at most
``ceil(log2(max_bucket)) + 1`` distinct shapes, whatever the traffic
looks like. Batches larger than max_bucket are chunked, so the biggest
compiled program is also bounded. Pad rows are zero-binned and masked
inert by `row_valid` (learner/predict.py), so bucket padding is
invisible in the scores — bit-identical to the unpadded call.

The bucket cache is also the compile COUNTER: a (model, bucket) miss is
exactly an XLA compilation of the serving predictor for that model, a
hit is a cached dispatch. Both counts surface in the metrics snapshot.
"""

from __future__ import annotations

import threading
from typing import Dict, Tuple

import numpy as np

from ..utils.timer import global_timer
from .forest import DeviceForest

__all__ = ["BucketedPredictor", "next_bucket", "max_compilations"]


def next_bucket(n: int, min_bucket: int, max_bucket: int) -> int:
    """Smallest power-of-two bucket >= n, clamped to [min_bucket,
    max_bucket]."""
    b = max(min_bucket, 1)
    while b < n and b < max_bucket:
        b <<= 1
    return min(b, max_bucket)


def max_compilations(max_bucket: int) -> int:
    """Upper bound on predictor compilations per model after warmup."""
    return int(np.ceil(np.log2(max(max_bucket, 2)))) + 1


class BucketedPredictor:
    """Device dispatch through the bucket cache. Thread-safe."""

    def __init__(self, min_bucket: int = 16, max_bucket: int = 1024):
        if min_bucket < 1 or max_bucket < min_bucket:
            raise ValueError("need 1 <= min_bucket <= max_bucket")
        self.min_bucket = int(min_bucket)
        self.max_bucket = int(max_bucket)
        self._seen: Dict[Tuple[int, int], int] = {}   # (forest id, bucket)
        self._lock = threading.Lock()
        self.compile_count = 0
        self.hit_count = 0
        self.device_batches = 0

    # ------------------------------------------------------------------
    def counters_for(self, forest: DeviceForest) -> Dict[str, int]:
        with self._lock:
            buckets = [b for (fid, b) in self._seen if fid == id(forest)]
        return {"buckets_compiled": len(buckets),
                "max_compilations": max_compilations(self.max_bucket)}

    def _record(self, forest: DeviceForest, bucket: int) -> bool:
        """Count the dispatch; True when the bucket was already warm."""
        with self._lock:
            key = (id(forest), bucket)
            hit = key in self._seen
            if hit:
                self._seen[key] += 1
                self.hit_count += 1
            else:
                self._seen[key] = 1
                self.compile_count += 1
            self.device_batches += 1
            return hit

    # ------------------------------------------------------------------
    def predict_raw(self, forest: DeviceForest, bins: np.ndarray,
                    metrics=None) -> np.ndarray:
        """[N, F] serving bins -> [N, num_outputs] raw f32 scores.

        `metrics` (serving.metrics.ModelMetrics, optional) receives a
        record_batch per device dispatch: hit = bucket already warm,
        compiled = first sighting of (model, bucket)."""
        import jax.numpy as jnp
        from ..learner.predict import predict_binned_forest
        from ..reliability import faults

        # registered fault site: the serving device-dispatch boundary
        # (retry + host-fallback handling live in serving/server.py)
        faults.inject("serving_device_predict")

        n = bins.shape[0]
        if n == 0:
            return np.zeros((0, forest.num_outputs), np.float32)
        outs = []
        lo = 0
        while lo < n:
            hi = min(lo + self.max_bucket, n)
            chunk = bins[lo:hi]
            rows = hi - lo
            bucket = next_bucket(rows, self.min_bucket, self.max_bucket)
            if rows < bucket:
                chunk = np.concatenate(
                    [chunk, np.zeros((bucket - rows, chunk.shape[1]),
                                     chunk.dtype)])
            valid = jnp.asarray(np.arange(bucket) < rows)
            hit = self._record(forest, bucket)
            if metrics is not None:
                metrics.record_batch(bucket_hit=hit, compiled=not hit)
            # a bucket-cache miss makes JAX build the serving predictor
            # for this shape: the compile ledger (observability/
            # compiles.py) books that under this span's name
            with global_timer.timeit("serve_device_predict", fine=True,
                                     bucket=bucket, rows=rows):
                raw = predict_binned_forest(
                    forest.stacked, forest.tree_class, jnp.asarray(chunk),
                    forest.num_bins, forest.missing_is_nan,
                    num_outputs=forest.num_outputs, row_valid=valid)
                raw = np.asarray(raw)    # device -> host sync
            outs.append(raw[:rows])
            lo = hi
        return np.concatenate(outs, axis=0) if len(outs) > 1 else outs[0]
