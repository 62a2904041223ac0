"""Device-utilization accounting: MACs -> achieved TFLOP/s -> MFU.

The MXU histogram kernel's arithmetic is known in closed form
(learner/histogram_mxu.py module docstring): one build pass over N rows
at frontier capacity S costs

    MACs = nchan * S * N * F * B_pad

bf16 multiply-accumulates, where nchan is the channel count (5 with
double-bf16 sums, 4 single-bf16, 3 quantized, 2 quantized +
constant-hessian — the same rules as `fits_v2`), F the feature count
and B_pad the bin axis padded to the 128-lane boundary. The batched
grower (grower_mxu.py) runs a deterministic doubling schedule
S = 2, 4, ..., s_max plus one full-capacity bridge pass, with sibling
subtraction halving the slots actually built per pass — so the MAC
count of a whole tree is a static function of the config, summed here
by `tree_macs`. Data-dependent fixup passes are excluded: the estimate
is a slight LOWER bound on device work, so the derived TFLOP/s and MFU
never overstate utilization. Routing matmul flops are negligible next
to the histogram (module docstring) and are likewise excluded.

MFU = achieved TFLOP/s / peak TFLOP/s of the device (bf16 peak per
chip; `LGBM_TPU_PEAK_TFLOPS` overrides the table). This is the
roofline-style accounting the GPU tree-boosting literature uses to
localize histogram kernels relative to hardware peak (PAPERS.md:
arxiv 1706.08359, 2011.02022).
"""

from __future__ import annotations

import math
import os
import threading
from typing import Dict, Optional

__all__ = ["hist_channels", "histogram_macs", "tree_macs",
           "achieved_tflops", "mfu_fraction", "device_peak_tflops",
           "peak_tflops_of_kind",
           "DeviceUtilization"]

# bf16 peak TFLOP/s per chip, by jax device_kind substring (most
# specific first). Sources: published TPU system specs per generation.
_PEAK_TFLOPS_BF16 = (
    ("v6e", 918.0), ("v6", 918.0),
    ("v5p", 459.0),
    ("v5 lite", 197.0), ("v5e", 197.0), ("v5litepod", 197.0),
    ("v5", 459.0),
    ("v4", 275.0),
    ("v3", 123.0),
    ("v2", 45.0),
)


def peak_tflops_of_kind(device_kind: str) -> Optional[float]:
    """bf16 peak from the table alone, None for a device_kind it does
    not hold (chip_smoke.py treats that as an error, not as 0.0)."""
    kind = str(device_kind).lower()
    if "tpu" not in kind and not kind.startswith("v"):
        return None
    for pat, tf in _PEAK_TFLOPS_BF16:
        if pat in kind:
            return tf
    return None


def device_peak_tflops(device=None) -> float:
    """bf16 peak of the (first) visible device; 0.0 when unknown (CPU,
    interpret mode) so downstream MFU reads as unavailable rather than
    wrong. LGBM_TPU_PEAK_TFLOPS env overrides."""
    env = os.environ.get("LGBM_TPU_PEAK_TFLOPS")
    if env:
        try:
            return float(env)
        except ValueError:
            pass
    try:
        if device is None:
            import jax
            device = jax.devices()[0]
        kind = getattr(device, "device_kind", "")
    except Exception:
        return 0.0
    return peak_tflops_of_kind(kind) or 0.0


def hist_channels(*, double_prec: bool = True, quantized: bool = False,
                  const_hess: bool = False) -> int:
    """Histogram dot channels — must mirror fits_v2's nchan logic
    (histogram_mxu.py): [g_hi, g_lo, h_hi, h_lo, cnt] double-bf16,
    [g, h, cnt] single/quantized, minus the hessian channel(s) under
    the constant-hessian fast path."""
    if const_hess:
        return 2 if quantized else 3
    return 3 if quantized else (5 if double_prec else 4)


def _lane_pad(x: int) -> int:
    return ((int(x) + 127) // 128) * 128


def histogram_macs(*, num_slots: int, num_rows: int, num_features: int,
                   bmax: int, nchan: int,
                   row_block: int = 4096) -> int:
    """MACs of ONE histogram build pass: nchan * S * N_pad * F * B_pad
    (N padded to the row block the kernel grids over)."""
    n_pad = ((int(num_rows) + row_block - 1) // row_block) * row_block
    return int(nchan) * int(num_slots) * n_pad * int(num_features) * \
        _lane_pad(bmax)


def tree_macs(*, num_leaves: int, num_rows: int, num_features: int,
              bmax: int, double_prec: bool = True,
              quantized: bool = False, const_hess: bool = False,
              hist_subtraction: bool = True, overshoot: float = 2.0,
              bridge_gate: float = 0.0, row_block: int = 4096) -> int:
    """Estimated histogram MACs to grow one tree on the MXU path.

    Sums the grower's deterministic doubling schedule (grower_mxu.py:
    S = min(2*s, s_max) for s = 1, 2, 4, ... while s < s_max) plus the
    full-capacity bridge pass; sibling subtraction builds only the
    smaller child per pair, halving the slots per pass. A nonzero
    bridge_gate skips the bridge for on-schedule trees — the estimate
    keeps it (data-dependent skip), so treat the result as the
    no-skip schedule cost. Fixup passes (data-dependent, ~0 at the
    bench posture) are excluded."""
    over = overshoot if overshoot and overshoot >= 1.0 else 0.0
    L_g = int(math.ceil(num_leaves * over)) if over else int(num_leaves)
    s_max = L_g + 1
    nchan = hist_channels(double_prec=double_prec, quantized=quantized,
                          const_hess=const_hess)
    slots = 0
    s = 1
    passes = 0
    while s < s_max and passes < 32:
        s_p = min(max(2 * s, 2), s_max)
        slots += (s_p + 1) // 2 if hist_subtraction else s_p
        s *= 2
        passes += 1
    if over:
        # bridge pass at full capacity (skipped per-tree when
        # bridge_gate is already satisfied; counted here — see above)
        slots += (s_max + 1) // 2 if hist_subtraction else s_max
    return histogram_macs(num_slots=slots, num_rows=num_rows,
                          num_features=num_features, bmax=bmax,
                          nchan=nchan, row_block=row_block)


def achieved_tflops(macs_per_second: float) -> float:
    """1 MAC = 2 FLOPs; returns TFLOP/s."""
    return 2.0 * float(macs_per_second) / 1e12


def mfu_fraction(tflops: float, peak_tflops: Optional[float] = None
                 ) -> Optional[float]:
    """Model-flops-utilization in [0, 1]; None when the peak is
    unknown (never report a made-up denominator)."""
    peak = device_peak_tflops() if peak_tflops is None else peak_tflops
    if not peak or peak <= 0:
        return None
    return float(tflops) / float(peak)


class DeviceUtilization:
    """Accumulates estimated MACs + wall seconds; thread-safe."""

    def __init__(self):
        self._lock = threading.Lock()
        self.macs = 0
        self.trees = 0
        self.seconds = 0.0

    def add(self, macs: int, seconds: float, trees: int = 1) -> None:
        with self._lock:
            self.macs += int(macs)
            self.seconds += float(seconds)
            self.trees += int(trees)

    def reset(self) -> None:
        with self._lock:
            self.macs = 0
            self.trees = 0
            self.seconds = 0.0

    def snapshot(self) -> Dict:
        with self._lock:
            macs, secs, trees = self.macs, self.seconds, self.trees
        tf = achieved_tflops(macs / secs) if secs > 0 else 0.0
        peak = device_peak_tflops()
        frac = mfu_fraction(tf, peak) if macs else None
        return {
            "estimated_macs": macs,
            "trees": trees,
            "train_seconds": round(secs, 6),
            "achieved_tflops": round(tf, 6),
            "device_peak_tflops": peak,
            "mfu": round(frac, 8) if frac is not None else None,
        }
