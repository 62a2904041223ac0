"""The tiled one-hot sweep's share of its roofline in the traced window:
the least time the chip could take for the work the kernel
`fused_route_hist_tiled` issued (the larger of its operations over the
bf16 peak and its bytes over the HBM bandwidth, `peaks.json`), over the
device time it took. Its operations and bytes are computed here from
each call's HLO text in the capture (`onehot_work`); the runner sums
them with the calls' device time (`runners/train_missing.py`,
`wide_onehot`). The operations are what the kernel's dots issue: two a
multiply-add, rows swept x (channels x slots) x one-hot columns, the
columns those of the data's features (every feature's bins padded to
the kernel's lane width: the padding is issued work too, and what it
costs is what the share cannot see); the bytes the least a sweep moves:
its operands read once and the histograms written once. A program that
builds no pass in tiles, or a device the peak table does not name,
gives nothing."""

import re

NAME = "kernels.wide_hist_roofline"
UNIT = "%"
BETTER = "higher"
LAYER = "kernels"
SOURCE = "device_trace"
MOVES = "trees_per_s"
WORKLOADS = ["bosch_missing_train"]

_SHAPE = re.compile(r"\b(f32|s32|u8|bf16)\[([\d,]*)\]")
_BYTES = {"f32": 4, "s32": 4, "u8": 1, "bf16": 2}


def onehot_work(text: str, num_features: int):
    """(operations, bytes) of one call of the tiled one-hot sweep, from
    its HLO text: results (hist f32[1, channels x slots, lanes x bin
    lanes], node ids s32[1, rows]) and operands (node ids, the bins
    u8[rows, lanes], their transpose u8[sublanes, rows], the channels
    f32[8, rows], the small tables)."""
    head, _, args = text.partition("custom-call(")
    out = [(t, [int(d) for d in dims.split(",") if d])
           for t, dims in _SHAPE.findall(head)]
    ins = [(t, [int(d) for d in dims.split(",") if d])
           for t, dims in _SHAPE.findall(args)]
    (_, hist), (_, nodes) = out[0], out[1]
    rows = nodes[1]
    lanes = next(dims[1] for t, dims in ins if t == "u8" and
                 dims[0] == rows)
    bin_lanes = hist[2] // lanes
    ops = 2.0 * rows * hist[1] * num_features * bin_lanes
    size = [_BYTES[t] * _prod(dims) for t, dims in ins] + \
        [_BYTES[t] * _prod(dims) for t, dims in out]
    return ops, float(sum(size))


def _prod(dims):
    n = 1
    for d in dims:
        n *= d
    return n


def read(r):
    work = r.get("wide_onehot")
    if not work or not work["seconds"] > 0:
        return None
    from benchmark import harness
    peak = harness.peak_of_kind(r.get("device_kind", ""))
    if peak is None:
        return None
    least = max(work["flops"] / (peak["bf16_tflops"] * 1e12),
                work["bytes"] / (peak["hbm_gb_per_s"] * 1e9))
    return 100.0 * least / work["seconds"]
