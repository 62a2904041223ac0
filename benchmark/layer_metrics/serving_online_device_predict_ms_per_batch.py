"""serving.device_predict_ms_per_batch for cells that report latency: at
0.8 of the knee a request waits for at most a batch or two, so this is
the floor under serve_p50_ms."""

NAME = "serving.online_device_predict_ms_per_batch"
UNIT = "ms"
BETTER = "lower"
LAYER = "serving"
SOURCE = "program_span"
MOVES = "serve_p50_ms"
WORKLOADS = None


def read(r):
    from . import serving_device_predict_ms_per_batch as same
    return same.read(r)
