"""Milliseconds per device batch inside BucketedPredictor.predict_raw's
serve_device_predict timer (utils.timer.global_timer): the dispatch and
the np.asarray that waits for it, so the device is in it. In serve_bulk
it is most of a request; in serve_online it is what the queue waits for
(serving.online_device_predict_ms_per_batch reads the same timer
there, under the metric that cell reports)."""

NAME = "serving.device_predict_ms_per_batch"
UNIT = "ms"
BETTER = "lower"
LAYER = "serving"
SOURCE = "program_span"
MOVES = "serve_rows_per_s"
WORKLOADS = None


def read(r):
    if r.get("kind") != "serve" or not r.get("batches"):
        return None
    return r["timers_window"].get("serve_device_predict", 0.0) * 1e3 \
        / r["batches"]
