"""Unified observability subsystem (lightgbm_tpu/observability/).

Covers: the one span primitive (nesting, ids, thread safety, totals,
what is in the ring with observe off and on, its agreement with a
jax.profiler capture), Chrome/Perfetto + JSONL trace round-trips, MFU
arithmetic against hand-computed MAC counts, the Prometheus text
endpoint (scraped over HTTP), per-iteration training telemetry from
live boosters (normal and fused paths), the compile ledger fed by JAX's
events, the span-count budget, that observe changes neither the model
nor the number of device syncs, and the custom-fobj constant-hessian
regression (Booster.update(fobj)
must neutralize the objective's is_constant_hessian gate exactly like
engine.train's objective="none" reset).
"""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.observability import mfu
from lightgbm_tpu.observability import registry as obs
from lightgbm_tpu.observability.export import prometheus_lines
from lightgbm_tpu.observability.telemetry import PHASE_KEYS
from lightgbm_tpu.observability.trace import Trace


@pytest.fixture(autouse=True)
def _obs_state():
    """Each test starts from a clean, disabled registry."""
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


def _data(n=400, f=6, seed=3):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)
    return X, y


#: the fused scan's name in JAX's events (boosting/fused.py)
FUSED_PROGRAM = "program"

PARAMS = {"objective": "binary", "num_leaves": 7, "learning_rate": 0.2,
          "max_bin": 31, "verbosity": -1, "min_data_in_leaf": 5}


def _mxu_booster(X, y, extra=None):
    """Force the fused-eligible MXU path on CPU (interpret mode) after
    one normal iteration — same trick as test_bench_robustness.py."""
    ds = lgb.Dataset(X, label=y, params={"max_bin": 31})
    bst = lgb.Booster(params=dict(PARAMS, **(extra or {})), train_set=ds)
    bst.update()
    g = bst.gbdt
    g._hist_impl = "mxu"
    g._mxu_interpret = True
    g._fused_run = None
    g._obs_tree_macs = None   # path change invalidates the MAC cache
    return bst


_EV = "/jax/core/compile/"


def _feed_ledger(ledger, fun, *, trace=0.0, lower=0.0, backend=0.0,
                 hit=None):
    """One program's worth of JAX monitoring events, by hand, in the
    order JAX sends them (a scalar when an event starts, its duration
    when it ends; the cache's hit or miss inside the backend event)."""
    for event, secs, name in (
            (_EV + "jaxpr_trace_duration", trace, fun),
            (_EV + "jaxpr_to_mlir_module_duration", lower, f"jit({fun})"),
            (_EV + "backend_compile_duration", backend, f"jit({fun})")):
        ledger._on_start(event, 0.0, fun_name=name)
        if hit is not None and event.endswith("backend_compile_duration"):
            ledger._on_event("/jax/compilation_cache/cache_%s"
                             % ("hits" if hit else "misses"))
        ledger._on_end(event, secs, fun_name=name)


# ---------------------------------------------------------------- spans
class TestSpans:
    def test_nesting_depth_and_parent(self):
        tr = Trace()
        tr.enabled = True
        with tr.span("outer", x=1):
            with tr.span("mid"):
                with tr.span("inner"):
                    pass
        by_name = {s["name"]: s for s in tr.spans()}
        assert by_name["outer"]["depth"] == 0
        assert "parent" not in by_name["outer"]
        assert by_name["mid"]["depth"] == 1
        assert by_name["mid"]["parent"] == "outer"
        assert by_name["inner"]["depth"] == 2
        assert by_name["inner"]["parent"] == "mid"
        assert by_name["outer"]["attrs"] == {"x": 1}
        # completion order: innermost exits (and lands) first
        assert [s["name"] for s in tr.spans()] == \
            ["inner", "mid", "outer"]

    def test_thread_safety_of_nesting(self):
        tr = Trace(capacity=4096)
        tr.enabled = True
        errs = []

        def work(tag):
            try:
                for i in range(50):
                    with tr.span(f"{tag}_outer", i=i):
                        with tr.span(f"{tag}_inner"):
                            pass
            except Exception as exc:  # pragma: no cover
                errs.append(exc)

        threads = [threading.Thread(target=work, args=(f"t{k}",))
                   for k in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs
        spans = tr.spans()
        assert len(spans) == 4 * 50 * 2
        # per-thread stacks: an inner span's parent is ALWAYS its own
        # thread's outer, never a concurrent thread's
        for s in spans:
            if s["name"].endswith("_inner"):
                assert s["parent"] == s["name"].replace("_inner",
                                                        "_outer")
                assert s["depth"] == 1

    def test_ring_eviction_counts_drops(self):
        tr = Trace(capacity=16)
        for i in range(30):
            with tr.span(f"s{i}"):
                pass
        assert len(tr) == 16
        assert tr.dropped == 14
        assert tr.spans()[0]["name"] == "s14"  # oldest evicted
        # the totals are never evicted
        assert len(tr.totals()) == 30 and tr.counts()["s0"] == 1

    def test_disabled_keeps_phase_spans_and_drops_fine_ones(self):
        # was test_disabled_returns_shared_null_span: with observe off a
        # fine span is not in the ring and a phase-level span is; both
        # are in the totals
        tr = Trace()
        assert not tr.enabled
        with tr.span("phase", k=1):
            with tr.span("request", fine=True, rows=3):
                pass
        assert [s["name"] for s in tr.spans()] == ["phase"]
        assert set(tr.totals()) == {"phase", "request"}
        tr.enabled = True
        with tr.span("request", fine=True, rows=3):
            pass
        assert [s["name"] for s in tr.spans()] == ["phase", "request"]
        assert tr.spans()[1]["attrs"] == {"rows": 3}   # not `fine`
        assert tr.counts()["request"] == 2

    def test_ids_name_the_enclosing_span_on_the_same_thread(self):
        # (b): two threads at once; every parent id is the id of a span
        # that encloses it in time on ITS thread
        tr = Trace(capacity=4096)
        gate = threading.Barrier(2)

        def work(tag):
            gate.wait()
            for i in range(40):
                with tr.span(f"{tag}.a", i=i):
                    with tr.span(f"{tag}.b"):
                        with tr.span(f"{tag}.c", fine=True):
                            pass
                    with tr.span(f"{tag}.b"):
                        pass

        threads = [threading.Thread(target=work, args=(t,))
                   for t in ("x", "y")]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        recs = tr.spans()
        by_id = {s["id"]: s for s in recs}
        assert len(by_id) == len(recs) == 2 * 40 * 3   # ids are unique
        for s in recs:
            if s["name"].endswith(".a"):
                assert s["parent_id"] == 0
                continue
            up = by_id[s["parent_id"]]
            assert up["tid"] == s["tid"]
            assert up["name"] == s["name"][:2] + "a"
            assert up["ts"] <= s["ts"]
            assert s["ts"] + s["dur"] <= up["ts"] + up["dur"]
            assert s["depth"] == up["depth"] + 1

    def test_timer_registry_and_module_span_are_one_function(self):
        from lightgbm_tpu import observability
        from lightgbm_tpu.utils.timer import global_timer
        before = global_timer.totals().get("one.region", 0.0)
        for opener in (global_timer.timeit, obs.trace.span,
                       observability.span):
            with opener("one.region", iter=1) as sp:
                pass
            assert type(sp).__name__ == "Span"
        assert [s["name"] for s in obs.trace.spans()] == ["one.region"] * 3
        assert global_timer.totals()["one.region"] > before
        assert obs.snapshot()["timers"]["one.region"] > 0
        assert "one.region" in global_timer.report()


# --------------------------------------------------------------- export
class TestTraceExport:
    def test_chrome_perfetto_round_trip(self, tmp_path):
        tr = Trace()
        tr.enabled = True
        with tr.span("grow_tree", iteration=3):
            time.sleep(0.001)
        path = tmp_path / "trace.json"
        assert tr.dump(str(path)) == "chrome"
        doc = json.loads(path.read_text())
        assert doc["displayTimeUnit"] == "ms"
        ev = doc["traceEvents"][0]
        assert ev["ph"] == "X"
        assert ev["name"] == "grow_tree"
        assert ev["cat"] == "lightgbm_tpu"
        assert ev["dur"] >= 1000            # microseconds
        assert ev["args"]["iteration"] == 3
        assert {"ts", "pid", "tid"} <= set(ev)

    def test_jsonl_round_trip(self, tmp_path):
        tr = Trace()
        for i in range(3):
            with tr.span("iter", iteration=i):
                pass
        path = tmp_path / "trace.jsonl"
        assert tr.dump(str(path)) == "jsonl"
        recs = [json.loads(ln) for ln in
                path.read_text().strip().splitlines()]
        assert len(recs) == 3
        assert [r["attrs"]["iteration"] for r in recs] == [0, 1, 2]
        assert all(r["dur"] >= 0 and r["id"] for r in recs)


# ------------------------------------------------------------------ mfu
class TestMFU:
    def test_histogram_macs_hand_computed(self):
        # nchan * S * N_pad * F * B_pad, N padded to the row block and
        # B to the 128-lane boundary (histogram_mxu.py docstring)
        macs = mfu.histogram_macs(num_slots=23, num_rows=1000,
                                  num_features=10, bmax=63, nchan=5)
        assert macs == 5 * 23 * 4096 * 10 * 128

    def test_hist_channels_mirror_fits_v2(self):
        assert mfu.hist_channels(double_prec=True) == 5
        assert mfu.hist_channels(double_prec=False) == 4
        assert mfu.hist_channels(quantized=True) == 3
        assert mfu.hist_channels(quantized=True, const_hess=True) == 2
        assert mfu.hist_channels(const_hess=True) == 3

    def test_tree_macs_hand_computed_schedule(self):
        # num_leaves=7, overshoot=2.0 -> L_g=14, s_max=15; doubling
        # schedule 2,4,8,15; subtraction halves slots per pass:
        # 1+2+4+8 = 15, bridge (15+1)//2 = 8 -> 23 slots total
        macs = mfu.tree_macs(num_leaves=7, num_rows=1000,
                             num_features=10, bmax=63, overshoot=2.0)
        assert macs == 5 * 23 * 4096 * 10 * 128

    def test_tree_macs_no_subtraction_no_overshoot(self):
        # overshoot off: s_max = num_leaves + 1 = 8; schedule 2,4,8;
        # full slots 2+4+8 = 14, no bridge
        macs = mfu.tree_macs(num_leaves=7, num_rows=1000,
                             num_features=10, bmax=63, overshoot=0.0,
                             hist_subtraction=False)
        assert macs == 5 * 14 * 4096 * 10 * 128

    def test_achieved_tflops_and_mfu(self, monkeypatch):
        assert mfu.achieved_tflops(0.5e12) == 1.0   # 1 MAC = 2 FLOPs
        assert mfu.mfu_fraction(45.0, 90.0) == 0.5
        assert mfu.mfu_fraction(45.0, 0.0) is None  # unknown peak
        monkeypatch.setenv("LGBM_TPU_PEAK_TFLOPS", "918")
        assert mfu.device_peak_tflops() == 918.0
        assert mfu.mfu_fraction(91.8) == pytest.approx(0.1)

    def test_device_utilization_accumulator(self, monkeypatch):
        monkeypatch.setenv("LGBM_TPU_PEAK_TFLOPS", "100")
        du = mfu.DeviceUtilization()
        du.add(25e12, 1.0, trees=2)   # 25e12 MACs/s = 50 TFLOP/s
        snap = du.snapshot()
        assert snap["trees"] == 2
        assert snap["achieved_tflops"] == pytest.approx(50.0)
        assert snap["mfu"] == pytest.approx(0.5)


# ----------------------------------------------------------- prometheus
class TestPrometheus:
    def test_flattener(self):
        lines = prometheus_lines(
            {"a": 1, "nested": {"b": 2.5, "skip": "str"},
             "flag": True}, "pre")
        assert "# TYPE pre_a gauge" in lines
        assert "pre_a 1" in lines
        assert "pre_nested_b 2.5" in lines
        assert "pre_flag 1" in lines
        assert not any("skip" in ln for ln in lines)

    def test_labels_and_name_sanitizing(self):
        lines = prometheus_lines({"p50 ms": 1.5}, "m",
                                 labels={"model": 'a"b'})
        assert 'm_p50_ms{model="a\\"b"} 1.5' in lines

    def test_registry_text_scrapeable_totals(self):
        obs.enable()
        _feed_ledger(obs.compiles, "fused_train", trace=0.5, lower=0.25,
                     backend=2.0, hit=True)
        text = obs.prometheus_text()
        assert "lightgbm_tpu_observability_enabled 1" in text
        assert "lightgbm_tpu_compiles_programs_built 1" in text
        assert "lightgbm_tpu_compiles_cache_hits 1" in text
        assert "lightgbm_tpu_compiles_backend_seconds 2" in text
        assert ("lightgbm_tpu_compiles_entries_fused_train_built 1"
                in text)

    def test_serving_metrics_http_endpoint(self):
        from lightgbm_tpu.serving import Server
        X, y = _data()
        ds = lgb.Dataset(X, label=y, params={"max_bin": 31})
        bst = lgb.Booster(params=dict(PARAMS), train_set=ds)
        for _ in range(3):
            bst.update()
        with Server(min_bucket=16, max_bucket=64) as srv:
            srv.load_model("m1", booster=bst)
            srv.predict("m1", X[:10])
            msrv = srv.start_metrics_server(port=0)
            assert msrv.port > 0
            # idempotent: second call returns the running endpoint
            assert srv.start_metrics_server() is msrv
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{msrv.port}/metrics",
                timeout=10).read().decode()
            assert "# TYPE" in body
            assert 'lightgbm_tpu_serving_model_requests{model="m1"} 1' \
                in body
            assert "lightgbm_tpu_serving_engine_device_batches 1" \
                in body
            ok = urllib.request.urlopen(
                f"http://127.0.0.1:{msrv.port}/healthz",
                timeout=10).read()
            assert ok == b"ok\n"
            snap = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{msrv.port}/snapshot",
                timeout=10).read())
            assert snap["models"]["m1"]["requests"] == 1
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(
                    f"http://127.0.0.1:{msrv.port}/nope", timeout=10)
        # server close shuts the endpoint down
        with pytest.raises(Exception):
            urllib.request.urlopen(
                f"http://127.0.0.1:{msrv.port}/healthz", timeout=2)


# ------------------------------------------------------ train telemetry
class TestTrainingTelemetry:
    def test_per_iteration_records(self):
        X, y = _data()
        ds = lgb.Dataset(X, label=y, params={"max_bin": 31})
        bst = lgb.Booster(params=dict(PARAMS, observe=True,
                                      observe_norms=True),
                          train_set=ds)
        assert obs.enabled
        for _ in range(4):
            bst.update()
        snap = obs.snapshot()
        assert snap["training"]["iterations"] == 4
        assert snap["training"]["trees"] == 4
        last = snap["training"]["last"]
        assert last["iteration"] == 3
        assert last["wall_s"] > 0
        assert "entry.dispatch" in last["phases"]
        assert set(last["phases"]) <= set(PHASE_KEYS)
        assert 0 < sum(last["phases"].values()) <= last["wall_s"] * 1.001
        assert last["grad_norm"] > 0
        assert last["hess_norm"] > 0
        assert last["leaves"] >= 2
        # the span ring holds each iteration's phases
        names = [s["name"] for s in obs.trace.spans()]
        assert names.count("entry.dispatch") == 4
        assert names.count("boosting.update_score") == 4

    def test_fused_block_record_and_compile_accounting(self):
        X, y = _data(seed=8)
        obs.enable()
        bst = _mxu_booster(X, y)
        bst.update_batch(3)
        last = obs.training.last()
        assert last["fused"] is True
        assert last["iterations"] == 3
        assert last["trees"] == 3
        # the forced-MXU booster has an analytic MAC model -> MFU
        # accumulates estimated MACs for the block
        assert last["estimated_macs"] > 0
        assert last["wall_s"] > 0
        # (c): the ledger names the growth program and counts it once
        # per block length, from JAX's own events
        prog = obs.compiles.snapshot()[FUSED_PROGRAM]
        assert prog["built"] == prog["lowered"] == 1
        assert prog["trace_seconds"] > 0 and prog["lower_seconds"] > 0
        assert prog["backend_seconds"] > 0
        assert prog["span"] == "boosting.build_program"
        bst.update_batch(2)            # a new length: a new program
        assert obs.compiles.snapshot()[FUSED_PROGRAM]["built"] == 2
        assert obs.training.last()["iterations"] == 2
        du = obs.mfu.snapshot()
        assert du["estimated_macs"] == obs.tree_macs_for(bst.gbdt) * 5
        assert du["trees"] == 5

    def test_counter_deltas_fold_into_records(self):
        X, y = _data(seed=9)
        obs.enable()
        ds = lgb.Dataset(X, label=y, params={"max_bin": 31})
        bst = lgb.Booster(params=dict(PARAMS), train_set=ds)
        bst.update()
        obs.counters.inc("guard_trips")
        bst.update()
        recs = obs.training.records()
        assert recs[-1]["counters"]["guard_trips"] == 1
        bst.update()
        assert "counters" not in obs.training.records()[-1]

    def test_disabled_path_keeps_phases_and_no_telemetry(self):
        # was test_disabled_path_records_nothing: with observe off no
        # telemetry record is made; the phase-level spans and the
        # compile ledger are there all the same, and so is the flight
        # recorder's post-mortem
        from lightgbm_tpu.observability import recorder
        recorder.reset()
        X, y = _data(seed=10)
        ds = lgb.Dataset(X, label=y, params={"max_bin": 31})
        bst = lgb.Booster(params=dict(PARAMS), train_set=ds)
        for _ in range(3):
            bst.update()
        assert not obs.enabled
        assert obs.training.iterations == 0
        names = [s["name"] for s in obs.trace.spans()]
        assert names.count("entry.dispatch") == 3
        assert "ingest.construct" in names
        import jax

        def never_built_before(x):
            return x * 3 + 1

        with obs.trace.span("somewhere"):
            jax.jit(never_built_before)(np.ones(7, np.float32))
        entry = obs.compiles.snapshot()["never_built_before"]
        assert entry["traced"] == entry["lowered"] == entry["built"] == 1
        assert entry["span"] == "somewhere"
        post = [e["name"] for e in recorder.events()
                if e["kind"] == "span"]
        assert post[-2:] == ["entry.append_tree", "somewhere"]

    def test_span_overhead_smoke(self, monkeypatch):
        # was test_disabled_span_overhead_smoke: a span is two clock
        # reads, an inactive annotation, a ring record and a flight
        # recorder event (which stamps two clocks of its own), with
        # observe off as with it on; a fine span with observe off stops
        # at the totals. The COUNT of clock reads is held here; what a
        # read and a span cost in microseconds is measured where a wall
        # clock belongs: `python -m lightgbm_tpu.observability clock`.
        me, reads = threading.get_ident(), []
        for clock in ("perf_counter", "time", "monotonic"):
            real = getattr(time, clock)
            monkeypatch.setattr(
                time, clock, lambda real=real: (
                    reads.append(1) if threading.get_ident() == me
                    else None, real())[1])
        n = 10_000
        before = obs.trace.counts().get("x", 0)
        for i in range(n):
            with obs.trace.span("x", iter=i):
                pass
        coarse, reads[:] = len(reads), []
        assert obs.trace.counts()["x"] == before + n
        assert 2 * n <= coarse <= 4 * n
        assert obs.trace.spans()[-1]["name"] == "x"
        assert not obs.trace.enabled
        for i in range(n):
            with obs.trace.span("y", fine=True, rows=i):
                pass
        assert len(reads) == 2 * n
        assert obs.trace.counts()["y"] >= n
        assert "y" not in {s["name"] for s in obs.trace.spans()}


# -------------------------------------------- custom-fobj const-hessian
class TestCustomObjectiveConstHessian:
    def _scaled_l2(self, y):
        def fobj(score, ds_):
            return 2.0 * (score - y), np.full_like(score, 2.0)
        return fobj

    def test_update_fobj_neutralizes_const_hessian_gate(self):
        X, y = _data(seed=11)
        ds = lgb.Dataset(X, label=y, params={"max_bin": 31})
        bst = lgb.Booster(params=dict(PARAMS, objective="regression"),
                          train_set=ds)
        assert bst.gbdt._const_hessian() == 1.0
        bst.update(fobj=self._scaled_l2(y))
        # the objective still claims constant hessians, but the
        # gradients trained on are the user's — the gate must be off
        # (reference mirrors this by resetting objective to "none")
        assert bst.gbdt._const_hessian() == 0.0

    def test_update_fobj_matches_objective_none_on_mxu(self):
        # pre-fix failure mode: objective="regression" + update(fobj)
        # kept const_hessian=1.0, so the MXU kernel dropped the hessian
        # channel and reconstructed h as the row count (1.0/row) —
        # silently wrong for any fobj with hessians != count. With the
        # gate fixed, the model must be identical to the one trained
        # with objective="none" (the engine.train normalization).
        X, y = _data(seed=12)
        fobj = self._scaled_l2(y)
        boosters = []
        for objective in ("regression", "none"):
            ds = lgb.Dataset(X, label=y, params={"max_bin": 31})
            bst = lgb.Booster(
                params=dict(PARAMS, objective=objective,
                            boost_from_average=False), train_set=ds)
            bst.update(fobj=fobj)      # iteration 0: normal path
            g = bst.gbdt
            g._hist_impl = "mxu"
            g._mxu_interpret = True
            g._fused_run = None
            for _ in range(3):
                bst.update(fobj=fobj)  # MXU path, custom hessians
            boosters.append(bst)
        a, b = boosters
        assert a.gbdt._const_hessian() == b.gbdt._const_hessian() == 0.0
        # identical trees; only the objective= header lines may differ
        def _trees(s):
            return "\n".join(ln for ln in s.splitlines()
                             if "objective" not in ln)
        assert _trees(a.model_to_string()) == _trees(b.model_to_string())
        np.testing.assert_array_equal(np.asarray(a.gbdt.train_score),
                                      np.asarray(b.gbdt.train_score))


# ------------------------------------------- the spans of a whole lgb.train
#: (where, name) of every span the two admitted benchmark cells run
#: through (ISSUE 24's table): "fused" is a data-parallel run over four
#: virtual devices, which takes the fused, pipelined executor on the
#: CPU; "tree" is the serial per-iteration loop with a valid set
TABLE = [
    ("fused", "ingest.construct"), ("fused", "dataset_sample"),
    ("fused", "dataset_bounds"), ("fused", "dataset_quantize"),
    ("fused", "boosting.init"), ("fused", "boosting.build_program"),
    ("fused", "entry.block"), ("fused", "entry.dispatch"),
    ("fused", "entry.unpack_block"), ("fused", "entry.unpack_tree"),
    ("fused", "entry.sync_metrics"), ("fused", "entry.callbacks"),
    ("tree", "entry.tree"), ("tree", "boosting.gradients"),
    ("tree", "boosting.bagging"), ("tree", "entry.dispatch"),
    ("tree", "boosting.shrink"), ("tree", "boosting.update_score"),
    ("tree", "entry.append_tree"), ("tree", "entry.callbacks"),
    ("tree", "entry.wait_device"), ("tree", "boosting.build_program"),
]
_FUSED = dict(PARAMS, tree_learner="data", num_devices=4,
              fused_block_size=5)
_ROUNDS = 20          # blocks at iterations 0 (1 + 4), 5, 10 and 15


def _train(params, rounds, *, seed, valid=False):
    X, y = _data(n=1500, seed=seed)
    kw = {}
    if valid:
        Xv, yv = _data(n=300, seed=seed + 1)
        kw = {"valid_sets": [lgb.Dataset(Xv, label=yv)],
              "callbacks": [lgb.record_evaluation({})]}
    return lgb.train(dict(params), lgb.Dataset(X, label=y), rounds, **kw)


class _SyncCount:
    """Counts the explicit device syncs of a run: calls of
    jax.block_until_ready."""

    def __enter__(self):
        import jax
        self.n, self._jax, self._real = 0, jax, jax.block_until_ready

        def counted(x):
            self.n += 1
            return self._real(x)

        jax.block_until_ready = counted
        return self

    def __exit__(self, *exc):
        self._jax.block_until_ready = self._real


@pytest.fixture(scope="module")
def captured(tmp_path_factory):
    """One jax.profiler capture around a fused run and a per-iteration
    run with observe off, then the fused run again with observe on:
    the ring, the capture's host events, the ledger's events, the model
    texts and the sync counts of each."""
    import jax
    from jax.profiler import ProfileData
    obs.disable()
    obs.reset()
    out = {}
    logdir = str(tmp_path_factory.mktemp("capture"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        with _SyncCount() as syncs:
            fused = _train(_FUSED, _ROUNDS, seed=21)
        out["fused_syncs"] = syncs.n
        out["fused_spans"] = obs.trace.spans()
        out["fused_ledger"] = obs.compiles.events()
        tree = _train(PARAMS, 6, seed=22, valid=True)
    finally:
        jax.profiler.stop_trace()
    out["all_spans"] = obs.trace.spans()
    out["tree_spans"] = out["all_spans"][len(out["fused_spans"]):]
    out["epoch_wall"] = obs.trace.epoch_wall
    out["fused_model"] = fused.model_to_string()
    out["blocks"] = fused.gbdt._pipeline_stats.as_dict()
    assert tree.current_iteration() == 6
    import glob
    path = glob.glob(logdir + "/**/*.xplane.pb", recursive=True)[0]
    host = next(p for p in ProfileData.from_file(path).planes
                if p.name == "/host:CPU")
    out["events"] = [(e.name, e.start_ns, e.duration_ns)
                     for line in host.lines for e in line.events]
    obs.reset()
    with _SyncCount() as syncs:
        observed = _train(dict(_FUSED, observe=True), _ROUNDS, seed=21)
    out["observed_syncs"] = syncs.n
    out["observed_model"] = observed.model_to_string()
    out["observed_spans"] = obs.trace.spans()
    out["observed_telemetry"] = obs.training.records()
    obs.disable()
    obs.reset()
    return out


class TestSpansOfATrainingRun:
    @pytest.mark.parametrize("where,name", TABLE)
    def test_the_capture_holds_the_span_and_agrees_with_the_ring(
            self, captured, where, name):
        # (a): each span of the table is a host event of the capture,
        # as often as the ring has it, and their starts and ends agree
        # to within 1 ms once the capture's own zero is taken out
        from lightgbm_tpu.observability.trace import capture_agreement
        recs = [s for s in captured[where + "_spans"]
                if s["name"] == name]
        assert recs, name
        events = [e for e in captured["events"] if e[0] == name]
        every = [s for s in captured["all_spans"] if s["name"] == name]
        assert len(events) == len(every)
        # one constant per capture, estimated from ALL spans
        whole = capture_agreement(captured["all_spans"],
                                  captured["events"],
                                  captured["epoch_wall"])
        assert whole["unmatched"] == []
        mine = capture_agreement(every, events, captured["epoch_wall"])
        assert mine["matched"] == len(every)
        assert abs(mine["offset_s"] - whole["offset_s"]) < 1e-3
        assert mine["max_err_s"] < 1e-3
        assert whole["max_err_s"] < 1e-3

    def test_the_capture_carries_the_attributes(self, captured):
        blocks = [s for s in captured["fused_spans"]
                  if s["name"] == "entry.block"]
        assert [(s["attrs"]["iter"], s["attrs"]["k"]) for s in blocks] \
            == [(0, 5), (5, 5), (10, 5), (15, 5)]
        assert captured["blocks"]["block_sizes"] == [5, 5, 5, 5]
        assert captured["blocks"]["blocks"] == 4
        trees = [s["attrs"]["tree"] for s in captured["fused_spans"]
                 if s["name"] == "entry.unpack_tree"]
        # iteration 0 ran on the per-iteration path (the sharded
        # learner's first tree): it was appended, not unpacked
        assert trees == list(range(1, _ROUNDS))
        init = next(s for s in captured["fused_spans"]
                    if s["name"] == "boosting.init")
        assert init["attrs"] == {"rows": 1500, "devices": 4}
        built = [s["attrs"] for s in captured["fused_spans"]
                 if s["name"] == "boosting.build_program"]
        # (PR 35: every build says what of the data is categorical)
        # (PR 36: and over how many devices its program runs)
        none = {"has_cat": False, "cat_columns": 0, "cat_bins": 0,
                "devices": 4}
        assert {"program": "fused_train", "iter": 1, "k": 4, **none} in built
        assert {"program": "fused_train", "iter": 5, "k": 5, **none} in built

    def test_every_parent_is_an_enclosing_span(self, captured):
        recs = captured["all_spans"]
        by_id = {s["id"]: s for s in recs}
        assert len(by_id) == len(recs)
        kids = 0
        for s in recs:
            if not s["parent_id"]:
                assert s["depth"] == 0
                continue
            up = by_id[s["parent_id"]]
            kids += 1
            assert up["tid"] == s["tid"] and up["name"] == s["parent"]
            assert up["ts"] <= s["ts"] and \
                s["ts"] + s["dur"] <= up["ts"] + up["dur"] + 1e-9
        assert kids > len(recs) // 2
        # the phases of a tree are the children of its entry.tree
        tree = next(s for s in captured["tree_spans"]
                    if s["name"] == "entry.tree"
                    and s["attrs"]["iter"] == 3)
        names = [s["name"] for s in captured["tree_spans"]
                 if s["parent_id"] == tree["id"]]
        assert names == ["boosting.gradients", "boosting.bagging",
                         "entry.dispatch", "boosting.shrink",
                         "boosting.update_score", "entry.append_tree",
                         "entry.callbacks"]

    def test_the_ledger_counts_nothing_between_two_warm_blocks(
            self, captured):
        # (c): the block at iteration 10 builds the last programs (its
        # own length ran once before, at 5, but the unpacking beside it
        # is the first of a five-tree block); the block at 15 and the
        # unpacking after it build nothing
        last = next(s for s in captured["fused_spans"]
                    if s["name"] == "entry.block"
                    and s["attrs"]["iter"] == 15)
        late = [e for e in captured["fused_ledger"] if e["ts"] > last["ts"]]
        assert late == []
        growth = [e for e in captured["fused_ledger"]
                  if e["kind"] == "backend"
                  and e["span"] == "boosting.build_program"]
        assert len(growth) >= 2       # one per block length, 4 and 5

    def test_the_span_budget(self, captured):
        # (d): at most 6 spans a block plus 2 a tree on the fused path,
        # at most 16 a tree on the per-iteration path, none of them fine
        for blk in (s for s in captured["fused_spans"]
                    if s["name"] == "entry.block"
                    and s["attrs"]["iter"] >= 5):
            inside = [s for s in captured["fused_spans"]
                      if blk["ts"] <= s["ts"]
                      and s["ts"] + s["dur"] <= blk["ts"] + blk["dur"]]
            assert len(inside) <= 6 + 2 * blk["attrs"]["k"], \
                [s["name"] for s in inside]
        for tree in (s for s in captured["tree_spans"]
                     if s["name"] == "entry.tree"):
            inside = [s for s in captured["tree_spans"]
                      if tree["ts"] <= s["ts"]
                      and s["ts"] + s["dur"] <= tree["ts"] + tree["dur"]]
            assert len(inside) <= 16, [s["name"] for s in inside]

    def test_no_serving_span_in_the_ring_with_observe_off(self):
        # (d): a server's per-request and per-batch spans stay out of
        # the ring (they would flush a trainer's set-up) but keep their
        # totals, which the held-back serving cells read
        from lightgbm_tpu.serving import Server
        from lightgbm_tpu.utils.timer import global_timer
        X, y = _data(seed=23)
        bst = lgb.train(dict(PARAMS), lgb.Dataset(X, label=y), 3)
        before = global_timer.totals().get("serve_device_predict", 0.0)
        obs.reset()
        srv = Server(max_wait_ms=1)
        try:
            srv.load_model("m", booster=bst)
            srv.predict("m", X[:16])
        finally:
            srv.close()
        assert [s["name"] for s in obs.trace.spans()
                if s["name"].startswith("serve")] == []
        assert global_timer.totals()["serve_device_predict"] > before
        assert global_timer.totals()["serve_bin_rows"] > 0

    def test_observe_changes_neither_the_model_nor_the_syncs(
            self, captured):
        # (e): byte-identical trees, and not one device sync more on
        # the fused path (observe used to block on every block's scores)
        def trees(text):
            return text.split("\nparameters:")[0]
        assert trees(captured["observed_model"]) == \
            trees(captured["fused_model"])
        assert captured["observed_syncs"] == captured["fused_syncs"]

        def waits(spans):
            return sorted((s["name"], s["attrs"]["iter"]) for s in spans
                          if s["name"] in ("entry.wait_device",
                                           "entry.sync_metrics"))
        assert waits(captured["observed_spans"]) == \
            waits(captured["fused_spans"])
        # and the telemetry is there: one record per fused block, its
        # wall the host clock from its dispatch to the next
        recs = [r for r in captured["observed_telemetry"]
                if r.get("fused")]
        assert [(r["iteration"], r["iterations"]) for r in recs] == \
            [(1, 4), (5, 5), (10, 5), (15, 5)]
        assert all(r["wall_s"] > 0 for r in recs)
