# Test tiers (see pytest.ini): the default tier must stay green on every
# commit; the slow tier (multihost subprocess tests, MXU interpret-mode
# kernel matrix, reference-consistency differential tests) must pass
# before a round is declared done. Both run on CPU via tests/conftest.py
# (virtual 8-device mesh). What runs on the chip: chip_smoke.py (the
# quickest proof the main path still starts there; --rehearse-cpu checks
# its control flow first), bench.py and bench_serve.py — each one
# process, each refusing any platform but "tpu" unless told --cpu.

PY ?= python

.PHONY: test test-slow test-all faults chaos postmortem distributed observe lint lint-sarif lint-ci pipeline kernels perf stream bench serve-chaos serve-bench loop loop-chaos elastic install

test:
	$(PY) -m pytest tests/ -x -q

# tpulint: AST invariant checker (jit hygiene, lock discipline, registry
# consistency — docs/StaticAnalysis.md); exits non-zero on any
# unsuppressed finding, plus the rule-engine's own fixture tests
lint:
	$(PY) -m lightgbm_tpu.analysis lightgbm_tpu --format=json
	$(PY) -m pytest tests/test_static_analysis.py -x -q -m lint

# same run, SARIF 2.1.0 on stdout — for CI diff annotators
lint-sarif:
	$(PY) -m lightgbm_tpu.analysis lightgbm_tpu --format=sarif

# hermetic CI gate: cache disabled (every trace rebuilt from scratch),
# human-readable text on stdout plus tpulint.sarif for annotators
lint-ci:
	$(PY) -m lightgbm_tpu.analysis lightgbm_tpu --no-cache
	$(PY) -m lightgbm_tpu.analysis lightgbm_tpu --no-cache --format=sarif > tpulint.sarif
	$(PY) -m pytest tests/test_static_analysis.py -x -q -m lint

# the pipelined-executor tier: byte-parity vs the serial block loop,
# device-eval fidelity, adaptive scheduler (tests/test_pipeline.py,
# docs/Performance.md) — fast subset by default; `-m pipeline` without
# the `not slow` filter adds the interpret-mode matrix
pipeline:
	$(PY) -m pytest tests/ -x -q -m "pipeline and not slow"
	$(PY) -m pytest tests/ -x -q -m "pipeline and slow"

# the histogram-kernel tier: scatter/mxu/oracle parity (incl.
# adversarial bin distributions and the quantized bit-exactness
# contract), hist_backend resolution + the per-pass rule (tests/
# test_hist_backends.py, docs/Performance.md) — the fast subset is
# tier-1; `-m "kernels and slow"` adds tree/model byte-parity
kernels:
	$(PY) -m pytest tests/ -x -q -m "kernels and not slow"
	$(PY) -m pytest tests/ -x -q -m "kernels and slow"

# the round-6 perf tier: microbench-shaped structural assertions for
# the row partition — counts reuse, sort-free jaxprs
# (tests/test_partition_scan.py). Count-based, never wall-clock: green
# means the structure the partition relies on is intact
perf:
	$(PY) -m pytest tests/ -x -q -m "perf and not slow"

# the out-of-core streaming tier: sketch/bin parity, adversarial chunk
# layouts, model.txt byte-parity vs in-memory, mid-stream checkpoint
# resume (tests/test_streaming.py, docs/Streaming.md) — fast subset is
# tier-1; `-m "streaming and slow"` adds the 10M-row bounded-memory smoke
stream:
	$(PY) -m pytest tests/ -x -q -m "streaming and not slow"

# the fault-injection tier: every registered reliability site fired and
# recovered (tests/test_reliability.py, docs/Reliability.md)
faults:
	$(PY) -m pytest tests/ -x -q -m faults

# the rank-death chaos tier: 2-rank run loses a rank mid-collective,
# survivor aborts with a named diagnostic, resume is byte-identical
# (tests/test_chaos.py, docs/Reliability.md "Distributed fault model");
# the trailing -m overrides pytest.ini's `not slow`
chaos:
	$(PY) -m pytest tests/test_chaos.py -x -q -m chaos

# the flight-recorder acceptance scenario: the 2-rank kill run must
# leave a postmortem_<rank>.json on BOTH ranks naming the hung
# collective site (tests/test_chaos.py::test_postmortem_bundles,
# docs/Observability.md "Post-mortem workflow")
postmortem:
	$(PY) -m pytest tests/test_chaos.py -x -q -m chaos -k postmortem

# the distributed-learner tier: crossbar byte-parity oracles (serial vs
# data-parallel reduce-scatter, bit-for-bit), hist_agg/binning units,
# fault-site + provision-latch checks (tests/test_distributed_learner.py,
# docs/Distributed.md) — fast subset is tier-1; the second invocation
# adds full-task parity, fused determinism, and the 8-device rank-death
# chaos scenario
distributed:
	$(PY) -m pytest tests/test_distributed_learner.py -x -q -m "distributed and not slow"
	$(PY) -m pytest tests/test_distributed_learner.py -x -q -m "distributed and slow"

# the elastic world-resize tier (docs/Distributed.md "Elasticity"):
# the fast subset (tier-1, no subprocesses) covers epoch agreement,
# the reshard loader's W->W'->W byte-identity, stale-epoch rejection
# and the shrink-vote state machine; the slow invocation runs the
# shrink-and-finish reincarnation scenario — kill a rank at 2x4
# devices, survivors vote a new epoch, re-shard, finish with zero
# aborts, byte-identical to a fixed-world resume
elastic:
	$(PY) -m pytest tests/test_elastic.py -x -q -m "elastic and not slow"
	$(PY) -m pytest tests/test_elastic.py -x -q -m "elastic and slow"

# the serving chaos tier: concurrent load while the fault registry
# kills replica dispatches, breakers trip/heal, and the model is
# hot-swapped mid-run — zero drops, bit-identical answers, breaker
# lifecycle visible in metrics (tests/test_serve_chaos.py,
# docs/Serving.md "Degradation ladder") — fast subset is tier-1; the
# second invocation adds the slow open-loop QPS ramp
serve-chaos:
	$(PY) -m pytest tests/test_serve_chaos.py -x -q -m "serve_chaos and not slow"
	$(PY) -m pytest tests/test_serve_chaos.py -x -q -m "serve_chaos and slow"

# the continuous-loop tier (docs/Continuous.md): `loop` is the fast
# state-machine/unit tier (tier-1); `loop-chaos` runs the slow
# kill-matrix — one kill per fault site on the cycle path under live
# traffic, plus poison quarantine and the freshness SLO alarm, with
# byte-identity against an unkilled reference run
# (tests/test_loop_chaos.py)
loop:
	$(PY) -m pytest tests/test_continuous.py -x -q -m "loop and not slow"

loop-chaos:
	$(PY) -m pytest tests/test_continuous.py -x -q -m "loop and not slow"
	$(PY) -m pytest tests/test_loop_chaos.py -x -q -m "loop and slow"

# the serving load bench: open-loop QPS ramp + chaos stage, emits
# SERVE_r<N>.json (sustained QPS at p99<10ms) into the same
# regression-sentinel trajectory as BENCH_r*
serve-bench:
	$(PY) bench_serve.py
	$(PY) bench.py --compare --strict

# the observability tier: spans, training telemetry, MFU accounting,
# Prometheus /metrics (tests/test_observability.py, docs/Observability.md)
observe:
	$(PY) -m pytest tests/test_observability.py -x -q

# batched: the whole slow tier in ONE pytest process hard-crashed the
# interpreter twice (not OOM; see TESTS.md round 4) — per-batch runs
# are 100% green and are the supported invocation
test-slow:
	$(PY) -m pytest tests/test_mxu_kernels.py tests/test_mxu_smoke.py \
	  tests/test_mxu_forced_cegb.py -x -q -m slow
	$(PY) -m pytest tests/test_efb.py tests/test_efb_mxu.py \
	  tests/test_packed_bins.py tests/test_fused.py \
	  tests/test_bench_robustness.py tests/test_dask_stub.py -x -q -m slow
	$(PY) -m pytest tests/test_multihost.py tests/test_distributed.py \
	  tests/test_cli.py -x -q -m slow
	$(PY) -m pytest tests/ -x -q -m slow --ignore=tests/test_mxu_kernels.py \
	  --ignore=tests/test_mxu_smoke.py --ignore=tests/test_mxu_forced_cegb.py \
	  --ignore=tests/test_efb.py --ignore=tests/test_efb_mxu.py \
	  --ignore=tests/test_packed_bins.py --ignore=tests/test_fused.py \
	  --ignore=tests/test_bench_robustness.py --ignore=tests/test_dask_stub.py \
	  --ignore=tests/test_multihost.py --ignore=tests/test_distributed.py \
	  --ignore=tests/test_cli.py

test-all: test test-slow

# the bench run, followed by the regression sentinel: the fresh record
# is compared against the BENCH_r*/MULTICHIP_r* trajectory and a >10%
# drop vs best-so-far fails the target (observability/regress.py)
bench:
	$(PY) bench.py
	$(PY) bench.py --compare --strict

install:
	pip install -e . --no-build-isolation --no-deps
