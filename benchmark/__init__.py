"""The benchmark: one cell, once, in one process.

    python3 -m benchmark --workload <name> --seed <n> --seconds <s> --trace <0|1>

A cell is one entry of `workloads` in BENCHMARK.json: a configuration
(benchmark/configs/) under a traffic mix (benchmark/traffic/), driven by
the runner of the configuration's kind (benchmark/runners/). The
yardstick lives here and takes from the program only the system under
test, its counters and its timers: nothing in this directory imports
bench.py, bench_serve.py, helpers/ or lightgbm_tpu.testing. README.md
says how to add a cell, a configuration, a traffic mix, a runner and a
per-layer metric as new files, with no edit to a file that is here.
"""
