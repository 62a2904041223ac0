"""One module per per-layer metric. Each states NAME, UNIT, BETTER,
LAYER (its name in PERF.md), SOURCE (device_trace, program_span,
program_counter or host_clock), MOVES (the end-to-end metric it should
move) and optionally WORKLOADS, and has read(readings) -> number or
None, where `readings` are the raw readings a runner took: timer
totals, counters, block walls, and `trace`, the reduced capture of a
--trace 1 run. A reader that finds nothing to read returns None and the
harness leaves the metric out of the line."""
