"""One module per kind of configuration; each has
run(cell, *, seed, seconds, trace, rehearsal) -> {"correct", "attempted",
"failed", "end_to_end": {name: value}, "readings": {...}}."""
