"""rtap_tpu's benchmark: one command runs one cell once (benchmark/run.py).

Everything a cell, a configuration, a traffic mix or a per-layer metric needs
is a file of its own, found by name (benchmark/registry.py); PERF.md says what
each measures and why."""
