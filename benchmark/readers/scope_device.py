"""Device milliseconds per group-tick under one of the step's `rtap.*`
scopes (`scope` in the definition; "unscoped" = the ops that carry none),
inside whole executions of `module` in the traced window
(benchmark/scoped_trace.py:by_scope). A scope no op carries reads 0."""

from benchmark.scoped_trace import scope_table


def read(record: dict, definition: dict):
    table = scope_table(record, definition["module"])
    return None if table is None else table.get(definition["scope"], 0.0)
