"""Seconds of Python tracing and lowering before the window opened: the
``trace_s`` and ``lower_s`` of the program's compile records of set-up.
Wall seconds of the outermost intervals (a function traced into its caller
is counted once, where ``compile_s`` sums JAX's nested events), and work no
cache can serve."""

from benchmarks.metrics import _setup


def read(run):
    records = _setup.setup_compiles(run)
    if records is None:
        return None
    return _setup.total(records, "trace_s") + _setup.total(records, "lower_s")
