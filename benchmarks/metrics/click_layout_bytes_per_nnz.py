"""``layout_bytes_per_nnz`` on this cell (both bands' leaves)."""

from benchmarks.metrics.layout_bytes_per_nnz import read  # noqa: F401
