"""``layout_bytes_per_nnz`` on this cell (the same corpus, the same
layout)."""

from benchmarks.metrics.layout_bytes_per_nnz import read  # noqa: F401
