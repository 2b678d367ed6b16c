"""Bytes of the fixed effect's resident feature matrix (sum of its leaves'
nbytes) per stored entry of its shard."""

from benchmarks.metrics.layout_bytes_per_nnz import read  # noqa: F401
