"""``data_ready_s`` on this cell (the same corpus, the same layout)."""

from benchmarks.metrics.data_ready_s import read  # noqa: F401
