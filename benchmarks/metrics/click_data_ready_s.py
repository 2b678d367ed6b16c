"""``data_ready_s`` on this cell (the wide layout's build and placement)."""

from benchmarks.metrics.data_ready_s import read  # noqa: F401
