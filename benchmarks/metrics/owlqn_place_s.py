"""``place_s`` on this cell (the same corpus, the same layout)."""

from benchmarks.metrics.place_s import read  # noqa: F401
