"""``layout_build_s`` on this cell (the same corpus, the same layout)."""

from benchmarks.metrics.layout_build_s import read  # noqa: F401
