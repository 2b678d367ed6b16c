"""``place_s`` on this cell: the wide layout's leaves (both bands) copied to
the chip until every one is ready."""

from benchmarks.metrics.place_s import read  # noqa: F401
