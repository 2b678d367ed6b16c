"""``layout_build_s`` on this cell: the wide layout's one ``layout.build``
span (both bands)."""

from benchmarks.metrics.layout_build_s import read  # noqa: F401
