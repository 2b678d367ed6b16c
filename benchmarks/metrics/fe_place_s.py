"""``place_s`` on the GAME cell: the fixed effect's ``layout.place`` span
(first host-to-device copy of the built layout until every leaf is
ready)."""

from benchmarks.metrics.place_s import read  # noqa: F401
