"""``layout_build_s`` on this cell: the ``layout.build`` span inside the run's
one ``data.make_glm_data`` (the fixed effect's shard)."""

from benchmarks.metrics.layout_build_s import read  # noqa: F401
