"""``place_s`` on this cell: the ``layout.place`` span inside the run's one
``data.make_glm_data`` (the fixed effect's shard)."""

from benchmarks.metrics.place_s import read  # noqa: F401
